"""Per-layer metric `step_mfu.offline`: the paper's required FLOPs of the
images completed in the traced window over the bf16 peak, offline cells.
"""
from harness import readers

LAYER = "model step (core/packed_runner.py segments)"
UNIT = "%"
SOURCE = "host_clock"
MOVES = "images_per_s"


def read(ctx):
    return readers.step_mfu(ctx)
