"""Per-layer metric `sbmm_roofline.offline`: least time of the SBMM calls over
the SBMM kernel's device time, offline cells.
"""
from harness import readers

LAYER = "kernels/sbmm"
UNIT = "%"
SOURCE = "device_trace"
MOVES = "images_per_s"


def read(ctx):
    return readers.sbmm_roofline(ctx)
