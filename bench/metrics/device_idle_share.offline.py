"""Per-layer metric `device_idle_share.offline`: share of the traced window
with no operation on the device, offline cells.
"""
from harness import readers

LAYER = "device"
UNIT = "%"
SOURCE = "device_trace"
MOVES = "images_per_s"


def read(ctx):
    return readers.device_idle_share(ctx)
