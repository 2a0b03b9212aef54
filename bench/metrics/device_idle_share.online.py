"""Per-layer metric `device_idle_share.online`: share of the traced window with
no operation on the device, online cells.
"""
from harness import readers

LAYER = "device"
UNIT = "%"
SOURCE = "device_trace"
MOVES = "latency_p50_ms"


def read(ctx):
    return readers.device_idle_share(ctx)
