"""Per-layer metric `padding_share.online`: share of dispatched token-rows that
were padding, online cells.
"""
from harness import readers

LAYER = "ragged batcher and planner (serving/ragged_batcher.py, serving/planner.py)"
UNIT = "%"
SOURCE = "program_counter"
MOVES = "latency_p50_ms"


def read(ctx):
    return readers.padding_share(ctx)
