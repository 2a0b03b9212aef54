"""Per-layer metric `engine_host_ms_per_step.online`: host ms of the engine's
plan, stage and dispatch spans per engine step, online cells.
"""
from harness import readers

LAYER = "serving engine host (serving/vision.py, serving/pipeline.py)"
UNIT = "ms"
SOURCE = "program_span"
MOVES = "latency_p50_ms"


def read(ctx):
    return readers.host_ms_per_step(ctx)
