"""Per-layer metric `queue_wait_ms_p50.online`: nearest-rank median of the
time a request waited between `enqueue` and its admission into a slot, over
the requests admitted inside the traced window, online cells. Reads the
engine's `queued` records (track `requests`); a program that writes none
gives nothing.
"""
import math

LAYER = "scheduler and admission (serving/scheduler.py, serving/vision.py)"
UNIT = "ms"
SOURCE = "program_span"
MOVES = "latency_p50_ms"


def read(ctx):
    w = ctx.window
    lo = (w["t_on"] - w["tracer_t0"]) * 1e3
    hi = (w["t_off"] - w["tracer_t0"]) * 1e3
    waits = sorted(s["dur_ms"] for s in w["spans"]
                   if s["name"] == "queued" and s["track"] == "requests"
                   and lo <= s["ts_ms"] + s["dur_ms"] <= hi)
    if not waits:
        return None
    return waits[math.ceil(0.5 * len(waits)) - 1]
