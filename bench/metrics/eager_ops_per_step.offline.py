"""Per-layer metric `eager_ops_per_step.offline`: device programs the engine
launches outside its segment programs (per-member pads, stack pieces, zero
rows, row slices, the slice copied to the host), per engine step, offline
cells. Reads the engine's `step` records (track `engine`, attr `eager_ops`)
that lie inside the traced window; a program that writes none gives nothing.
"""
LAYER = "serving engine host (serving/vision.py, serving/pipeline.py)"
UNIT = "ops"
SOURCE = "program_counter"
MOVES = "images_per_s"


def read(ctx):
    w = ctx.window
    lo = (w["t_on"] - w["tracer_t0"]) * 1e3
    hi = (w["t_off"] - w["tracer_t0"]) * 1e3
    ops = [s["attrs"]["eager_ops"] for s in w["spans"]
           if s["name"] == "step" and s["track"] == "engine"
           and "eager_ops" in s["attrs"]
           and lo <= s["ts_ms"] and s["ts_ms"] + s["dur_ms"] <= hi]
    if not ops:
        return None
    return sum(ops) / len(ops)
