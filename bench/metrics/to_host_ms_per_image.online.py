"""Per-layer metric `to_host_ms_per_image.online`: host ms of the pipeline's
`to_host` spans (the step's logits sliced and copied to the host, after the
wait for the device) that end inside the traced window, over the logit rows
they delivered (`to_host` of the engine's `step` records that end inside
it), online cells. A program that writes no such records gives nothing.
"""
LAYER = "serving engine host (serving/vision.py, serving/pipeline.py)"
UNIT = "ms"
SOURCE = "program_span"
MOVES = "latency_p50_ms"


def read(ctx):
    w = ctx.window
    lo = (w["t_on"] - w["tracer_t0"]) * 1e3
    hi = (w["t_off"] - w["tracer_t0"]) * 1e3
    inside = [s for s in w["spans"] if lo <= s["ts_ms"] + s["dur_ms"] <= hi]
    images = sum(s["attrs"]["to_host"] for s in inside
                 if s["name"] == "step" and s["track"] == "engine"
                 and "to_host" in s["attrs"])
    if images <= 0:
        return None
    ms = sum(s["dur_ms"] for s in inside
             if s["name"] == "to_host" and s["track"] == "pipeline")
    return ms / images
