"""Per-layer metric `lane_device_ms_p50.online`: nearest-rank median device
time of one express lane's program, online cells. Reads the `XLA Modules`
line of each `/device:TPU:<i>` plane of the traced window's profile: the
runs of the module `jit_vit_lane` that lie inside the `bench_window`
annotation. A trace with no TPU plane, or a program whose lane module has
another name, gives nothing.
"""
import math
import re

from harness import xtrace as X

LAYER = "model step (core/packed_runner.py segments)"
UNIT = "ms"
SOURCE = "device_trace"
MOVES = "latency_p50_ms"

MODULES_LINE = "XLA Modules"
LANE = re.compile(r"^jit_vit_lane(\(|$)")


def lane_ms(planes, lo, hi):
    """Device ms of each run of the lane module on the `XLA Modules` line
    of the TPU planes (ProfileData's planes, lines and events) that lies
    inside [lo, hi] (ns)."""
    runs = []
    for plane in planes:
        if not X.DEVICE_PLANE.match(plane.name):
            continue
        for line in plane.lines:
            if line.name != MODULES_LINE:
                continue
            for e in line.events:
                a, b = e.start_ns, e.start_ns + e.duration_ns
                if LANE.match(e.name) and lo <= a and b <= hi:
                    runs.append((b - a) * 1e-6)
    return runs


def read(ctx):
    if ctx.trace is None:
        return None
    from jax.profiler import ProfileData
    pd = ProfileData.from_file(X.find_xplane(ctx.window["dir"]))
    runs = sorted(lane_ms(pd.planes, *ctx.trace.window))
    if not runs:
        return None
    return runs[math.ceil(0.5 * len(runs)) - 1]
