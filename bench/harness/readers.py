"""Reductions that the per-layer metric files share.

Each takes the run's :class:`Context` and returns a number, or ``None``
where it finds nothing to read; the harness then leaves the metric out of
the result line. A share of a roofline or a peak is never reported as 0
for want of data.
"""
from __future__ import annotations

import dataclasses
from typing import List, Optional

from . import flops as F
from . import xtrace as X


@dataclasses.dataclass
class Context:
    model: dict
    pruning: dict
    peaks: dict
    trace: Optional[X.Trace]       # the traced sub-window's device trace
    window: dict                   # marks and spans of the traced window
    done_in_window: List[float]    # keep rates of images completed in it


def host_ms_per_step(ctx: Context) -> Optional[float]:
    """Host milliseconds of the engine's plan, stage and dispatch spans in
    the traced window, per engine step taken in it."""
    w = ctx.window
    steps = w["marks_off"]["steps"] - w["marks_on"]["steps"]
    if steps <= 0:
        return None
    lo = (w["t_on"] - w["tracer_t0"]) * 1e3
    hi = (w["t_off"] - w["tracer_t0"]) * 1e3
    ms = sum(s["dur_ms"] for s in w["spans"]
             if s["name"] in ("plan", "stage", "dispatch")
             and lo <= s["ts_ms"] and s["ts_ms"] + s["dur_ms"] <= hi)
    return ms / steps


def padding_share(ctx: Context) -> Optional[float]:
    """Share (%) of the token-rows the engine dispatched in the window that
    were padding: 1 - real cells / padded-tile cells, from the batcher's
    counters."""
    on, off = ctx.window["marks_on"], ctx.window["marks_off"]
    padded = off["padded_cells"] - on["padded_cells"]
    real = off["real_cells"] - on["real_cells"]
    if padded <= 0:
        return None
    return 100.0 * (padded - real) / padded


def step_mfu(ctx: Context) -> Optional[float]:
    """The paper's required FLOPs of the images completed in the traced
    window, over the window times the chip's bf16 peak (%)."""
    if ctx.trace is None or not ctx.done_in_window:
        return None
    need = sum(F.image_flops(ctx.model, ctx.pruning, r)
               for r in ctx.done_in_window)
    return 100.0 * need / (ctx.trace.window_s * ctx.peaks["bf16_flops"])


SBMM_KERNEL = r"sbmm"


def sbmm_roofline(ctx: Context) -> Optional[float]:
    """Least time of the SBMM calls made in the traced window (the larger
    of operations over the bf16 peak and bytes over HBM bandwidth, per
    call) over the device time of the SBMM kernel's events (%)."""
    if ctx.trace is None:
        return None
    secs, count = X.matching_seconds(ctx.trace, SBMM_KERNEL)
    if count == 0 or secs <= 0:
        return None
    least = 0.0
    for seg, batch, n, k in ctx.window["calls"]:
        work = F.sbmm_segment_work(ctx.model, ctx.pruning, seg, batch, n, k)
        if work["calls"]:
            per = work["calls"]
            least += per * max(work["ops"] / per / ctx.peaks["bf16_flops"],
                               work["bytes"] / per
                               / ctx.peaks["hbm_bytes_per_s"])
    if least <= 0:
        return None
    return 100.0 * least / secs


def device_idle_share(ctx: Context) -> Optional[float]:
    if ctx.trace is None:
        return None
    share = X.idle_share(ctx.trace)
    return None if share is None else 100.0 * share
