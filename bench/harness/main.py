"""A run of one cell, from set-up to the result line."""
from __future__ import annotations

import gc
import math
import sys
from typing import Dict, Optional

from . import check as C
from . import drive
from . import spec
from . import traffic as T
from . import xtrace as X
from .readers import Context

CACHE = spec.ROOT / ".bench_cache"


class NoAccelerator(RuntimeError):
    pass


def device_info(chips: int, require_tpu: bool) -> dict:
    import jax
    devs = jax.devices()
    if require_tpu and (devs[0].platform != "tpu" or len(devs) < chips):
        raise NoAccelerator(
            f"the cell needs {chips} TPU chip(s); JAX finds "
            f"{len(devs)} {devs[0].platform} device(s)")
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": chips}


def enable_cache() -> None:
    """JAX's persistent cache at a fixed path in the checkout, keeping
    every program (the segment programs of one bucket compile in under a
    second) and never evicting: an evicting cache written from several
    threads loses entries."""
    import jax
    jax.config.update("jax_compilation_cache_dir", str(CACHE / "jax"))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    jax.config.update("jax_compilation_cache_max_size", -1)


def memory_peak_bytes() -> int:
    import jax
    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use", 0)
             for d in jax.local_devices()]
    return int(max(peaks))


def end_to_end(cell, records) -> Dict[str, dict]:
    """Every end-to-end metric the cell reports, from the host clock."""
    vals: Dict[str, float] = {"setup_s": records.setup_s}
    done = records.out.done_t
    if cell.mix["loop"] == "closed":
        n = sum(1 for t in done.values() if records.t0 < t <= records.t_end)
        vals["images_per_s"] = n / (records.t_end - records.t0)
    else:
        lat = [(done[u] - (records.t0 + r.due_s)) * 1e3 if u in done
               else math.inf for u, r in records.requests.items()]
        vals["latency_p50_ms"] = T.percentile(lat, 50)
        vals["latency_p95_ms"] = T.percentile(lat, 95)
    return {m["name"]: {"value": vals[m["name"]], "unit": m["unit"]}
            for m in cell.end_to_end}


def attempted_failed(cell, records):
    if cell.mix["loop"] == "closed":
        n = sum(1 for t in records.out.done_t.values()
                if records.t0 < t <= records.t_end)
        return n, 0
    n = len(records.requests)
    return n, n - sum(1 for u in records.requests if u in records.out)


def traced(cell, records, info, peaks: dict):
    """(per-layer metrics, busy_s, window_s, breakdown) of a traced run."""
    tw = records.trace
    trace = X.load(X.find_xplane(tw["dir"]))
    done = records.out.done_t
    in_win = [records.requests[u].r_t for u, t in done.items()
              if tw["t_on"] <= t <= tw["t_off"]]
    ctx = Context(model=info["model"], pruning=info["pruning"],
                  peaks=peaks, trace=trace, window=tw,
                  done_in_window=in_win)
    metrics = {}
    for m in cell.per_layer:
        v = cell.metric_module(m["name"]).read(ctx)
        if v is not None:
            metrics[m["name"]] = {"value": v, "unit": m["unit"]}
    breakdown = {"device_ops": X.top_ops(trace),
                 "idle_gaps": X.idle_by_host_span(trace)}
    return metrics, X.busy_s(trace), trace.window_s, breakdown


def correctness(cell, records, info, seed: int):
    """(correct, numbers with limits) from the reference over a sample."""
    uids = C.sample(records, int(cell.mix["check_sample"]), seed)
    reqs = [records.requests[u] for u in uids]
    ref = C.reference_logits(cell.reference, info["params"], info["model"],
                             info["pruning"], info["scores"], info["pool"],
                             reqs)
    check = cell.config["check"]
    nums = C.numbers(C.errors(records.out, ref), C.groups_of(records, uids),
                     check["tol"])
    print(f"readings {C.candidates(records.out, ref)}", file=sys.stderr)
    limits = check["limits"]
    ok = bool(uids) and C.verdict(nums, limits)
    lanes = sum(1 for r in reqs
                if C.served_by(r, records.segments_per_request) == "lane")
    nums_out = {k: {"value": nums[k], "limit": limits[k]} for k in limits}
    return ok, nums_out, {"compared": len(uids), "lanes": lanes}


def run_cell(name: str, seed: int, seconds: float, trace: bool,
             t_start: float, require_tpu: bool = True, cfg=None,
             bench: Optional[dict] = None, mix: Optional[dict] = None,
             peaks: Optional[dict] = None, log=sys.stderr) -> dict:
    """One run of ``name``; returns the result line's object. ``cfg``,
    ``mix`` and ``peaks`` replace the cell's model configuration, update
    its mix and replace the device's peaks (a rehearsal at a small size,
    off the chip)."""
    cell = spec.cell(name, bench)
    cell.mix.update(mix or {})
    device = device_info(cell.chips, require_tpu)
    enable_cache()
    drive.CompileWatch.get()
    records, engine, info = drive.run(
        cell, seed, seconds, trace, t_start, cfg=cfg,
        trace_dir=str(CACHE / "trace" / name))
    print(f"set-up: {records.setup_s:.3f} s, {info['programs_warmed']} "
          f"programs warmed; compiles inside the window: "
          f"{records.window_compiles}", file=log)
    for name in records.window_lowered[:20]:
        print(f"lowered inside the window: {name}", file=log)
    attempted, failed = attempted_failed(cell, records)
    result: dict = {"attempted": attempted, "failed": failed}
    if trace:
        metrics, busy, window, breakdown = traced(
            cell, records, info, peaks or spec.peaks(device["kind"]))
    else:
        metrics = end_to_end(cell, records)
    device["memory_peak_bytes"] = memory_peak_bytes()
    if trace:
        device["busy_s"] = busy
        device["window_s"] = window
    # the program's state goes before the reference runs
    del engine
    gc.collect()
    ok, nums, seen = correctness(cell, records, info, seed)
    print(f"engine steps {records.steps}, requests completed "
          f"{len(records.out)}, compared {seen['compared']} "
          f"({seen['lanes']} served as express lanes); planner: "
          f"{records.planner}", file=log)
    result = {"correct": ok, **result, "metrics": metrics, "device": device}
    if trace:
        result["breakdown"] = breakdown
    result["check"] = nums
    return result
