"""One run of a cell: set-up, warm-up, the measured window, the records.

The window drives the program's ``VisionEngine`` through its public
``enqueue``/``tick`` calls with the configuration's engine settings:

* a closed loop keeps ``backlog_per_slot * max_batch`` requests waiting
  beyond the slots in flight, so the engine never starves;
* an open loop sends each request at its due time and, once the window
  closes, follows every request due in it to completion.

A request is complete when its logits are on the host: the moment the
engine stores them in the result dict it was given. With ``trace`` on, a
steady sub-window of ``trace_seconds`` in the middle of the window is
traced by ``jax.profiler``; the program's host spans go into that trace as
annotations, and the benchmark records the shape of every segment call the
engine makes (for the SBMM roofline).
"""
from __future__ import annotations

import dataclasses
import logging
import os
import shutil
import sys
import time
from collections import Counter
from concurrent.futures import ThreadPoolExecutor
from typing import Dict, List, Optional, Tuple

import numpy as np

from . import traffic as T
from . import weights as W


WARM_THREADS = 8


class CompileWatch:
    """Counts the programs JAX compiles or loads from its persistent
    cache in this process (segment programs and the engine's eager ops
    alike), and the seconds spent compiling. While ``names`` is a list,
    the name and argument shapes of every program lowered go into it."""

    _installed = None

    def __init__(self):
        self.programs = 0
        self.cache_hits = 0
        self.compile_s = 0.0
        self.names: Optional[List[str]] = None
        self.missed: List[str] = []  # programs not found in the cache

    @classmethod
    def get(cls) -> "CompileWatch":
        if cls._installed is None:
            import jax
            watch = cls._installed = cls()

            def event(name, **kw):
                if name == "/jax/compilation_cache/compile_requests_use_cache":
                    watch.programs += 1
                elif name == "/jax/compilation_cache/cache_hits":
                    watch.cache_hits += 1

            def duration(name, secs, **kw):
                if name == "/jax/core/compile/backend_compile_duration":
                    watch.compile_s += secs

            jax.monitoring.register_event_listener(event)
            jax.monitoring.register_event_duration_secs_listener(duration)
            # JAX logs every program it lowers at DEBUG on this logger
            lowered = logging.getLogger("jax._src.interpreters.pxla")
            lowered.setLevel(logging.DEBUG)
            lowered.propagate = False
            lowered.addHandler(_Lowered(watch))
            compiled = logging.getLogger("jax._src.compiler")
            compiled.setLevel(logging.DEBUG)
            compiled.propagate = False
            compiled.addHandler(_Lowered(watch))
        return cls._installed


class _Lowered(logging.Handler):
    def __init__(self, watch: CompileWatch):
        super().__init__(logging.DEBUG)
        self.watch = watch

    def emit(self, record):
        if record.msg.startswith("PERSISTENT COMPILATION CACHE MISS"):
            self.watch.missed.append(record.args[0])
        if self.watch.names is not None and record.msg.startswith(
                "Compiling %s with global shapes"):
            # the name and the shapes of the data (a segment's weights
            # come first, its activations last)
            name, avals = record.args[0], list(record.args[1])
            shown = [str(a) for a in (avals if len(avals) <= 4
                                      else avals[-2:])]
            self.watch.names.append(f"{name}[{', '.join(shown)}]")


class Outbox(dict):
    """The engine's result dict, stamping the host time of each result."""

    def __init__(self):
        super().__init__()
        self.done_t: Dict[int, float] = {}

    def __setitem__(self, uid, logits):
        self.done_t[uid] = time.perf_counter()
        super().__setitem__(uid, logits)


def make_profiled_tracer():
    """The program's span tracer, with every span also a profiler
    annotation so the device trace can name what the host was doing."""
    import jax
    from repro.obs.trace import Tracer

    class ProfiledTracer(Tracer):
        def __init__(self):
            super().__init__()
            self._open: List = []

        def begin(self, name, track="main", t_ms=None, **attrs):
            super().begin(name, track=track, t_ms=t_ms, **attrs)
            ann = jax.profiler.TraceAnnotation(name)
            ann.__enter__()
            self._open.append(ann)

        def end(self, name=None, track="main", t_ms=None):
            super().end(name, track=track, t_ms=t_ms)
            self._open.pop().__exit__(None, None, None)

    return ProfiledTracer()


class CallLog:
    """Shapes of the segment calls the engine makes while ``on``:
    ``(segment, batch, entry tokens, k)`` for tiles and for every segment
    of a fused express lane."""

    def __init__(self, segments):
        self.on = False
        self.calls: List[Tuple] = []
        run, run_fused = segments.run, segments.run_fused

        def logged_run(seg, x, *a, **kw):
            if self.on:
                self.calls.append((seg, x.shape[0], x.shape[1],
                                   kw.get("k")))
            return run(seg, x, *a, **kw)

        def logged_fused(steps, x, *a, **kw):
            if self.on:
                n = x.shape[1]
                for step in steps:
                    seg, k = step[0], step[1]
                    self.calls.append((seg, 1, n, k))
                    if seg[0] == "embed":
                        n += 1
                    elif seg[0] == "tdm":
                        n = k + 2
            return run_fused(steps, x, *a, **kw)

        segments.run = logged_run
        segments.run_fused = logged_fused


@dataclasses.dataclass
class Request:
    uid: int
    image: int
    r_t: float
    due_s: Optional[float] = None   # open loop: due time from window start
    sent_s: Optional[float] = None
    admitted_step: Optional[int] = None
    done_step: Optional[int] = None


@dataclasses.dataclass
class Records:
    """What one run observed, for the metrics and the correctness check."""
    requests: Dict[int, Request]
    out: Outbox
    t0: float
    t_end: float
    setup_s: float
    window_compiles: int
    steps: int
    segments_per_request: int
    window_lowered: List[str] = dataclasses.field(default_factory=list)
    trace: Optional[dict] = None  # the traced sub-window, trace mode only
    planner: Optional[dict] = None  # the planner's merge/lane counters


def plan_trajectory(plan, n_patches: int, r_t: float, reference):
    """(segment, entry tokens, k) along the segment plan for one image:
    tokens grow by the CLS at the embedding and shrink to CLS + k + fused
    at each TDM (the reference's keep rule)."""
    out, n = [], n_patches
    for seg in plan:
        k = reference.tdm_keep(n, r_t) if seg[0] == "tdm" else None
        out.append((seg, n, k))
        if seg[0] == "embed":
            n += 1
        elif seg[0] == "tdm":
            n = k + 2
    return out


def warm_up(engine, cfg, mix: dict, reference) -> int:
    """Compile (or load from the cache) every program this mix's traffic
    can make the engine run, and run each once. Returns how many."""
    import jax
    import jax.numpy as jnp

    segs = engine.segments
    mb = engine.vc.max_batch
    warm = mix["warm"]
    batches = ([mb] if warm["batches"] == "max"
               else [1 << i for i in range(mb.bit_length()) if 1 << i <= mb])
    n_patches = mix["patches"]
    rates = sorted({float(r) for r, _ in mix["keep_rates"]})
    trajs = {r: plan_trajectory(segs.plan, n_patches, r, reference)
             for r in rates}
    D = cfg.d_model
    P = cfg.patch_size ** 2 * 3
    feat = lambda seg: P if seg[0] == "embed" else D
    # tiles: one stage per (segment index, k); its members' entry counts
    stages: Dict[Tuple, set] = {}
    for traj in trajs.values():
        for si, (seg, n, k) in enumerate(traj):
            stages.setdefault((si, k), set()).add(n)
    shapes = set()
    for (si, k), ns in stages.items():
        seg = segs.plan[si]
        for n in ns:
            for b in batches:
                shapes.add((si, k, b, n, False))
        # the planner may merge buckets of one stage into a masked tile as
        # wide as the widest member; only encoder segments take the mask
        if warm.get("merged") and seg[0] in ("layers", "tdm"):
            for n in sorted(ns)[1:]:
                for b in batches:
                    if b >= 2:
                        shapes.add((si, k, b, n, True))

    def merged(seg, b, n_tile, nv, k, members):
        """A masked tile run as the engine stages and unstages it: each
        narrower member padded to the tile's width on the host, and each
        member's rows sliced out of the output (eager ops, one program
        per shape)."""
        y = segs.run(seg, jnp.zeros((b, n_tile, feat(seg)), jnp.float32),
                     n_valid=nv, k=k)
        outs = [y]
        for n in members:
            outs.append(jnp.pad(jnp.zeros((n, feat(seg)), jnp.float32),
                                ((0, n_tile - n), (0, 0))))
            outs.append(y[0, : k + 2 if seg[0] == "tdm" else n])
        return outs

    jobs = []
    for si, k, b, n, masked in sorted(shapes, key=repr):
        seg = segs.plan[si]
        nv = np.full((b,), n, np.int32) if masked else None
        if masked:
            members = sorted(m for m in stages[(si, k)] if m <= n)
            jobs.append(lambda seg=seg, b=b, n=n, nv=nv, k=k, ms=members:
                        merged(seg, b, n, nv, k, ms))
            continue
        jobs.append(lambda seg=seg, b=b, n=n, nv=nv, k=k: segs.run(
            seg, jnp.zeros((b, n, feat(seg)), jnp.float32), n_valid=nv, k=k))
    if warm.get("lanes"):
        # a lane starts where a request's bucket companions can part: at
        # admission, or at a TDM, where keep rates set different k
        starts = [si for si, (seg, _, _) in enumerate(trajs[rates[0]])
                  if si == 0 or seg[0] == "tdm"]
        for r, traj in trajs.items():
            for si in starts:
                seg0, n0, _ = traj[si]
                steps = tuple((seg, k) for seg, _, k in traj[si:])
                jobs.append(lambda steps=steps, shape=(1, n0, feat(seg0)):
                            segs.run_fused(steps, jnp.zeros(shape,
                                                            jnp.float32)))
    # compiling and loading from the cache release the GIL: overlap them
    with ThreadPoolExecutor(WARM_THREADS) as pool:
        outs = list(pool.map(lambda job: job(), jobs))
    count = len(jobs)
    jax.block_until_ready(outs)
    return count


def warm_cohorts(engine, mix: dict, pool: np.ndarray) -> int:
    """Serve cohorts through the engine itself, so that the host-side
    staging ops (per-member pad, stack, slice) have run at every tile
    shape the mix can make: for each keep rate, cohorts of 1 (an express
    lane from admission), 3 (a tile padded with a zero row) and every
    power of two up to ``max_batch``; and a pair of every two keep rates
    (one tile up to the first TDM, then two lanes). Returns the number of
    requests served."""
    from repro.serving import VisionRequest
    mb = engine.vc.max_batch
    rates = sorted({float(r) for r, _ in mix["keep_rates"]})
    sizes = sorted({1, 3} | {1 << i for i in range(mb.bit_length())
                             if 1 << i <= mb})
    cohorts = [[r] * b for r in rates for b in sizes if b <= mb]
    cohorts += [[a, b] for i, a in enumerate(rates) for b in rates[i + 1:]]
    uid, served = -1, 0
    for cohort in cohorts:
        reqs = []
        for r in cohort:
            reqs.append(VisionRequest(uid=uid, patches=pool[served %
                                                            len(pool)],
                                      r_t=r))
            uid -= 1
            served += 1
        engine.serve(reqs)
    return served


def build_engine(cfg, config: dict, params, scores, tracer=None):
    from repro.serving import VisionEngine, VisionEngineConfig
    e = dict(config["engine"])
    policy = e.pop("policy")
    vc = VisionEngineConfig(**e)
    return VisionEngine.from_pruned(cfg, params, W.program_scores(scores),
                                    vc=vc, policy=policy, tracer=tracer)


class Window:
    """Drives one measured window; see the module docstring."""

    def __init__(self, engine, mix: dict, seed: int, pool: np.ndarray,
                 log: Optional[CallLog]):
        from repro.serving import VisionRequest
        self.VisionRequest = VisionRequest
        self.engine = engine
        self.mix = mix
        self.seed = seed
        self.pool = pool
        self.log = log
        self.out = Outbox()
        self.requests: Dict[int, Request] = {}
        self.step = 0
        self.trace_state = None
        self.on_tick = None  # optional probe, called with the host time

    # -- requests -----------------------------------------------------------
    def _submit(self, reqs: List[Request], now: float) -> None:
        self.engine.enqueue([self.VisionRequest(
            uid=r.uid, patches=self.pool[r.image], r_t=r.r_t) for r in reqs])
        for r in reqs:
            r.sent_s = now
            self.requests[r.uid] = r

    def _tick(self) -> None:
        rep = self.engine.tick(self.out)
        for uid in rep.admitted:
            self.requests[uid].admitted_step = self.step
        for uid in rep.completed:
            self.requests[uid].done_step = self.step
        self.step += 1

    # -- tracing ------------------------------------------------------------
    def _maybe_trace(self, now: float) -> None:
        ts = self.trace_state
        if ts is None or ts["done"]:
            return
        import jax
        if not ts["on"] and now >= ts["start"]:
            jax.profiler.start_trace(ts["dir"], profiler_options=ts["opts"])
            ts["ann"] = jax.profiler.TraceAnnotation("bench_window")
            ts["ann"].__enter__()
            ts["on"] = True
            ts["t_on"] = time.perf_counter()
            ts["marks_on"] = self._marks()
            self.log.on = True
        elif ts["on"] and now >= ts["stop"]:
            self.log.on = False
            ts["t_off"] = time.perf_counter()
            ts["marks_off"] = self._marks()
            ts["ann"].__exit__(None, None, None)
            jax.profiler.stop_trace()
            ts["on"] = False
            ts["done"] = True

    def _marks(self) -> dict:
        st = self.engine.stats()
        return {"steps": st["steps"], "calls": len(self.log.calls),
                "real_cells": st["batcher_real_cells"],
                "padded_cells": st["batcher_padded_cells"],
                "done": len(self.out)}

    def arm_trace(self, t0: float, seconds: float, log_dir: str) -> None:
        import jax
        span = min(float(self.mix["trace_seconds"]), seconds)
        start = t0 + (seconds - span) / 2
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        opts.host_tracer_level = 1
        self.trace_state = {"start": start, "stop": start + span,
                            "dir": log_dir, "opts": opts, "on": False,
                            "done": False}

    # -- loops --------------------------------------------------------------
    def _refill(self, r_cycle: List[float], uid: int, now: float) -> int:
        """Top the closed loop's queue up; returns the next free uid."""
        mb = self.engine.vc.max_batch
        target = mb + int(self.mix["backlog_per_slot"]) * mb
        missing = target - (len(self.requests) - len(self.out))
        if missing > 0:
            self._submit([Request(uid=u, image=u % len(self.pool),
                                  r_t=r_cycle[u % len(r_cycle)])
                          for u in range(uid, uid + missing)], now)
            uid += missing
        return uid

    def closed(self, t0: float, seconds: float, r_cycle: List[float],
               uid: int) -> float:
        """Closed loop from ``t0``, a completion, to the first completion
        at or after ``t0 + seconds``; returns that completion's time. The
        window so spans whole cohorts of the lock-step population."""
        while True:
            now = time.perf_counter()
            self._maybe_trace(now)
            uid = self._refill(r_cycle, uid, now)
            n_done = len(self.out)
            self._tick()
            if len(self.out) > n_done and (
                    max(self.out.done_t.values()) >= t0 + seconds):
                return max(self.out.done_t.values())

    def open(self, t0: float, reqs: List[Request], drain_s: float) -> float:
        """Open loop: send each request at ``t0 + due_s``; after the last
        is sent, tick until every request is complete or ``drain_s`` has
        passed since the last due time. Returns the end of the window."""
        import jax
        i, n = 0, len(reqs)
        t_last = t0 + (reqs[-1].due_s if reqs else 0.0)
        tr = self.trace_state
        while True:
            now = time.perf_counter()
            self._maybe_trace(now)
            if self.on_tick is not None:
                self.on_tick(now)
            j = i
            while j < n and t0 + reqs[j].due_s <= now:
                j += 1
            if j > i:
                if tr is not None and tr["on"]:
                    with jax.profiler.TraceAnnotation("enqueue"):
                        self._submit(reqs[i:j], now)
                else:
                    self._submit(reqs[i:j], now)
                i = j
            if len(self.requests) > len(self.out):
                self._tick()
            elif i < n:
                wait = t0 + reqs[i].due_s - time.perf_counter()
                if wait > 0:
                    if tr is not None and tr["on"]:
                        with jax.profiler.TraceAnnotation("client_wait"):
                            time.sleep(wait)
                    else:
                        time.sleep(wait)
            else:
                return max(t_last, t0)
            if i == n and now > t_last + drain_s:
                return max(t_last, t0)

    def ramp(self, r_cycle: List[float], images: int) -> Tuple[int, float]:
        """Closed-loop ticks before the window, until ``images`` are done
        and a tick has just completed some: the window opens in steady
        state, at a completion. Returns the next free uid and that
        completion's time."""
        uid = 0
        while True:
            uid = self._refill(r_cycle, uid, time.perf_counter())
            n_done = len(self.out)
            self._tick()
            if len(self.out) > n_done and len(self.out) >= images:
                return uid, max(self.out.done_t.values())


def open_requests(mix: dict, seconds: float, seed: int, pool_size: int
                  ) -> List[Request]:
    due = T.arrivals_ms(mix, float(mix["rate_rps"]), seconds, seed)
    rates = T.keep_rates(mix, len(due), seed)
    images = T.seed_rng(seed, 4).integers(pool_size, size=len(due))
    return [Request(uid=i, image=int(images[i]), r_t=float(rates[i]),
                    due_s=float(due[i]) / 1e3) for i in range(len(due))]


def run(cell, seed: int, seconds: float, trace: bool, t_start: float,
        cfg=None, trace_dir: Optional[str] = None):
    """Set up, warm up and measure one run of ``cell``. Returns the
    records, the engine's inputs kept for the reference, and the device
    memory peak."""
    import jax

    from . import spec

    config, mix = cell.config, cell.mix
    if cfg is None:
        cfg = spec.model_config(config)
    model = {"num_layers": cfg.num_layers, "d_model": cfg.d_model,
             "num_heads": cfg.num_heads, "d_ff": cfg.d_ff,
             "image_size": cfg.image_size, "patch_size": cfg.patch_size,
             "num_classes": cfg.num_classes, "norm_eps": cfg.norm_eps}
    pruning = {"block_size": cfg.pruning.block_size,
               "r_b": cfg.pruning.r_b,
               "tdm_layers": list(cfg.pruning.tdm_layers)}
    marks = [("start", t_start)]
    # the program's XLA matmuls (and any kernel dot left at its default)
    # run at the precision the configuration states
    jax.config.update("jax_default_matmul_precision",
                      config["numerics"]["matmul_precision"])
    params = W.make_params(model, seed)
    jax.block_until_ready(params)
    marks.append(("weights", time.perf_counter()))
    scores = W.make_scores(model, pruning, config["structure_seed"])
    tracer = make_profiled_tracer() if trace else None
    engine = build_engine(cfg, config, params, scores, tracer=tracer)
    marks.append(("engine (prune, pack)", time.perf_counter()))
    log = CallLog(engine.segments) if trace else None
    n_programs = warm_up(engine, cfg, mix, cell.reference)
    marks.append(("warm-up", time.perf_counter()))
    P = cfg.patch_size ** 2 * 3
    pool = T.image_pool(mix, mix["patches"], P, seed)
    if mix["warm"].get("cohorts"):
        warm_cohorts(engine, mix, pool)
    marks.append(("cohorts", time.perf_counter()))
    win = Window(engine, mix, seed, pool, log)
    n_segs = len(engine.segments.plan)
    if mix["loop"] == "closed":
        r_cycle = T.cyclic_keep_rates(mix, seed)
        first_uid, t0 = win.ramp(r_cycle, 2 * engine.vc.max_batch)
    else:
        reqs = open_requests(mix, seconds, seed, len(pool))
        t0 = time.perf_counter()
    watch = CompileWatch.get()
    compiles0 = watch.programs
    watch.names = []
    marks.append(("images, ramp", t0))
    setup_s = t0 - t_start
    print("set-up phases: " + ", ".join(
        f"{name} {b - a:.3f} s" for (_, a), (name, b) in
        zip(marks, marks[1:])) + f"; programs {watch.programs}, of them "
        f"from the cache {watch.cache_hits}, compiling "
        f"{watch.compile_s:.3f} s; not in the cache: "
        f"{dict(Counter(watch.missed))}", file=sys.stderr)
    if trace:
        if os.path.isdir(trace_dir):
            shutil.rmtree(trace_dir)
        win.arm_trace(t0, seconds, trace_dir)
    if mix["loop"] == "closed":
        t_end = win.closed(t0, seconds, r_cycle, first_uid)
    else:
        t_end = win.open(t0, reqs, float(mix["drain_seconds"]))
    engine.finish()
    lowered, watch.names = watch.names, None
    records = Records(
        requests=win.requests, out=win.out, t0=t0, t_end=t_end,
        setup_s=setup_s,
        window_compiles=watch.programs - compiles0,
        window_lowered=lowered,
        steps=win.step, segments_per_request=n_segs,
        trace=win.trace_state,
        planner={k: v for k, v in engine.stats().items()
                 if k.startswith("plan_") and isinstance(v, (int, float))})
    if trace:
        records.trace["spans"] = tracer.span_log
        records.trace["tracer_t0"] = tracer._t0
        records.trace["calls"] = log.calls
    info = {"programs_warmed": n_programs, "model": model,
            "pruning": pruning, "scores": scores, "params": params,
            "mix_rates": sorted({float(r) for r, _ in mix["keep_rates"]}),
            "pool": pool, "cfg": cfg}
    return records, engine, info
