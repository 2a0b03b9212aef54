"""Execution planning for ragged ViT serving: naive padding vs balanced
bucketing vs the cost-model-driven TilePlanner.

The pruned ViT's token pruning leaves the in-flight population ragged:
images enter at different resolutions and shed tokens at every TDM layer at
their own keep rates. This bench serves identical request streams through
the ``VisionEngine`` under the batching/planning strategies, over two
scenarios:

* ``mixed``  — the PR-4 workload: skewed resolution mix, dense arrivals.
  Modes: ``naive`` (pad-to-max batch), ``balanced`` (RaggedBatcher exact
  buckets, planner off), ``planned`` (TilePlanner — ``--planner`` selects
  merge/fuse/full; ``off`` makes this an A/A control).
* ``sparse`` — singleton-heavy: every request has a distinct patch count
  and arrivals are spread out, so buckets almost never batch and the
  balanced path pays one dispatch per segment per image. This is the
  express-lane case: the planner fuses each bucket-singleton's remaining
  trajectory into ONE jitted program. Modes: ``balanced`` vs ``planned``.

Before the timed windows (full runs), the bench calibrates the planner's
``TileCostModel`` from measured dispatch timings
(``TileCostModel.calibrate``), so merge decisions trade measured host
dispatch overhead against modeled padding cost instead of the FPGA-era
default constant.

Reported per mode: throughput (images/s and token·segment cells/s), padding
waste, the recompile-discipline columns (jit compiles vs the bucket ∪
trajectory budget), and the plan-stats columns (merge count, fused-lane
count, deadline dispatches, modeled saving).

    PYTHONPATH=src python benchmarks/vision_bench.py                # full
    PYTHONPATH=src python benchmarks/vision_bench.py --smoke        # CI lane
    PYTHONPATH=src python benchmarks/vision_bench.py --smoke --planner off

Every timed arm runs at ``--pipeline-depth`` (1 = synchronous) and under
``--quality`` / ``--keep-floor`` (the QualityController: ``strict`` = off,
the control CI also runs; ``degrade``/``auto`` enable keep-rate
tightening). A quality Pareto block always sweeps the ``degrade`` floor
over the keep-level grid on a uniform-rate stream and asserts the
elasticity property: modeled latency strictly decreases as the floor
tightens, recompiles stay within the bucket ∪ trajectory budget, and a
top-1 agreement column proxies the accuracy cost. A cross-depth
comparison block always serves the planned mixed arm at depths
1 and 2: outputs must be bit-identical (sha256 ``outputs_digest`` — the CI
fast lane also compares digests between whole ``--pipeline-depth 1`` and
``2`` artifacts), and the ``wall_vs_device`` / ``device_idle_s`` columns
quantify how much host time the double-buffered pipeline hides behind
device execution.

A ``BENCH_vision.json`` artifact is written through the schema-versioned
``repro.bench`` envelope shared with serving_bench.py (``--out``
overrides). Exit is non-zero if any mode fails to serve every request,
exceeds its recompile budget, or disagrees across pipeline depths; the
full run additionally requires balanced bucketing to beat naive padding,
``--planner full`` to be at least as fast as balanced on the mixed
workload, strictly faster on the sparse singleton-heavy scenario (the
planner's acceptance claims), and pipeline depth 2 to idle the device
strictly less than depth 1.
"""
from __future__ import annotations

import argparse
import sys
import time


def make_requests(cfg, num: int, arrival_spread: int, seed: int,
                  unique_sizes: bool = False):
    from repro.launch.serve_vision import make_requests as _mk

    if unique_sizes:
        # the sparse scenario: every patch count distinct -> every bucket a
        # singleton; arrivals spread so the population stays thin
        return _mk(cfg, num, arrival_spread, seed,
                   r_ts=[0.5, cfg.pruning.r_t], unique_sizes=True)
    # the launcher's stream generator, skewed toward small images (the
    # realistic mix where naive padding hurts: most requests pay the
    # largest in-flight image's cost)
    return _mk(cfg, num, arrival_spread, seed,
               r_ts=[0.5, cfg.pruning.r_t],
               size_weights=[0.5, 0.3, 0.2])


def calibrate_cost_model(cfg, masked, cost_model, seed: int,
                         reps: int = 3):
    """Fit the cost model's dispatch-overhead constant and cycle->seconds
    scale from measured wall-clock dispatch timings (the satellite hook:
    ``TileCostModel.calibrate``). Probes a jitted encoder segment at two
    batch widths on a THROWAWAY executor so the serving engines' compile
    ledgers stay untouched."""
    import jax
    import numpy as np

    from repro.core import packed_runner as PR
    from repro.serving import Tile

    probe = PR.PackedVitSegments(cfg, masked)
    seg = next(s for s in probe.plan if s[0] == "layers")
    si = probe.plan.index(seg)
    rng = np.random.default_rng(seed)
    n = 16
    samples = []
    for b in (1, 8):
        x = rng.standard_normal((b, n, cfg.d_model)).astype(np.float32)
        jax.block_until_ready(probe.run(seg, x))  # compile outside timing
        times = []
        for _ in range(reps):
            t0 = time.perf_counter()
            jax.block_until_ready(probe.run(seg, x))
            times.append(time.perf_counter() - t0)
        tile = Tile(stage=(si, seg, None), members=tuple(range(b)),
                    n_tokens=(n,) * b, n_tile=n, b_tile=b)
        samples.append((cost_model.tile_work_cycles(tile), min(times)))
    return cost_model.calibrate(samples)


def outputs_digest(out) -> str:
    """Order-independent sha256 over every served logit vector — equal
    digests mean bit-identical outputs (the cross-depth CI check compares
    these between ``--pipeline-depth 1`` and ``2`` artifacts)."""
    import hashlib

    import numpy as np

    h = hashlib.sha256()
    for uid in sorted(out):
        h.update(np.asarray(out[uid], np.float32).tobytes())
    return h.hexdigest()


def run_mode(cfg, masked, cost_model, reqs_factory, *, slots: int,
             bmode: str, planner: str, pipeline_depth: int = 1,
             quality: str = "strict", keep_floor: float = 0.4,
             tracer=None, registry=None, metrics_prefix: str = "vision"):
    """Serve the stream twice (warmup compiles every shape on the identical
    stream — arrival dynamics replay exactly) and time the second pass.
    ``tracer`` (repro.obs) records wall-clock plan/stage/dispatch/complete
    spans; ``registry`` receives the engine's end-of-run metrics under
    ``metrics_prefix``. Both observe only — results and digests are
    identical with or without them."""
    from repro.serving import VisionEngine, VisionEngineConfig

    vc = VisionEngineConfig(max_batch=slots, mode=bmode, token_tile=1,
                            planner=planner, pipeline_depth=pipeline_depth,
                            quality=quality, keep_floor=keep_floor)
    engine = VisionEngine(cfg, masked, vc, cost_model=cost_model,
                          tracer=tracer)
    engine.serve(reqs_factory())
    warm = engine.stats()
    reqs = reqs_factory()
    t0 = time.time()
    out = engine.serve(reqs)
    dt = time.time() - t0
    st = engine.stats()
    real = (st["batcher_real_cells"] - warm["batcher_real_cells"]
            + st["plan_lane_cells"] - warm["plan_lane_cells"])
    # device-busy proxy: at depth 1 the host dispatches then blocks, so
    # dispatch + block wall time brackets the device's work (less what
    # unwaited steps, which read nothing on the host, ran while the host
    # staged the next step); wall_vs_device > 1 is host overhead the
    # pipeline can hide
    busy = (st["pipeline_block_s"] - warm["pipeline_block_s"]
            + st["pipeline_dispatch_s"] - warm["pipeline_dispatch_s"])
    if registry is not None:
        engine.export_metrics(registry, prefix=metrics_prefix)
    return {
        "seconds": dt,
        "images_s": len(out) / dt,
        "cells_s": real / dt,
        "outputs_digest": outputs_digest(out),
        "pipeline_depth": pipeline_depth,
        "pipeline_block_s": st["pipeline_block_s"] - warm["pipeline_block_s"],
        "wall_vs_device": dt / max(busy, 1e-9),
        "served": len(out), "expected": len(reqs),
        "padding_waste": st["batcher_padding_waste"],
        "buckets": st["bucket_count"],
        "trajectories": st["trajectory_count"],
        "compile_budget": st["compile_budget"],
        "jit_compiles": st["jit_compile_count"],
        "recompile_bound_ok":
            st["jit_compile_count"] <= st["compile_budget"],
        # plan-stats columns (schema: vision kind, v1 envelope)
        "planner": st["plan_mode"],
        "merge_count": st["plan_merges"],
        "fused_lane_count": st["plan_lanes"],
        "fused_segments": st["plan_fused_segments"],
        "deadline_dispatches": st["plan_deadline_urgent"],
        "modeled_saving_ms": st["plan_modeled_saving_ms"],
        "calibrated": st["plan_calibrated"],
    }


def quality_pareto(cfg, masked, cost_model, reqs_factory, *,
                   slots: int, planner: str, registry=None):
    """The quality-elasticity Pareto sweep: serve the identical stream at
    progressively tighter keep floors (``degrade`` mode pins every
    consenting request to the lowest usable grid level, so each arm IS one
    floor) and report modeled latency vs a top-1 agreement accuracy proxy
    against the ``strict`` (controller off) arm.

    ``modeled_ms`` is a deterministic end-to-end price of the stream under
    each arm's resolved schedules — the Pareto x-axis the acceptance
    criterion asserts on (strictly decreasing as the floor tightens),
    immune to shared-CI wall-clock noise. It is priced at token
    resolution (the cost model's attention-shaped proxy, quadratic +
    linear in the token count); the paper's accelerator tile model
    (``modeled_tile_ms``, also reported) quantizes token counts to tile
    boundaries, which ties neighboring keep counts at smoke scale and
    would hide real load reductions. Recompiles must stay within the
    bucket ∪ trajectory budget in every arm: the controller only resolves
    onto the quantized grid."""
    import numpy as np

    from repro.serving import TileCostModel, VisionEngine, VisionEngineConfig

    # cfg=None -> every stage priced by the token-resolution proxy; same
    # overhead/scale as the (possibly calibrated) tile model
    proxy_cm = TileCostModel(
        None, dispatch_overhead_cycles=cost_model.dispatch_overhead_cycles,
        seconds_per_cycle=cost_model.seconds_per_cycle)
    levels = (1.0, 0.8, 0.65, 0.5, 0.35)
    arms = [("strict", "strict", 0.35)] + [
        (f"floor={f}", "degrade", f) for f in (0.65, 0.5, 0.35)]
    rows = []
    base_top1 = None
    for name, qmode, floor in arms:
        vc = VisionEngineConfig(max_batch=slots, mode="balanced",
                                token_tile=1, planner=planner,
                                quality=qmode, keep_levels=levels,
                                keep_floor=floor)
        eng = VisionEngine(cfg, masked, vc, cost_model=cost_model)
        eng.serve(reqs_factory())  # warmup compiles the arm's shapes
        reqs = reqs_factory()
        t0 = time.time()
        out = eng.serve(reqs)
        dt = time.time() - t0
        st = eng.stats()
        # price the whole stream under this arm's resolved schedules
        # (degrade resolution is pressure-independent, so the host-side
        # replay here matches what the engine dispatched)
        q = eng.planner.quality
        modeled = tile_modeled = 0.0
        for r in reqs:
            eff = q.resolve(eng._base_schedule(r), preference=r.quality)
            traj = eng._traj_from(0, r.n_patches, eff, r.soft_prune)
            modeled += proxy_cm.ms(proxy_cm.trajectory_cycles(traj))
            tile_modeled += cost_model.ms(
                cost_model.trajectory_cycles(traj))
        top1 = {u: int(np.argmax(lg)) for u, lg in out.items()}
        if base_top1 is None:
            base_top1 = top1
        if registry is not None:
            # per-floor quality-tighten counters (schema-v4 metrics block)
            pfx = f"pareto.floor_{floor:g}" if qmode != "strict" \
                else "pareto.strict"
            registry.gauge(f"{pfx}.tightened_steps").set(
                st["quality_tightened"])
            for lvl, n in sorted(q.level_counts.items()):
                registry.gauge(
                    f"{pfx}.quality_tightened_level_{lvl:g}").set(n)
        rows.append({
            "arm": name, "quality": qmode, "keep_floor": floor,
            "keep_levels": list(levels),
            "modeled_ms": modeled,
            "modeled_tile_ms": tile_modeled,
            "seconds": dt, "images_s": len(out) / dt,
            "top1_agreement": (sum(top1[u] == base_top1[u] for u in top1)
                               / max(len(top1), 1)),
            "served": len(out), "expected": len(reqs),
            "tightened_steps": st["quality_tightened"],
            "levels_used": list(st["quality_levels_used"]),
            "jit_compiles": st["jit_compile_count"],
            "compile_budget": st["compile_budget"],
            "recompile_bound_ok":
                st["jit_compile_count"] <= st["compile_budget"],
        })
    return rows


def pipeline_compare(cfg, masked, cost_model, reqs_factory, *,
                     slots: int, planner: str):
    """Serve the identical mixed stream through the planned arm at
    pipeline depth 1 (synchronous) and 2 (double-buffered): outputs must
    be bit-identical, and the ``wall_vs_device`` column shows how much
    host overhead sits on top of the depth-1 device-busy proxy (dispatch
    + block — at depth 1 the host blocks right after each dispatch, so
    that sum brackets the device's work). ``device_idle_s`` is the
    pipeline's measured starvation time: wall seconds with zero steps in
    flight, i.e. the device waiting while the host plans/stages. Depth 2
    keeps a step queued across every stage window, so on the full bench
    it must idle the device strictly less than depth 1 — a queue-
    occupancy fact that holds even on shared-core CPU hosts where
    overlap cannot shrink the wall clock itself."""
    from repro.serving import VisionEngine, VisionEngineConfig

    rows = {}
    for depth in (1, 2):
        vc = VisionEngineConfig(max_batch=slots, mode="balanced",
                                token_tile=1, planner=planner,
                                pipeline_depth=depth)
        engine = VisionEngine(cfg, masked, vc, cost_model=cost_model)
        engine.serve(reqs_factory())
        warm = engine.stats()
        t0 = time.time()
        out = engine.serve(reqs_factory())
        wall = time.time() - t0
        st = engine.stats()
        rows[f"depth{depth}"] = {
            "wall_s": wall,
            "block_s": st["pipeline_block_s"] - warm["pipeline_block_s"],
            "dispatch_s": (st["pipeline_dispatch_s"]
                           - warm["pipeline_dispatch_s"]),
            "steps": st["pipeline_steps"] - warm["pipeline_steps"],
            "overlap_hits": (st["pipeline_overlap_hits"]
                             - warm["pipeline_overlap_hits"]),
            "device_idle_s": (st["pipeline_starved_s"]
                              - warm["pipeline_starved_s"]),
            "plan_ahead_hits": st["plan_ahead_hits"],
            "served": len(out),
            "outputs_digest": outputs_digest(out),
        }
    busy_ref = rows["depth1"]["block_s"] + rows["depth1"]["dispatch_s"]
    for r in rows.values():
        r["wall_vs_device"] = r["wall_s"] / max(busy_ref, 1e-9)
    rows["bitexact"] = (rows["depth1"]["outputs_digest"]
                        == rows["depth2"]["outputs_digest"])
    return rows


def bench(arch: str, num: int, slots: int, arrival_spread: int,
          image_size: int, d_model: int, seed: int, planner: str,
          calibrate: bool, pipeline_depth: int = 1,
          quality: str = "strict", keep_floor: float = 0.4,
          tracer=None, registry=None):
    import jax

    from repro.configs import get_config
    from repro.models import model as M
    from repro.models import pruning_glue as PG
    from repro.serving import TileCostModel

    # reduced() shrinks depth/width for CPU; image_size and d_model set
    # the per-cell compute — big enough that cell count (not dispatch
    # overhead) dominates, which is where load balancing pays
    cfg = get_config(arch).reduced().replace(image_size=image_size)
    if d_model:
        cfg = cfg.replace(d_model=d_model, d_ff=2 * d_model,
                          head_dim=d_model // cfg.num_heads)
    key = jax.random.PRNGKey(seed)
    params = M.init_params(cfg, key)
    scores = PG.init_scores(cfg, params, jax.random.fold_in(key, 7))
    masked = PG.apply_pruning(cfg, params, scores)

    cost_model = TileCostModel(cfg)
    fit = None
    if calibrate:
        fit = calibrate_cost_model(cfg, masked, cost_model, seed)

    mixed = lambda: make_requests(cfg, num, arrival_spread, seed)
    sparse = lambda: make_requests(cfg, num, max(2 * num, arrival_spread),
                                   seed + 1, unique_sizes=True)
    # the Pareto stream runs every request at the config keep rate so each
    # sweep floor below it actually tightens (mixed per-request rates would
    # leave sub-floor requests untouched and flatten the curve)
    from repro.launch.serve_vision import make_requests as _mk
    pareto = lambda: _mk(cfg, num, arrival_spread, seed + 2, r_ts=[None],
                         size_weights=[0.5, 0.3, 0.2])
    results = {"mixed": {}, "sparse": {}}
    for mode, bmode, pmode in (("naive", "naive", "off"),
                               ("balanced", "balanced", "off"),
                               ("planned", "balanced", planner)):
        # the planned mixed arm is the bench's headline configuration —
        # it is the one that carries the trace and the metrics snapshot
        planned = mode == "planned"
        results["mixed"][mode] = run_mode(
            cfg, masked, cost_model, mixed,
            slots=slots, bmode=bmode, planner=pmode,
            pipeline_depth=pipeline_depth,
            quality=quality, keep_floor=keep_floor,
            tracer=tracer if planned else None,
            registry=registry if planned else None)
    for mode, pmode in (("balanced", "off"), ("planned", planner)):
        results["sparse"][mode] = run_mode(
            cfg, masked, cost_model, sparse,
            slots=slots, bmode="balanced", planner=pmode,
            pipeline_depth=pipeline_depth,
            quality=quality, keep_floor=keep_floor)
    results["pipeline"] = pipeline_compare(
        cfg, masked, cost_model, mixed, slots=slots, planner=planner)
    results["quality_pareto"] = quality_pareto(
        cfg, masked, cost_model, pareto, slots=slots,
        planner=planner, registry=registry)
    return results, fit


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="deit-small")
    ap.add_argument("--requests", type=int, default=32)
    ap.add_argument("--slots", type=int, default=4)
    ap.add_argument("--arrival-spread", type=int, default=4,
                    help="admission staggered over this many engine steps")
    ap.add_argument("--image-size", type=int, default=64,
                    help="reduced-config image size (token load knob)")
    ap.add_argument("--d-model", type=int, default=128,
                    help="reduced-config width override (0 = keep)")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--planner", default="full",
                    choices=("off", "merge", "fuse", "full"),
                    help="TilePlanner mode for the 'planned' arm (off = "
                         "A/A control against balanced)")
    ap.add_argument("--pipeline-depth", type=int, default=1,
                    help="StepPipeline depth for every timed arm (1 = "
                         "synchronous; 2 = stage N+1 while the device "
                         "runs N). The cross-depth comparison block "
                         "always runs at both depths regardless.")
    ap.add_argument("--quality", default="strict",
                    choices=("strict", "auto", "degrade"),
                    help="QualityController mode for the timed arms "
                         "(strict = off, the control; the Pareto "
                         "sweep block always runs its own strict + "
                         "degrade-floor arms regardless)")
    ap.add_argument("--keep-floor", type=float, default=0.4,
                    help="controller keep-rate floor for the timed arms "
                         "(no request is tightened below it)")
    ap.add_argument("--out", default="BENCH_vision.json",
                    help="JSON artifact path")
    ap.add_argument("--trace-out", default="", metavar="PATH",
                    help="write a Chrome trace_event JSON (Perfetto-"
                         "loadable) of the planned mixed arm's plan/"
                         "stage/dispatch/complete spans; tracing observes "
                         "only — outputs_digest is identical with it on "
                         "or off (CI asserts this)")
    ap.add_argument("--smoke", action="store_true",
                    help="seconds-scale run for the CI fast lane (skips "
                         "cost-model calibration and perf assertions)")
    args = ap.parse_args()
    if args.smoke:
        args.requests, args.slots = 8, 4
        args.arrival_spread, args.image_size, args.d_model = 3, 32, 0

    from repro.obs import MetricsRegistry, Tracer
    tracer = Tracer() if args.trace_out else None
    registry = MetricsRegistry()
    res, fit = bench(args.arch, args.requests, args.slots,
                     args.arrival_spread, args.image_size, args.d_model,
                     args.seed, args.planner, calibrate=not args.smoke,
                     pipeline_depth=args.pipeline_depth,
                     quality=args.quality, keep_floor=args.keep_floor,
                     tracer=tracer, registry=registry)
    if args.trace_out:
        tracer.write_chrome_trace(args.trace_out)
        print(f"wrote {args.trace_out} ({tracer.event_count} trace events)")
    if fit:
        print(f"cost model calibrated: overhead="
              f"{fit['dispatch_overhead_cycles']:.0f} cycles "
              f"({fit['overhead_seconds'] * 1e6:.0f}us), "
              f"r2={fit['r2']:.3f}")
    ok = True
    hdr = (f"{'scenario':9s} {'mode':9s} {'img/s':>8s} {'cells/s':>10s} "
           f"{'served':>7s} {'waste':>7s} {'jit<=budget':>11s} "
           f"{'merges':>6s} {'lanes':>6s} {'save_ms':>8s}")
    print(hdr)
    for scen, modes in res.items():
        if scen in ("pipeline", "quality_pareto"):
            continue
        for mode, r in modes.items():
            served = f"{r['served']}/{r['expected']}"
            budget = f"{r['jit_compiles']}<={r['compile_budget']}"
            print(f"{scen:9s} {mode:9s} {r['images_s']:8.2f} "
                  f"{r['cells_s']:10.0f} {served:>7s} "
                  f"{r['padding_waste']:7.1%} {budget:>11s} "
                  f"{r['merge_count']:6d} {r['fused_lane_count']:6d} "
                  f"{r['modeled_saving_ms']:8.2f}")
            ok &= r["served"] == r["expected"]
            ok &= r["recompile_bound_ok"]

    mixed, sparse = res["mixed"], res["sparse"]
    bal_naive = mixed["balanced"]["images_s"] / mixed["naive"]["images_s"]
    plan_mixed = mixed["planned"]["images_s"] / mixed["balanced"]["images_s"]
    plan_sparse = (sparse["planned"]["images_s"]
                   / sparse["balanced"]["images_s"])
    measured_saving_ms = (sparse["balanced"]["seconds"]
                          - sparse["planned"]["seconds"]) * 1e3
    print(f"balanced vs naive (mixed): {bal_naive:.2f}x images/s; padding "
          f"waste {mixed['naive']['padding_waste']:.1%} -> "
          f"{mixed['balanced']['padding_waste']:.1%}")
    print(f"planner={args.planner} vs balanced: {plan_mixed:.2f}x (mixed), "
          f"{plan_sparse:.2f}x (sparse); sparse saving modeled="
          f"{sparse['planned']['modeled_saving_ms']:.1f}ms measured="
          f"{measured_saving_ms:.1f}ms")
    pareto = res["quality_pareto"]
    print(f"{'pareto arm':12s} {'modeled_ms':>10s} {'img/s':>8s} "
          f"{'top1_agree':>10s} {'tightened':>9s} {'jit<=budget':>11s}")
    for row in pareto:
        budget = f"{row['jit_compiles']}<={row['compile_budget']}"
        print(f"{row['arm']:12s} {row['modeled_ms']:10.4f} "
              f"{row['images_s']:8.2f} {row['top1_agreement']:10.2f} "
              f"{row['tightened_steps']:9d} {budget:>11s}")
        ok &= row["served"] == row["expected"]
        ok &= row["recompile_bound_ok"]
    # the quality-elasticity acceptance property: tightening the keep
    # floor must strictly shrink the modeled latency of the stream, in
    # smoke and full runs alike (it is a deterministic cost-model fact)
    pareto_monotone = all(
        a["modeled_ms"] > b["modeled_ms"]
        for a, b in zip(pareto, pareto[1:]))
    print(f"pareto modeled latency strictly decreasing as keep floor "
          f"tightens: {pareto_monotone}")
    ok &= pareto_monotone

    pipe = res["pipeline"]
    d1, d2 = pipe["depth1"], pipe["depth2"]
    print(f"pipeline (planned, mixed): depth1 wall={d1['wall_s']:.3f}s "
          f"wall_vs_device={d1['wall_vs_device']:.2f} "
          f"idle={d1['device_idle_s'] * 1e3:.0f}ms | depth2 "
          f"wall={d2['wall_s']:.3f}s "
          f"wall_vs_device={d2['wall_vs_device']:.2f} "
          f"idle={d2['device_idle_s'] * 1e3:.0f}ms "
          f"overlap={d2['overlap_hits']}/{d2['steps']} "
          f"bitexact={pipe['bitexact']}")
    ok &= pipe["bitexact"]

    from repro.bench import write_bench_artifact
    write_bench_artifact(
        args.out, kind="vision",
        config={k: v for k, v in vars(args).items()
                if k not in ("out", "trace_out")},
        results=res,
        extra={"balanced_vs_naive": bal_naive,
               "planned_vs_balanced_mixed": plan_mixed,
               "planned_vs_balanced_sparse": plan_sparse,
               "sparse_measured_saving_ms": measured_saving_ms,
               "calibration": fit},
        seed=args.seed,
        metrics=registry.snapshot())
    print(f"wrote {args.out}")
    if not ok:
        print("FAIL: unserved requests, recompile budget exceeded, "
              "pipeline depths disagreed bit-for-bit, or the quality "
              "Pareto sweep was not strictly monotone", file=sys.stderr)
        sys.exit(1)
    if not args.smoke:
        if bal_naive <= 1.0:
            print(f"FAIL: balanced bucketing ({bal_naive:.2f}x) did not "
                  f"beat naive padding", file=sys.stderr)
            sys.exit(1)
        if args.planner != "off" and plan_mixed < 1.0:
            print(f"FAIL: planner {args.planner} ({plan_mixed:.2f}x) lost "
                  f"to balanced on the mixed workload", file=sys.stderr)
            sys.exit(1)
        if args.planner in ("fuse", "full") and plan_sparse <= 1.0:
            print(f"FAIL: planner {args.planner} ({plan_sparse:.2f}x) must "
                  f"be strictly faster than balanced on the sparse "
                  f"singleton-heavy scenario", file=sys.stderr)
            sys.exit(1)
        if d2["device_idle_s"] >= d1["device_idle_s"]:
            print(f"FAIL: pipeline depth 2 must idle the device strictly "
                  f"less than depth 1 "
                  f"({d2['device_idle_s'] * 1e3:.0f}ms >= "
                  f"{d1['device_idle_s'] * 1e3:.0f}ms)", file=sys.stderr)
            sys.exit(1)


if __name__ == "__main__":
    main()
