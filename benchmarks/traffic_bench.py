"""Traffic bench: offered-load sweep, SLOs, and the admission-control knee.

The serving benches so far measure throughput on closed request lists;
this bench asks the production question instead: **what happens past
saturation?** A seeded bursty trace (``repro.traffic.workload``) is
replayed through the ``VisionEngine`` on the harness's virtual clock at a
ladder of offered loads, expressed as multiples of the engine's measured
saturation capacity, under three arms:

    unbounded  admission off, quality strict — the pre-traffic engine
               path (the harness's outputs are asserted equal to a
               direct ``serve()`` call's, to float32 rounding).
    admission  cost-model admission control (``traffic.admission``),
               quality strict: accept-or-reject against a modeled
               backlog budget.
    degrade    the same controller on a quality-enabled engine: requests
               that would be rejected are first retried at the quality
               floor (PR 7's QualityController) — quality degrades
               before goodput does.

Past the knee (offered > capacity) unbounded queueing serves everything
but the queue — and therefore every completion's latency — grows without
bound, so *goodput* (deadline-met completions per virtual second)
collapses while throughput looks healthy. The full run asserts the two
defining properties: the admission arms' queue depth stays bounded, and
their goodput strictly dominates unbounded queueing at every past-knee
load point.

Everything is virtual-time deterministic: the cost model is deliberately
left uncalibrated (calibration fits wall clock), so the artifact's
numbers — including every admission decision — are a pure function of
(seed, trace, config). The ``BENCH_traffic.json`` envelope records the
trace fingerprint + seed + git SHA (schema v3 provenance).

    PYTHONPATH=src python benchmarks/traffic_bench.py --smoke

``--smoke`` (the CI fast lane) replays one bursty trace at 4x capacity
under the unbounded and degrade arms, checks digest equality against the
direct serve path, bounded queues, and the envelope schema.
"""
from __future__ import annotations

import argparse
import math
import sys

import jax
import numpy as np

from repro.configs import get_config
from repro.models import model as M
from repro.models import pruning_glue as PG
from repro.serving import VisionEngine, VisionEngineConfig
from repro.traffic import (TraceSpec, TrafficHarness, VisionDriver,
                           make_trace, trace_fingerprint)

LOAD_FACTORS = (0.5, 2.0, 4.0)   # offered load as multiples of capacity
KNEE = 1.0


def build_engine(cfg, masked, slots: int, quality: str):
    return VisionEngine(
        cfg, masked,
        VisionEngineConfig(max_batch=slots, planner="full", quality=quality,
                           keep_floor=0.4))


def measure_capacity_rps(cfg, masked, slots, sizes, seed):
    """Saturation throughput on the virtual clock: replay a back-to-back
    trace (offered load far above any plausible capacity) and read the
    drain rate. Deterministic — it prices modeled cycles, not wall
    time."""
    eng = build_engine(cfg, masked, slots, "strict")
    probe = TraceSpec(n=4 * slots, rate_rps=1e6, process="poisson",
                      sizes=sizes, r_ts=(None,), deadlines_ms=(None,))
    h = TrafficHarness(VisionDriver(eng))
    rep = h.run(make_trace(probe, seed=seed + 101))
    mean_service_ms = rep["virtual_ms"] / probe.n
    return rep["throughput_rps"], mean_service_ms, eng


def run_arm(cfg, masked, slots, trace, arm, limit_ms):
    quality = "auto" if arm == "degrade" else "strict"
    eng = build_engine(cfg, masked, slots, quality)
    h = TrafficHarness(VisionDriver(eng),
                       admission_limit_ms=(None if arm == "unbounded"
                                           else limit_ms))
    rep = h.run(trace)
    rep["arm"] = arm
    return rep, h


def bench(arch: str, num: int, slots: int, seed: int, smoke: bool):
    cfg = get_config(arch).reduced()
    key = jax.random.PRNGKey(seed)
    params = M.init_params(cfg, key)
    scores = PG.init_scores(cfg, params, jax.random.fold_in(key, 7))
    masked = PG.apply_pruning(cfg, params, scores)

    side = cfg.image_size // cfg.patch_size
    sizes = tuple(sorted({max(1, side - 1) ** 2, side ** 2}))
    capacity_rps, mean_service_ms, cap_engine = measure_capacity_rps(
        cfg, masked, slots, sizes, seed)

    # SLO + budget geometry. Two different units on purpose: the
    # admission budget (``limit_ms``) is in MODELED solo ms — the units
    # the controller prices backlog in — while the deadline is in the
    # harness's virtual ms, anchored to the measured saturated service
    # time. A ~2-solo backlog budget drains in a few service times, so
    # admitted requests land inside a 6-service-time SLO; an unbounded
    # queue at 4x offered load pushes tail waits to ~9 service times and
    # blows through it. (Solo pricing overstates drain time — batching
    # and lane fusion make real steps cheaper — which only makes the
    # admitted arm's deadlines safer.)
    from repro.serving.vision import VisionRequest
    probe_req = VisionRequest(uid=-1, patches=np.zeros(
        (sizes[-1], cfg.patch_size ** 2 * 3), np.float32))
    solo_ms = cap_engine.modeled_request_ms(probe_req)
    limit_ms = 2.0 * solo_ms
    deadline_ms = 6.0 * mean_service_ms

    factors = (4.0,) if smoke else LOAD_FACTORS
    arms = ("unbounded", "degrade") if smoke else ("unbounded", "admission",
                                                   "degrade")
    results = {"capacity_rps": capacity_rps, "solo_ms": solo_ms,
               "mean_service_ms": mean_service_ms, "limit_ms": limit_ms,
               "deadline_ms": deadline_ms, "loads": {}}
    fingerprints = {}
    ok = True
    for lf in factors:
        spec = TraceSpec(n=num, rate_rps=lf * capacity_rps,
                         process="bursty", sizes=sizes, r_ts=(None,),
                         deadlines_ms=(deadline_ms,))
        trace = make_trace(spec, seed=seed)
        fingerprints[f"x{lf:g}"] = trace_fingerprint(trace)
        point = {}
        for arm in arms:
            rep, h = run_arm(cfg, masked, slots, trace, arm,
                             limit_ms)
            point[arm] = rep
            print(f"  load {lf:g}x {arm:>9}: completed={rep['completed']} "
                  f"rejected={rep['rejected']} "
                  f"goodput={rep['goodput_rps']:.1f}/s "
                  f"p50={rep['latency_p50_ms']:.2f}ms "
                  f"p99={rep['latency_p99_ms']:.2f}ms "
                  f"miss={rep['deadline_miss_rate']:.0%} "
                  f"peakq={rep['peak_queue_depth']}")
            if arm == "unbounded" and lf == factors[0]:
                # pre-PR equivalence: the harness with admission off must
                # serve a direct engine.serve()'s logits on the same
                # materialized requests (to float32 rounding: merged and
                # fused programs may sum in another order)
                eng = build_engine(cfg, masked, slots, "strict")
                drv = VisionDriver(eng)
                direct = eng.serve([drv.materialize(t)
                                    for t in trace.requests])
                same = (sorted(direct) == sorted(h.outputs) and all(
                    np.allclose(h.outputs[u], lg, rtol=1e-5, atol=1e-5)
                    for u, lg in direct.items()))
                point["harness_matches_direct_serve"] = same
                print(f"  load {lf:g}x harness==direct serve: {same}")
                ok &= same
        results["loads"][f"x{lf:g}"] = point

        if lf > KNEE:
            unb = point["unbounded"]
            for arm in arms[1:]:
                adm = point[arm]
                dominates = adm["goodput_rps"] > unb["goodput_rps"]
                bounded = (adm["peak_queue_depth"]
                           < unb["peak_queue_depth"])
                print(f"  load {lf:g}x {arm}: goodput dominates unbounded="
                      f"{dominates} queue bounded={bounded}")
                ok &= dominates and bounded

    return results, fingerprints, ok


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="deit-small")
    ap.add_argument("--requests", type=int, default=24)
    ap.add_argument("--slots", type=int, default=2)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--smoke", action="store_true",
                    help="CI fast lane: one bursty trace at 4x capacity, "
                         "unbounded vs degrade arms")
    ap.add_argument("--out", default="BENCH_traffic.json")
    args = ap.parse_args()
    if args.smoke:
        args.requests = min(args.requests, 12)

    res, fps, ok = bench(args.arch, args.requests, args.slots, args.seed,
                         args.smoke)

    from repro.bench import load_bench_artifact, write_bench_artifact
    # the sweep replays several traces; the envelope's provenance slot
    # records the first (the knee trace), the full set rides in extra
    first_fp = next(iter(fps.values()))
    write_bench_artifact(
        args.out, kind="traffic",
        config={k: v for k, v in vars(args).items() if k != "out"},
        results=res,
        extra={"trace_fingerprints": fps, "assertions_ok": ok},
        seed=args.seed, trace_fingerprint=first_fp)
    load_bench_artifact(args.out, expect_kind="traffic")  # self-check
    print(f"wrote {args.out} (trace {first_fp[:12]}..., "
          f"assertions_ok={ok})")
    if not ok:
        sys.exit(1)


if __name__ == "__main__":
    main()
