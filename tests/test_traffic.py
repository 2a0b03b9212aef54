"""repro.traffic: traces, virtual-clock SLO accounting, admission control.

The acceptance properties of the traffic subsystem:

* traces are pure functions of (spec, seed) — same fingerprint on every
  synthesis, fingerprint-preserving JSONL roundtrip, versioned schema;
* harness timestamps (submit / first-dispatch / retire, hence TTFD,
  latency and every deadline verdict) are identical at pipeline depths 1
  and 2 for the same trace, on both engines — the virtual clock prices
  plans, and PR 6 guarantees identical plans at any depth;
* with admission off, the harness serves byte-identical outputs to a
  direct ``engine.serve()`` call on the same requests (digest equality —
  the replay path adds accounting, never math);
* admission decisions are deterministic given (seed, trace, limit), the
  controller degrades before it rejects, and a rejected request never
  enters the queue (no submitted_total advance, no slot, a ``reject``
  event).
"""
import jax
import numpy as np
import pytest

from conftest import assert_logits_close
from repro.configs import DEIT_SMALL
from repro.models import model as M
from repro.models import pruning_glue as PG
from repro.serving import (EngineConfig, ServeEngine, Scheduler,
                           VisionEngine, VisionEngineConfig)
from repro.traffic import (AdmissionController, TraceSpec, TrafficHarness,
                           LMDriver, VisionDriver, bursty_arrivals,
                           diurnal_arrivals, load_trace, make_trace,
                           outputs_digest, percentile, poisson_arrivals,
                           save_trace, trace_fingerprint)


@pytest.fixture(scope="module")
def packed_vit(rng_key):
    cfg = DEIT_SMALL.reduced()
    params = M.init_params(cfg, rng_key)
    scores = PG.init_scores(cfg, params, jax.random.fold_in(rng_key, 7))
    masked = PG.apply_pruning(cfg, params, scores)
    return cfg, masked


def _vision_engine(packed_vit, depth=1, quality="strict", slots=2):
    cfg, masked = packed_vit
    return VisionEngine(cfg, masked, VisionEngineConfig(
        max_batch=slots, planner="full", pipeline_depth=depth,
        quality=quality))


def _vision_spec(n=8, rate=60000.0, deadline=0.05):
    # rate far above the uncalibrated model's modeled capacity so the
    # bursty stream actually queues; sizes small to keep compiles cheap
    return TraceSpec(n=n, rate_rps=rate, process="bursty", sizes=(9, 4),
                     r_ts=(None, 0.7), deadlines_ms=(deadline, None))


# ===========================================================================
# workload: arrivals, traces, serialization
# ===========================================================================
def test_arrival_processes_are_seeded_and_monotone():
    for fn in (poisson_arrivals, bursty_arrivals, diurnal_arrivals):
        a = fn(64, 100.0, np.random.default_rng(3))
        b = fn(64, 100.0, np.random.default_rng(3))
        np.testing.assert_array_equal(a, b)
        assert np.all(np.diff(a) > 0) and a[0] > 0


def test_bursty_matches_offered_load_but_overdisperses():
    a = bursty_arrivals(4000, 200.0, np.random.default_rng(0))
    rate = 4000 / (a[-1] * 1e-3)
    assert rate == pytest.approx(200.0, rel=0.1)
    gaps = np.diff(a)
    # MMPP gap CV must exceed the exponential's 1.0 — that's the burst
    assert np.std(gaps) / np.mean(gaps) > 1.05


def test_trace_is_pure_function_of_spec_and_seed():
    spec = _vision_spec()
    fp = trace_fingerprint(make_trace(spec, seed=5))
    assert trace_fingerprint(make_trace(spec, seed=5)) == fp
    assert trace_fingerprint(make_trace(spec, seed=6)) != fp
    assert trace_fingerprint(
        make_trace(_vision_spec(rate=1000.0), seed=5)) != fp


def test_trace_jsonl_roundtrip_preserves_fingerprint(tmp_path):
    trace = make_trace(_vision_spec(), seed=2)
    path = str(tmp_path / "t.jsonl")
    fp = save_trace(path, trace)
    loaded = load_trace(path)
    assert fp == trace_fingerprint(trace) == trace_fingerprint(loaded)
    assert loaded.requests == trace.requests
    assert loaded.meta == trace.meta


def test_trace_schema_version_is_enforced(tmp_path):
    trace = make_trace(_vision_spec(n=2), seed=0)
    path = str(tmp_path / "t.jsonl")
    save_trace(path, trace)
    lines = open(path).read().splitlines()
    import json
    meta = json.loads(lines[0])
    meta["trace_schema"] = 999
    (tmp_path / "bad.jsonl").write_text(
        "\n".join([json.dumps(meta)] + lines[1:]) + "\n")
    with pytest.raises(ValueError, match="trace_schema"):
        load_trace(str(tmp_path / "bad.jsonl"))


def test_trace_validation():
    with pytest.raises(ValueError, match="process"):
        TraceSpec(process="lognormal")
    with pytest.raises(ValueError, match="arrival_ms"):
        from repro.traffic import TraceRequest
        TraceRequest(uid=0, arrival_ms=-1.0)
    with pytest.raises(ValueError, match="sorted"):
        from repro.traffic import Trace, TraceRequest
        Trace(meta={}, requests=(TraceRequest(uid=0, arrival_ms=2.0),
                                 TraceRequest(uid=1, arrival_ms=1.0)))


def test_percentile_nearest_rank():
    assert percentile([1.0, 2.0, 3.0, 4.0], 50) == 2.0
    assert percentile([1.0, 2.0, 3.0, 4.0], 99) == 4.0
    assert np.isnan(percentile([], 50))


# ===========================================================================
# scheduler: admission hook + first-class stats
# ===========================================================================
class _Req:
    def __init__(self, uid):
        self.uid = uid


def test_scheduler_admission_hook_rejects_without_side_effects():
    seen = []

    def hook(req):
        seen.append(req.uid)
        return req.uid % 2 == 0

    sched = Scheduler(2, admission_control=hook)
    sched.submit([_Req(i) for i in range(4)])
    assert seen == [0, 1, 2, 3]
    # rejected uids never entered the queue and never advanced
    # submitted_total (a reject must not trigger engine mid-step replans)
    assert [r.uid for r in sched.waiting] == [0, 2]
    assert sched.submitted_total == 2
    assert sched.rejected_total == 2
    assert [e for e in sched.events if e[0] == "reject"] == [
        ("reject", 1), ("reject", 3)]
    st = sched.stats()
    assert st["queue_depth"] == st["peak_queue_depth"] == 2
    assert st["rejected_total"] == 2 and st["submitted_total"] == 2


def test_scheduler_stats_track_lifecycle():
    sched = Scheduler(2)
    sched.submit([_Req(i) for i in range(3)])
    assert sched.stats()["peak_queue_depth"] == 3
    sched.schedule()
    st = sched.stats()
    assert st["running"] == 2 and st["free_slots"] == 0
    assert st["queue_depth"] == 1 and st["peak_queue_depth"] == 3
    sched.retire(0)
    assert sched.stats()["retired_total"] == 1


# ===========================================================================
# admission controller (stub pricers — engine-free semantics)
# ===========================================================================
def test_admission_degrades_before_rejecting():
    backlog = {"ms": 0.0}
    ctrl = AdmissionController(
        limit_ms=10.0,
        cost_ms=lambda r: 6.0,
        backlog_ms=lambda: backlog["ms"],
        degraded_cost_ms=lambda r: 2.0,
        degrade=lambda r: setattr(r, "quality", "degrade"))
    r0, r1, r2 = _Req(0), _Req(1), _Req(2)
    assert ctrl.gate(r0)                       # 6 <= 10: accept
    backlog["ms"] = 6.0
    assert ctrl.gate(r1)                       # 6 > 4 but 2 <= 4: degrade
    assert getattr(r1, "quality") == "degrade"
    backlog["ms"] = 9.0
    assert not ctrl.gate(r2)                   # even degraded 2 > 1: reject
    assert [d.action for d in ctrl.decisions] == [
        "accept", "degrade", "reject"]
    assert ctrl.counts() == {"accept": 1, "degrade": 1, "reject": 1}
    d = ctrl.decisions[1]
    assert d.cost_ms == 2.0 and d.backlog_ms == 6.0 and d.limit_ms == 10.0


def test_admission_without_degrade_arm_is_accept_or_reject():
    ctrl = AdmissionController(limit_ms=5.0, cost_ms=lambda r: 6.0,
                               backlog_ms=lambda: 0.0)
    assert not ctrl.gate(_Req(0))
    assert ctrl.decisions[0].action == "reject"
    with pytest.raises(ValueError, match="limit_ms"):
        AdmissionController(limit_ms=0.0, cost_ms=lambda r: 1.0,
                            backlog_ms=lambda: 0.0)


# ===========================================================================
# harness: vision engine
# ===========================================================================
def test_vision_harness_timestamps_identical_across_depths(packed_vit):
    trace = make_trace(_vision_spec(), seed=9)
    reports, lifecycles, digests = [], [], []
    for depth in (1, 2):
        h = TrafficHarness(VisionDriver(_vision_engine(packed_vit, depth)))
        rep = h.run(trace)
        reports.append(rep)
        lifecycles.append(h.lifecycle())
        digests.append(rep["outputs_digest"])
        # basic lifecycle sanity: submit at arrival, dispatch after
        # submit, retire after dispatch, all on the virtual clock
        for rec in h.records.values():
            assert rec.submit_ms >= rec.arrival_ms
            assert rec.first_dispatch_ms >= rec.submit_ms
            assert rec.retire_ms > rec.first_dispatch_ms
            assert rec.ttfd_ms >= 0.0
    # the whole point of the virtual clock: pipeline depth changes wall
    # time, never virtual timestamps — byte-identical lifecycles,
    # reports, and served outputs
    assert lifecycles[0] == lifecycles[1]
    assert digests[0] == digests[1]
    assert reports[0] == reports[1]


def test_vision_harness_replay_is_deterministic(packed_vit):
    trace = make_trace(_vision_spec(), seed=4)
    h1 = TrafficHarness(VisionDriver(_vision_engine(packed_vit)))
    h2 = TrafficHarness(VisionDriver(_vision_engine(packed_vit)))
    r1, r2 = h1.run(trace), h2.run(trace)
    assert h1.lifecycle() == h2.lifecycle()
    assert r1 == r2


def test_vision_harness_equals_direct_serve(packed_vit):
    trace = make_trace(_vision_spec(), seed=4)
    h = TrafficHarness(VisionDriver(_vision_engine(packed_vit)))
    rep = h.run(trace)
    assert rep["completed"] == len(trace.requests)
    eng = _vision_engine(packed_vit)
    drv = VisionDriver(eng)
    direct = eng.serve([drv.materialize(t) for t in trace.requests])
    assert sorted(h.outputs) == sorted(direct)
    for uid in direct:
        assert_logits_close(h.outputs[uid], direct[uid])


def test_vision_deadline_accounting(packed_vit):
    # every request gets an impossible SLO, then a generous one: the
    # miss-rate column must see through both
    tight = make_trace(TraceSpec(n=4, rate_rps=1e5, process="poisson",
                                 sizes=(9,), deadlines_ms=(1e-6,)), seed=1)
    h = TrafficHarness(VisionDriver(_vision_engine(packed_vit)))
    rep = h.run(tight)
    assert rep["deadline_total"] == 4
    assert rep["deadline_miss_rate"] == 1.0
    assert rep["goodput_rps"] == 0.0      # completions, but none in SLO
    assert rep["throughput_rps"] > 0.0
    loose = make_trace(TraceSpec(n=4, rate_rps=1e5, process="poisson",
                                 sizes=(9,), deadlines_ms=(1e6,)), seed=1)
    rep2 = TrafficHarness(
        VisionDriver(_vision_engine(packed_vit))).run(loose)
    assert rep2["deadline_miss_rate"] == 0.0
    assert rep2["goodput_rps"] == rep2["throughput_rps"]


def test_vision_admission_decisions_deterministic(packed_vit):
    trace = make_trace(_vision_spec(n=10, rate=2e5), seed=7)
    runs = []
    for _ in range(2):
        h = TrafficHarness(
            VisionDriver(_vision_engine(packed_vit, quality="auto")),
            admission_limit_ms=0.02)
        h.run(trace)
        runs.append([(d.uid, d.action, d.cost_ms, d.backlog_ms)
                     for d in h.controller.decisions])
    assert runs[0] == runs[1]
    assert len(runs[0]) == 10
    actions = {a for _, a, _, _ in runs[0]}
    assert "reject" in actions            # the limit actually binds
    # rejected requests produced no outputs, accepted ones all did
    h3 = TrafficHarness(
        VisionDriver(_vision_engine(packed_vit, quality="auto")),
        admission_limit_ms=0.02)
    rep = h3.run(trace)
    rejected = {d.uid for d in h3.controller.decisions
                if d.action == "reject"}
    assert set(h3.outputs) == set(range(10)) - rejected
    assert rep["rejected"] == len(rejected)
    for uid in rejected:
        rec = h3.records[uid]
        assert rec.rejected and rec.retire_ms is None


def test_vision_admission_bounds_queue_vs_unbounded(packed_vit):
    trace = make_trace(_vision_spec(n=10, rate=2e5), seed=7)
    unb = TrafficHarness(VisionDriver(_vision_engine(packed_vit)))
    unb_rep = unb.run(trace)
    adm = TrafficHarness(
        VisionDriver(_vision_engine(packed_vit, quality="auto")),
        admission_limit_ms=0.02)
    adm_rep = adm.run(trace)
    assert adm_rep["peak_queue_depth"] < unb_rep["peak_queue_depth"]
    assert adm_rep["rejected"] > 0


def test_harness_rejects_mismatched_trace_kind(packed_vit):
    lm_trace = make_trace(TraceSpec(n=2, kind="lm", process="poisson",
                                    rate_rps=10.0), seed=0)
    h = TrafficHarness(VisionDriver(_vision_engine(packed_vit)))
    with pytest.raises(ValueError, match="kind"):
        h.run(lm_trace)


# ===========================================================================
# harness: LM engine
# ===========================================================================
def _lm_engine(depth=1):
    from repro.configs import get_config
    cfg = get_config("stablelm-1.6b").reduced()
    params = M.init_params(cfg, jax.random.PRNGKey(0))
    return ServeEngine(cfg, params, EngineConfig(max_batch=2, max_len=128,
                                                 pipeline_depth=depth))


def _lm_spec(n=6, deadline=80.0):
    return TraceSpec(n=n, rate_rps=150.0, process="bursty", kind="lm",
                     prompt_sizes=(8, 16), max_new_tokens=4,
                     deadlines_ms=(deadline, None))


def test_lm_harness_timestamps_identical_across_depths():
    trace = make_trace(_lm_spec(), seed=3)
    lifecycles, digests = [], []
    for depth in (1, 2):
        h = TrafficHarness(LMDriver(_lm_engine(depth), per_token_ms=1.0))
        rep = h.run(trace)
        assert rep["completed"] == len(trace.requests)
        lifecycles.append(h.lifecycle())
        digests.append(rep["outputs_digest"])
    assert lifecycles[0] == lifecycles[1]
    assert digests[0] == digests[1]


def test_lm_harness_equals_direct_serve():
    trace = make_trace(_lm_spec(), seed=3)
    eng = _lm_engine()
    drv = LMDriver(eng, per_token_ms=1.0)
    h = TrafficHarness(drv)
    rep = h.run(trace)
    eng2 = _lm_engine()
    drv2 = LMDriver(eng2, per_token_ms=1.0)
    direct = eng2.serve([drv2.materialize(t) for t in trace.requests],
                        continuous=True)
    assert outputs_digest(direct) == rep["outputs_digest"]


def test_lm_admission_rejects_under_token_budget():
    trace = make_trace(_lm_spec(n=8, deadline=None), seed=6)
    drv = LMDriver(_lm_engine(), per_token_ms=1.0)
    h = TrafficHarness(drv, admission_limit_ms=30.0)
    rep = h.run(trace)
    assert rep["rejected"] > 0
    assert rep["completed"] + rep["rejected"] == 8
    # deterministic decisions
    drv2 = LMDriver(_lm_engine(), per_token_ms=1.0)
    h2 = TrafficHarness(drv2, admission_limit_ms=30.0)
    h2.run(trace)
    assert ([(d.uid, d.action) for d in h.controller.decisions]
            == [(d.uid, d.action) for d in h2.controller.decisions])
