"""repro.obs: span tracer, metrics registry, bounded event log.

The acceptance properties of the observability layer:

* spans nest LIFO per track and the exported Chrome trace satisfies the
  trace_event contract (validate_chrome_trace: well-formed envelope,
  monotonic per-track timestamps, balanced B/E pairs);
* a disabled tracer (NullTracer or Tracer(enabled=False)) records
  nothing and costs the hot path one attribute check — and enabling it
  never changes served outputs (digest-neutral);
* virtual-clock traces from the traffic harness are byte-identical at
  pipeline depths 1 and 2 (the PR-8 timestamp-equality guarantee carries
  over to the exported timeline);
* metrics are deterministic: fixed bucket edges, nearest-rank percentile
  reads, same sample stream -> byte-identical snapshots;
* the scheduler's EventLog is a bounded ring with ABSOLUTE indices, so
  the existing ``mark = len(events)`` / ``events[mark:]`` incremental
  consumption pattern survives eviction, and ``drain()`` hands the
  buffer over without disturbing the total.
"""
import json
import math

import jax
import numpy as np
import pytest

from repro.configs import DEIT_SMALL
from repro.core import packed_runner as PR
from repro.models import model as M
from repro.models import pruning_glue as PG
from repro.obs import (EventLog, MetricsRegistry, NULL_TRACER, NullTracer,
                       Tracer, log_buckets, validate_chrome_trace)
from repro.serving import (Scheduler, VisionEngine, VisionEngineConfig,
                           VisionRequest)
from repro.traffic import TraceSpec, TrafficHarness, VisionDriver, make_trace


# ===========================================================================
# tracer: span discipline + chrome export
# ===========================================================================
def test_span_nesting_and_ordering():
    tr = Tracer()
    tr.begin("outer", t_ms=1.0)
    tr.begin("inner", t_ms=2.0, depth=1)
    tr.end("inner", t_ms=3.0)
    tr.end("outer", t_ms=5.0)
    with tr.span("ctx", track="wall"):   # wall clock on its own track
        pass
    # closed spans appear innermost-first within a nest
    names = [s["name"] for s in tr.span_log]
    assert names == ["inner", "outer", "ctx"]
    inner, outer = tr.span_log[0], tr.span_log[1]
    assert inner["ts_ms"] == 2.0 and inner["dur_ms"] == 1.0
    assert outer["ts_ms"] == 1.0 and outer["dur_ms"] == 4.0
    assert inner["attrs"] == {"depth": 1}
    doc = tr.chrome_trace()
    info = validate_chrome_trace(doc)
    assert info["spans"] == 3
    # B/E events come out in chronological order per track
    bes = [(e["ph"], e["name"]) for e in doc["traceEvents"]
           if e["ph"] in "BE"]
    assert bes[:4] == [("B", "outer"), ("B", "inner"),
                       ("E", "inner"), ("E", "outer")]


def test_mismatched_end_raises():
    tr = Tracer()
    tr.begin("a", t_ms=0.0)
    with pytest.raises(ValueError, match="does not match"):
        tr.end("b", t_ms=1.0)
    tr.end("a", t_ms=1.0)
    with pytest.raises(ValueError, match="no open span"):
        tr.end("a", t_ms=2.0)


def test_chrome_trace_refuses_open_spans():
    tr = Tracer()
    tr.begin("dangling", t_ms=0.0)
    assert tr.open_spans() == ["dangling"]
    with pytest.raises(ValueError, match="open span"):
        tr.chrome_trace()


def test_tracks_get_distinct_tids_and_metadata():
    tr = Tracer()
    tr.begin("a", track="engine", t_ms=0.0)
    tr.end("a", track="engine", t_ms=1.0)
    tr.instant("mark", track="pipeline", t_ms=0.5)
    doc = tr.chrome_trace()
    meta = {e["args"]["name"]: e["tid"] for e in doc["traceEvents"]
            if e["ph"] == "M"}
    assert set(meta) == {"engine", "pipeline"}
    assert meta["engine"] != meta["pipeline"]
    info = validate_chrome_trace(doc)
    assert info["tracks"] == 2


def test_disabled_tracer_records_nothing():
    for tr in (NullTracer(), Tracer(enabled=False), NULL_TRACER):
        assert not tr.enabled
        tr.begin("x", t_ms=0.0)
        with tr.span("y", t_ms=1.0):
            tr.instant("z", t_ms=1.5)
        tr.end("x", t_ms=2.0)
        assert tr.event_count == 0
        assert tr.span_log == []
        doc = tr.chrome_trace()
        assert doc["traceEvents"] == []
        assert validate_chrome_trace(doc)["events"] == 0


def test_write_chrome_trace_and_jsonl(tmp_path):
    tr = Tracer()
    with tr.span("s", t_ms=0.0, k=1):
        pass
    p = str(tmp_path / "t.json")
    tr.write_chrome_trace(p)
    validate_chrome_trace(json.load(open(p)))
    pj = str(tmp_path / "t.jsonl")
    tr.write_jsonl(pj)
    rows = [json.loads(l) for l in open(pj)]
    assert rows[0]["name"] == "s" and rows[0]["attrs"] == {"k": 1}


def test_validator_rejects_malformed_traces():
    ok = {"displayTimeUnit": "ms", "traceEvents": [
        {"ph": "B", "name": "a", "pid": 1, "tid": 1, "ts": 0.0},
        {"ph": "E", "name": "a", "pid": 1, "tid": 1, "ts": 1.0}]}
    validate_chrome_trace(ok)
    bad_order = {"traceEvents": [
        {"ph": "B", "name": "a", "pid": 1, "tid": 1, "ts": 5.0},
        {"ph": "E", "name": "a", "pid": 1, "tid": 1, "ts": 1.0}]}
    with pytest.raises(ValueError, match="decreases"):
        validate_chrome_trace(bad_order)
    unbalanced = {"traceEvents": [
        {"ph": "B", "name": "a", "pid": 1, "tid": 1, "ts": 0.0}]}
    with pytest.raises(ValueError, match="unbalanced"):
        validate_chrome_trace(unbalanced)
    crossed = {"traceEvents": [
        {"ph": "B", "name": "a", "pid": 1, "tid": 1, "ts": 0.0},
        {"ph": "E", "name": "b", "pid": 1, "tid": 1, "ts": 1.0}]}
    with pytest.raises(ValueError, match="does not match"):
        validate_chrome_trace(crossed)
    with pytest.raises(ValueError, match="traceEvents"):
        validate_chrome_trace({"events": []})


# ===========================================================================
# metrics: determinism, histogram reads, absorb
# ===========================================================================
def test_log_buckets_deterministic_and_ascending():
    a = log_buckets(1e-3, 1e5, 4)
    assert a == log_buckets(1e-3, 1e5, 4)
    assert all(x < y for x, y in zip(a, a[1:]))
    assert a[0] <= 1e-3 and a[-1] >= 1e5
    with pytest.raises(ValueError, match="lo"):
        log_buckets(0.0, 1.0)


def test_counter_gauge_semantics():
    mx = MetricsRegistry()
    c = mx.counter("c")
    c.inc()
    c.inc(2)
    assert c.value == 3
    with pytest.raises(ValueError, match="monotone"):
        c.inc(-1)
    mx.gauge("g").set(2.5)
    assert mx.gauge("g").value == 2.5
    with pytest.raises(TypeError, match="counter"):
        mx.gauge("c")


def test_histogram_percentile_nearest_rank():
    mx = MetricsRegistry()
    h = mx.histogram("h", buckets=(1.0, 2.0, 4.0, 8.0))
    for v in (0.5, 1.5, 3.0, 3.5, 100.0):
        h.record(v)
    assert h.count == 5 and h.max == 100.0
    assert h.percentile(50) == 4.0     # rank 3 -> bucket edge 4.0
    assert h.percentile(99) == 100.0   # overflow bucket reads as max
    snap = h.snapshot()
    assert snap["counts"] == [1, 1, 2, 0, 1]
    assert math.isnan(mx.histogram("empty").percentile(50))


def test_same_stream_gives_identical_snapshots():
    def fill(mx):
        mx.counter("n").inc(7)
        h = mx.histogram("lat")
        for v in (0.01, 0.5, 3.0, 42.0):
            h.record(v)
        mx.absorb("s", {"a": 1, "b": 2.5, "mode": "full",
                        "flag": True, "tup": (1, 2)})
    m1, m2 = MetricsRegistry(), MetricsRegistry()
    fill(m1)
    fill(m2)
    assert (json.dumps(m1.snapshot(), sort_keys=True)
            == json.dumps(m2.snapshot(), sort_keys=True))
    # absorb: numerics become gauges, bools/strings/tuples are skipped
    assert m1.names() == ["lat", "n", "s.a", "s.b"]


def test_registry_write_json(tmp_path):
    mx = MetricsRegistry()
    mx.counter("x").inc()
    p = str(tmp_path / "m.json")
    mx.write_json(p)
    assert json.load(open(p))["x"] == {"type": "counter", "value": 1.0}


# ===========================================================================
# event log: bounded ring with absolute indices
# ===========================================================================
def test_eventlog_absolute_indexing_survives_eviction():
    log = EventLog(capacity=4)
    for i in range(3):
        log.append(("ev", i))
    mark = len(log)                      # the harness's consumption pattern
    for i in range(3, 10):
        log.append(("ev", i))
    assert len(log) == 10                # total ever, not buffered
    assert log.buffered == 4 and log.dropped == 6
    # absolute slice: evicted entries silently absent, live ones correct
    assert log[mark:] == [("ev", i) for i in range(6, 10)]
    assert log[0:] == [("ev", i) for i in range(6, 10)]
    assert log[7] == ("ev", 7)
    with pytest.raises(IndexError, match="evicted"):
        log[2]
    with pytest.raises(IndexError):
        log[10]
    assert list(log) == [("ev", i) for i in range(6, 10)]


def test_eventlog_drain_preserves_total():
    log = EventLog(capacity=8)
    for i in range(5):
        log.append(i)
    out = log.drain()
    assert out == [0, 1, 2, 3, 4]
    assert len(log) == 5 and log.buffered == 0
    log.append(5)
    assert log[5] == 5 and len(log) == 6


def test_scheduler_event_ring_keeps_counters_exact():
    class _R:
        def __init__(self, uid):
            self.uid = uid

    sched = Scheduler(2, event_capacity=4)
    sched.submit([_R(i) for i in range(6)])
    for _ in range(3):
        for slot, _req in sched.schedule():
            sched.retire(slot)
    st = sched.stats()
    # the ring evicted early events, but lifecycle counters are exact
    assert st["admitted_total"] == st["retired_total"] == 6
    assert sched.num_admissions == sched.num_retirements == 6
    assert st["events_dropped"] > 0
    assert len(sched.events) > sched.events.buffered
    drained = sched.drain_events()
    assert drained and sched.events.buffered == 0
    assert sched.stats()["admitted_total"] == 6   # drain changes nothing


# ===========================================================================
# harness traces: virtual clock, cross-depth byte-identity
# ===========================================================================
@pytest.fixture(scope="module")
def packed_vit(rng_key):
    cfg = DEIT_SMALL.reduced()
    params = M.init_params(cfg, rng_key)
    scores = PG.init_scores(cfg, params, jax.random.fold_in(rng_key, 7))
    masked = PG.apply_pruning(cfg, params, scores)
    packed = PR.pack_model(cfg, params, scores)
    return cfg, masked, packed


def _vision_engine(packed_vit, depth=1):
    cfg, masked, packed = packed_vit
    return VisionEngine(cfg, masked, packed, VisionEngineConfig(
        max_batch=2, planner="full", pipeline_depth=depth))


def _spec(n=6):
    return TraceSpec(n=n, rate_rps=60000.0, process="bursty", sizes=(9, 4),
                     r_ts=(None, 0.7), deadlines_ms=(0.05, None))


def test_virtual_traces_identical_across_depths(packed_vit):
    trace = make_trace(_spec(), seed=9)
    docs, metrics, digests = [], [], []
    for depth in (1, 2):
        tr, mx = Tracer(), MetricsRegistry()
        h = TrafficHarness(VisionDriver(_vision_engine(packed_vit, depth)),
                           tracer=tr, metrics=mx)
        rep = h.run(trace)
        digests.append(rep["outputs_digest"])
        docs.append(json.dumps(tr.chrome_trace(), sort_keys=True))
        metrics.append(json.dumps(mx.snapshot(), sort_keys=True))
        info = validate_chrome_trace(tr.chrome_trace())
        assert info["spans"] > 0
        # per-step spans + per-request lifecycle spans are both present
        names = {s["name"] for s in tr.span_log}
        assert {"step", "plan", "stage", "dispatch", "complete",
                "enqueue", "serve"} <= names
        # lifecycle span timestamps match the records (virtual clock)
        for s in tr.span_log:
            if s["name"] == "serve":
                rec = h.records[s["attrs"]["uid"]]
                assert s["ts_ms"] == rec.first_dispatch_ms
                assert s["ts_ms"] + s["dur_ms"] == rec.retire_ms
    # pipeline depth changes wall time, never the virtual timeline:
    # byte-identical trace documents, metrics snapshots, and outputs
    assert docs[0] == docs[1]
    assert metrics[0] == metrics[1]
    assert digests[0] == digests[1]


def test_harness_tracing_is_digest_neutral(packed_vit):
    trace = make_trace(_spec(), seed=4)
    plain = TrafficHarness(VisionDriver(_vision_engine(packed_vit)))
    rep_plain = plain.run(trace)
    tr = Tracer()
    traced = TrafficHarness(VisionDriver(_vision_engine(packed_vit)),
                            tracer=tr)
    rep_traced = traced.run(trace)
    assert rep_plain["outputs_digest"] == rep_traced["outputs_digest"]
    assert rep_plain == rep_traced      # the report itself is unchanged
    assert tr.event_count > 0
    # disabled tracer through the same path records nothing
    off = TrafficHarness(VisionDriver(_vision_engine(packed_vit)),
                         tracer=NULL_TRACER)
    rep_off = off.run(trace)
    assert rep_off == rep_plain


def test_engine_wallclock_spans_balanced(packed_vit):
    # engine + pipeline tracks (plan/stage/dispatch/complete) on the real
    # clock: the export must validate with no dangling spans after serve
    cfg, masked, packed = packed_vit
    tr = Tracer()
    eng = VisionEngine(cfg, masked, packed,
                       VisionEngineConfig(max_batch=2, planner="full"),
                       tracer=tr)
    from repro.launch.serve_vision import make_requests
    out = eng.serve(make_requests(cfg, 4, 2, 0))
    assert len(out) == 4
    doc = tr.chrome_trace()
    assert validate_chrome_trace(doc)["spans"] > 0
    names = {s["name"] for s in tr.span_log}
    assert {"plan", "stage", "dispatch", "complete"} <= names
    mx = eng.export_metrics(MetricsRegistry())
    assert mx.gauge("vision.jit_compile_count").value > 0
    assert "vision.plan_cost_error" in mx.names()


# ===========================================================================
# schema v4: metrics block in the bench envelope
# ===========================================================================
def test_artifact_metrics_block_roundtrip(tmp_path):
    from repro.bench import load_bench_artifact, write_bench_artifact
    mx = MetricsRegistry()
    mx.counter("vision.recompiles").inc(3)
    path = str(tmp_path / "a.json")
    write_bench_artifact(path, "vision", {"k": 1}, {"r": 2},
                         metrics=mx.snapshot())
    art = load_bench_artifact(path, expect_kind="vision")
    assert art["schema_version"] == 4
    assert art["metrics"]["vision.recompiles"]["value"] == 3.0
    # metrics omitted -> key present, null (always-present envelope field)
    path2 = str(tmp_path / "b.json")
    write_bench_artifact(path2, "vision", {}, {})
    assert load_bench_artifact(path2)["metrics"] is None


# ===========================================================================
# closed spans, profiler annotations
# ===========================================================================
def test_record_exports_x_events_that_validate():
    tr = Tracer()
    t0 = tr._t0
    tr.begin("outer", track="requests", t_ms=0.0)
    tr.end("outer", track="requests", t_ms=50.0)
    # closed spans logged late and out of order, overlapping each other
    tr.record("served", t0 + 0.030, t0 + 0.040, track="requests", uid=2)
    tr.record("served", t0 + 0.010, t0 + 0.045, track="requests", uid=1)
    tr.record("queued", t0 + 0.005, t0 + 0.010, track="requests", uid=1)
    log = [s for s in tr.span_log if s["name"] != "outer"]
    assert [s["attrs"]["uid"] for s in log] == [2, 1, 1]
    assert log[1]["ts_ms"] == pytest.approx(10.0)
    assert log[1]["dur_ms"] == pytest.approx(35.0)
    doc = tr.chrome_trace()
    assert validate_chrome_trace(doc)["spans"] == 1
    xs = [e for e in doc["traceEvents"] if e["ph"] == "X"]
    assert [e["ts"] for e in xs] == sorted(e["ts"] for e in xs)
    assert xs[0]["name"] == "queued" and xs[0]["dur"] == pytest.approx(5e3)
    assert xs[0]["args"] == {"uid": 1}
    with pytest.raises(ValueError, match="before it began"):
        tr.record("bad", t0 + 1.0, t0 + 0.5)
    NULL_TRACER.record("x", 0.0, 1.0)
    assert NULL_TRACER.span_log == []


def test_annotate_opens_and_closes_a_profiler_annotation(monkeypatch):
    seen = []

    class FakeAnnotation:
        def __init__(self, name, **kw):
            assert not kw  # the plain span name, no metadata
            self.name = name

        def __enter__(self):
            seen.append(("enter", self.name))

        def __exit__(self, *exc):
            seen.append(("exit", self.name))

    monkeypatch.setattr(jax.profiler, "TraceAnnotation", FakeAnnotation)
    tr = Tracer(annotate=True)
    tr.begin("dispatch", track="pipeline", label="step-0")
    with tr.span("tile", track="pipeline", seg=("layers", 0, 1)):
        pass
    tr.end("dispatch", track="pipeline")
    tr.record("queued", tr._t0, tr._t0 + 1e-3)   # closed: not annotated
    assert seen == [("enter", "dispatch"), ("enter", "tile"),
                    ("exit", "tile"), ("exit", "dispatch")]
    with pytest.raises(ValueError, match="does not match"):
        tr.begin("a")
        tr.end("b")
    assert seen[-1] == ("enter", "a")   # a failed end leaves it open
    tr.end("a")
    assert seen[-1] == ("exit", "a")
    plain = Tracer()
    plain.begin("x")
    plain.end("x")
    assert seen[-1] == ("exit", "a")


# ===========================================================================
# VisionEngine spans, records and counters
# ===========================================================================
class _OneStackTracer(Tracer):
    """A tracer whose spans share one LIFO stack across tracks, as a
    profiler's annotations do."""

    def __init__(self):
        super().__init__()
        self.stack = []

    def begin(self, name, track="main", t_ms=None, **attrs):
        super().begin(name, track=track, t_ms=t_ms, **attrs)
        self.stack.append(name)

    def end(self, name=None, track="main", t_ms=None):
        super().end(name, track=track, t_ms=t_ms)
        assert self.stack.pop() == name


def _requests(cfg, mixes):
    rng = np.random.default_rng(3)
    pdim = cfg.patch_size ** 2 * 3
    return [VisionRequest(
        uid=i, patches=rng.standard_normal((n, pdim)).astype(np.float32),
        r_t=r_t, arrival_step=arr)
        for i, (n, r_t, arr) in enumerate(mixes)]


MIXED = [(16, 0.5, 0), (16, 0.5, 0), (9, 0.7, 0), (16, None, 1),
         (4, 0.5, 2), (16, 0.7, 2)]


@pytest.mark.parametrize("depth", [1, 2])
def test_engine_spans_nest_in_one_stack_across_tracks(packed_vit, depth):
    cfg, masked, packed = packed_vit
    tr = _OneStackTracer()
    eng = VisionEngine(cfg, masked, packed, VisionEngineConfig(
        max_batch=4, planner="full", pipeline_depth=depth), tracer=tr)
    out = eng.serve(_requests(cfg, MIXED))
    assert len(out) == len(MIXED) and tr.stack == [] and not tr.open_spans()
    names = {s["name"] for s in tr.span_log}
    assert {"plan", "stage", "dispatch", "complete", "tile", "lane",
            "unstage", "block", "to_host", "queued", "served",
            "step"} <= names
    validate_chrome_trace(tr.chrome_trace())
    tiles = [s["attrs"] for s in tr.span_log if s["name"] == "tile"]
    assert all(set(a) == {"seg", "batch", "n", "k"} for a in tiles)
    lanes = [s["attrs"] for s in tr.span_log if s["name"] == "lane"]
    assert lanes and all(a["batch"] == 1 and a["steps"] for a in lanes)


def test_request_records_share_uid_and_order(packed_vit):
    cfg, masked, packed = packed_vit
    tr = Tracer()
    eng = VisionEngine(cfg, masked, packed, VisionEngineConfig(
        max_batch=2, planner="full"), tracer=tr)
    reqs = _requests(cfg, MIXED)
    eng.serve(reqs)
    by = {}
    for s in tr.span_log:
        if s["name"] in ("queued", "served"):
            assert s["track"] == "requests"
            by.setdefault(s["attrs"]["uid"], {}).setdefault(
                s["name"], []).append(s)
    assert sorted(by) == [r.uid for r in reqs]
    for r in reqs:
        (q,), (s,) = by[r.uid]["queued"], by[r.uid]["served"]
        assert q["attrs"] == s["attrs"]
        assert s["attrs"]["path"] in ("lane", "tile")
        assert s["attrs"]["r_t"] == (cfg.pruning.r_t if r.r_t is None
                                     else r.r_t)
        assert q["ts_ms"] + q["dur_ms"] <= s["ts_ms"] + 1e-9
        assert q["dur_ms"] >= 0 and s["dur_ms"] > 0
    # two slots for six requests: later arrivals wait for a slot
    assert max(by[u]["queued"][0]["dur_ms"] for u in by) > 0


def test_eager_ops_match_a_hand_count(packed_vit):
    """Three requests of one size and keep rate at max_batch 4 (planner
    off): every step runs one 3-member tile padded with a zero row, so no
    tile passes the previous output through."""
    cfg, masked, packed = packed_vit
    tr = Tracer()
    eng = VisionEngine(cfg, masked, packed, VisionEngineConfig(
        max_batch=4), tracer=tr)
    before = eng.stats()
    eng.serve(_requests(cfg, [(16, 0.5, 0)] * 3))
    after = eng.stats()
    steps = [s["attrs"] for s in tr.span_log if s["name"] == "step"]
    kinds = [seg[0] for seg in eng.segments.plan]
    assert [a["step"] for a in steps] == list(range(len(kinds)))
    # the embed step stacks the patches on the host: no program. Every
    # later step slices each member's rows out of the previous output
    # (dynamic_slice + squeeze: 6), pads each member (3), adds a zero row
    # (convert + broadcast: 2) and stacks 4 rows (4 expand_dims + 1
    # concatenate); the head's logits reach the host as one copy
    assert [a["eager_ops"] for a in steps] == [
        0 if k == "embed" else 6 + 3 + 2 + 5 for k in kinds]
    # puts: the stacked patches at the embed step (1); later, a start
    # index per axis of each 3-d row slice (9), the pads' fill values (3)
    # and the zero row's (1)
    assert [a["h2d_puts"] for a in steps] == [
        1 if k == "embed" else 3 * 3 + 3 + 1 for k in kinds]
    assert [a["to_host"] for a in steps] == [3 if k == "head" else 0
                                             for k in kinds]
    assert [a["passthrough_tiles"] for a in steps] == [0] * len(kinds)
    assert all(a["tiles"] == 1 and a["lanes"] == 0 for a in steps)
    # a fresh engine compiles each segment at its first step, and the
    # same requests served again compile nothing
    assert [a["compiled"] for a in steps] == [1] * len(kinds)
    for key in ("eager_ops", "h2d_puts", "to_host", "passthrough_tiles"):
        assert after[key] - before[key] == sum(a[key] for a in steps)
    n = len(tr.span_log)
    eng.serve(_requests(cfg, [(16, 0.5, 0)] * 3))
    again = [s["attrs"] for s in tr.span_log[n:] if s["name"] == "step"]
    assert [a["compiled"] for a in again] == [0] * len(kinds)


def test_engine_counts_device_ops_with_tracing_off(packed_vit):
    cfg, masked, packed = packed_vit
    eng = VisionEngine(cfg, masked, packed, VisionEngineConfig(max_batch=4))
    eng.serve(_requests(cfg, [(16, 0.5, 0)] * 3))
    n = len(eng.segments.plan)
    st = eng.stats()
    assert st["eager_ops"] == 16 * (n - 1) and st["to_host"] == 3
    assert st["passthrough_tiles"] == 0


def test_tracing_keeps_logits_bitexact(packed_vit):
    cfg, masked, packed = packed_vit
    outs = []
    for tracer in (None, _OneStackTracer()):
        eng = VisionEngine(cfg, masked, packed, VisionEngineConfig(
            max_batch=4, planner="full"), tracer=tracer)
        outs.append(eng.serve(_requests(cfg, MIXED)))
    assert sorted(outs[0]) == sorted(outs[1])
    for uid in outs[0]:
        np.testing.assert_array_equal(outs[0][uid], outs[1][uid])


def test_segment_programs_lower_to_named_modules(packed_vit):
    import jax.numpy as jnp
    cfg, masked, packed = packed_vit
    seg = PR.PackedVitSegments(cfg, masked, packed)
    P = cfg.patch_size ** 2 * 3
    D = cfg.d_model
    x = jnp.zeros((1, 5, D), jnp.float32)
    pk = seg.packed_for("fp32")
    tdm = next(s for s in seg.plan if s[0] == "tdm")
    embed_steps = tuple((s, PR.tdm_keep_count(5, 0.5) if s[0] == "tdm"
                         else None) for s in seg.plan)
    lowered = {
        "vit_embed": seg._embed.lower(masked, jnp.zeros((1, 4, P))),
        "vit_layers": seg._layers.lower(masked, pk, x, None, lo=0, hi=1,
                                        prec="fp32"),
        "vit_tdm": seg._tdm.lower(masked, pk, x, None, layer=tdm[1], k=2,
                                  prec="fp32"),
        "vit_tdm_soft": seg._tdm_soft.lower(masked, pk, x, None, None,
                                            layer=tdm[1], k=2, prec="fp32"),
        "vit_head": seg._head.lower(masked, x),
        "vit_lane": seg._fused.lower(masked, pk, jnp.zeros((1, 4, P)), None,
                                     steps=embed_steps, prec="fp32"),
    }
    for name, low in lowered.items():
        assert low.as_text().startswith(f"module @jit_{name} "), name


def test_serve_vision_profile_dir_puts_spans_on_the_profile(tmp_path):
    import glob
    from jax.profiler import ProfileData
    from repro.launch.serve_vision import serve
    out = serve(num_requests=3, slots=2, arrival_spread=0,
                profile_dir=str(tmp_path))
    assert len(out["outputs"]) == 3
    (path,) = glob.glob(str(tmp_path / "**" / "*.xplane.pb"),
                        recursive=True)
    names = {e.name for plane in ProfileData.from_file(path).planes
             if plane.name.startswith("/host:")
             for line in plane.lines for e in line.events}
    assert {"plan", "stage", "dispatch", "complete", "block",
            "to_host"} <= names
