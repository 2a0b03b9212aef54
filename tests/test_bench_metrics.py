"""The benchmark's readers of the engine's spans, records and named
programs (``bench/metrics``), on hand-made span logs and profiles.

Each reader takes the traced window's span log (times in ms from the
tracer's start) and returns a number, or ``None`` where it finds nothing
to read, as on a program that writes no such spans.
"""
import gzip
import importlib.util
import shutil
import sys
from pathlib import Path
from types import SimpleNamespace

import pytest

BENCH = Path(__file__).resolve().parents[1] / "bench"


def _metric(name):
    if str(BENCH) not in sys.path:
        sys.path.insert(0, str(BENCH))
    spec = importlib.util.spec_from_file_location(
        "bench_metric_" + name.replace(".", "_"),
        BENCH / "metrics" / f"{name}.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def _span(name, track, ts, dur, **attrs):
    return {"name": name, "track": track, "ts_ms": ts, "dur_ms": dur,
            "attrs": attrs}


def _ctx(spans, trace=None, log_dir=""):
    # the traced window is [100, 200] ms after the tracer's start
    return SimpleNamespace(trace=trace, window={
        "spans": spans, "tracer_t0": 10.0, "t_on": 10.1, "t_off": 10.2,
        "dir": log_dir})


def test_eager_ops_per_step_reads_whole_steps_in_the_window():
    read = _metric("eager_ops_per_step.offline").read
    spans = [_span("step", "engine", 90.0, 20.0, eager_ops=1000),  # straddles
             _span("step", "engine", 110.0, 20.0, eager_ops=131),
             _span("step", "engine", 140.0, 20.0, eager_ops=129),
             _span("step", "engine", 170.0, 30.0, eager_ops=130),
             _span("step", "harness", 120.0, 1.0, eager_ops=7),
             _span("plan", "engine", 120.0, 1.0)]
    assert read(_ctx(spans)) == pytest.approx((131 + 129 + 130) / 3)
    assert read(_ctx([])) is None
    assert read(_ctx([_span("plan", "engine", 120.0, 1.0)])) is None


def test_queue_wait_p50_is_the_nearest_rank_median_of_admissions():
    read = _metric("queue_wait_ms_p50.online").read
    spans = [_span("queued", "requests", 95.0, 10.0, uid=1),   # admitted 105
             _span("queued", "requests", 120.0, 0.4, uid=2),
             _span("queued", "requests", 130.0, 0.2, uid=3),
             _span("queued", "requests", 140.0, 3.0, uid=4),
             _span("queued", "requests", 199.0, 2.0, uid=5),   # admitted 201
             _span("served", "requests", 120.4, 9.0, uid=2)]
    # admitted inside: 10.0, 0.4, 0.2, 3.0 -> rank 2 of 4
    assert read(_ctx(spans)) == pytest.approx(0.4)
    assert read(_ctx(spans[:2])) == pytest.approx(0.4)   # rank 1 of 2
    assert read(_ctx([])) is None


def test_to_host_ms_per_image_pairs_spans_with_step_records():
    read = _metric("to_host_ms_per_image.online").read
    spans = [_span("to_host", "pipeline", 110.0, 2.0),
             _span("step", "engine", 105.0, 7.5, to_host=1),
             _span("to_host", "pipeline", 150.0, 4.5),
             _span("step", "engine", 140.0, 14.6, to_host=3),
             _span("to_host", "pipeline", 160.0, 0.5),   # nothing delivered
             _span("step", "engine", 158.0, 2.6, to_host=0),
             _span("to_host", "pipeline", 199.0, 3.0),   # ends after
             _span("step", "engine", 198.0, 4.1, to_host=5)]
    assert read(_ctx(spans)) == pytest.approx((2.0 + 4.5 + 0.5) / 4)
    assert read(_ctx(spans[4:6])) is None
    assert read(_ctx([])) is None


def _plane(name, **lines):
    return SimpleNamespace(name=name, lines=[
        SimpleNamespace(name=ln, events=[
            SimpleNamespace(name=n, start_ns=a, duration_ns=d)
            for n, a, d in evs]) for ln, evs in lines.items()])


def test_lane_device_p50_reads_named_lane_modules_in_the_window():
    mod = _metric("lane_device_ms_p50.online")
    modules = {"XLA Modules": [
        ("jit_vit_lane(123)", 1_000_000, 4_000_000),
        ("jit_vit_lane(123)", 6_000_000, 3_500_000),
        ("jit_vit_lane(456)", 11_000_000, 5_000_000),
        ("jit_vit_lane(123)", 19_000_000, 3_000_000),   # ends after 20 ms
        ("jit_vit_layers(9)", 2_000_000, 1_000_000),
        ("jit__pad(7)", 3_000_000, 1_000)],
        "XLA Ops": [("jit_vit_lane(123)", 1_000_000, 9_000_000)]}
    planes = [_plane("/device:TPU:0", **modules),
              _plane("/host:CPU", **modules)]
    runs = mod.lane_ms(planes, 0, 20_000_000)
    assert sorted(runs) == pytest.approx([3.5, 4.0, 5.0])
    assert mod.lane_ms(planes[1:], 0, 20_000_000) == []


def test_lane_device_p50_is_none_without_lane_modules(tmp_path):
    read = _metric("lane_device_ms_p50.online").read
    assert read(_ctx([])) is None   # no trace
    # a chip trace recorded before the lane program had a name
    from harness import xtrace as X
    run = tmp_path / "plugins" / "profile" / "run"
    run.mkdir(parents=True)
    src = BENCH / "testdata" / "offline_trace.xplane.pb.gz"
    with gzip.open(src, "rb") as f, open(run / "t.xplane.pb", "wb") as g:
        shutil.copyfileobj(f, g)
    trace = X.load(str(run / "t.xplane.pb"))
    assert read(_ctx([], trace=trace, log_dir=str(tmp_path))) is None
