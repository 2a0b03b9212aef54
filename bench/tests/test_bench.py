"""CPU tests of the benchmark's arithmetic, files and serving loop.

Run from the repository root: ``python -m pytest bench/tests``. Nothing
here needs the chip or loads the TPU's library: JAX runs on the CPU, and
the serving loop is rehearsed on the reduced DeiT-Small.
"""
from __future__ import annotations

import math
import os
import re
import subprocess
import sys
import time
from pathlib import Path

import numpy as np
import pytest

BENCH = Path(__file__).resolve().parents[1]
ROOT = BENCH.parent
sys.path.insert(0, str(BENCH))
sys.path.insert(0, str(ROOT / "src"))

from harness import check as C  # noqa: E402
from harness import flops as F  # noqa: E402
from harness import spec  # noqa: E402
from harness import traffic as T  # noqa: E402
from harness import xtrace as X  # noqa: E402

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")
TESTDATA = BENCH / "testdata"


# ---------------------------------------------------------------------------
# traffic
# ---------------------------------------------------------------------------
def test_poisson_arrivals_rate_and_dispersion():
    t = T.poisson_arrivals(20000, 50.0, np.random.default_rng(0))
    gaps = np.diff(np.concatenate([[0.0], t]))
    assert abs(len(t) / (t[-1] / 1e3) - 50.0) / 50.0 < 0.03
    assert abs(gaps.std() / gaps.mean() - 1.0) < 0.05  # exponential: CV 1


def test_bursty_arrivals_keep_the_mean_rate_and_burst():
    t = T.bursty_arrivals(40000, 50.0, np.random.default_rng(0))
    gaps = np.diff(np.concatenate([[0.0], t]))
    assert abs(len(t) / (t[-1] / 1e3) - 50.0) / 50.0 < 0.05
    assert gaps.std() / gaps.mean() > 1.15  # burstier than Poisson (CV 1)


@pytest.mark.parametrize("arrivals", ["poisson", "bursty"])
def test_every_seed_offers_the_same_work(arrivals):
    mix = {"arrivals": arrivals, "arrival_seed": 0}
    a = T.arrivals_ms(mix, 40.0, 10.0, 1)
    b = T.arrivals_ms(mix, 40.0, 10.0, 2**31 + 77)
    assert len(a) == len(b) > 300
    assert a[-1] == pytest.approx(b[-1]) and a[-1] <= 10e3
    gaps = lambda t: np.sort(np.diff(np.concatenate([[0.0], t])))
    assert np.allclose(gaps(a), gaps(b))
    assert not np.allclose(a, b)


def test_keep_rates_split_exactly_and_shuffle_by_seed():
    mix = {"keep_rates": [[0.5, 1], [0.7, 2], [0.9, 1]]}
    r1 = T.keep_rates(mix, 10, 3)
    r2 = T.keep_rates(mix, 10, 4)
    for r in (r1, r2):
        vals, counts = np.unique(r, return_counts=True)
        assert list(vals) == [0.5, 0.7, 0.9]
        assert sorted(counts) == [2, 3, 5]
    assert T.cyclic_keep_rates(mix, 5).count(0.7) == 2


def test_nearest_rank_percentile():
    v = list(range(1, 101))
    assert T.percentile(v, 50) == 50
    assert T.percentile(v, 95) == 95
    assert T.percentile([3.0, 1.0, 2.0], 50) == 2.0
    assert T.percentile([7.0], 95) == 7.0
    assert math.isnan(T.percentile([], 50))
    assert T.percentile([1.0, math.inf, 2.0, 3.0], 95) == math.inf


def _records(done_t, t0, t_end, requests):
    from harness.drive import Outbox, Records
    out = Outbox()
    for uid, t in done_t.items():
        out[uid] = np.zeros(3)
        out.done_t[uid] = t
    return Records(requests=requests, out=out, t0=t0, t_end=t_end,
                   setup_s=12.5, window_compiles=0, steps=0,
                   segments_per_request=9)


def test_window_rate_and_latency_arithmetic():
    from harness import main as M
    from harness.drive import Request
    bench = spec.benchmark()
    off = spec.cell("deit-small.offline", bench)
    # closed loop: completions in (t0, t_end], t0 and t_end completions
    rec = _records({0: 9.0, 1: 10.0, 2: 12.0, 3: 14.0, 4: 14.5}, 10.0,
                   14.0, {})
    m = M.end_to_end(off, rec)
    assert m["images_per_s"]["value"] == pytest.approx(2 / 4.0)
    assert m["setup_s"]["value"] == 12.5
    assert M.attempted_failed(off, rec) == (2, 0)
    # open loop: from due time to logits; a request never done is missing
    on = spec.cell("deit-small.online-poisson", bench)
    reqs = {u: Request(uid=u, image=0, r_t=0.7, due_s=u * 0.1)
            for u in range(20)}
    done = {u: 100.0 + u * 0.1 + 0.001 * (u + 1) for u in range(19)}
    m = M.end_to_end(on, _records(done, 100.0, 102.0, reqs))
    assert m["latency_p50_ms"]["value"] == pytest.approx(10.0)
    assert "latency_p95_ms" not in m  # measured, but no cell reports it
    lat = [(done[u] - (100.0 + u * 0.1)) * 1e3 if u in done else math.inf
           for u in range(20)]
    assert T.percentile(lat, 95) == pytest.approx(19.0)
    assert M.attempted_failed(on, _records(done, 100.0, 102.0, reqs)) == (
        20, 1)


# ---------------------------------------------------------------------------
# operation counts and peaks
# ---------------------------------------------------------------------------
def _small():
    c = spec.load_json(BENCH / "configs" / "deit-small.json")
    return c["model"], c["pruning"]


def test_image_macs_track_paper_table_vi():
    """Paper Table VI: 1.79 GMACs at b 16, r_b 0.5, r_t 0.7. The count
    takes alpha = r_b for every pruned matmul (the paper's alpha was
    measured after training), which puts it 12% under the paper's; the
    program's own test holds it within 16%."""
    model, pruning = _small()
    macs = F.image_macs(model, pruning, 0.7)
    assert abs(macs / 1e9 - 1.79) / 1.79 < 0.16
    assert F.image_macs(model, pruning, 0.5) < macs < F.image_macs(
        model, pruning, 0.9)
    assert F.image_flops(model, pruning, 0.7) == 2 * macs


def test_image_macs_equal_the_programs_count():
    import dataclasses
    from repro.configs import get_config
    from repro.core.complexity import model_macs
    cfg = get_config("deit-small")
    model, pruning = _small()
    for r_t in (0.5, 0.7, 0.9):
        p = dataclasses.replace(cfg.pruning, r_t=r_t)
        assert F.image_macs(model, pruning, r_t) == pytest.approx(
            model_macs(cfg, pruning=p)["total"], rel=1e-12)


def test_sbmm_call_hand_worked():
    """One q projection of a tile of 32 images at 197 tokens, DeiT-S:
    M = 6304 rows, K = N = 384, 24 x 24 blocks of 16, 288 kept."""
    ops, nbytes = F.sbmm_call(6304, 384, 384, 288, 16)
    assert ops == 2 * 6304 * 288 * 16 * 16 == 929_562_624
    assert nbytes == 4 * (6304 * 384 + 288 * 256 + 6304 * 384) + 4 * 288
    work = F.sbmm_segment_work(*_small(), ("layers", 0, 2), 32, 197, None)
    assert work["calls"] == 8
    assert work["ops"] == 8 * ops and work["bytes"] == 8 * nbytes
    assert F.sbmm_segment_work(*_small(), ("tdm", 2), 32, 197, 138)[
        "calls"] == 4
    assert F.sbmm_segment_work(*_small(), ("head",), 32, 67, None)[
        "calls"] == 0


def test_peaks_table():
    p = spec.peaks("TPU v5 lite")
    assert p["bf16_flops"] == 197e12
    assert p["int8_ops"] == 393e12
    assert p["hbm_bytes_per_s"] == 819e9
    with pytest.raises(KeyError):
        spec.peaks("cpu")


# ---------------------------------------------------------------------------
# BENCHMARK.json and the files it names
# ---------------------------------------------------------------------------
def test_benchmark_json_keeps_the_contract():
    b = spec.benchmark()
    assert set(b) == {"command", "paths", "run_seconds", "configs",
                      "workloads", "end_to_end", "per_layer"}
    assert b["command"] == ["python3", "bench/run.py"]
    assert b["paths"] == ["bench"]
    assert 1 <= b["run_seconds"] <= 51
    names = [x["name"] for k in ("configs", "workloads", "end_to_end",
                                 "per_layer") for x in b[k]]
    assert len(names) == len(set(names))
    assert all(NAME.match(n) for n in names)
    for c in b["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
        assert c["file"].startswith("bench/") and not c["reduced"]
    e2e = {m["name"]: m for m in b["end_to_end"]}
    assert set(e2e) == {"images_per_s", "latency_p50_ms", "setup_s"}
    assert e2e["setup_s"]["bound"] <= 0.25 and "workloads" not in e2e[
        "setup_s"]
    for m in b["end_to_end"] + b["per_layer"]:
        assert UNIT.match(m["unit"]) and m["better"] in ("lower", "higher")
        assert m["source"] in ("device_trace", "program_span",
                               "program_counter", "host_clock")
    for m in b["end_to_end"]:
        assert 0.01 <= m["bound"] <= 0.25
        assert m["source"] in ("host_clock", "device_trace")
    layers = {}
    for m in b["per_layer"]:
        assert m["moves"] in e2e
        layers.setdefault(m["name"].split(".")[0], set()).add(m["layer"])
        for w in m["workloads"]:
            assert w in e2e[m["moves"]].get("workloads", [w])
    assert all(len(v) == 1 for v in layers.values())
    for w in b["workloads"]:
        assert w["chips"] == 1 and len(w["why"]) <= 200


@pytest.mark.parametrize("cell_name", [
    w["name"] for w in spec.benchmark()["workloads"]])
def test_every_cell_resolves_to_its_files(cell_name):
    cell = spec.cell(cell_name)
    assert cell_name == f"{cell.config['name']}.{cell.traffic}"
    assert {m["name"] for m in cell.end_to_end} >= {"setup_s"}
    assert len(cell.end_to_end) >= 2 and cell.per_layer
    assert hasattr(cell.reference, "forward")
    for m in cell.per_layer:
        mod = cell.metric_module(m["name"])
        assert (mod.LAYER, mod.UNIT, mod.SOURCE, mod.MOVES) == (
            m["layer"], m["unit"], m["source"], m["moves"])
    cfg = spec.model_config(cell.config)
    assert (cfg.d_model, cfg.num_layers) == (cell.config["model"]["d_model"],
                                             12)
    assert set(cell.config["check"]["limits"]) == set(C.NUMBERS)
    assert cell.config["numerics"]["matmul_precision"] in C.CONTROL


def test_metric_files_are_named_in_benchmark_json():
    named = {m["name"] for m in spec.benchmark()["per_layer"]}
    files = {p.name[:-3] for p in (BENCH / "metrics").glob("*.py")}
    assert named <= files


# ---------------------------------------------------------------------------
# trace reduction, on a trace recorded on the chip
# ---------------------------------------------------------------------------
@pytest.fixture(scope="module")
def chip_trace():
    path = TESTDATA / "offline_trace.xplane.pb.gz"
    return X.load(str(path)), spec.load_json(TESTDATA /
                                             "offline_trace.json")


def test_trace_reduction_reads_the_recorded_trace(chip_trace):
    tr, rec = chip_trace
    assert len(tr.chips) == 1 and tr.chips[0]
    assert tr.window_s == pytest.approx(rec["window_s"], rel=1e-12)
    busy = X.busy_s(tr)
    assert busy == pytest.approx(rec["busy_s"], rel=1e-12)
    assert 0 < busy < tr.window_s
    assert X.idle_share(tr) == pytest.approx(1 - busy / tr.window_s)
    secs, count = X.matching_seconds(tr, "sbmm")
    assert count > 0 and 0 < secs <= busy
    assert [secs, count] == pytest.approx(rec["sbmm"], rel=1e-12)
    # the idle time, split among host spans, adds up to the idle time
    idle = sum(v for _, v in X.idle_by_host_span(tr, n=100))
    assert idle == pytest.approx(tr.window_s - busy, rel=1e-9)
    names = {k for k, _ in X.idle_by_host_span(tr)}
    assert names <= set(X.HOST_SPANS) | {X.NO_SPAN}
    top = X.top_ops(tr)
    assert len(top) == 10 and top[0][1] >= top[-1][1]
    assert all("=" not in k for k, _ in top)


def test_busy_union_and_gaps_by_hand():
    ops = [("a", 0, 10), ("b", 5, 20), ("c", 30, 40)]
    assert X.busy_intervals(ops) == [(0, 20), (30, 40)]
    tr = X.Trace(window=(0, 50), chips=[ops],
                 host=[("stage", 15, 35), ("dispatch", 25, 28)])
    assert X.busy_s(tr) == pytest.approx(30e-9)
    assert X.idle_gaps(tr) == [(20, 30), (40, 50)]
    got = dict(X.idle_by_host_span(tr))
    assert got["stage"] == pytest.approx(7e-9)   # 20-25 and 28-30
    assert got["dispatch"] == pytest.approx(3e-9)
    assert got[X.NO_SPAN] == pytest.approx(10e-9)
    assert X.short_name("%_sbmm_raw_jit.10 = f32[8] custom-call()") == \
        "_sbmm_raw_jit"


# ---------------------------------------------------------------------------
# the serving loop, rehearsed on the CPU at a small size
# ---------------------------------------------------------------------------
SMALL_MIX = {"patches": 16, "image_pool": 8, "check_sample": 16,
             "trace_seconds": 0.3}
FAKE_PEAKS = {"bf16_flops": 1e12, "hbm_bytes_per_s": 1e11}


def _rehearse(name, trace=False, seconds=1.0, **mix):
    from harness import main as M
    from repro.configs import get_config
    cfg = get_config("deit-small").reduced()
    return M.run_cell(name, 2**31 + 5, seconds, trace, time.perf_counter(),
                      require_tpu=False, cfg=cfg,
                      mix={**SMALL_MIX, **mix}, peaks=FAKE_PEAKS)


def test_rehearsal_offline_traced():
    r = _rehearse("deit-small.offline", trace=True)
    assert r["correct"] is True and r["failed"] == 0 and r["attempted"] > 0
    assert list(r)[-1] == "check"
    assert set(r["metrics"]) >= {"engine_host_ms_per_step.offline",
                                 "step_mfu.offline"}
    assert r["device"]["window_s"] > 0
    assert set(r["check"]) == set(C.NUMBERS)
    assert r["check"]["group_median_err"]["value"] < 1e-5
    assert r["check"]["n_err_over_tol"]["value"] == 0


def test_rehearsal_online():
    r = _rehearse("deit-small.online-poisson", rate_rps=20.0,
                  warm={"batches": "max", "merged": False, "lanes": False})
    assert r["correct"] is True
    assert set(r["metrics"]) == {"latency_p50_ms", "setup_s"}
    assert r["failed"] == 0 and r["attempted"] >= 10


def _alter_head_rows(y, rows):
    """Half a logit added to logit 3 of the given rows of a head output."""
    return y.at[rows, 3].add(0.5)


def _fault_all(monkeypatch):
    """Every answer, in the head segment (tiles and lanes alike)."""
    from repro.core import packed_runner as PR
    head = PR.vit_head
    monkeypatch.setattr(PR, "vit_head", lambda cfg, params, x:
                        _alter_head_rows(head(cfg, params, x), slice(None)))


def _fault_one_keep_rate(monkeypatch):
    """The answers of one keep rate only: the head alters the rows that
    arrive with r_t 0.9's token count (tiles and lanes alike)."""
    from repro.core import packed_runner as PR
    head = PR.vit_head

    def altered(cfg, params, x):
        y = head(cfg, params, x)
        n = SMALL_MIX["patches"] + 1
        for _ in cfg.pruning.tdm_layers:
            n = 2 + PR.tdm_keep_count(n, 0.9)
        return _alter_head_rows(y, slice(None)) if x.shape[1] == n else y

    monkeypatch.setattr(PR, "vit_head", altered)


def _fault_tiles(monkeypatch):
    """The answers of tiles only: the head segment of the tile path."""
    from repro.core import packed_runner as PR
    run = PR.PackedVitSegments.run

    def altered(self, seg, x, *a, **kw):
        y = run(self, seg, x, *a, **kw)
        return _alter_head_rows(y, slice(None)) if seg[0] == "head" else y

    monkeypatch.setattr(PR.PackedVitSegments, "run", altered)


def _fault_one_slot(monkeypatch):
    """One slot of every full tile: row 5 of a 32-row head batch."""
    from repro.core import packed_runner as PR
    head = PR.vit_head

    def altered(cfg, params, x):
        y = head(cfg, params, x)
        return _alter_head_rows(y, 5) if x.shape[0] == 32 else y

    monkeypatch.setattr(PR, "vit_head", altered)


# fault: (plant, cell, mix update, the numbers it has to fail)
FAULTS = {
    "all": (_fault_all, "deit-small.offline", {}, C.NUMBERS),
    "one_keep_rate": (_fault_one_keep_rate, "deit-small.online-poisson",
                      {"rate_rps": 20.0, "warm": {"batches": "max",
                                                  "merged": False,
                                                  "lanes": False}},
                      C.NUMBERS),
    "tiles": (_fault_tiles, "deit-small.offline", {}, C.NUMBERS),
    "one_slot": (_fault_one_slot, "deit-small.offline", {},
                 ("n_err_over_tol",)),
}


@pytest.mark.parametrize("fault", list(FAULTS))
def test_an_altered_answer_fails_the_check(monkeypatch, fault):
    """The fault a served classifier can have: answers altered where they
    are produced, in the segments the window drives: every answer, those
    of one keep rate, those of tiles and not lanes, and one slot of each
    full tile (which touches a thirty-second of the answers)."""
    plant, cell, mix, fails = FAULTS[fault]
    plant(monkeypatch)
    r = _rehearse(cell, seconds=2.0, check_sample=384, **mix)
    assert r["correct"] is False
    for k in fails:
        assert r["check"][k]["value"] > r["check"][k]["limit"], k


def test_numbers_by_hand():
    err = {0: 1e-6, 1: 2e-6, 2: 3e-6, 3: 0.2, 4: 5e-6, 5: math.inf}
    groups = {0: (0.5, "lane"), 1: (0.5, "lane"), 2: (0.5, "lane"),
              3: (0.9, "tile"), 4: (0.9, "tile"), 5: (0.7, "lane")}
    nums = C.numbers({u: err[u] for u in range(5)}, groups, 1e-5)
    assert nums == {"group_median_err": pytest.approx((0.2 + 5e-6) / 2),
                    "n_err_over_tol": 1}
    nums = C.numbers(err, groups, 1e-5)
    assert nums["group_median_err"] == math.inf
    assert nums["n_err_over_tol"] == 2
    assert not C.verdict(nums, {"group_median_err": 1.0,
                                "n_err_over_tol": 5})
    assert C.numbers({}, {}, 1e-5) == {"group_median_err": math.inf,
                                       "n_err_over_tol": 0}
    assert C.verdict({"group_median_err": 1e-6, "n_err_over_tol": 2},
                     {"group_median_err": 1e-5, "n_err_over_tol": 2})


def _three_pass(einsum):
    """``jnp.einsum`` whose ``high`` precision computes as the TPU's
    three-pass bfloat16 matmul does (hi*hi + hi*lo + lo*hi, each operand
    split into a bfloat16 high part and a bfloat16 remainder), so that the
    control reads on the CPU what it reads on the chip."""
    import jax
    import jax.numpy as jnp

    def split(a):
        hi = a.astype(jnp.bfloat16).astype(jnp.float32)
        return hi, (a - hi).astype(jnp.bfloat16).astype(jnp.float32)

    def patched(spec_, a, b, precision=None, **kw):
        if precision != jax.lax.Precision.HIGH:
            return einsum(spec_, a, b, precision=precision, **kw)
        (ah, al), (bh, bl) = split(a), split(b)
        top = jax.lax.Precision.HIGHEST
        return (einsum(spec_, ah, bh, precision=top, **kw)
                + einsum(spec_, ah, bl, precision=top, **kw)
                + einsum(spec_, al, bh, precision=top, **kw))

    return patched


@pytest.mark.parametrize("config", ["deit-small", "deit-base"])
def test_control_fails_the_check(monkeypatch, config):
    """The control, the plain reference one precision step below the
    configuration's (three-pass ``high`` for ``highest``) in the
    program's place, against the configuration's limits: at full width,
    on 8 images of each keep rate (the reference alone runs on the CPU,
    with the three passes computed explicitly)."""
    import jax.numpy as jnp
    from harness.drive import Request
    from harness import weights as W
    c = spec.load_json(BENCH / "configs" / f"{config}.json")
    ref_mod = spec.load_module(BENCH / "configs" / c["reference"])
    model, pruning = c["model"], c["pruning"]
    params = W.make_params(model, 11)
    scores = W.make_scores(model, pruning, c["structure_seed"])
    P = model["patch_size"] ** 2 * 3
    pool = T.image_pool({"image_pool": 8}, 196, P, 11)
    reqs = [Request(uid=i, image=i % 8, r_t=r)
            for i, r in enumerate([0.7] * 8 + [0.9] * 8)]
    args = (ref_mod, params, model, pruning, scores, pool, reqs)
    ref = C.reference_logits(*args)
    dtype, prec = C.CONTROL[c["numerics"]["matmul_precision"]]
    monkeypatch.setattr(jnp, "einsum", _three_pass(jnp.einsum))
    low = C.reference_logits(*args, dtype=dtype, precision=prec)
    groups = {r.uid: (r.r_t,) for r in reqs}
    check = c["check"]
    assert not C.verdict(C.numbers(C.errors(low, ref), groups,
                                   check["tol"]), check["limits"])
    assert C.verdict(C.numbers(C.errors(ref, ref), groups, check["tol"]),
                     check["limits"])


# ---------------------------------------------------------------------------
# the command
# ---------------------------------------------------------------------------
def _run_py(cwd, tmp_path):
    env = dict(os.environ, JAX_PLATFORMS="cpu", HOME=str(tmp_path))
    return subprocess.run(
        [sys.executable, "bench/run.py", "--workload", "deit-small.offline",
         "--seed", "1", "--seconds", "1", "--trace", "0"],
        cwd=cwd, env=env, capture_output=True, text=True, timeout=300)


def test_run_py_without_a_chip_prints_no_result(tmp_path):
    p = _run_py(ROOT, tmp_path)
    assert p.returncode == 3
    assert p.stdout.strip() == ""
    assert "no" in p.stderr and "TPU" in p.stderr
