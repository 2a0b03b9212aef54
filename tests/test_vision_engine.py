"""VisionEngine: bit-exact ragged-batch serving of the packed ViT.

The acceptance property of the vision serving subsystem: for every request
in a mixed continuous batch (mixed resolutions, mixed per-request keep
rates, staggered arrivals), the served logits are BIT-EXACT against the
single-request offline ``forward_vit_packed`` — and jit recompiles stay
within the ragged batcher's bucket set."""
import dataclasses

import jax
import numpy as np
import pytest

from repro.configs import DEIT_SMALL
from repro.core import packed_runner as PR
from repro.models import model as M
from repro.models import pruning_glue as PG
from repro.obs import Tracer
from repro.serving import (Request, ServeEngine, EngineConfig,
                           VisionEngine, VisionEngineConfig, VisionRequest)


@pytest.fixture(scope="module")
def packed_vit(rng_key):
    cfg = DEIT_SMALL.reduced()
    params = M.init_params(cfg, rng_key)
    scores = PG.init_scores(cfg, params, jax.random.fold_in(rng_key, 7))
    masked = PG.apply_pruning(cfg, params, scores)
    packed = PR.pack_model(cfg, params, scores)
    return cfg, masked, packed


def _mixed_requests(cfg, mixes):
    rng = np.random.default_rng(0)
    pdim = cfg.patch_size ** 2 * 3
    return [VisionRequest(
        uid=i, patches=rng.standard_normal((n, pdim)).astype(np.float32),
        r_t=r_t, arrival_step=arr)
        for i, (n, r_t, arr) in enumerate(mixes)]


def _offline(cfg, masked, packed, req, segments=None):
    c = cfg if req.r_t is None else cfg.replace(
        pruning=dataclasses.replace(cfg.pruning, r_t=req.r_t))
    return np.asarray(PR.forward_vit_packed(
        c, masked, packed, req.patches[None], segments=segments).logits[0])


def test_mixed_batch_bitexact_and_bounded_recompiles(packed_vit):
    """Mixed sizes + keep rates + staggered arrivals through 3 slots: every
    logit vector bit-exact vs the offline path; recompiles <= buckets; one
    unified admit/retire event stream."""
    cfg, masked, packed = packed_vit
    reqs = _mixed_requests(cfg, [(16, None, 0), (9, 0.5, 0), (4, 0.7, 1),
                                 (16, 0.5, 2), (9, None, 3), (4, 0.5, 3)])
    eng = VisionEngine(cfg, masked, packed, VisionEngineConfig(max_batch=3))
    out = eng.serve(reqs)
    assert sorted(out) == [r.uid for r in reqs]

    # recompile discipline: check BEFORE the reference runs below add
    # their own (B=1) shapes to the shared executor's caches
    st = eng.stats()
    assert st["jit_compile_count"] <= st["bucket_count"]
    assert st["batcher_padding_waste"] == 0.0  # token_tile=1: exact tiles

    # unified event stream (same shape as the LM path's)
    admits = [uid for kind, uid in eng.events if kind == "admit"]
    retires = [uid for kind, uid in eng.events if kind == "retire"]
    assert sorted(admits) == sorted(retires) == [r.uid for r in reqs]

    for r in reqs:
        ref = _offline(cfg, masked, packed, r, segments=eng.segments)
        assert np.array_equal(ref, out[r.uid]), (
            f"uid {r.uid}: batched serving changed the logits")
        assert r.done and r.logits is not None


def test_batch_composition_invariance(packed_vit):
    """The same request served alone and in a different mix produces the
    same bits (batch composition independence)."""
    cfg, masked, packed = packed_vit
    probe = _mixed_requests(cfg, [(9, 0.5, 0)])[0]

    def fresh(u):
        return VisionRequest(uid=u, patches=probe.patches.copy(), r_t=0.5)

    solo = VisionEngine(cfg, masked, packed,
                        VisionEngineConfig(max_batch=1))
    out_solo = solo.serve([fresh(0)])
    crowd_reqs = [fresh(7)] + _mixed_requests(
        cfg, [(16, None, 0), (4, 0.7, 0), (9, 0.7, 1)])
    crowd = VisionEngine(cfg, masked, packed,
                         VisionEngineConfig(max_batch=4))
    out_crowd = crowd.serve(crowd_reqs)
    assert np.array_equal(out_solo[0], out_crowd[7])


def _serve(eng, tr, cfg, mixes):
    """Serve ``mixes`` through a traced engine; returns the requests,
    their logits and the attrs of the ``step`` records of this serve."""
    mark = len(tr.span_log)
    reqs = _mixed_requests(cfg, mixes)
    out = eng.serve(reqs)
    steps = [s["attrs"] for s in tr.span_log[mark:] if s["name"] == "step"]
    return reqs, out, steps


def _stacked(segments, reqs, b_tile):
    """Logits of a cohort of requests of one size and keep rate, every
    tile built the per-member way: each member's rows sliced out of the
    last output, zero rows up to ``b_tile``, stacked on the device. (On
    the CPU a batched tile need not match the one-row offline path bit
    for bit, so this is the reference for batched tiles.)"""
    import jax.numpy as jnp
    n, r_t = reqs[0].n_patches, reqs[0].r_t
    xs = [jnp.asarray(r.patches) for r in reqs]
    for seg in segments.plan:
        k = PR.tdm_keep_count(n, r_t) if seg[0] == "tdm" else None
        rows = xs + [jnp.zeros_like(xs[0])] * (b_tile - len(xs))
        y = segments.run(seg, jnp.stack(rows), k=k)
        n = n + 1 if seg[0] == "embed" else k + 2 if k is not None else n
        xs = [y[b] if seg[0] == "head" else y[b, :n]
              for b in range(len(reqs))]
    return [np.asarray(x) for x in xs]


@pytest.mark.parametrize("depth", [1, 2])
def test_lockstep_cohort_passes_tiles_through(packed_vit, depth):
    """A cohort of four requests of one size and keep rate at max_batch
    4, served twice. The first time every tile is stacked (a bucket's
    first tile always is, so its eager programs compile with it). The
    second time every tile after the embed step is the previous tile's
    output whole, staged with no device program, and the head's logits
    come back in one copy; at depth 2 the next step consumes (donates)
    an output of the step still in flight. Both give the same bits."""
    cfg, masked, packed = packed_vit
    tr = Tracer()
    eng = VisionEngine(cfg, masked, packed, VisionEngineConfig(
        max_batch=4, pipeline_depth=depth), tracer=tr)
    mixes = [(16, 0.5, 0)] * 4
    reqs, first, steps = _serve(eng, tr, cfg, mixes)
    kinds = [seg[0] for seg in eng.segments.plan]
    # after the host-stacked embed step: 4 row slices (8), 4 pads, a
    # stack of 4 (5)
    assert [(a["passthrough_tiles"], a["eager_ops"]) for a in steps] == [
        (0, 0 if k == "embed" else 8 + 4 + 5) for k in kinds]
    _, out, steps = _serve(eng, tr, cfg, mixes)
    st = eng.stats()
    assert st["jit_compile_count"] <= st["bucket_count"]
    assert [a["tiles"] for a in steps] == [1] * len(kinds)
    assert [a["passthrough_tiles"] for a in steps] == [
        0 if k == "embed" else 1 for k in kinds]
    assert [a["eager_ops"] for a in steps] == [0] * len(kinds)
    # the patches, stacked on the host, are the one put
    assert [a["h2d_puts"] for a in steps] == [
        1 if k == "embed" else 0 for k in kinds]
    assert st["passthrough_tiles"] == len(kinds) - 1
    ref = _stacked(eng.segments, reqs, 4)
    for r, want in zip(reqs, ref):
        assert np.array_equal(want, first[r.uid]), r.uid
        assert np.array_equal(want, out[r.uid]), r.uid


# requests, max_batch, and per step of a second serve (tiles,
# passthrough_tiles, eager_ops)
FALLBACK = {
    # three members padded to four rows: every tile is stacked. Past the
    # embed step each costs the hand count of the per-member path, 16
    # programs: 3 row slices (6), 3 pads, a zero row (2), a stack of 4 (5)
    "padded": ([(16, 0.5, 0)] * 3, 4, [(1, 0, 0)] + [(1, 0, 16)] * 4),
    # 16 and 9 patches both keep 8 tokens at the TDM: singleton tiles
    # pass through until the second layers tile, which holds rows of two
    # outputs and is stacked at the same hand count, 9 programs: 2 row
    # slices (4), 2 pads, a stack of 2 (3)
    "ragged": ([(16, 0.5, 0), (9, 0.8, 0)], 2,
               [(2, 0, 0), (2, 2, 0), (2, 2, 0), (1, 0, 9), (1, 1, 0)]),
}


@pytest.mark.parametrize("case", sorted(FALLBACK))
def test_tiles_that_are_no_whole_output_keep_the_stacking_path(
        packed_vit, case):
    """A tile with a zero row, or with rows of two outputs, is sliced,
    padded and stacked as before: same programs, and logits bit for bit
    those of the first serve, in which every bucket is new and so every
    tile is stacked."""
    cfg, masked, packed = packed_vit
    mixes, max_batch, want = FALLBACK[case]
    tr = Tracer()
    eng = VisionEngine(cfg, masked, packed,
                       VisionEngineConfig(max_batch=max_batch), tracer=tr)
    assert [seg[0] for seg in eng.segments.plan] == [
        "embed", "layers", "tdm", "layers", "head"]
    reqs, ref, steps = _serve(eng, tr, cfg, mixes)
    assert sum(a["passthrough_tiles"] for a in steps) == 0
    reqs, out, steps = _serve(eng, tr, cfg, mixes)
    assert [(a["tiles"], a["passthrough_tiles"], a["eager_ops"])
            for a in steps] == want
    st = eng.stats()
    assert st["jit_compile_count"] <= st["bucket_count"]
    for r in reqs:
        assert np.array_equal(ref[r.uid], out[r.uid]), r.uid


def test_padded_modes_serve_everyone_close(packed_vit):
    """token_tile > 1 and naive padding run masked kernels: same math,
    different FP reduction order — allclose, all requests served, bound
    still holds."""
    cfg, masked, packed = packed_vit
    mixes = [(16, None, 0), (9, 0.5, 0), (4, 0.7, 1), (13, 0.5, 1)]
    for vc in (VisionEngineConfig(max_batch=2, token_tile=8),
               VisionEngineConfig(max_batch=2, mode="naive")):
        eng = VisionEngine(cfg, masked, packed, vc)
        reqs = _mixed_requests(cfg, mixes)
        out = eng.serve(reqs)
        assert sorted(out) == [r.uid for r in reqs]
        st = eng.stats()
        assert st["jit_compile_count"] <= st["bucket_count"]
        for r in reqs:
            ref = _offline(cfg, masked, packed, r)
            np.testing.assert_allclose(ref, out[r.uid], atol=1e-5,
                                       rtol=1e-5)


@pytest.mark.parametrize("pmode", ["merge", "fuse", "full"])
def test_planner_modes_bitexact_and_bounded_recompiles(packed_vit, pmode):
    """The tentpole acceptance: merged and fused ExecutionPlans produce
    BIT-EXACT head logits vs the unmerged balanced path (planner off) and
    vs the offline single-request oracle, with jit recompiles bounded by
    the bucket ∪ trajectory budget."""
    cfg, masked, packed = packed_vit
    mixes = [(16, None, 0), (9, 0.5, 0), (4, 0.7, 1),
             (16, 0.5, 2), (9, None, 3), (4, 0.5, 3),
             (9, 0.5, 4)]  # uid 6 shares uid 1's bucket -> merge fodder

    base = VisionEngine(cfg, masked, packed,
                        VisionEngineConfig(max_batch=3, planner="off"))
    out_base = base.serve(_mixed_requests(cfg, mixes))

    eng = VisionEngine(cfg, masked, packed,
                       VisionEngineConfig(max_batch=3, planner=pmode))
    reqs = _mixed_requests(cfg, mixes)
    out = eng.serve(reqs)
    assert sorted(out) == [r.uid for r in reqs]

    st = eng.stats()
    assert st["jit_compile_count"] <= st["compile_budget"]
    assert st["compile_budget"] == (st["bucket_count"]
                                    + st["trajectory_count"])
    if pmode in ("fuse", "full"):
        assert st["plan_lanes"] > 0  # express lanes actually ran

    for r in reqs:
        assert np.array_equal(out_base[r.uid], out[r.uid]), (
            f"uid {r.uid}: planner {pmode} changed the logits vs the "
            f"unmerged balanced path")
        ref = _offline(cfg, masked, packed, r, segments=eng.segments)
        assert np.array_equal(ref, out[r.uid])


def test_merge_mode_actually_merges(packed_vit):
    """With fusion disabled and a dispatch-dominated cost model, same-stage
    neighboring buckets must bin-pack into masked tiles."""
    from repro.serving import TileCostModel
    cfg, masked, packed = packed_vit
    cm = TileCostModel(cfg, dispatch_overhead_cycles=1e9)
    eng = VisionEngine(cfg, masked, packed,
                       VisionEngineConfig(max_batch=4, planner="merge"),
                       cost_model=cm)
    reqs = _mixed_requests(cfg, [(16, 0.5, 0), (9, 0.5, 0), (4, 0.5, 0)])
    out = eng.serve(reqs)
    st = eng.stats()
    assert st["plan_merges"] > 0
    assert st["batcher_padding_waste"] > 0.0  # merged tiles are masked
    for r in reqs:
        ref = _offline(cfg, masked, packed, r)
        assert np.array_equal(ref, out[r.uid])


def test_deadline_requests_split_dispatch_first_and_discount_load(
        packed_vit):
    """Deadline-aware tiling: an already-expired SLO makes the planner
    carve the request out of shared tiles (counted in plan stats) while
    results stay bit-exact; the admission annotation shrinks so
    prune_pressure_aware prefers tight-deadline requests."""
    cfg, masked, packed = packed_vit
    # same size + r_t so the deadline request shares every bucket (not
    # fusible -> must go through the split path)
    mixes = [(9, 0.5, 0), (9, 0.5, 0), (9, 0.5, 0)]
    reqs = _mixed_requests(cfg, mixes)
    reqs[0].deadline_ms = 1e-6  # expired by the first plan
    eng = VisionEngine(cfg, masked, packed,
                       VisionEngineConfig(max_batch=3, planner="full"))
    out = eng.serve(reqs)
    st = eng.stats()
    assert st["plan_deadline_urgent"] > 0
    assert st["plan_deadline_splits"] > 0
    for r in reqs:
        ref = _offline(cfg, masked, packed, r)
        assert np.array_equal(ref, out[r.uid])

    # generous deadlines are not urgent
    eng2 = VisionEngine(cfg, masked, packed,
                        VisionEngineConfig(max_batch=3, planner="full"))
    reqs2 = _mixed_requests(cfg, mixes)
    for r in reqs2:
        r.deadline_ms = 1e9
    eng2.serve(reqs2)
    assert eng2.stats()["plan_deadline_urgent"] == 0

    # the prune_pressure_aware annotation: tighter deadline -> smaller load
    tight, loose = _mixed_requests(cfg, [(9, 0.5, 0), (9, 0.5, 0)])
    tight.deadline_ms = 1e-6
    eng3 = VisionEngine(cfg, masked, packed,
                        VisionEngineConfig(max_batch=1, planner="full"))
    eng3.serve([tight, loose])
    assert tight.prune_load < loose.prune_load


def test_admission_policies_order_vision_requests(packed_vit):
    """shortest_prompt_first admits small images first;
    prune_pressure_aware admits by predicted post-prune token load — a
    heavily-pruned large image can overtake a lightly-pruned medium one."""
    cfg, masked, packed = packed_vit
    # uid 0: 16 patches r_t=0.1 (heavy pruning), uid 1: 16 patches r_t=1.0,
    # uid 2: 9 patches r_t=1.0, uid 3: 4 patches r_t=1.0
    mixes = [(16, 0.1, 0), (16, 1.0, 0), (9, 1.0, 0), (4, 1.0, 0)]

    def admit_order(policy):
        eng = VisionEngine(cfg, masked, packed,
                           VisionEngineConfig(max_batch=1), policy=policy)
        eng.serve(_mixed_requests(cfg, mixes))
        return [uid for kind, uid in eng.events if kind == "admit"]

    assert admit_order("fifo") == [0, 1, 2, 3]
    assert admit_order("shortest_prompt_first") == [3, 2, 0, 1]
    loads = {r.uid: r.prune_load
             for r in _annotated(cfg, masked, packed, mixes)}
    expected = sorted(loads, key=lambda u: loads[u])
    assert admit_order("prune_pressure_aware") == expected
    # the heavily-pruned large image must overtake the unpruned one
    assert expected.index(0) < expected.index(1)


def _annotated(cfg, masked, packed, mixes):
    reqs = _mixed_requests(cfg, mixes)
    for r in reqs:
        r.prune_load = float(sum(PR.token_trajectory(
            cfg, r.n_patches, r_t=r.r_t)))
    return reqs


def test_lm_requests_get_prune_load_annotation(rng_key):
    """The LM ServeEngine annotates prune_load (KV-prune-discounted
    footprint) so prune_pressure_aware is meaningful on both paths."""
    from repro.configs import get_config
    cfg = get_config("stablelm-1.6b").reduced()
    params = M.init_params(cfg, rng_key)
    ec = EngineConfig(max_batch=2, max_len=64, kv_prune_interval=4,
                      kv_prune_keep=0.5)
    eng = ServeEngine(cfg, params, ec)
    reqs = [Request(uid=0, prompt=np.arange(10, dtype=np.int32),
                    max_new_tokens=6)]
    eng._annotate_prune_load(reqs)
    assert reqs[0].prune_load == pytest.approx((10 + 6) * 0.5)
    # disabled pruning -> undiscounted footprint
    eng2 = ServeEngine(cfg, params, EngineConfig(max_batch=2, max_len=64))
    reqs2 = [Request(uid=0, prompt=np.arange(10, dtype=np.int32),
                     max_new_tokens=6)]
    eng2._annotate_prune_load(reqs2)
    assert reqs2[0].prune_load == pytest.approx(16.0)


def test_validation_and_config_errors(packed_vit):
    cfg, masked, packed = packed_vit
    pdim = cfg.patch_size ** 2 * 3
    eng = VisionEngine(cfg, masked, packed)
    with pytest.raises(ValueError, match="patches outside"):
        eng.serve([VisionRequest(uid=0, patches=np.zeros((99, pdim),
                                                         np.float32))])
    with pytest.raises(ValueError, match="patch dim"):
        eng.serve([VisionRequest(uid=0, patches=np.zeros((4, 7),
                                                         np.float32))])
    with pytest.raises(ValueError, match="r_t"):
        eng.serve([VisionRequest(uid=0, patches=np.zeros((4, pdim),
                                                         np.float32),
                                 r_t=1.5)])
    with pytest.raises(ValueError, match="deadline_ms"):
        eng.serve([VisionRequest(uid=0, patches=np.zeros((4, pdim),
                                                         np.float32),
                                 deadline_ms=-5.0)])
    with pytest.raises(ValueError):
        VisionEngineConfig(max_batch=0)
    with pytest.raises(ValueError):
        VisionEngineConfig(token_tile=0)
    with pytest.raises(ValueError):
        VisionEngineConfig(mode="magic")
    with pytest.raises(ValueError):
        VisionEngineConfig(planner="aggressive")
    with pytest.raises(ValueError, match="balanced"):
        VisionEngineConfig(mode="naive", planner="full")
    with pytest.raises(ValueError, match="family"):
        VisionEngine(DEIT_SMALL.reduced().replace(family="dense"),
                     masked, packed)
    with pytest.raises(ValueError, match="unknown policy"):
        VisionEngine(cfg, masked, packed, policy="best_effort")


def test_invalid_request_does_not_leak_siblings(packed_vit):
    """A serve() that raises on one request must not enqueue the others —
    they would silently surface in the next serve()'s results."""
    cfg, masked, packed = packed_vit
    pdim = cfg.patch_size ** 2 * 3
    eng = VisionEngine(cfg, masked, packed, VisionEngineConfig(max_batch=2))
    good = _mixed_requests(cfg, [(4, 0.5, 0)])[0]
    bad = VisionRequest(uid=9, patches=np.zeros((4, 7), np.float32))
    with pytest.raises(ValueError, match="patch dim"):
        eng.serve([good, bad])
    other = _mixed_requests(cfg, [(4, 0.5, 0)])[0]
    other.uid = 5
    out = eng.serve([other])
    assert sorted(out) == [5]  # `good` must NOT ride along


def test_large_token_tile_respects_pos_table(packed_vit):
    """token_tile rounding must clamp at the position-table capacity: a
    full-resolution image under a coarse tile previously crashed the embed
    stage with a broadcast error."""
    cfg, masked, packed = packed_vit
    eng = VisionEngine(cfg, masked, packed,
                       VisionEngineConfig(max_batch=2, token_tile=15))
    reqs = _mixed_requests(cfg, [(16, None, 0), (4, 0.5, 0)])
    out = eng.serve(reqs)
    assert sorted(out) == [0, 1]
    for r in reqs:
        ref = _offline(cfg, masked, packed, r)
        np.testing.assert_allclose(ref, out[r.uid], atol=1e-5, rtol=1e-5)


def test_from_pruned_builds_serving_engine(rng_key):
    cfg = DEIT_SMALL.reduced()
    params = M.init_params(cfg, rng_key)
    scores = PG.init_scores(cfg, params, jax.random.fold_in(rng_key, 7))
    eng = VisionEngine.from_pruned(cfg, params, scores,
                                   vc=VisionEngineConfig(max_batch=2))
    reqs = _mixed_requests(cfg, [(16, None, 0), (9, 0.5, 0)])
    out = eng.serve(reqs)
    assert sorted(out) == [0, 1]
    for lg in out.values():
        assert lg.shape == (cfg.num_classes,)
        assert np.isfinite(lg).all()


def test_validation_rejects_nonfinite_and_bad_quality(packed_vit):
    """NaN fails every range comparison, so it used to slip through the
    ``deadline_ms <= 0`` check; non-finite r_t / deadline / schedules and
    unknown quality preferences must all be rejected at submit."""
    cfg, masked, packed = packed_vit
    pdim = cfg.patch_size ** 2 * 3
    eng = VisionEngine(cfg, masked, packed)

    def rq(**kw):
        return VisionRequest(uid=0, patches=np.zeros((4, pdim), np.float32),
                             **kw)

    for bad in (float("nan"), float("inf"), -float("inf")):
        with pytest.raises(ValueError, match="r_t"):
            eng.serve([rq(r_t=bad)])
        with pytest.raises(ValueError, match="deadline_ms"):
            eng.serve([rq(deadline_ms=bad)])
    with pytest.raises(ValueError, match="keep_schedule"):
        eng.serve([rq(keep_schedule=(float("nan"),))])
    with pytest.raises(ValueError, match="keep_schedule"):
        eng.serve([rq(keep_schedule=(0.5, 0.5))])  # model has 1 TDM
    with pytest.raises(ValueError, match="quality"):
        eng.serve([rq(quality="fastest")])


def test_prune_load_refreshes_while_waiting(packed_vit):
    """The deadline discount is recomputed each admission pass: waiting
    time consumes slack, so a queued deadline request's annotated load
    keeps falling (its admission urgency keeps RISING) — not frozen at
    its submit-time value."""
    import time as _time

    cfg, masked, packed = packed_vit
    pdim = cfg.patch_size ** 2 * 3
    eng = VisionEngine(cfg, masked, packed)
    req = VisionRequest(uid=0, patches=np.zeros((9, pdim), np.float32),
                        deadline_ms=100.0, prune_load_base=100.0,
                        prune_load=100.0, solo_ms=50.0,
                        submit_t=_time.monotonic())
    eng.scheduler.waiting.append(req)
    eng._refresh_prune_loads(req.submit_t)      # full slack at submit
    assert req.prune_load == pytest.approx(100.0)
    eng._refresh_prune_loads(req.submit_t + 0.075)  # 75ms waited
    mid = req.prune_load
    assert mid == pytest.approx(100.0 * (25.0 / 50.0))
    eng._refresh_prune_loads(req.submit_t + 1.0)    # deadline blown
    assert req.prune_load == 0.0 < mid


def test_soft_prune_requests_bitexact_vs_offline(packed_vit):
    """Soft-pruning requests (package token) served in a mixed batch with
    hard-pruning ones: each bit-exact against its own offline path."""
    cfg, masked, packed = packed_vit
    reqs = _mixed_requests(cfg, [(16, None, 0), (9, 0.5, 0), (16, 0.5, 1)])
    reqs[0].soft_prune = True
    reqs[1].soft_prune = True
    eng = VisionEngine(cfg, masked, packed,
                       VisionEngineConfig(max_batch=3, planner="full",
                                          pipeline_depth=2))
    out = eng.serve(reqs)
    for r in reqs:
        c = cfg if r.r_t is None else dataclasses.replace(
            cfg, pruning=dataclasses.replace(cfg.pruning, r_t=r.r_t))
        ref = np.asarray(PR.forward_vit_packed(
            c, masked, packed, r.patches[None],
            soft=r.soft_prune).logits[0])
        assert np.array_equal(ref, out[r.uid]), r.uid
    st = eng.stats()
    assert st["jit_compile_count"] <= st["compile_budget"]
