"""StepPipeline + pipelined step loops: depth semantics, staged-state
rollback on drop (the replan-between-stage-and-dispatch regression),
mid-step admission landing in the NEXT plan, plan_ahead memoization
equivalence, and cross-depth bit-exactness for both engines."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import DEIT_SMALL, get_config
from repro.core import packed_runner as PR
from repro.models import model as M
from repro.models import pruning_glue as PG
from repro.serving import (EngineConfig, PlanItem, Request, ServeEngine,
                           StagedStep, StepPipeline, VisionEngine,
                           VisionEngineConfig, VisionRequest)
from repro.serving.planner import TileCostModel, TilePlanner
from repro.serving.ragged_batcher import RaggedBatcher


# ---------------------------------------------------------------------------
# StepPipeline unit semantics
# ---------------------------------------------------------------------------
def _step(i, done):
    return StagedStep(dispatch=lambda: jnp.full((2,), i),
                      complete=lambda h: done.append(i), label=f"s{i}")


def test_depth_one_completes_inside_submit():
    done = []
    p = StepPipeline(1)
    p.submit(_step(0, done))
    assert done == [0] and p.in_flight == 0
    assert p.stats()["steps"] == 1


def test_depth_two_keeps_one_step_in_flight():
    done = []
    p = StepPipeline(2)
    p.submit(_step(0, done))
    assert done == [] and p.in_flight == 1
    p.submit(_step(1, done))  # dispatch 1 completes 0
    assert done == [0] and p.in_flight == 1
    p.flush()
    assert done == [0, 1] and p.in_flight == 0


def test_drop_runs_rollback_and_dispatched_steps_cannot_drop():
    rolled, done = [], []
    p = StepPipeline(2)
    s = StagedStep(dispatch=lambda: jnp.zeros(()), complete=lambda h: None,
                   rollback=lambda: rolled.append(True))
    p.drop(s)
    assert rolled == [True] and p.stats()["drops"] == 1
    live = _step(7, done)
    p.submit(live)
    with pytest.raises(RuntimeError, match="dispatched"):
        p.drop(live)
    p.flush()


def test_starvation_counts_empty_queue_gaps_only():
    """starved_s accumulates host time spent while NOTHING is in flight
    (depth 1: every inter-step gap) and skips gaps covered by an
    in-flight step (depth 2) — the bench's device_idle_s column."""
    import time as _time

    gap = 0.03
    done = []
    p1 = StepPipeline(1)
    p1.submit(_step(0, done))      # completes inside submit -> queue empty
    _time.sleep(gap)               # host "staging" with the device starved
    p1.submit(_step(1, done))
    assert p1.stats()["starved_s"] >= gap

    p2 = StepPipeline(2)
    p2.submit(_step(0, done))      # stays in flight
    base = p2.stats()["starved_s"]
    _time.sleep(gap)               # device has queued work the whole gap
    p2.submit(_step(1, done))
    assert p2.stats()["starved_s"] - base < gap / 2
    p2.flush()


@pytest.mark.parametrize("depth", [1, 2])
def test_unread_steps_complete_without_blocking_up_to_the_bound(
        monkeypatch, depth):
    """A step whose dispatch returns no handles (the host reads none of
    its outputs) completes without ``block_until_ready``: it counts in
    ``steps_unwaited`` and never as an overlap hit. After ``max_unwaited``
    such steps in a row the next one waits on its device ``outputs``; a
    step with handles always waits on them."""
    blocked = []
    real = jax.block_until_ready
    monkeypatch.setattr(jax, "block_until_ready",
                        lambda x: blocked.append(len(x)) or real(x))
    done = []

    def unread(i):
        return StagedStep(dispatch=lambda: [], outputs=[jnp.full((2,), i)],
                          complete=lambda h: done.append(i))

    free = StepPipeline(depth, max_unwaited=100)
    steps = [unread(i) for i in range(3)]
    for s in steps:
        free.submit(s)
    free.flush()
    assert done == [0, 1, 2] and blocked == []
    assert not any(s.waited for s in steps) and all(s.completed
                                                    for s in steps)
    st = free.stats()
    assert st["steps_unwaited"] == 3 and st["overlap_hits"] == 0
    assert st["block_s"] == 0.0

    bounded = StepPipeline(depth, max_unwaited=2)
    steps = [unread(i) for i in range(6)] + [_step(6, done)]
    for s in steps:
        bounded.submit(s)
    bounded.flush()
    assert [s.waited for s in steps] == [False, False, True] * 2 + [True]
    assert blocked == [1, 1, 1]
    st = bounded.stats()
    assert st["steps_unwaited"] == 4 and st["overlap_hits"] <= 3

    # the default bound 0 waits on every step, as the LM engine's do
    sync = StepPipeline(depth)
    sync.submit(unread(7))
    sync.flush()
    assert blocked == [1, 1, 1, 1] and sync.stats()["steps_unwaited"] == 0


def test_bound_waits_on_the_step_that_consumed_the_outputs(monkeypatch):
    """At the run-ahead bound, a step whose device outputs a later
    in-flight step already consumed (donated) waits on that step's
    outputs, which the device runs after it: the bound holds at depth 2
    too."""
    blocked = []
    real = jax.block_until_ready
    monkeypatch.setattr(jax, "block_until_ready",
                        lambda x: blocked.append(x) or real(x))
    outs = [jnp.full((2,), i) for i in range(3)]

    def consuming(i):
        def dispatch():
            if i:
                outs[i - 1].delete()   # as a donating segment call does
            return []
        return StagedStep(dispatch=dispatch, outputs=[outs[i]],
                          complete=lambda h: None)

    p = StepPipeline(2, max_unwaited=1)
    steps = [consuming(i) for i in range(3)]
    for s in steps:
        p.submit(s)
    p.flush()
    assert [s.waited for s in steps] == [False, True, False]
    assert len(blocked) == 1 and len(blocked[0]) == 1
    assert blocked[0][0] is outs[2]
    assert p.stats()["steps_unwaited"] == 2


def test_depth_validation():
    with pytest.raises(ValueError, match="depth"):
        StepPipeline(0)
    with pytest.raises(ValueError, match="max_unwaited"):
        StepPipeline(1, max_unwaited=-1)
    with pytest.raises(ValueError, match="pipeline_depth"):
        EngineConfig(pipeline_depth=0)
    with pytest.raises(ValueError, match="pipeline_depth"):
        VisionEngineConfig(pipeline_depth=0)


# ---------------------------------------------------------------------------
# plan_ahead: memoized fusibility == exact per-horizon scan, and purity
# ---------------------------------------------------------------------------
def _planner(mode="fuse"):
    return TilePlanner(RaggedBatcher(token_tile=1, max_batch=8),
                       TileCostModel(dispatch_overhead_cycles=1000.0),
                       mode=mode)


def _traj_items(*trajs):
    return [PlanItem(stage=t[0][0], n_tokens=t[0][1], trajectory=t)
            for t in trajs]


@pytest.mark.parametrize("mode", ["off", "merge", "fuse", "full"])
def test_plan_ahead_matches_exact_replan_per_horizon(mode):
    """Every speculative plan must equal what a from-scratch ``_build``
    (exact pairwise fusion scan) produces on the advanced population —
    the memoized last-collision offsets are an optimization, never a
    semantic change."""
    p = _planner(mode)
    items = _traj_items(
        (("a", 16), ("b", 12), ("c", 8), ("d", 6), ("e", 4), ("f", 2)),
        (("a", 16), ("b", 11), ("c", 8), ("d", 5), ("e", 3), ("f", 1)),
        (("x", 9), ("y", 7), ("z", 5)),
    )
    plans = p.plan_ahead(items, 5)
    assert len(plans) > 1
    cur = list(items)
    for h in range(1, len(plans)):
        cur = p.advance_items(cur, plans[h - 1])
        exact = p._build(cur)
        assert plans[h].tiles == exact.tiles, f"horizon {h}"
        assert plans[h].lanes == exact.lanes, f"horizon {h}"
    if mode in ("fuse", "full"):
        # items 0/1 last collide at offset 2 -> both go solo at horizon 3
        assert any(pl.lanes for pl in plans[1:])


def test_plan_ahead_is_pure_and_commit_folds_ledgers():
    p = _planner("full")
    items = _traj_items((("a", 16), ("b", 12), ("c", 8)),
                        (("a", 16), ("b", 11), ("c", 8)))
    plans = p.plan_ahead(items, 3)
    assert p.plans == 0 and p.batcher.tiles_planned == 0
    p.commit(plans[0])
    assert p.plans == 1 and p.batcher.tiles_planned == len(plans[0].tiles)


# ---------------------------------------------------------------------------
# LM engine: rollback regression + cross-depth bit-exactness
# ---------------------------------------------------------------------------
@pytest.fixture(scope="module")
def lm_setup():
    cfg = get_config("stablelm-1.6b").reduced()
    params = M.init_params(cfg, jax.random.PRNGKey(0))
    return cfg, params


def _lm_reqs():
    return [Request(uid=0, prompt=np.arange(10, dtype=np.int32),
                    max_new_tokens=10),
            Request(uid=1, prompt=np.arange(5, dtype=np.int32) + 7,
                    max_new_tokens=12)]


def _kvm_state(kvm):
    return (kvm.caches, kvm.lengths.copy(), kvm.starts.copy(),
            kvm.active.copy(), kvm.steps_since_prune, kvm.prune_events)


def _assert_kvm_equal(kvm, pre):
    assert kvm.caches is pre[0]  # handle identity: nothing was dispatched
    assert np.array_equal(kvm.lengths, pre[1])
    assert np.array_equal(kvm.starts, pre[2])
    assert np.array_equal(kvm.active, pre[3])
    assert kvm.steps_since_prune == pre[4]
    assert kvm.prune_events == pre[5]


def test_lm_stage_then_drop_leaves_no_trace(lm_setup):
    """Replan between stage and dispatch: dropping a staged admission or
    decode step must restore every KVCacheManager counter/mirror and the
    cache handle, and the restaged step must still produce tokens."""
    cfg, params = lm_setup
    eng = ServeEngine(cfg, params, EngineConfig(
        max_batch=2, max_len=24, kv_prune_interval=2, kv_prune_keep=0.5,
        pipeline_depth=2))
    sched, kvm = eng.scheduler, eng.cache
    reqs = _lm_reqs()
    eng._annotate_prune_load(reqs)
    sched.submit(reqs)
    kvm.reset()
    eng._toks = np.zeros((2,), np.int64)
    eng._scheduled = {}
    admitted = sched.schedule()
    out = {}

    pre = _kvm_state(kvm)
    apt = eng.admission_prefill_tokens
    staged = eng._stage_admissions(admitted, out)
    assert kvm.active.any()  # staging DID mutate the mirrors
    eng.pipeline.drop(staged)
    _assert_kvm_equal(kvm, pre)
    assert eng.admission_prefill_tokens == apt  # counter is dispatch-side
    assert eng._scheduled == {}

    eng.pipeline.submit(eng._stage_admissions(admitted, out))
    pre2 = _kvm_state(kvm)
    staged2 = eng._stage_decode(out)
    assert kvm.steps_since_prune != pre2[4]  # prune cadence ticked in stage
    assert not np.array_equal(kvm.lengths, pre2[1])  # on_decode advanced
    eng.pipeline.drop(staged2)
    _assert_kvm_equal(kvm, pre2)
    assert eng.pipeline.stats()["drops"] == 2

    eng.pipeline.submit(eng._stage_decode(out))
    eng.pipeline.flush()
    for _, req in admitted:
        assert len(req.generated) == 2  # prefill token + one decode token


@pytest.mark.parametrize("depth", [2, 3])
def test_lm_continuous_depth_bitexact(lm_setup, depth):
    """Pipelined depths must reproduce the depth-1 (synchronous) path
    exactly: same tokens, same admit/retire event stream, same prune
    count — with KV pruning firing mid-stream and slot churn."""
    cfg, params = lm_setup

    def run(d):
        eng = ServeEngine(cfg, params, EngineConfig(
            max_batch=2, max_len=24, kv_prune_interval=2,
            kv_prune_keep=0.5, pipeline_depth=d))
        out = eng.serve(_lm_reqs(), continuous=True)
        return out, list(eng.events), eng.prune_events, eng

    base, base_ev, base_pr, _ = run(1)
    got, got_ev, got_pr, eng = run(depth)
    assert got == base
    assert got_ev == base_ev
    assert got_pr == base_pr and base_pr > 0
    st = eng.stats()
    assert st["pipeline_steps"] > 0
    assert st["pipeline_drops"] == 0  # no mid-step submissions here


# ---------------------------------------------------------------------------
# Vision engine: stage/drop leak audit + mid-step admission
# ---------------------------------------------------------------------------
@pytest.fixture(scope="module")
def packed_vit():
    cfg = DEIT_SMALL.reduced()
    key = jax.random.PRNGKey(0)
    params = M.init_params(cfg, key)
    scores = PG.init_scores(cfg, params, jax.random.fold_in(key, 7))
    masked = PG.apply_pruning(cfg, params, scores)
    return cfg, masked


def _vision_reqs(cfg, mixes, seed=0):
    rng = np.random.default_rng(seed)
    pdim = cfg.patch_size ** 2 * 3
    return [VisionRequest(
        uid=i, patches=rng.standard_normal((n, pdim)).astype(np.float32),
        r_t=r_t, arrival_step=arr)
        for i, (n, r_t, arr) in enumerate(mixes)]


def test_vision_stage_then_drop_leaves_no_trace(packed_vit):
    """Vision staging is mutation-free by construction: a staged-then-
    dropped step must leave the planner/batcher ledgers, the step
    counter, and every in-flight request exactly as they were — and the
    restaged steps must still serve the requests to completion."""
    cfg, masked = packed_vit
    eng = VisionEngine(cfg, masked,
                       VisionEngineConfig(max_batch=3, planner="full",
                                          pipeline_depth=2))
    reqs = _vision_reqs(cfg, [(16, None, 0), (9, 0.5, 0)])
    for r in reqs:
        eng._validate(r)
    eng.scheduler.submit(reqs)
    eng.scheduler.schedule()
    eng._sync_admissions()

    pre_live = {s: (lv.seg_idx, lv.n_tokens, lv.x)
                for s, lv in eng._live.items()}
    pre = (eng.planner.plans, eng.batcher.tiles_planned,
           eng.batcher.padded_cells, eng.steps)
    out = {}
    staged = eng._stage_step(out)
    eng.pipeline.drop(staged)
    assert (eng.planner.plans, eng.batcher.tiles_planned,
            eng.batcher.padded_cells, eng.steps) == pre
    for s, (seg, n, x) in pre_live.items():
        lv = eng._live[s]
        assert (lv.seg_idx, lv.n_tokens) == (seg, n) and lv.x is x
    assert eng.pipeline.stats()["drops"] == 1 and out == {}

    while eng.scheduler.has_work():
        eng.pipeline.submit(eng._stage_step(out))
        eng.pipeline.flush()
        eng._retire_finished()
    assert sorted(out) == [0, 1]
    for r in reqs:  # bit-exact against the offline oracle
        c = cfg if r.r_t is None else cfg.replace(
            pruning=dataclasses.replace(cfg.pruning, r_t=r.r_t))
        ref = np.asarray(PR.forward_vit_packed(
            c, masked, r.patches[None]).logits[0])
        assert np.array_equal(out[r.uid], ref)


def test_vision_midstep_submission_lands_in_next_plan(packed_vit):
    """A request submitted while a step is being staged must trigger a
    drop + replan: it joins the REBUILT plan for this step (never mutates
    the staged one), and the whole serve is bit-exact — logits and event
    stream — against submitting it at the step boundary."""
    cfg, masked = packed_vit
    mixes = [(16, None, 0), (9, 0.5, 0), (4, 0.7, 0)]

    def run(hook):
        eng = VisionEngine(cfg, masked,
                           VisionEngineConfig(max_batch=4, planner="full",
                                              pipeline_depth=2))
        reqs = _vision_reqs(cfg, mixes)
        late = _vision_reqs(cfg, [(9, None, 0)], seed=1)[0]
        late = dataclasses.replace(late, uid=3)
        populations = []
        if hook:
            real, fired = eng.planner.plan_ahead, []

            def spy(items, horizon):
                populations.append(len(items))
                if not fired:  # submit mid-staging, exactly once
                    fired.append(True)
                    eng._validate(late)
                    eng.scheduler.submit([late])
                return real(items, horizon)

            eng.planner.plan_ahead = spy
            out = eng.serve(reqs)
        else:
            out = eng.serve(reqs + [late])
        return out, list(eng.events), populations, eng

    ref, ref_ev, _, _ = run(hook=False)
    got, got_ev, pops, eng = run(hook=True)

    # staged plan N covered 3 items; the replanned step covers all 4
    assert pops[:2] == [3, 4]
    assert eng.pipeline.stats()["drops"] == 1
    # only dispatched plans reached the ledgers
    assert eng.planner.plans == eng.pipeline.stats()["steps"]
    assert sorted(got) == [0, 1, 2, 3]
    assert got_ev == ref_ev
    for uid in ref:
        assert np.array_equal(got[uid], ref[uid])


@pytest.mark.parametrize("planner", ["off", "full", "merge", "fuse"])
def test_vision_depth_bitexact(packed_vit, planner):
    """Depth 2 reproduces depth 1 logits bit-for-bit under staggered
    arrivals, slot churn and soft-pruning requests, and the speculative
    plan cache actually gets consulted (identical concurrent trajectories
    never fuse away, so populations persist across steps in every planner
    mode). Steps the host reads nothing of go unwaited alike at both
    depths."""
    cfg, masked = packed_vit
    mixes = [(16, None, 0), (16, None, 0), (9, 0.5, 1), (9, 0.5, 2),
             (16, None, 3), (16, 0.5, 3), (9, None, 4)]

    def run(d):
        eng = VisionEngine(cfg, masked,
                           VisionEngineConfig(max_batch=2, planner=planner,
                                              pipeline_depth=d))
        reqs = _vision_reqs(cfg, mixes)
        for r in reqs[5:]:
            r.soft_prune = True
        return eng.serve(reqs), list(eng.events), eng

    base, base_ev, base_eng = run(1)
    got, got_ev, eng = run(2)
    assert got_ev == base_ev
    assert sorted(got) == sorted(base) == list(range(len(mixes)))
    for uid in base:
        assert np.array_equal(base[uid], got[uid])
    st = eng.stats()
    assert st["pipeline_steps"] == st["steps"]
    assert st["plan_ahead_hits"] + st["plan_ahead_drops"] > 0
    unwaited = base_eng.stats()["pipeline_steps_unwaited"]
    assert 0 < unwaited < base_eng.stats()["pipeline_steps"]
    assert st["pipeline_steps_unwaited"] == unwaited


def test_vision_lockstep_cohort_waits_once(packed_vit):
    """Two lock-step cohorts of ``max_batch`` at depth 1: each step but
    the head reads nothing on the host and goes unwaited, so a cohort
    blocks once, and its logits are in ``out`` when the head step's
    ``tick`` returns."""
    cfg, masked = packed_vit
    mb = 4
    eng = VisionEngine(cfg, masked,
                       VisionEngineConfig(max_batch=mb, planner="full"))
    n_segs = len(eng.segments.plan)
    reqs = _vision_reqs(cfg, [(16, 0.5, 0)] * (2 * mb))
    eng.enqueue(reqs)
    out = {}
    heads = 0
    while eng._pending or eng.scheduler.has_work():
        rep = eng.tick(out)
        if rep.completed:
            heads += 1
            assert set(rep.completed) <= set(out)
            assert eng.pipeline.in_flight == 0
        else:
            assert not (set(out) - {r.uid for r in reqs[:heads * mb]})
    eng.finish()
    assert heads == 2 and sorted(out) == [r.uid for r in reqs]
    st = eng.stats()
    assert st["pipeline_steps"] == 2 * n_segs
    assert st["pipeline_steps_unwaited"] == 2 * (n_segs - 1)
    assert st["passthrough_tiles"] > 0
