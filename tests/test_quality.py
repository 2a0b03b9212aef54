"""QualityController: keep rates as a runtime load-control surface.

Two halves. Unit: the controller's grid algebra — resolution is pure,
tightening moves down the quantized grid only, never loosens, never
crosses the floor, and a ``strict`` controller is an exact identity.
Integration: through the ``VisionEngine`` — controller-off is bit-exact
with the fixed-rate path across planner modes and pipeline depths,
``degrade`` serves exactly the floor schedule, preferences override the
engine mode, and recompiles stay inside the grid-bounded budget.
"""
import dataclasses

import jax
import numpy as np
import pytest

from conftest import assert_logits_close
from repro.configs import DEIT_SMALL
from repro.core import packed_runner as PR
from repro.models import model as M
from repro.models import pruning_glue as PG
from repro.serving import (QUALITY_MODES, QualityConfig, QualityController,
                           VisionEngine, VisionEngineConfig, VisionRequest)


# ---------------------------------------------------------------------------
# unit: config + grid algebra
# ---------------------------------------------------------------------------
def test_quality_config_validation():
    with pytest.raises(ValueError, match="mode"):
        QualityConfig(mode="fastest")
    with pytest.raises(ValueError, match="descending"):
        QualityConfig(keep_levels=(0.4, 0.7, 1.0))
    with pytest.raises(ValueError, match="descending"):
        QualityConfig(keep_levels=(1.0, 0.7, 0.7))
    with pytest.raises(ValueError, match="non-empty"):
        QualityConfig(keep_levels=())
    with pytest.raises(ValueError, match="finite"):
        QualityConfig(keep_levels=(1.0, float("nan")))
    with pytest.raises(ValueError, match="keep_floor"):
        QualityConfig(keep_floor=float("nan"))
    with pytest.raises(ValueError, match="no usable grid"):
        QualityConfig(keep_levels=(0.5, 0.4), keep_floor=0.9)
    with pytest.raises(ValueError, match="backlog_per_level"):
        QualityConfig(backlog_per_level=0)
    cfgq = QualityConfig(keep_levels=(1.0, 0.7, 0.4, 0.2), keep_floor=0.4)
    assert cfgq.usable_levels == (1.0, 0.7, 0.4)


def test_tighten_moves_down_grid_only():
    q = QualityController(QualityConfig(
        mode="auto", keep_levels=(1.0, 0.8, 0.6, 0.4), keep_floor=0.4))
    assert q.tighten(0.9, 0) == 0.9          # no pressure: untouched
    assert q.tighten(0.9, 1) == 0.8
    assert q.tighten(0.9, 2) == 0.6
    assert q.tighten(0.9, 99) == 0.4         # clamps at the floor level
    assert q.tighten(0.8, 1) == 0.6          # strictly below, not equal
    assert q.tighten(0.3, 99) == 0.3         # below every level: NEVER
    assert q.tighten(0.4, 99) == 0.4         # loosened or touched


def test_pressure_steps_scales_with_slots():
    q = QualityController(QualityConfig(mode="auto", backlog_per_level=2),
                          num_slots=4)
    assert q.pressure_steps(0) == 0
    assert q.pressure_steps(7) == 0          # less than one backlog unit
    assert q.pressure_steps(8) == 1
    assert q.pressure_steps(17) == 2
    assert q.pressure_steps(-3) == 0


def test_resolve_strict_controller_is_identity():
    """Controller off: every schedule untouched — even for requests that
    ASK for degradation (bit-exactness with the pre-controller engine
    cannot depend on request payloads)."""
    q = QualityController()  # default strict
    assert not q.enabled
    base = (0.9, 0.5)
    assert q.resolve(base, queue_depth=10 ** 6) == base
    assert q.resolve(base, preference="degrade", queue_depth=10 ** 6) == base


def test_resolve_degrade_and_done_prefix():
    q = QualityController(QualityConfig(
        mode="degrade", keep_levels=(1.0, 0.7, 0.5), keep_floor=0.5))
    assert q.resolve((0.9, 0.9)) == (0.5, 0.5)
    # executed entries are history: never rewritten
    assert q.resolve((0.9, 0.9), done=1) == (0.9, 0.5)
    # per-request strict preference pins the base schedule under load
    assert q.resolve((0.9, 0.9), preference="strict") == (0.9, 0.9)
    with pytest.raises(ValueError, match="preference"):
        q.resolve((0.9,), preference="turbo")


def test_resolve_auto_queue_and_deadline_pressure():
    q = QualityController(QualityConfig(
        mode="auto", keep_levels=(1.0, 0.8, 0.6, 0.4), keep_floor=0.4),
        num_slots=2)
    base = (0.9,)
    assert q.resolve(base, queue_depth=0) == base
    assert q.resolve(base, queue_depth=2) == (0.8,)
    assert q.resolve(base, queue_depth=4) == (0.6,)
    # deadline loop: keep tightening until the modeled remainder fits
    cost = {(0.9,): 10.0, (0.8,): 8.0, (0.6,): 5.0, (0.4,): 2.0}
    out = q.resolve(base, queue_depth=0, deadline_left_ms=4.0,
                    remaining_ms=lambda s: cost[s])
    assert out == (0.4,)
    out = q.resolve(base, queue_depth=0, deadline_left_ms=6.0,
                    remaining_ms=lambda s: cost[s])
    assert out == (0.6,)
    # slack already fits: queue pressure alone decides
    assert q.resolve(base, queue_depth=0, deadline_left_ms=100.0,
                     remaining_ms=lambda s: cost[s]) == base


def test_record_and_stats_accounting():
    q = QualityController(QualityConfig(mode="auto"))
    q.record(3, 2, levels=(0.7, 0.55), deadline_tightened=1)
    q.record(1, 0)
    st = q.stats()
    assert st["decisions"] == 4 and st["tightened"] == 2
    assert st["deadline_tightened"] == 1
    assert st["levels_used"] == (0.55, 0.7)
    assert tuple(QUALITY_MODES) == ("strict", "auto", "degrade")


# ---------------------------------------------------------------------------
# integration: through the VisionEngine
# ---------------------------------------------------------------------------
@pytest.fixture(scope="module")
def packed_vit(rng_key):
    cfg = DEIT_SMALL.reduced()
    params = M.init_params(cfg, rng_key)
    scores = PG.init_scores(cfg, params, jax.random.fold_in(rng_key, 7))
    masked = PG.apply_pruning(cfg, params, scores)
    return cfg, masked


def _reqs(cfg, n_list, **kw):
    rng = np.random.default_rng(5)
    pdim = cfg.patch_size ** 2 * 3
    return [VisionRequest(
        uid=i, patches=rng.standard_normal((n, pdim)).astype(np.float32),
        **kw) for i, n in enumerate(n_list)]


def _offline(cfg, masked, req, schedule=None, soft=False):
    c = cfg if req.r_t is None else cfg.replace(
        pruning=dataclasses.replace(cfg.pruning, r_t=req.r_t))
    return np.asarray(PR.forward_vit_packed(
        c, masked, req.patches[None], schedule=schedule,
        soft=soft).logits[0])


def _digest(out):
    import hashlib
    h = hashlib.sha256()
    for uid in sorted(out):
        h.update(np.asarray(out[uid], np.float32).tobytes())
    return h.hexdigest()


@pytest.mark.parametrize("pmode", ["off", "merge", "fuse", "full"])
def test_controller_off_bitexact_every_planner_and_depth(packed_vit, pmode):
    """An engine with the (default, strict) controller serves the offline
    fixed-rate path's logits, within ``assert_logits_close``, at pipeline
    depths 1 and 2 — quality plumbing must be invisible until enabled."""
    cfg, masked = packed_vit
    digests = set()
    for depth in (1, 2):
        reqs = _reqs(cfg, [16, 9, 16])
        reqs[1].r_t = 0.5
        vc = VisionEngineConfig(max_batch=2, planner=pmode,
                                pipeline_depth=depth)
        eng = VisionEngine(cfg, masked, vc)
        out = eng.serve(reqs)
        for r in reqs:
            assert_logits_close(out[r.uid], _offline(cfg, masked, r))
        digests.add(_digest(out))
        st = eng.stats()
        assert st["quality_mode"] == "strict"
        assert st["quality_tightened"] == 0
        assert st["quality_levels_used"] == ()
    assert len(digests) == 1  # depth cannot change the bits


def test_degrade_serves_exactly_the_floor_schedule(packed_vit):
    """Shed-load mode pins every consenting request to the lowest usable
    grid level — bit-exact against the offline path run at precisely that
    schedule (the controller changes WHICH schedule runs, never the
    math)."""
    cfg, masked = packed_vit
    vc = VisionEngineConfig(max_batch=2, planner="full",
                            quality="degrade",
                            keep_levels=(1.0, 0.7, 0.5), keep_floor=0.5)
    eng = VisionEngine(cfg, masked, vc)
    reqs = _reqs(cfg, [16, 9])
    reqs[0].r_t = 0.9
    reqs[1].quality = "strict"  # opts out: pinned to its base schedule
    out = eng.serve(reqs)
    assert np.array_equal(out[0], _offline(cfg, masked, reqs[0],
                                           schedule=(0.5,)))
    assert np.array_equal(out[1], _offline(cfg, masked, reqs[1]))
    st = eng.stats()
    assert st["quality_levels_used"] == (0.5,)
    assert st["jit_compile_count"] <= st["compile_budget"]


def test_auto_tightens_only_under_backlog(packed_vit):
    """Auto mode is a no-op on an unloaded engine and tightens (onto grid
    levels only) when the queue outgrows the slots."""
    cfg, masked = packed_vit
    grid = (1.0, 0.85, 0.7, 0.55)
    # unloaded: 2 requests into 2 slots -> no pressure -> base schedules
    vc = VisionEngineConfig(max_batch=2, quality="auto", keep_levels=grid,
                            keep_floor=0.55)
    eng = VisionEngine(cfg, masked, vc)
    reqs = _reqs(cfg, [16, 9])
    out = eng.serve(reqs)
    for r in reqs:
        assert np.array_equal(out[r.uid], _offline(cfg, masked, r))
    assert eng.stats()["quality_tightened"] == 0
    # backlogged: one slot, simultaneous arrivals -> pressure tightens,
    # resolved rates come from the grid only
    eng2 = VisionEngine(cfg, masked, VisionEngineConfig(
        max_batch=1, quality="auto", keep_levels=grid, keep_floor=0.55))
    out2 = eng2.serve(_reqs(cfg, [16, 16, 16, 16, 16, 16]))
    st = eng2.stats()
    assert len(out2) == 6
    assert st["quality_tightened"] > 0
    assert set(st["quality_levels_used"]) <= set(grid)
    assert st["jit_compile_count"] <= st["compile_budget"]


def test_explicit_keep_schedule_request(packed_vit):
    """A request carrying its own per-step schedule is served under it
    verbatim (controller off), bit-exact vs the offline schedule path."""
    cfg, masked = packed_vit
    eng = VisionEngine(cfg, masked, VisionEngineConfig(max_batch=2))
    reqs = _reqs(cfg, [16])
    reqs[0].keep_schedule = (0.6,)
    out = eng.serve(reqs)
    assert np.array_equal(out[0], _offline(cfg, masked, reqs[0],
                                           schedule=(0.6,)))
