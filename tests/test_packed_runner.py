"""End-to-end pruned-model execution: the segment walk must match the
masked-dense oracle — the accelerator-vs-software parity check. Its
projections are XLA matmuls by the block-masked weights of the masked
parameter tree; ``pack_model`` packs the same weights for the SBMM kernel
library."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import DEIT_SMALL
from repro.core import packed_runner as PR
from repro.models import model as M
from repro.models import pruning_glue as PG


def test_packed_vit_matches_masked_dense(rng_key):
    cfg = DEIT_SMALL.reduced()
    params = M.init_params(cfg, rng_key)
    scores = PG.init_scores(cfg, params, jax.random.fold_in(rng_key, 7))
    packed = PR.pack_model(cfg, params, scores)
    assert len(packed) == cfg.num_layers * 4  # wq,wk,wv,wo per layer

    n = (cfg.image_size // cfg.patch_size) ** 2
    patches = jax.random.normal(rng_key, (2, n, cfg.patch_size ** 2 * 3))

    # the deployment path runs MLPs masked-dense (DBMM analog) and the
    # attention projections by the block-masked dense weights, which are
    # exactly what the packed blocks hold
    masked = PG.apply_pruning(cfg, params, scores)
    for path, pw in packed.items():
        _, i, _, name = path.split("/")
        np.testing.assert_array_equal(
            np.asarray(masked["layers"][int(i)]["attn"][name]),
            pw.to_dense())
    out_packed = PR.forward_vit_packed(cfg, masked, patches, use_tdm=False)
    out_oracle = PR.masked_dense_reference(cfg, params, scores, patches,
                                           use_tdm=False)
    np.testing.assert_allclose(np.asarray(out_packed.logits),
                               np.asarray(out_oracle.logits),
                               atol=2e-3, rtol=2e-3)


@pytest.mark.parametrize("tier", ["fp32"])
def test_sbmm_kernel_only_in_quantized_tier_programs(rng_key, tier):
    """The served programs that hold attention projections (tiles of
    encoder layers, TDM layers, and express lanes) call no Pallas kernel:
    the projections are XLA matmuls by the block-masked weights."""
    cfg = DEIT_SMALL.reduced()
    params = M.init_params(cfg, rng_key)
    scores = PG.init_scores(cfg, params, jax.random.fold_in(rng_key, 7))
    masked = PG.apply_pruning(cfg, params, scores)
    seg = PR.PackedVitSegments(cfg, masked)
    n = (cfg.image_size // cfg.patch_size) ** 2
    x = jnp.zeros((2, n + 1, cfg.d_model), jnp.float32)
    layers = next(s for s in seg.plan if s[0] == "layers")
    tdm = next(s for s in seg.plan if s[0] == "tdm")
    lane = tuple((s, PR.tdm_keep_count(n + 1, 0.5) if s[0] == "tdm"
                  else None) for s in seg.plan)
    traced = {
        "vit_layers": seg._layers.trace(masked, x, None, lo=layers[1],
                                        hi=layers[2]),
        "vit_tdm": seg._tdm.trace(masked, x, None, layer=tdm[1], k=2),
        "vit_lane": seg._fused.trace(
            masked, jnp.zeros((1, n, cfg.patch_size ** 2 * 3)), None,
            steps=lane),
    }
    for name, tr in traced.items():
        assert str(tr.jaxpr).count("pallas_call") == 0, name


def test_packed_vit_with_tdm_runs(rng_key):
    """Both prunings simultaneously active on the packed execution path —
    the full deployment configuration of the paper."""
    cfg = DEIT_SMALL.reduced()
    params = M.init_params(cfg, rng_key)
    scores = PG.init_scores(cfg, params, jax.random.fold_in(rng_key, 7))
    masked = PG.apply_pruning(cfg, params, scores)
    n = (cfg.image_size // cfg.patch_size) ** 2
    patches = jax.random.normal(rng_key, (2, n, cfg.patch_size ** 2 * 3))
    out = PR.forward_vit_packed(cfg, masked, patches, use_tdm=True)
    assert out.logits.shape == (2, cfg.num_classes)
    assert bool(jnp.isfinite(out.logits).all())


# ---------------------------------------------------------------------------
# keep-count rules and per-step keep schedules (quality-elastic serving)
# ---------------------------------------------------------------------------
def test_tdm_keep_count_agrees_with_num_kept_tokens():
    """``tdm_keep_count`` is derived from ``TP.num_kept_tokens`` (the one
    source of truth for the ceil/clamp rule): output = CLS + k + fused."""
    from repro.core import token_pruning as TP

    for n in (2, 3, 5, 17, 50, 197):
        for r in (1e-9, 0.1, 0.25, 0.5, 0.7, 0.99, 1.0):
            k = PR.tdm_keep_count(n, r)
            assert k + 2 == TP.num_kept_tokens(n, r, has_cls=True)
            assert k >= 1  # the max(1, ceil(...)) floor


def test_trajectory_monotone_in_keep_rate():
    """Pointwise monotone: a tighter keep rate never carries MORE tokens
    through any segment; r_t -> 0 bottoms out at the 1-token floor and
    r_t = 1 keeps every body token (hard TDM even grows by the fused
    slot)."""
    cfg = DEIT_SMALL.reduced()
    n = 16
    rates = (1.0, 0.7, 0.5, 0.25, 0.1, 1e-9)
    trajs = [PR.token_trajectory(cfg, n, r_t=r, use_tdm=True)
             for r in rates]
    for hi, lo in zip(trajs, trajs[1:]):
        assert all(a >= b for a, b in zip(hi, lo)), (hi, lo)
    # r_t -> 0: every TDM collapses to the floor count CLS + 1 + fused
    assert min(trajs[-1]) == 3
    # r_t = 1: the hard TDM appends the fused slot on top of a full keep
    full = PR.token_trajectory(cfg, n, r_t=1.0, use_tdm=True)
    assert max(full) == n + 2  # CLS + n kept + fused


def test_keep_schedule_broadcast_equivalence():
    """A scalar r_t is exactly its broadcast schedule — the frozen-scalar
    path is a special case of the per-step machinery, not a twin."""
    cfg = DEIT_SMALL.reduced()
    sched = PR.keep_schedule(cfg, r_t=0.6, use_tdm=True)
    assert sched == (0.6,) * len(sched) and len(sched) >= 1
    assert (PR.token_trajectory(cfg, 16, r_t=0.6, use_tdm=True)
            == PR.token_trajectory(cfg, 16, schedule=sched, use_tdm=True))


def test_soft_keep_count_clamps_at_package_row():
    """Once a package row exists, soft top-k draws from n-2 real body rows:
    k clamps there (binds only as r_t -> 1), so the soft output count
    never exceeds the input count."""
    for n in (5, 18, 50):
        assert PR.tdm_soft_keep_count(n, 1.0, has_pkg=True) == n - 2
        assert (PR.tdm_soft_keep_count(n, 1.0, has_pkg=False)
                == PR.tdm_keep_count(n, 1.0))
        # away from r=1 the clamp is inactive: same k as the hard rule
        assert (PR.tdm_soft_keep_count(n, 0.5, has_pkg=True)
                == PR.tdm_keep_count(n, 0.5))
    soft = PR.token_trajectory(DEIT_SMALL.reduced(), 16, r_t=1.0,
                               use_tdm=True, soft=True)
    assert all(c <= 16 + 2 for c in soft)


def test_forward_soft_matches_fused_soft_lane(rng_key):
    """The fused express-lane program threads the package mass across soft
    steps in-program — it must agree with the per-segment soft path."""
    cfg = DEIT_SMALL.reduced()
    params = M.init_params(cfg, rng_key)
    scores = PG.init_scores(cfg, params, jax.random.fold_in(rng_key, 7))
    masked = PG.apply_pruning(cfg, params, scores)
    n = 16
    patches = jax.random.normal(rng_key, (1, n, cfg.patch_size ** 2 * 3))
    seq = PR.forward_vit_packed(cfg, masked, patches, use_tdm=True,
                                soft=True)
    sched = PR.keep_schedule(cfg, use_tdm=True)
    traj = PR.token_trajectory(cfg, n, use_tdm=True, soft=True)
    steps = []
    cur, ordinal = n, 0
    for seg, after in zip(PR.vit_segments(cfg, True), traj):
        if seg[0] == "tdm":
            k = PR.tdm_soft_keep_count(cur, sched[ordinal],
                                       has_pkg=ordinal > 0)
            steps.append((seg, k, True))
            ordinal += 1
        else:
            steps.append((seg, None))
        cur = after
    fused = PR.run_fused_steps(cfg, masked, patches, tuple(steps))
    np.testing.assert_allclose(np.asarray(fused),
                               np.asarray(seq.logits), atol=1e-5)
