"""Per-kernel validation: Pallas (interpret mode) vs pure-jnp oracles,
swept over shapes and dtypes."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import block_pruning as bp
from repro.core import packing
from repro.kernels.sbmm import sbmm, sbmm_raw, sbmm_ref
from repro.kernels.token_drop import token_drop, token_drop_ref
from repro.kernels.flash_attention import flash_attention, attention_ref
from repro.core.token_pruning import tdm


# ---------------------------------------------------------------------------
# SBMM
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("M,K,N,b,rb", [
    (32, 32, 32, 16, 0.5),
    (64, 64, 128, 16, 0.3),
    (100, 96, 80, 16, 0.7),   # non-multiples: padding path
    (128, 128, 256, 32, 0.5),
    (48, 64, 64, 32, 0.9),
])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_sbmm_vs_masked_dense(M, K, N, b, rb, dtype):
    key = jax.random.PRNGKey(hash((M, K, N, b)) % 2**31)
    w = np.asarray(jax.random.normal(key, (K, N)), np.float32)
    sc = np.asarray(jax.random.normal(jax.random.fold_in(key, 1),
                                      bp.score_shape((K, N), b)))
    n_blocks = sc.size
    keep = max(1, int(np.ceil(n_blocks * rb)))
    mask = np.asarray(bp._hard_topk(jnp.asarray(sc), keep))
    pk = packing.pack_weight(w.astype(dtype), mask, b)
    x = jax.random.normal(jax.random.fold_in(key, 2), (M, K), dtype)
    y = sbmm(x, pk, tm=32)
    y_ref = (x.astype(jnp.float32) @ pk.to_dense().astype(jnp.float32)
             ).astype(dtype)
    tol = 1e-3 if dtype == jnp.float32 else 6e-2
    np.testing.assert_allclose(np.asarray(y, np.float32),
                               np.asarray(y_ref, np.float32),
                               atol=tol, rtol=tol)


def test_sbmm_raw_vs_ref_oracle():
    key = jax.random.PRNGKey(7)
    w = np.asarray(jax.random.normal(key, (64, 96)), np.float32)
    sc = np.asarray(jax.random.normal(key, bp.score_shape((64, 96), 16)))
    mask = np.asarray(bp._hard_topk(jnp.asarray(sc), 12))
    pk = packing.pack_weight(w, mask, 16)
    x = jax.random.normal(key, (64, 64), jnp.float32)
    y = sbmm_raw(x, pk.blocks, pk.header, tm=32)
    y_ref = sbmm_ref(x, pk.blocks, pk.header)
    np.testing.assert_allclose(np.asarray(y), np.asarray(y_ref), atol=1e-4)


def test_sbmm_empty_column():
    """A fully pruned block-column must produce zeros."""
    w = np.ones((32, 32), np.float32)
    mask = np.zeros((2, 2))
    mask[0, 0] = 1  # only block (0,0) survives
    pk = packing.pack_weight(w, mask, 16)
    x = jnp.ones((32, 32))
    y = np.asarray(sbmm(x, pk, tm=32))
    dense = np.asarray(pk.to_dense())
    np.testing.assert_allclose(y, np.ones((32, 32)) @ dense, atol=1e-4)
    assert np.abs(y[:, 16:]).sum() == 0


# ---------------------------------------------------------------------------
# token_drop
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("B,N,D,rt", [
    (1, 17, 32, 0.5), (2, 197, 384, 0.7), (3, 33, 130, 0.9), (1, 9, 64, 0.25),
])
def test_token_drop_matches_tdm(B, N, D, rt):
    key = jax.random.PRNGKey(B * N)
    z = jax.random.normal(key, (B, N, D), jnp.float32)
    s = jax.random.uniform(jax.random.fold_in(key, 1), (B, N))
    out_k = token_drop(z, s, rt, td=32)
    out_j, _ = tdm(z, s, rt, has_cls=True)
    assert out_k.shape == out_j.shape
    np.testing.assert_allclose(np.asarray(out_k), np.asarray(out_j),
                               atol=1e-4)


def test_token_drop_ref_oracle():
    key = jax.random.PRNGKey(11)
    z = jax.random.normal(key, (9, 16))
    keep_idx = jnp.asarray([0, 3, 7], jnp.int32)
    w = jnp.zeros((9,)).at[jnp.asarray([1, 2])].set(0.5)
    from repro.kernels.token_drop.token_drop import token_drop_pallas
    out = token_drop_pallas(z, keep_idx, w, td=16)
    ref = token_drop_ref(z, keep_idx, w)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=1e-5)


# ---------------------------------------------------------------------------
# flash attention
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("B,Nq,Nk,Hq,KV,Dh,causal,qoff", [
    (2, 64, 64, 4, 4, 32, True, 0),
    (1, 197, 197, 6, 6, 64, False, 0),   # ViT shape, padding path
    (2, 128, 128, 8, 2, 64, True, 0),    # GQA 4:1
    (1, 1, 96, 4, 4, 32, True, 95),      # decode
    (1, 16, 48, 4, 2, 16, True, 32),     # chunked prefill continuation
])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_flash_attention_vs_ref(B, Nq, Nk, Hq, KV, Dh, causal, qoff, dtype):
    key = jax.random.PRNGKey(Nq * Nk)
    q = jax.random.normal(key, (B, Nq, Hq, Dh), dtype)
    k = jax.random.normal(jax.random.fold_in(key, 1), (B, Nk, KV, Dh), dtype)
    v = jax.random.normal(jax.random.fold_in(key, 2), (B, Nk, KV, Dh), dtype)
    out = flash_attention(q, k, v, causal=causal, q_offset=qoff,
                          tq=64, tk=32)
    per = Hq // KV
    ke = jnp.repeat(k, per, axis=2)
    ve = jnp.repeat(v, per, axis=2)
    ref = jnp.moveaxis(jax.vmap(
        lambda qq, kk, vv: attention_ref(
            jnp.moveaxis(qq, 1, 0), jnp.moveaxis(kk, 1, 0),
            jnp.moveaxis(vv, 1, 0), causal=causal, q_offset=qoff))(
                q, ke, ve), 1, 2)
    tol = 2e-5 if dtype == jnp.float32 else 2e-2
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(ref, np.float32), atol=tol)


def test_flash_bounded_equals_unbounded():
    key = jax.random.PRNGKey(5)
    q = jax.random.normal(key, (1, 128, 4, 32))
    k = jax.random.normal(jax.random.fold_in(key, 1), (1, 128, 4, 32))
    v = jax.random.normal(jax.random.fold_in(key, 2), (1, 128, 4, 32))
    a = flash_attention(q, k, v, causal=True, tq=32, tk=32, bounded=True)
    b = flash_attention(q, k, v, causal=True, tq=32, tk=32, bounded=False)
    np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=1e-6)


# ---------------------------------------------------------------------------
# Backend selection (kernels.backend): interpret on CPU CI, compiled on
# real TPU, env-overridable
# ---------------------------------------------------------------------------
def test_backend_auto_detection(monkeypatch):
    from repro.kernels import backend

    monkeypatch.delenv(backend.ENV_VAR, raising=False)
    # this suite runs on the CPU host platform -> interpreter by default
    assert backend.default_interpret() == (jax.default_backend() != "tpu")
    assert backend.resolve_interpret(None) == backend.default_interpret()
    # explicit values pass through untouched
    assert backend.resolve_interpret(True) is True
    assert backend.resolve_interpret(False) is False


def test_backend_env_override(monkeypatch):
    from repro.kernels import backend

    monkeypatch.setenv(backend.ENV_VAR, "interpret")
    assert backend.default_interpret() is True
    monkeypatch.setenv(backend.ENV_VAR, "compiled")
    assert backend.default_interpret() is False
    monkeypatch.setenv(backend.ENV_VAR, "auto")
    assert backend.default_interpret() == (jax.default_backend() != "tpu")
    monkeypatch.setenv(backend.ENV_VAR, "sideways")
    with pytest.raises(ValueError, match="REPRO_KERNEL_INTERPRET"):
        backend.default_interpret()


def test_forced_interpret_raises_on_tpu(monkeypatch):
    """On a TPU backend the served kernels always compile: an env var that
    forces the interpreter there is an error, while auto and compiled
    resolve to compiled Pallas."""
    from repro.kernels import backend

    monkeypatch.setattr(backend, "on_tpu", lambda: True)
    for forced in ("1", "interpret"):
        monkeypatch.setenv(backend.ENV_VAR, forced)
        with pytest.raises(RuntimeError, match="REPRO_KERNEL_INTERPRET"):
            backend.default_interpret()
    for mode in ("auto", "compiled"):
        monkeypatch.setenv(backend.ENV_VAR, mode)
        assert backend.default_interpret() is False


def test_compiled_sbmm_needs_lane_aligned_tile():
    """The compiled kernel puts the token tile on the 128 lanes; a tile
    that is not a multiple of 128 is refused before lowering."""
    pw = packing.pack_weight(np.ones((32, 32), np.float32),
                             np.ones((2, 2), bool), 16)
    with pytest.raises(ValueError, match="multiple of 128"):
        sbmm(jnp.ones((8, 32)), pw, tm=64, interpret=False)


def test_kernels_honor_env_interpret(monkeypatch):
    """The non-jitted entry points resolve the env override per call (the
    resolved value is a static jit arg, so flipping the env re-dispatches
    instead of reusing a stale trace)."""
    from repro.kernels import backend

    monkeypatch.setenv(backend.ENV_VAR, "interpret")
    rng = np.random.default_rng(0)
    x = jnp.asarray(rng.standard_normal((8, 32)), jnp.float32)
    w = rng.standard_normal((32, 32)).astype(np.float32)
    mask = np.ones(bp.score_shape(w.shape, 16), bool)
    pw = packing.pack_weight(w, mask, 16)
    out = sbmm(x, pw, tm=8)
    np.testing.assert_allclose(np.asarray(out), np.asarray(x) @ w,
                               atol=1e-4)


# ---------------------------------------------------------------------------
# token_package (soft-pruning TDM)
# ---------------------------------------------------------------------------
def test_token_package_ref_oracle_edge_k():
    """Kernel vs jnp reference at the k extremes: k=1 (drop almost
    everything into the package) and k=n (keep every row; the package is
    an empty weighted sum)."""
    from repro.kernels.token_package import (token_package_pallas,
                                             token_package_ref)

    key = jax.random.PRNGKey(3)
    n, d = 9, 32
    z = jax.random.normal(key, (n, d), jnp.float32)
    w = jax.random.uniform(jax.random.fold_in(key, 1), (n,))
    for keep in (jnp.asarray([4], jnp.int32),
                 jnp.arange(n, dtype=jnp.int32)):
        wk = jnp.where(jnp.isin(jnp.arange(n), keep), 0.0, w)
        out = token_package_pallas(z, keep, wk, td=16)
        ref = token_package_ref(z, keep, wk)
        assert out.shape == (len(keep) + 1, d)
        assert np.array_equal(np.asarray(out), np.asarray(ref))


@pytest.mark.parametrize("B,N,D,k", [(2, 17, 64, 1), (1, 9, 32, 7),
                                     (3, 33, 128, 10)])
def test_token_package_matches_tdm_soft(B, N, D, k):
    """The batched wrapper (padding + mass substitution + vmap) agrees
    with the pure-jnp soft TDM, including the accumulated masses across a
    chained second application."""
    from repro.core.token_pruning import tdm_soft
    from repro.kernels.token_package import token_package

    key = jax.random.PRNGKey(B * N + k)
    z = jax.random.normal(key, (B, N, D), jnp.float32)
    s = jax.random.uniform(jax.random.fold_in(key, 1), (B, N))
    out_k, mass_k = token_package(z, s, k=k, td=32)
    out_j, mass_j = tdm_soft(z, s, k=k)
    np.testing.assert_allclose(np.asarray(out_k), np.asarray(out_j),
                               atol=1e-4)
    np.testing.assert_allclose(np.asarray(mass_k), np.asarray(mass_j),
                               rtol=1e-5)
    # chained: the package row participates at its accumulated mass
    s2 = jax.random.uniform(jax.random.fold_in(key, 2), out_k.shape[:2])
    k2 = max(1, k - 1)
    out_k2, mass_k2 = token_package(out_k, s2, k=k2, pkg_mass=mass_k, td=32)
    out_j2, mass_j2 = tdm_soft(out_j, s2, k=k2, pkg_mass=mass_j)
    np.testing.assert_allclose(np.asarray(out_k2), np.asarray(out_j2),
                               atol=1e-4)
    np.testing.assert_allclose(np.asarray(mass_k2), np.asarray(mass_j2),
                               rtol=1e-4)
