"""The int8/fp16 SBMM kernel library (repro.core.quant +
kernels.sbmm.quant + the fp16 attention variant), kept for the paper's
kernel with no serving caller — the engine serves float32 only.

Layers of defense:
  * format roundtrip — symmetric quantize→dequantize error bounded by
    scale/2 per element (deterministic sweep here; the hypothesis
    properties live in tests/test_quant_property.py, skipped without the
    optional 'test' extra);
  * kernel vs oracle — the dequant-in-kernel Pallas SBMM bit-matches the
    accumulation-order-matched jnp reference in interpret mode, and the
    fp16 attention variant matches the jnp oracle on the same fp16-cast
    operands;
  * perf model — the paper-table cycle model prices int8 < fp16 < fp32 on
    encoder segments;
  * accounting — nbytes/packed_model_size_bytes derive from actual dtypes
    and include scales.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import DEIT_SMALL
from repro.core import block_pruning as bp
from repro.core import packing
from repro.core import quant as Q
from repro.core.perf_model import (PRECISION_SPEEDUP, precision_speedup,
                                   vit_segment_cycles)
from repro.kernels.flash_attention import flash_attention_fp16
from repro.kernels.sbmm import (sbmm, sbmm_quant_pallas, sbmm_quant_ref,
                                sbmm_quant_raw)
from repro.models import attention as A
from repro.serving.vision import VisionEngineConfig


def _packed(key, K=64, N=96, b=16, keep=12, dtype=np.float32):
    w = np.asarray(jax.random.normal(key, (K, N)), dtype)
    sc = np.asarray(jax.random.normal(key, bp.score_shape((K, N), b)))
    mask = np.asarray(bp._hard_topk(jnp.asarray(sc), keep))
    return packing.pack_weight(w, mask, b)


# ---------------------------------------------------------------------------
# Quantization format: roundtrip bounds, pytree, dtype handling
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("b,granularity", [
    (16, "block"), (16, "channel"), (32, "block"), (32, "channel"),
])
def test_int8_roundtrip_error_bound(b, granularity):
    """|w - dequant(quant(w))| <= scale/2 elementwise — the symmetric
    quantizer's defining bound, at both scale granularities."""
    key = jax.random.PRNGKey(b)
    pw = _packed(key, K=4 * b, N=6 * b, b=b, keep=9)
    qpw = Q.quantize_packed(pw, "int8", granularity)
    assert qpw.granularity == granularity
    assert qpw.blocks.dtype == jnp.int8
    want_ndim = 2 if granularity == "block" else 3
    assert qpw.scales.ndim == want_ndim
    w = np.asarray(pw.blocks, np.float32)
    wq = np.asarray(Q.dequantize_packed(qpw).blocks, np.float32)
    bound = np.asarray(Q._expand_scales(np.asarray(qpw.scales)),
                       np.float32) / 2.0
    assert np.all(np.abs(w - wq) <= np.broadcast_to(bound, w.shape) + 1e-7)
    assert Q.quantization_error(pw, qpw) <= float(bound.max()) + 1e-7


def test_channel_scales_never_looser_than_block():
    """Per-output-channel scales refine per-block scales, so the roundtrip
    error cannot get worse (it's the serving default for a reason)."""
    pw = _packed(jax.random.PRNGKey(3))
    e_block = Q.quantization_error(pw, Q.quantize_packed(pw, "int8", "block"))
    e_chan = Q.quantization_error(pw,
                                  Q.quantize_packed(pw, "int8", "channel"))
    assert e_chan <= e_block + 1e-7


def test_quantize_fp32_identity_fp16_halves():
    pw = _packed(jax.random.PRNGKey(1))
    assert Q.quantize_packed(pw, "fp32") is pw
    h = Q.quantize_packed(pw, "fp16")
    assert isinstance(h, packing.PackedWeight)
    assert h.blocks.dtype == jnp.float16
    # fp16 roundtrip: plain cast, error bounded by half-precision ulp
    w = np.asarray(pw.blocks, np.float32)
    wh = np.asarray(h.blocks, np.float32)
    assert np.abs(w - wh).max() <= np.abs(w).max() * 2 ** -10
    with pytest.raises(ValueError):
        Q.quantize_packed(pw, "int4")
    with pytest.raises(ValueError):
        Q.quantize_packed(pw, "int8", "tensor")


def test_all_zero_block_roundtrips_exactly():
    """The scale zero-guard: an all-zero kept block must dequantize to
    exactly zero (scale falls back to 1.0, not 0 or NaN)."""
    w = np.zeros((32, 32), np.float32)
    mask = np.ones(bp.score_shape(w.shape, 16), bool)
    pw = packing.pack_weight(w, mask, 16)
    for g in Q.GRANULARITIES:
        qpw = Q.quantize_packed(pw, "int8", g)
        assert np.all(np.isfinite(np.asarray(qpw.scales)))
        assert Q.quantization_error(pw, qpw) == 0.0


def test_quantized_packed_weight_is_pytree():
    pw = _packed(jax.random.PRNGKey(2))
    qpw = Q.quantize_packed(pw, "int8", "channel")
    leaves, treedef = jax.tree_util.tree_flatten(qpw)
    assert len(leaves) == 4  # blocks, scales, header, counts
    rebuilt = jax.tree_util.tree_unflatten(treedef, leaves)
    assert rebuilt.granularity == "channel"
    assert rebuilt.shape == qpw.shape
    np.testing.assert_array_equal(np.asarray(rebuilt.blocks),
                                  np.asarray(qpw.blocks))
    # hashable aux data -> usable as a jit operand
    hash(treedef)


# ---------------------------------------------------------------------------
# Size accounting (satellite: dtype-derived, scales included)
# ---------------------------------------------------------------------------
def test_nbytes_derives_from_dtypes():
    pw = _packed(jax.random.PRNGKey(4), b=16, keep=12)
    kept = int(np.asarray(pw.counts).sum())
    assert pw.nbytes() == kept * 16 * 16 * 4 + kept * 4  # f32 blocks, i32 hdr
    h = Q.quantize_packed(pw, "fp16")
    assert h.nbytes() == kept * 16 * 16 * 2 + kept * 4
    q_b = Q.quantize_packed(pw, "int8", "block")
    assert q_b.nbytes() == kept * 16 * 16 * 1 + kept * 4 + kept * 1 * 4
    q_c = Q.quantize_packed(pw, "int8", "channel")
    assert q_c.nbytes() == kept * 16 * 16 * 1 + kept * 4 + kept * 16 * 4
    assert Q.packed_dict_nbytes({"a": pw, "b": q_c}) == \
        pw.nbytes() + q_c.nbytes()


def test_packed_model_size_bytes_scales_term():
    mw = [((64, 64), None), ((64, 64), np.ones((4, 4), bool))]
    base = packing.packed_model_size_bytes(mw, 16, dtype_bytes=1)
    with_scales = packing.packed_model_size_bytes(
        mw, 16, dtype_bytes=1, scale_bytes=4, scales_per_block=16)
    assert with_scales - base == 16 * 16 * 4  # 16 kept blocks × 16 ch × f32
    # backward-compatible default is the paper's int16 + 4-byte header
    legacy = packing.packed_model_size_bytes(mw, 16)
    assert legacy == 64 * 64 * 2 + 16 * (16 * 16 * 2 + 4)


# ---------------------------------------------------------------------------
# Kernels: Pallas dequant vs jnp oracle (bit-match), fp16 attention
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("M,K,N,b,keep", [
    (32, 32, 32, 16, 2),
    (64, 64, 128, 16, 10),
    (100, 96, 80, 16, 14),   # non-multiples: the ops.py padding path
    (48, 64, 64, 32, 3),
])
@pytest.mark.parametrize("granularity", ["block", "channel"])
def test_sbmm_quant_kernel_bit_matches_ref(M, K, N, b, keep, granularity):
    key = jax.random.PRNGKey(hash((M, K, N, b)) % 2 ** 31)
    pw = _packed(key, K=K, N=N, b=b, keep=keep)
    qpw = Q.quantize_packed(pw, "int8", granularity)
    x = jax.random.normal(jax.random.fold_in(key, 2), (M, K), jnp.float32)
    y = sbmm_quant_raw(x, qpw.blocks, qpw.header, qpw.scales, tm=32)
    y_ref = sbmm_quant_ref(x, qpw.blocks, qpw.header, qpw.scales)
    np.testing.assert_array_equal(np.asarray(y), np.asarray(y_ref))


def test_sbmm_quant_pallas_direct_bit_match():
    """Unpadded direct kernel call (M multiple of tm) — the pure kernel
    grid, no ops.py involvement."""
    pw = _packed(jax.random.PRNGKey(11), K=64, N=64, b=16, keep=8)
    qpw = Q.quantize_packed(pw, "int8", "channel")
    x = jax.random.normal(jax.random.PRNGKey(12), (64, 64), jnp.float32)
    y = sbmm_quant_pallas(x, qpw.blocks, qpw.header, qpw.scales, tm=32)
    y_ref = sbmm_quant_ref(x, qpw.blocks, qpw.header, qpw.scales)
    np.testing.assert_array_equal(np.asarray(y), np.asarray(y_ref))


def test_sbmm_dispatches_quantized_and_matches_dense_dequant():
    """The public sbmm() entry point routes QuantizedPackedWeight to the
    dequant kernel and undoes the column permutation: result must match
    x @ dequant(W) computed dense."""
    pw = _packed(jax.random.PRNGKey(5), K=64, N=96, b=16, keep=12)
    qpw = Q.quantize_packed(pw, "int8", "channel")
    x = jax.random.normal(jax.random.PRNGKey(6), (40, 64), jnp.float32)
    y = sbmm(x, qpw, tm=32)
    y_dense = x @ qpw.to_dense()
    np.testing.assert_allclose(np.asarray(y), np.asarray(y_dense),
                               atol=1e-4, rtol=1e-4)


def test_sbmm_quant_empty_column_zero():
    w = np.ones((32, 32), np.float32)
    mask = np.zeros(bp.score_shape(w.shape, 16), bool)
    mask[:, 0] = True  # second block-column fully pruned
    pw = packing.pack_weight(w, mask, 16)
    qpw = Q.quantize_packed(pw, "int8", "block")
    x = jnp.ones((32, 32), jnp.float32)
    y = np.asarray(sbmm(x, qpw, tm=32))
    assert np.all(y[:, 16:] == 0.0)
    assert np.all(y[:, :16] != 0.0)


def test_flash_attention_fp16_matches_jnp_oracle():
    """The cast IS the quantizer: the fp16 kernel variant must match the
    jnp online-softmax oracle evaluated on the SAME fp16-cast operands
    (fp32 softmax/accumulation both sides), output fp32."""
    key = jax.random.PRNGKey(9)
    B, N, H, Dh = 2, 33, 4, 16
    q = jax.random.normal(key, (B, N, H, Dh), jnp.float32)
    k = jax.random.normal(jax.random.fold_in(key, 1), (B, N, H, Dh))
    v = jax.random.normal(jax.random.fold_in(key, 2), (B, N, H, Dh))
    out = flash_attention_fp16(q, k, v, causal=False)
    assert out.dtype == jnp.float32
    oracle = A.flash_attention_jnp(q.astype(jnp.float16),
                                   k.astype(jnp.float16),
                                   v.astype(jnp.float16),
                                   causal=False).astype(jnp.float32)
    np.testing.assert_allclose(np.asarray(out), np.asarray(oracle),
                               atol=2e-3, rtol=2e-3)
    # and it is a genuinely different rounding than fp32 attention
    full = A.flash_attention_jnp(q, k, v, causal=False)
    assert np.abs(np.asarray(out) - np.asarray(full)).max() > 0.0


# ---------------------------------------------------------------------------
# Perf model: the paper-table precision pricing
# ---------------------------------------------------------------------------
def test_vit_segment_cycles_precision_ordering():
    cfg = DEIT_SMALL.reduced()
    for seg in (("layers", 0, 2), ("tdm", 1)):
        c32 = vit_segment_cycles(cfg, seg, 64)
        c16 = vit_segment_cycles(cfg, seg, 64, precision="fp16")
        c8 = vit_segment_cycles(cfg, seg, 64, precision="int8")
        assert c8 < c16 < c32
        assert c32 / c8 == pytest.approx(PRECISION_SPEEDUP["int8"])
    for seg in (("embed",), ("head",)):  # always fp32: no discount
        assert vit_segment_cycles(cfg, seg, 64) == \
            vit_segment_cycles(cfg, seg, 64, precision="int8")
    with pytest.raises(ValueError):
        precision_speedup("int4")


def test_engine_config_validation():
    """The engine serves float32 only: any other tier is refused when the
    config is built."""
    VisionEngineConfig(precision="fp32")
    for tier in ("int8", "fp16", "int4"):
        with pytest.raises(ValueError, match="removed"):
            VisionEngineConfig(precision=tier)


def test_sbmm_refuses_fp16_where_kernels_compile(monkeypatch):
    """Compiled Pallas cannot load float16 blocks on the TPU, so a direct
    kernel call on fp16 blocks fails before lowering rather than falling
    back to the interpreter."""
    from repro.kernels import backend
    monkeypatch.setenv(backend.ENV_VAR, "compiled")
    pw16 = Q.quantize_packed(_packed(jax.random.PRNGKey(3)), "fp16")
    with pytest.raises(ValueError, match="float16"):
        sbmm(jnp.ones((8, 64), jnp.float32), pw16)
