"""Quantized serving path (repro.core.quant + kernels.sbmm.quant +
precision-threaded runner/planner/engine).

Layers of defense, mirroring the fp32 stack's test structure:
  * format roundtrip — symmetric quantize→dequantize error bounded by
    scale/2 per element (deterministic sweep here; the hypothesis
    properties live in TestQuantProperties below, skipped without the
    optional 'test' extra);
  * kernel vs oracle — the dequant-in-kernel Pallas SBMM bit-matches the
    accumulation-order-matched jnp reference in interpret mode, and the
    fp16 attention variant matches the jnp oracle on the same fp16-cast
    operands;
  * runner — forward_vit_packed(precision=...) chains the quantized
    kernels across TDM steps; the engine (tiles AND express lanes) is
    bit-exact against it per request;
  * planner — precision decisions deterministic, fp32 ties win, pricing
    strictly ordered int8 < fp16 < fp32 on encoder segments;
  * accounting — nbytes/packed_model_size_bytes derive from actual dtypes
    and include scales.
"""
import math

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import DEIT_SMALL
from repro.core import block_pruning as bp
from repro.core import packed_runner as PR
from repro.core import packing
from repro.core import quant as Q
from repro.core.perf_model import (PRECISION_SPEEDUP, precision_speedup,
                                   vit_segment_cycles)
from repro.kernels.flash_attention import flash_attention_fp16
from repro.kernels.sbmm import (sbmm, sbmm_quant_pallas, sbmm_quant_ref,
                                sbmm_quant_raw)
from repro.models import attention as A
from repro.models import model as M
from repro.models import pruning_glue as PG
from repro.serving.planner import TileCostModel, TilePlanner
from repro.serving.ragged_batcher import RaggedBatcher
from repro.serving.vision import (VisionEngine, VisionEngineConfig,
                                  VisionRequest)


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
# Runner: precision threads through segments, TDM chains, fused lanes
# ---------------------------------------------------------------------------
@pytest.fixture(scope="module")
def small_model():
    cfg = DEIT_SMALL.reduced()
    key = jax.random.PRNGKey(0)
    params = M.init_params(cfg, key)
    scores = PG.init_scores(cfg, params, jax.random.fold_in(key, 7))
    masked = PG.apply_pruning(cfg, params, scores)
    packed = PR.pack_model(cfg, params, scores)
    return cfg, masked, packed


@pytest.mark.parametrize("precision", ["int8", "fp16"])
def test_forward_vit_packed_quantized_close_to_fp32(small_model, precision):
    """Full forward (TDM chained) at a quantized tier: close to fp32 in
    logits, identical in top-1 at this scale, and actually different
    (the quantized kernels really ran)."""
    cfg, masked, packed = small_model
    n = (cfg.image_size // cfg.patch_size) ** 2
    patches = jax.random.normal(jax.random.PRNGKey(1),
                                (2, n, cfg.patch_size ** 2 * 3))
    l32 = np.asarray(PR.forward_vit_packed(cfg, masked, packed,
                                           patches).logits)
    lq = np.asarray(PR.forward_vit_packed(cfg, masked, packed, patches,
                                          precision=precision).logits)
    d = np.abs(l32 - lq).max()
    assert 0.0 < d < 0.1
    # top-1 may only flip where fp32 itself was within the quantization
    # perturbation of a tie (random-init logits are near-uniform; the
    # accuracy gate proper is vision_bench's precision_compare arm)
    for row32, rowq in zip(l32, lq):
        if row32.argmax() != rowq.argmax():
            top2 = np.sort(row32)[-2:]
            assert top2[1] - top2[0] <= 2.0 * d


def test_segments_runner_precision_ledger(small_model):
    """fp32 ledger keys unchanged; quantized runs append a marker; embed
    and head tiles are shared across precisions (no marker, no re-entry)."""
    cfg, masked, packed = small_model
    seg = PR.PackedVitSegments(cfg, masked, packed, use_tdm=False)
    n = (cfg.image_size // cfg.patch_size) ** 2
    patches = jax.random.normal(jax.random.PRNGKey(2),
                                (1, n, cfg.patch_size ** 2 * 3))
    x = seg.run(("embed",), patches)
    seg.run(("layers", 0, cfg.num_layers), x)
    keys_fp32 = set(seg.compiled_tiles())
    assert all(k[-1] not in Q.PRECISIONS for k in keys_fp32)
    count_fp32 = seg.compile_count
    seg.run(("embed",), patches)  # embed ignores precision entirely
    seg.run(("layers", 0, cfg.num_layers), x, precision="int8")
    assert seg.compile_count == count_fp32 + 1
    new = set(seg.compiled_tiles()) - keys_fp32
    assert len(new) == 1 and next(iter(new))[-1] == "int8"
    with pytest.raises(ValueError):
        seg.run(("layers", 0, cfg.num_layers), x, precision="int4")


def test_run_fused_quantized_matches_segmented(small_model):
    """Express lane at int8: the fused trajectory program must be
    bit-exact against the per-segment quantized path (same pure bodies,
    one XLA program — the fp32 exactness argument carries over)."""
    cfg, masked, packed = small_model
    runner = PR.PackedVitSegments(cfg, masked, packed)
    n = (cfg.image_size // cfg.patch_size) ** 2
    patches = np.asarray(jax.random.normal(
        jax.random.PRNGKey(3), (1, n, cfg.patch_size ** 2 * 3)))
    ref = PR.forward_vit_packed(cfg, masked, packed,
                                jnp.asarray(patches), segments=runner,
                                precision="int8").logits
    steps = []
    ntok = n + 1
    sched = PR.keep_schedule(cfg)
    ti = 0
    for s in runner.plan:
        if s[0] == "tdm":
            k = PR.tdm_keep_count(ntok, sched[ti])
            steps.append((s, k))
            ntok = k + 2
            ti += 1
        else:
            steps.append((s, None))
    fused = runner.run_fused(tuple(steps), patches, precision="int8")
    np.testing.assert_array_equal(np.asarray(fused), np.asarray(ref))
    # trajectory ledger keys carry the precision marker
    assert any(t[-1] == "int8" for t in runner._fused_trajectories)


# ---------------------------------------------------------------------------
# Perf model + planner: precision pricing and decisions
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


def test_cost_model_reads_precision_marker():
    cfg = DEIT_SMALL.reduced()
    cm = TileCostModel(cfg)
    seg = ("layers", 0, 2)
    base = cm.stage_row_cycles((1, seg, None), 64)
    assert cm.stage_row_cycles((1, seg, None, "int8"), 64) == \
        pytest.approx(base / 4.0)
    assert cm.stage_row_cycles((1, seg, 7, "soft", "fp16"), 64) == \
        pytest.approx(cm.stage_row_cycles((1, seg, 7, "soft"), 64) / 2.0)
    # opaque proxy scales consistently too
    proxy = TileCostModel(None)
    assert proxy.stage_row_cycles(("op", "x", 0, "int8"), 10) == \
        pytest.approx(proxy.stage_row_cycles("opaque-10", 10) / 4.0)


def _mk_planner(mode="full"):
    return TilePlanner(RaggedBatcher(mode="balanced"), TileCostModel(None),
                       mode=mode)


def test_choose_precision_deterministic_and_strict():
    pl = _mk_planner()
    traj32 = ((("s", 0), 8),)
    traj8 = ((("s", 0, "int8"), 8),)
    # strictly cheaper int8 wins; repeated calls identical
    picks = [pl.choose_precision([("fp32", traj32), ("int8", traj8)],
                                 record=False) for _ in range(5)]
    assert picks == ["int8"] * 5
    # equal-cost tie keeps fp32 (first candidate, strict < required)
    assert pl.choose_precision([("fp32", traj32), ("int8", traj32)],
                               record=False) == "fp32"
    assert pl.precision_decisions == {p: 0 for p in Q.PRECISIONS}
    pl.choose_precision([("fp32", traj32), ("int8", traj8)])
    assert pl.precision_decisions["int8"] == 1
    assert pl.stats()["precision_int8"] == 1
    with pytest.raises(ValueError):
        pl.choose_precision([])


# ---------------------------------------------------------------------------
# Engine: bit-exactness per precision, strict pinning, counters, cache
# ---------------------------------------------------------------------------
def _requests(cfg, n_req=5, strict_uid=None):
    key = jax.random.PRNGKey(42)
    n_max = (cfg.image_size // cfg.patch_size) ** 2
    reqs = []
    for i in range(n_req):
        n = n_max - (i % 3)
        p = np.asarray(jax.random.normal(
            jax.random.fold_in(key, i), (n, cfg.patch_size ** 2 * 3)),
            np.float32)
        reqs.append(VisionRequest(
            uid=i, patches=p, arrival_step=i // 2,
            quality="strict" if i == strict_uid else None))
    return reqs


@pytest.mark.parametrize("precision", ["int8", "fp16"])
def test_engine_quantized_bit_exact_vs_offline_oracle(small_model,
                                                      precision):
    """Every request served by a quantized engine (tiles, merged tiles and
    express lanes mixed by planner=full) is bit-exact against the offline
    single-request forward at the same precision."""
    cfg, masked, packed = small_model
    vc = VisionEngineConfig(max_batch=4, planner="full",
                            precision=precision)
    eng = VisionEngine(cfg, masked, packed, vc=vc)
    reqs = _requests(cfg)
    out = eng.serve(reqs)
    # budget check BEFORE the oracle runs below add their own (unbatched,
    # unpadded) entries to the shared segment jit caches
    s = eng.stats()
    assert s["precision"] == precision
    assert s[f"dispatch_{precision}"] > 0
    assert s["jit_compile_count"] <= s["compile_budget"]
    for r in reqs:
        ref = PR.forward_vit_packed(
            cfg, masked, packed, jnp.asarray(r.patches)[None],
            segments=eng.segments, precision=precision).logits
        np.testing.assert_array_equal(out[r.uid], np.asarray(ref[0]))
    if precision == "int8":
        assert s["dequant_dispatches"] == s["dispatch_int8"]
    else:
        assert s["dequant_dispatches"] == 0


def test_engine_strict_quality_pins_fp32(small_model):
    """quality='strict' requests run fp32 on a quantized engine — their
    logits bit-match the fp32 engine's."""
    cfg, masked, packed = small_model
    reqs32 = _requests(cfg, strict_uid=2)
    out32 = VisionEngine(cfg, masked, packed,
                         vc=VisionEngineConfig(max_batch=4)).serve(reqs32)
    reqs8 = _requests(cfg, strict_uid=2)
    eng8 = VisionEngine(cfg, masked, packed,
                        vc=VisionEngineConfig(max_batch=4, precision="int8"))
    out8 = eng8.serve(reqs8)
    np.testing.assert_array_equal(out32[2], out8[2])
    # the non-strict ones really quantized
    assert any(not np.array_equal(out32[u], out8[u]) for u in out32
               if u != 2)
    assert eng8.planner.precision_decisions["int8"] > 0


def test_engine_fp32_path_untouched_by_quant_plumbing(small_model):
    """An fp32 engine never builds quantized dicts, never marks a stage
    key, and records zero precision decisions — the pre-PR fp32 surface."""
    cfg, masked, packed = small_model
    eng = VisionEngine(cfg, masked, packed,
                       vc=VisionEngineConfig(max_batch=4, planner="full"))
    eng.serve(_requests(cfg))
    assert set(eng.segments._packed_by) == {"fp32"}
    assert all(k[-1] not in Q.PRECISIONS
               for k in eng.segments.compiled_tiles())
    assert eng.planner.precision_decisions == {p: 0 for p in Q.PRECISIONS}
    s = eng.stats()
    assert s["dispatch_fp32"] > 0 and s["dequant_dispatches"] == 0
    rep = eng.quantization_report()
    assert rep["quant_max_abs_error"] == 0.0
    assert rep["packed_bytes"] == rep["packed_bytes_fp32"]


def test_items_fingerprint_precision_aware():
    """Plan-cache stability: stage-key precision markers flow into the
    population fingerprint, so an int8 population never reuses an fp32
    speculative plan (and vice versa)."""
    from repro.serving.planner import PlanItem
    a = PlanItem(stage=(0, ("layers", 0, 2), None), n_tokens=8,
                 trajectory=(((0, ("layers", 0, 2), None), 8),))
    b = PlanItem(stage=(0, ("layers", 0, 2), None, "int8"), n_tokens=8,
                 trajectory=(((0, ("layers", 0, 2), None, "int8"), 8),))
    fa = VisionEngine._items_fingerprint([a])
    fb = VisionEngine._items_fingerprint([b])
    assert fa is not None and fb is not None and fa != fb


def test_engine_quantization_report(small_model):
    cfg, masked, packed = small_model
    eng = VisionEngine(cfg, masked, packed,
                       vc=VisionEngineConfig(max_batch=2, precision="int8"))
    rep = eng.quantization_report()
    assert rep["precision"] == "int8"
    assert rep["granularity"] == "channel"
    assert 0.0 < rep["quant_max_abs_error"] < 0.1
    assert rep["packed_bytes"] < rep["packed_bytes_fp32"]
    # metrics export carries the counters as gauges
    from repro.obs.metrics import MetricsRegistry
    eng.serve(_requests(cfg, n_req=2))
    snap = eng.export_metrics(MetricsRegistry()).snapshot()
    for name in ("vision.dequant_dispatches", "vision.dispatch_int8",
                 "vision.plan_precision_int8"):
        assert snap[name]["type"] == "gauge"
    assert snap["vision.dispatch_int8"]["value"] > 0


def test_engine_config_validation():
    with pytest.raises(ValueError):
        VisionEngineConfig(precision="int4")
    with pytest.raises(ValueError):
        VisionEngineConfig(quant_granularity="tensor")


def test_fp16_tier_refused_where_kernels_compile(small_model, monkeypatch):
    """Compiled Pallas cannot load float16 blocks on the TPU, so the fp16
    tier fails when the engine is built, and a direct kernel call fails
    before lowering: neither falls back to another tier or the
    interpreter."""
    from repro.kernels import backend
    cfg, masked, packed = small_model
    monkeypatch.setenv(backend.ENV_VAR, "compiled")
    with pytest.raises(ValueError, match="float16"):
        VisionEngine(cfg, masked, packed,
                     vc=VisionEngineConfig(max_batch=2, precision="fp16"))
    pw16 = Q.quantize_packed(_packed(jax.random.PRNGKey(3)), "fp16")
    with pytest.raises(ValueError, match="float16"):
        sbmm(jnp.ones((8, 64), jnp.float32), pw16)
    # the int8 tier and fp32 still build
    VisionEngine(cfg, masked, packed,
                 vc=VisionEngineConfig(max_batch=2, precision="int8"))
