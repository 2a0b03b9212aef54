"""Packed-model execution — the deployment path of the paper's accelerator.

After simultaneous pruning, ``pack_model`` hardens the masks and converts
every block-pruned attention weight into the block-compressed SBMM format
(load-balanced column order included). ``forward_vit_packed`` then runs the
ViT from those weights, validated end-to-end against the masked-dense
forward (tests/test_packed_runner.py). The fp32 tier multiplies by each
projection's block-masked dense matrix, rebuilt once from its packed
blocks: at b = 16 and r_b = 0.5 no 128x128 MXU tile of the weight is
empty, so a TPU-shaped SBMM can at best match that dense matmul. The
quantized tiers (``repro.core.quant``) keep the packed format and run the
SBMM kernels, which stream their int8/fp16 blocks.

MLP column/row-pruned weights stay dense-masked (the paper maps them to
DBMM — a dense matmul over the shrunken width — which XLA already emits).

Per-stage segmentation (serving.vision)
---------------------------------------
The forward is decomposed into *segments* whose boundaries are the TDM
layers — exactly the points where per-image token counts change:

    ("embed",)          patches -> tokens          (count = n_patches + 1)
    ("layers", lo, hi)  encoder layers [lo, hi)    (count constant)
    ("tdm", i)          encoder layer i with the TDM (count shrinks)
    ("head",)           final norm + CLS readout   (-> logits)

``forward_vit_packed`` composes the segments sequentially (one request,
offline), while the vision serving engine schedules each segment over a
*ragged* population of in-flight images, regrouping between segments
(``repro.serving.ragged_batcher``). ``PackedVitSegments`` owns the jitted
per-segment step functions behind a compile ledger, mirroring
``serving.runner.ModelRunner`` for the LM path.

Every segment optionally takes ``n_valid`` ([B] int32, real token count per
row): token-padded rows are masked out of attention and accumulate exactly
zero TDM score, so batching never leaks padding into a request's logits.
"""
from __future__ import annotations

from typing import Callable, Dict, List, Optional, Sequence, Tuple

import numpy as np
import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.core import packing
from repro.core import quant as Q
from repro.core import token_pruning as TP
from repro.kernels.sbmm import sbmm
from repro.models import attention as A
from repro.models import layers as L
from repro.models import model as M
from repro.models import pruning_glue as PG


def pack_model(cfg: ModelConfig, params: Dict, scores: Dict,
               lanes: int = 8) -> Dict[str, packing.PackedWeight]:
    """Block-compress every masked attention weight. Returns
    {path: PackedWeight}; paths match pruning_glue.hard_masks keys."""
    masks = PG.hard_masks(cfg, params, scores)
    out = {}
    for path, mask in masks.items():
        layer_idx = int(path.split("/")[1])
        leafname = path.split("/")[-1]
        w = np.asarray(params["layers"][layer_idx]["attn"][leafname],
                       np.float32)
        out[path] = packing.pack_weight(
            w, np.asarray(mask), cfg.pruning.block_size, lanes)
    return out


# ===========================================================================
# Stage plan
# ===========================================================================
Segment = Tuple  # ("embed",) | ("layers", lo, hi) | ("tdm", i) | ("head",)


def vit_segments(cfg: ModelConfig,
                 use_tdm: Optional[bool] = None) -> Tuple[Segment, ...]:
    """Per-stage segmentation of the packed ViT forward: one segment per
    maximal run of constant token count, TDM layers as their own segments
    (prune boundaries ARE batching boundaries for the serving engine)."""
    p = cfg.pruning
    if use_tdm is None:
        use_tdm = p.token_pruning_enabled
    tdm_layers = sorted(p.tdm_layers) if use_tdm else []
    segs: List[Segment] = [("embed",)]
    prev = 0
    for t in tdm_layers:
        if not 0 <= t < cfg.num_layers:
            raise ValueError(f"tdm layer {t} outside [0, {cfg.num_layers})")
        if t > prev:
            segs.append(("layers", prev, t))
        segs.append(("tdm", t))
        prev = t + 1
    if prev < cfg.num_layers:
        segs.append(("layers", prev, cfg.num_layers))
    segs.append(("head",))
    return tuple(segs)


def tdm_keep_count(n_tokens: int, r_t: float) -> int:
    """Static top-k count for a TDM applied at a *real* token count of
    ``n_tokens`` (CLS included) — the per-request ``k`` the serving engine
    passes into padded TDM segments. Derived from ``TP.num_kept_tokens``
    (the one source of truth for the clamp rule): output count is
    ``1 (CLS) + k + 1 (fused)``."""
    return TP.num_kept_tokens(n_tokens, r_t, has_cls=True) - 2


def tdm_soft_keep_count(n_tokens: int, r_t: float, has_pkg: bool) -> int:
    """Static top-k count for a SOFT TDM at ``n_tokens`` real tokens. Same
    rule as :func:`tdm_keep_count`, except that once a package row exists
    (``has_pkg``: every soft TDM after the first) it is pinned — the top-k
    draws from the ``n_tokens - 2`` real body rows, so ``k`` clamps there
    (only binds as ``r_t -> 1``; output count ``k + 2`` then never exceeds
    the input count, unlike the hard TDM's ``+1`` fused-row growth)."""
    k = tdm_keep_count(n_tokens, r_t)
    return min(k, n_tokens - 2) if has_pkg else k


def keep_schedule(cfg: ModelConfig, r_t: Optional[float] = None,
                  use_tdm: Optional[bool] = None) -> Tuple[float, ...]:
    """Uniform per-step keep schedule: ``r_t`` (default ``cfg.pruning.r_t``)
    broadcast over every TDM segment of ``vit_segments``, in segment order.
    The serving engine generalizes this — requests may carry a non-uniform
    schedule, and the QualityController may tighten entries at plan time —
    but a scalar ``r_t`` is always exactly this broadcast."""
    if r_t is None:
        r_t = cfg.pruning.r_t
    n_tdm = sum(1 for seg in vit_segments(cfg, use_tdm)
                if seg[0] == "tdm")
    return (float(r_t),) * n_tdm


def token_trajectory(cfg: ModelConfig, n_patches: int,
                     r_t: Optional[float] = None,
                     use_tdm: Optional[bool] = None,
                     schedule: Optional[Sequence[float]] = None,
                     soft: bool = False) -> Tuple[int, ...]:
    """Real token count a single image carries *after* each segment of
    ``vit_segments`` (head repeats the final count). Drives the ragged
    batcher's bucket keys and the prune-pressure-aware admission policy.

    ``schedule`` gives the keep rate per TDM segment (in segment order);
    ``None`` broadcasts ``r_t`` over every TDM segment (the classic
    frozen-scalar behavior, now a special case). ``soft`` prices the
    soft-pruning variant (``tdm_soft_keep_count``'s package-row clamp)."""
    n = n_patches + 1  # + CLS
    counts = []
    ordinal = 0
    if schedule is None:
        schedule_t: Tuple[float, ...] = keep_schedule(cfg, r_t, use_tdm)
    else:
        schedule_t = tuple(float(r) for r in schedule)
    for seg in vit_segments(cfg, use_tdm):
        if seg[0] == "tdm":
            if ordinal >= len(schedule_t):
                raise ValueError(
                    f"keep schedule has {len(schedule_t)} entries but the "
                    f"segment plan reaches TDM ordinal {ordinal}")
            r = schedule_t[ordinal]
            k = (tdm_soft_keep_count(n, r, has_pkg=ordinal > 0) if soft
                 else tdm_keep_count(n, r))
            n = k + 2
            ordinal += 1
        counts.append(n)
    return tuple(counts)


# ===========================================================================
# Segment bodies (pure functions; jitted by PackedVitSegments)
# ===========================================================================
def _proj(params: Dict, packed: Dict, i: int, name: str, inp: jax.Array
          ) -> jax.Array:
    """One attention projection by the weight ``packed`` holds for it: a
    block-compressed weight runs the SBMM kernel, a dense (block-masked)
    matrix an XLA matmul; an unpruned weight comes from ``params``."""
    w = packed.get(f"layers/{i}/attn/{name}")
    if isinstance(w, (packing.PackedWeight, Q.QuantizedPackedWeight)):
        return sbmm(inp, w)
    return L.linear(inp, params["layers"][i]["attn"][name] if w is None
                    else w)


def _encoder_attn(cfg: ModelConfig, params: Dict, packed: Dict,
                  x: jax.Array, i: int, *, collect_scores: bool = False,
                  n_valid: Optional[jax.Array] = None,
                  precision: str = "fp32"
                  ) -> Tuple[jax.Array, Optional[jax.Array]]:
    """Attention sublayer + residual of encoder layer ``i`` (projections
    by ``_proj``: block-masked dense matmuls at fp32, SBMM for quantized
    packed weights). ``n_valid`` masks token padding out of the
    attention and of the TDM scoring; padded rows' scores are exactly 0.

    ``precision`` is the quantized-serving knob: weight precision is
    carried by the ``packed`` dict itself (int8/fp16 entries dispatch the
    matching SBMM kernel), while ``"fp16"`` additionally quantizes the
    attention operands — q/k/v cast to float16 before the online-softmax
    attention (whose accumulation stays fp32) — with the output and TDM
    scores returned in fp32 so residuals and top-k run full-precision."""
    H, KV, Dh = cfg.num_heads, cfg.num_kv_heads, cfg.head_dim
    lp = params["layers"][i]
    h = L.layer_norm(x, lp["ln1_s"], lp["ln1_b"], cfg.norm_eps)
    Bc, Nc, _ = h.shape
    q = (_proj(params, packed, i, "wq", h)
         + lp["attn"].get("bq", 0.0)).reshape(Bc, Nc, H, Dh)
    k = (_proj(params, packed, i, "wk", h)
         + lp["attn"].get("bk", 0.0)).reshape(Bc, Nc, KV, Dh)
    v = (_proj(params, packed, i, "wv", h)
         + lp["attn"].get("bv", 0.0)).reshape(Bc, Nc, KV, Dh)
    if precision == "fp16":
        q = q.astype(jnp.float16)
        k = k.astype(jnp.float16)
        v = v.astype(jnp.float16)
    o = A.flash_attention_jnp(q, k, v, causal=False, kv_len=n_valid)
    o = o.astype(x.dtype)
    scores = None
    if collect_scores:
        probs = A.attention_probs_row(q[:, 0], k, kv_len=n_valid)
        scores = probs.mean(axis=1).astype(x.dtype)
    o = o.reshape(Bc, Nc, H * Dh)
    attn_out = _proj(params, packed, i, "wo", o) + lp["attn"].get("bo", 0.0)
    return x + attn_out, scores


def _encoder_mlp(cfg: ModelConfig, params: Dict, x: jax.Array,
                 i: int) -> jax.Array:
    lp = params["layers"][i]
    h = L.layer_norm(x, lp["ln2_s"], lp["ln2_b"], cfg.norm_eps)
    return x + L.gelu_mlp(h, lp["mlp"])


def vit_embed(cfg: ModelConfig, params: Dict,
              patches: jax.Array) -> jax.Array:
    """patches [B, N, P²·3] -> tokens [B, N+1, D] (fp32, CLS prepended).
    Token-padded patch rows simply embed to don't-care rows; downstream
    segments mask them via ``n_valid``."""
    adt = jnp.float32  # kernel path runs fp32 end to end
    x = L.linear(patches.astype(adt), params["patch_embed"],
                 params["patch_bias"])
    B, N, D = x.shape
    cls = jnp.broadcast_to(params["cls"].astype(adt), (B, 1, D))
    x = jnp.concatenate([cls, x], axis=1)
    return x + params["pos"][None, : N + 1].astype(adt)


def vit_layers(cfg: ModelConfig, params: Dict, packed: Dict, x: jax.Array,
               lo: int, hi: int,
               n_valid: Optional[jax.Array] = None,
               precision: str = "fp32") -> jax.Array:
    """Encoder layers [lo, hi) at constant token count."""
    for i in range(lo, hi):
        x, _ = _encoder_attn(cfg, params, packed, x, i, n_valid=n_valid,
                             precision=precision)
        x = _encoder_mlp(cfg, params, x, i)
    return x


def vit_tdm_layer(cfg: ModelConfig, params: Dict, packed: Dict,
                  x: jax.Array, layer: int, r_t: Optional[float] = None,
                  k: Optional[int] = None,
                  n_valid: Optional[jax.Array] = None,
                  precision: str = "fp32") -> jax.Array:
    """Encoder layer ``layer`` with the TDM between its attention and MLP
    sublayers: [B, N, D] -> [B, k + 2, D] (CLS + k kept + fused). ``k``
    must be passed when rows are token-padded (see ``TP.tdm``); otherwise
    it derives from N and ``r_t`` exactly as the monolithic forward did."""
    if r_t is None:
        r_t = cfg.pruning.r_t
    x, scores = _encoder_attn(cfg, params, packed, x, layer,
                              collect_scores=True, n_valid=n_valid,
                              precision=precision)
    x, _ = TP.tdm(x, scores, r_t, has_cls=True, k=k)
    return _encoder_mlp(cfg, params, x, layer)


def vit_tdm_soft_layer(cfg: ModelConfig, params: Dict, packed: Dict,
                       x: jax.Array, layer: int, k: int,
                       pkg_mass: Optional[jax.Array] = None,
                       n_valid: Optional[jax.Array] = None,
                       precision: str = "fp32"
                       ) -> Tuple[jax.Array, jax.Array]:
    """Soft-pruning variant of :func:`vit_tdm_layer`: the dropped tokens
    fold into a persistent package token (``TP.tdm_soft``). Same output
    token count as the hard TDM, plus the accumulated package mass ([B])
    the NEXT soft TDM needs (``pkg_mass=None`` marks the first TDM, where
    no package row exists yet). With ``pkg_mass``, each row's package sits
    at its own valid-token boundary (body index ``n_valid - 2``) so
    token-padded tiles pin the right row."""
    x, scores = _encoder_attn(cfg, params, packed, x, layer,
                              collect_scores=True, n_valid=n_valid,
                              precision=precision)
    pkg_pos = None
    if pkg_mass is not None and n_valid is not None:
        pkg_pos = jnp.asarray(n_valid, jnp.int32) - 2
    x, mass = TP.tdm_soft(x, scores, has_cls=True, k=k, pkg_mass=pkg_mass,
                          pkg_pos=pkg_pos)
    return _encoder_mlp(cfg, params, x, layer), mass


def vit_head(cfg: ModelConfig, params: Dict, x: jax.Array) -> jax.Array:
    """Final norm + CLS readout -> logits [B, num_classes] (fp32)."""
    x = L.layer_norm(x, params["ln_f_s"], params["ln_f_b"], cfg.norm_eps)
    logits = L.linear(x[:, 0], params["head"])
    return logits.astype(jnp.float32)


def run_fused_steps(cfg: ModelConfig, params: Dict, packed: Dict,
                    x: jax.Array, steps: Tuple[Tuple, ...],
                    pkg_mass: Optional[jax.Array] = None,
                    precision: str = "fp32") -> jax.Array:
    """Compose consecutive segments into ONE program: ``steps`` is a static
    tuple of ``(segment, k)`` pairs — or ``(segment, k, soft)`` triples for
    soft-pruning TDM steps (``k`` only for TDM segments). This is the
    express-lane body the planner compiles per trajectory for requests
    that are singletons in every bucket — unbatched and unpadded, so no
    ``n_valid`` is ever needed. All shapes are static given the entry shape
    and the ``k`` sequence. ``pkg_mass`` seeds the package mass for a lane
    entered AFTER a soft request's first TDM already ran tiled (``None``
    otherwise); the mass threads through in-program across soft steps.
    ``precision`` applies to the encoder steps only — embed and head run
    fp32 regardless, matching the tiled path's segment rule."""
    for step in steps:
        seg, k = step[0], step[1]
        soft = bool(step[2]) if len(step) > 2 else False
        kind = seg[0]
        if kind == "embed":
            x = vit_embed(cfg, params, x)
        elif kind == "layers":
            x = vit_layers(cfg, params, packed, x, seg[1], seg[2],
                           precision=precision)
        elif kind == "tdm":
            if k is None:
                raise ValueError("fused tdm steps need an explicit static k")
            if soft:
                x, pkg_mass = vit_tdm_soft_layer(cfg, params, packed, x,
                                                 seg[1], k=k,
                                                 pkg_mass=pkg_mass,
                                                 precision=precision)
            else:
                x = vit_tdm_layer(cfg, params, packed, x, seg[1], k=k,
                                  precision=precision)
                pkg_mass = None  # a hard TDM drops/keeps the package like
                #                  any token; its mass is meaningless after
        elif kind == "head":
            x = vit_head(cfg, params, x)
        else:
            raise ValueError(f"unknown segment {seg!r} in fused steps")
    return x


# ===========================================================================
# Offline single-batch forward — the segments composed sequentially
# ===========================================================================
# Executor memo for forward_vit_packed: id-keyed is safe here because the
# cached executor holds strong references to its params/packed trees, which
# pins their ids for exactly as long as the entry lives. Bounded FIFO so
# sweeps over many packed models don't accumulate jit caches.
_SEGMENT_MEMO: "dict[Tuple, PackedVitSegments]" = {}
_SEGMENT_MEMO_CAP = 8


def _cached_segments(cfg, params, packed, use_tdm) -> "PackedVitSegments":
    # r_t / tdm_layers only matter through the segment plan (the executor
    # always receives k explicitly), so cfgs differing only in keep rate —
    # the per-request-r_t reference loop — share one executor
    import dataclasses as _dc
    plan = vit_segments(cfg, use_tdm)
    cfg_norm = cfg.replace(pruning=_dc.replace(cfg.pruning, r_t=1.0,
                                               tdm_layers=()))
    key = (plan, cfg_norm, id(params), id(packed))
    runner = _SEGMENT_MEMO.get(key)
    if runner is None:
        runner = PackedVitSegments(cfg, params, packed, use_tdm=use_tdm)
        if len(_SEGMENT_MEMO) >= _SEGMENT_MEMO_CAP:
            _SEGMENT_MEMO.pop(next(iter(_SEGMENT_MEMO)))
        _SEGMENT_MEMO[key] = runner
    return runner
def forward_vit_packed(cfg: ModelConfig, params: Dict,
                       packed: Dict[str, packing.PackedWeight],
                       patches: jax.Array,
                       use_tdm: bool | None = None,
                       segments: "Optional[PackedVitSegments]" = None,
                       schedule: Optional[Sequence[float]] = None,
                       soft: bool = False,
                       precision: str = "fp32") -> M.Output:
    """ViT forward with the attention projections taken from ``packed``
    through the segment executor's weight set at ``precision``: at fp32
    XLA matmuls by the block-masked dense weights, at int8/fp16 the SBMM
    kernels (interpret mode on CPU; native Pallas on TPU backends).

    ``params`` should be the MASKED tree (``PG.apply_pruning``) so the
    MLPs run masked-dense (the paper's DBMM path); the packed attention
    weights carry their masks structurally.

    This is the single-request oracle the vision serving engine is
    bit-exact against: it walks the same ``vit_segments`` plan through the
    same *jitted* segment executor, unbatched and unpadded. (Executing the
    segments jitted matters for exactness — XLA's fusion choices shift FP
    reduction order relative to op-by-op eager dispatch, and jitted
    programs are deterministic given the HLO.) Pass ``segments`` to reuse
    an already-compiled executor (e.g. an engine's); otherwise one is
    memoized per (cfg, params, packed, use_tdm) so repeated calls — batch
    evaluation loops, parity tests — compile once.

    ``schedule`` is a per-TDM-segment keep schedule (``None`` broadcasts
    ``cfg.pruning.r_t``) and ``soft`` selects the package-token soft TDM —
    together the offline oracle for the serving engine's quality-elastic
    and soft-pruning paths. ``precision`` runs the encoder segments
    through the quantized weight set + kernels (``repro.core.quant``) —
    the single-request oracle for the engine's quantized tiles."""
    runner = segments if segments is not None else _cached_segments(
        cfg, params, packed, use_tdm)
    if schedule is None:
        schedule = keep_schedule(cfg, use_tdm=use_tdm)
    x = patches
    n = patches.shape[1] + 1  # + CLS after embed
    pkg_mass = None
    ordinal = 0
    for seg in runner.plan:
        if seg[0] == "tdm":
            r = schedule[ordinal]
            if soft:
                k = tdm_soft_keep_count(n, r, has_pkg=ordinal > 0)
                x, pkg_mass = runner.run(seg, x, k=k, soft=True,
                                         pkg_mass=pkg_mass,
                                         precision=precision)
            else:
                k = tdm_keep_count(n, r)
                x = runner.run(seg, x, k=k, precision=precision)
            n = k + 2
            ordinal += 1
        elif seg[0] == "head":
            return M.Output(runner.run(seg, x))
        else:
            x = runner.run(seg, x, precision=precision)
    raise AssertionError("vit_segments plan must end with ('head',)")


def masked_dense_reference(cfg: ModelConfig, params: Dict, scores: Dict,
                           patches: jax.Array,
                           use_tdm: bool | None = None) -> M.Output:
    """Oracle: same model with masked-dense weights (fp32 activations to
    match the kernel path's numerics)."""
    masked = PG.apply_pruning(cfg, params, scores)
    cfg32 = cfg.replace(dtype="float32")
    return M.forward_vit(cfg32, masked, patches, use_tdm=use_tdm)


# ===========================================================================
# Jitted segment executor (the vision serving engine's ModelRunner analog)
# ===========================================================================
def _program(name: str, fn: Callable, **jit_kwargs):
    """``jax.jit`` of ``fn`` under ``name``: the compiled module, and so
    its events on the device trace, is called ``jit_<name>``."""
    fn.__name__ = fn.__qualname__ = name
    return jax.jit(fn, **jit_kwargs)


class PackedVitSegments:
    """Owns the jitted per-segment step functions for one
    (cfg, params, packed) triple, behind a compile ledger.

    Shape discipline mirrors ``serving.runner.ModelRunner``: each distinct
    (segment, batch tile, token tile, masked?) combination compiles once;
    ``compile_count`` is our ledger and ``jit_compile_count()`` asks the
    jit caches themselves. The ragged batcher bounds the distinct
    combinations to its bucket set."""

    def __init__(self, cfg: ModelConfig, params: Dict,
                 packed: Dict[str, packing.PackedWeight],
                 use_tdm: Optional[bool] = None,
                 donate_activations: bool = False,
                 quant_granularity: str = "channel"):
        self.cfg = cfg
        self.params = params
        self.packed = packed
        self.plan = vit_segments(cfg, use_tdm)
        self.donate_activations = donate_activations
        if quant_granularity not in Q.GRANULARITIES:
            raise ValueError(
                f"quant_granularity must be one of {Q.GRANULARITIES}, "
                f"got {quant_granularity!r}")
        self.quant_granularity = quant_granularity
        # The fp32 weight set is every packed projection as its
        # block-masked dense [K, N] matrix in logical column order, built
        # on the host and put on the device once. Quantized packed dicts
        # are derived from ``packed`` lazily on first use — an fp32-only
        # engine never pays the quantization pass, and precisions share the
        # one params tree (embed/MLP/head weights are precision-independent:
        # only the packed attention weights re-quantize).
        self._packed_by: Dict[str, Dict] = {
            "fp32": {path: pw.to_dense() for path, pw in packed.items()}}
        # Only the "layers" segment preserves the activation shape
        # [B, n, D] input->output, so only its input tile is donatable
        # (embed/tdm/head change shapes — donating them would just warn
        # and allocate anyway). Donation requires callers never to re-read
        # a dispatched tile: the serving engine's tiles are fresh padded
        # batches, or an output passed through whole whose rows all move
        # on, and forward_vit_packed rebinds x each segment, so both
        # satisfy it; keep the default off for ad-hoc callers
        # that reuse inputs across calls (e.g. timing probes).
        don = dict(donate_argnums=(2,)) if donate_activations else {}
        # each segment program under its own name, so the device trace
        # names its module jit_<name> (jit_vit_layers, jit_vit_lane, ...)
        self._embed = _program(
            "vit_embed",
            lambda params, patches: vit_embed(cfg, params, patches))
        self._layers = _program(
            "vit_layers",
            lambda params, packed, x, n_valid, lo, hi, prec: vit_layers(
                cfg, params, packed, x, lo, hi, n_valid=n_valid,
                precision=prec),
            static_argnames=("lo", "hi", "prec"), **don)
        self._tdm = _program(
            "vit_tdm",
            lambda params, packed, x, n_valid, layer, k, prec: vit_tdm_layer(
                cfg, params, packed, x, layer, k=k, n_valid=n_valid,
                precision=prec),
            static_argnames=("layer", "k", "prec"))
        self._tdm_soft = _program(
            "vit_tdm_soft",
            lambda params, packed, x, n_valid, pkg_mass, layer, k, prec:
            vit_tdm_soft_layer(cfg, params, packed, x, layer, k=k,
                               pkg_mass=pkg_mass, n_valid=n_valid,
                               precision=prec),
            static_argnames=("layer", "k", "prec"))
        self._head = _program(
            "vit_head", lambda params, x: vit_head(cfg, params, x))
        self._fused = _program(
            "vit_lane",
            lambda params, packed, x, pkg_mass, steps, prec: run_fused_steps(
                cfg, params, packed, x, steps, pkg_mass=pkg_mass,
                precision=prec),
            static_argnames=("steps", "prec"))
        self._compiled: set = set()
        self._fused_trajectories: set = set()

    def packed_for(self, precision: str) -> Dict:
        """The attention weight set at ``precision``: ``fp32`` is the
        block-masked dense matrices built at construction; ``fp16``/
        ``int8`` are quantized lazily on first use from the packed dict
        via :func:`repro.core.quant.quantize_packed_dict` at this runner's
        ``quant_granularity``, and memoized so every tile/lane at a given
        precision shares one set of device buffers."""
        if precision not in Q.PRECISIONS:
            raise ValueError(
                f"precision must be one of {Q.PRECISIONS}, "
                f"got {precision!r}")
        pk = self._packed_by.get(precision)
        if pk is None:
            pk = Q.quantize_packed_dict(self.packed, precision,
                                        self.quant_granularity)
            self._packed_by[precision] = pk
        return pk

    def _ledger_key(self, base: Tuple, precision: str) -> Tuple:
        # fp32 keys stay byte-identical to the pre-quantization ledger so
        # fp32 compile counts / digests are unchanged; other precisions
        # append a marker (soft-marker ordering preserved: soft, then
        # precision).
        return base if precision == "fp32" else base + (precision,)

    def run(self, seg: Segment, x: jax.Array,
            n_valid: Optional[np.ndarray] = None,
            k: Optional[int] = None, soft: bool = False,
            pkg_mass: Optional[jax.Array] = None,
            precision: str = "fp32"):
        """Execute one segment on a dense tile ``x``. ``n_valid`` ([B]) is
        required whenever rows are token-padded; ``k`` is required for
        ``tdm`` segments (uniform across the tile by batcher construction).
        ``soft`` selects the package-token TDM variant: the call takes the
        tile's accumulated package masses (``None`` before the first TDM)
        and returns ``(y, new_mass)`` instead of ``y``. ``precision``
        selects the quantized weight set + kernels for the encoder
        segments; embed and head ignore it (always fp32, so those tiles
        are shared across precisions and never recompile).
        """
        kind = seg[0]
        nv = None if n_valid is None else jnp.asarray(n_valid, jnp.int32)
        base = ((seg, tuple(x.shape), nv is not None, k, "soft") if soft
                else (seg, tuple(x.shape), nv is not None, k))
        if kind == "embed":
            self._compiled.add(base)
            return self._embed(self.params, x)
        if kind == "layers":
            self._compiled.add(self._ledger_key(base, precision))
            return self._layers(self.params, self.packed_for(precision),
                                x, nv, lo=seg[1], hi=seg[2], prec=precision)
        if kind == "tdm":
            if k is None:
                raise ValueError("tdm segments need an explicit static k "
                                 "(per-request keep count)")
            self._compiled.add(self._ledger_key(base, precision))
            if soft:
                return self._tdm_soft(self.params,
                                      self.packed_for(precision), x, nv,
                                      pkg_mass, layer=seg[1], k=k,
                                      prec=precision)
            return self._tdm(self.params, self.packed_for(precision), x, nv,
                             layer=seg[1], k=k, prec=precision)
        if kind == "head":
            self._compiled.add(base)
            return self._head(self.params, x)
        raise ValueError(f"unknown segment {seg!r}")

    def run_fused(self, steps: Tuple[Tuple, ...], x: jax.Array,
                  pkg_mass: Optional[jax.Array] = None,
                  precision: str = "fp32") -> jax.Array:
        """Express lane: execute ``steps`` — consecutive ``(segment, k)``
        pairs, or ``(segment, k, soft)`` triples for soft TDM steps — as
        ONE jitted trajectory program (one dispatch for the whole remaining
        forward of a bucket-singleton request). ``pkg_mass`` ([1]) seeds
        the package mass when the lane starts after a soft request's first
        TDM. Compiles once per distinct (steps, entry shape, precision);
        the per-trajectory ledger is ``fused_trajectory_count`` and its
        keys bound the extra jit entries beyond the tile bucket set."""
        steps = tuple(
            (tuple(s[0]), None if s[1] is None else int(s[1]))
            + ((True,) if len(s) > 2 and s[2] else ())
            for s in steps)
        if not steps:
            raise ValueError("fused run needs at least one step")
        traj_key = self._ledger_key((steps, tuple(x.shape)), precision)
        self._fused_trajectories.add(traj_key)
        self._compiled.add(self._ledger_key(
            (("fused",) + steps, tuple(x.shape), False, None), precision))
        return self._fused(self.params, self.packed_for(precision),
                           jnp.asarray(x), pkg_mass, steps=steps,
                           prec=precision)

    # -- compile observability ---------------------------------------------
    @property
    def compile_count(self) -> int:
        """Distinct segment tiles dispatched so far (our ledger)."""
        return len(self._compiled)

    def compiled_tiles(self) -> List[Tuple]:
        return sorted(self._compiled, key=repr)

    @property
    def fused_trajectory_count(self) -> int:
        """Distinct fused trajectory programs dispatched (the express-lane
        half of the bucket ∪ trajectory recompile bound)."""
        return len(self._fused_trajectories)

    def jit_compile_count(self) -> int:
        """Total entries across the jit caches (what XLA actually
        compiled), fused trajectory programs included."""
        return sum(fn._cache_size()
                   for fn in (self._embed, self._layers, self._tdm,
                              self._tdm_soft, self._head, self._fused))
