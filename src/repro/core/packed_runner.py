"""Pruned-ViT execution — the deployment path of the paper's model.

After simultaneous pruning, ``PG.apply_pruning`` hardens the masks into a
masked parameter tree: every block-pruned attention weight is its
block-masked dense matrix, every column/row-pruned MLP weight its masked
dense matrix. The segments below run the ViT from that tree, all
projections as XLA matmuls: at b = 16 and r_b = 0.5 no 128x128 MXU tile of
an attention weight is empty, so a TPU-shaped SBMM can at best match the
dense matmul (the MLPs are the paper's DBMM — a dense matmul over the
shrunken width — which XLA already emits). ``pack_model`` still converts
the masked attention weights into the block-compressed format of the
paper's SBMM kernel library; no serving path runs it.

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
from repro.core import token_pruning as TP
from repro.models import attention as A
from repro.models import layers as L
from repro.models import model as M
from repro.models import pruning_glue as PG


def pack_model(cfg: ModelConfig, params: Dict, scores: Dict,
               lanes: int = 8) -> Dict[str, packing.PackedWeight]:
    """Block-compress every masked attention weight for the SBMM kernel
    library. Returns {path: PackedWeight}; paths match
    pruning_glue.hard_masks keys."""
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
    """Per-stage segmentation of the pruned ViT forward: one segment per
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
def _encoder_attn(cfg: ModelConfig, params: Dict, x: jax.Array, i: int, *,
                  collect_scores: bool = False,
                  n_valid: Optional[jax.Array] = None
                  ) -> Tuple[jax.Array, Optional[jax.Array]]:
    """Attention sublayer + residual of encoder layer ``i``, its
    projections XLA matmuls by the block-masked weights in ``params``.
    ``n_valid`` masks token padding out of the attention and of the TDM
    scoring; padded rows' scores are exactly 0."""
    H, KV, Dh = cfg.num_heads, cfg.num_kv_heads, cfg.head_dim
    lp = params["layers"][i]
    a = lp["attn"]
    h = L.layer_norm(x, lp["ln1_s"], lp["ln1_b"], cfg.norm_eps)
    Bc, Nc, _ = h.shape
    q = (L.linear(h, a["wq"]) + a.get("bq", 0.0)).reshape(Bc, Nc, H, Dh)
    k = (L.linear(h, a["wk"]) + a.get("bk", 0.0)).reshape(Bc, Nc, KV, Dh)
    v = (L.linear(h, a["wv"]) + a.get("bv", 0.0)).reshape(Bc, Nc, KV, Dh)
    o = A.flash_attention_jnp(q, k, v, causal=False, kv_len=n_valid)
    o = o.astype(x.dtype)
    scores = None
    if collect_scores:
        probs = A.attention_probs_row(q[:, 0], k, kv_len=n_valid)
        scores = probs.mean(axis=1).astype(x.dtype)
    o = o.reshape(Bc, Nc, H * Dh)
    attn_out = L.linear(o, a["wo"]) + a.get("bo", 0.0)
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


def vit_layers(cfg: ModelConfig, params: Dict, x: jax.Array,
               lo: int, hi: int,
               n_valid: Optional[jax.Array] = None) -> jax.Array:
    """Encoder layers [lo, hi) at constant token count."""
    for i in range(lo, hi):
        x, _ = _encoder_attn(cfg, params, x, i, n_valid=n_valid)
        x = _encoder_mlp(cfg, params, x, i)
    return x


def vit_tdm_layer(cfg: ModelConfig, params: Dict, x: jax.Array, layer: int,
                  r_t: Optional[float] = None, k: Optional[int] = None,
                  n_valid: Optional[jax.Array] = None) -> jax.Array:
    """Encoder layer ``layer`` with the TDM between its attention and MLP
    sublayers: [B, N, D] -> [B, k + 2, D] (CLS + k kept + fused). ``k``
    must be passed when rows are token-padded (see ``TP.tdm``); otherwise
    it derives from N and ``r_t`` exactly as the monolithic forward did."""
    if r_t is None:
        r_t = cfg.pruning.r_t
    x, scores = _encoder_attn(cfg, params, x, layer, collect_scores=True,
                              n_valid=n_valid)
    x, _ = TP.tdm(x, scores, r_t, has_cls=True, k=k)
    return _encoder_mlp(cfg, params, x, layer)


def vit_tdm_soft_layer(cfg: ModelConfig, params: Dict, x: jax.Array,
                       layer: int, k: int,
                       pkg_mass: Optional[jax.Array] = None,
                       n_valid: Optional[jax.Array] = None
                       ) -> Tuple[jax.Array, jax.Array]:
    """Soft-pruning variant of :func:`vit_tdm_layer`: the dropped tokens
    fold into a persistent package token (``TP.tdm_soft``). Same output
    token count as the hard TDM, plus the accumulated package mass ([B])
    the NEXT soft TDM needs (``pkg_mass=None`` marks the first TDM, where
    no package row exists yet). With ``pkg_mass``, each row's package sits
    at its own valid-token boundary (body index ``n_valid - 2``) so
    token-padded tiles pin the right row."""
    x, scores = _encoder_attn(cfg, params, x, layer, collect_scores=True,
                              n_valid=n_valid)
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


def run_fused_steps(cfg: ModelConfig, params: Dict, x: jax.Array,
                    steps: Tuple[Tuple, ...],
                    pkg_mass: Optional[jax.Array] = None) -> jax.Array:
    """Compose consecutive segments into ONE program: ``steps`` is a static
    tuple of ``(segment, k)`` pairs — or ``(segment, k, soft)`` triples for
    soft-pruning TDM steps (``k`` only for TDM segments). This is the
    express-lane body the planner compiles per trajectory for requests
    that are singletons in every bucket — unbatched and unpadded, so no
    ``n_valid`` is ever needed. All shapes are static given the entry shape
    and the ``k`` sequence. ``pkg_mass`` seeds the package mass for a lane
    entered AFTER a soft request's first TDM already ran tiled (``None``
    otherwise); the mass threads through in-program across soft steps."""
    for step in steps:
        seg, k = step[0], step[1]
        soft = bool(step[2]) if len(step) > 2 else False
        kind = seg[0]
        if kind == "embed":
            x = vit_embed(cfg, params, x)
        elif kind == "layers":
            x = vit_layers(cfg, params, x, seg[1], seg[2])
        elif kind == "tdm":
            if k is None:
                raise ValueError("fused tdm steps need an explicit static k")
            if soft:
                x, pkg_mass = vit_tdm_soft_layer(cfg, params, x, seg[1], k=k,
                                                 pkg_mass=pkg_mass)
            else:
                x = vit_tdm_layer(cfg, params, x, seg[1], k=k)
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
# cached executor holds a strong reference to its params tree, which pins
# its id for exactly as long as the entry lives. Bounded FIFO so sweeps
# over many pruned models don't accumulate jit caches.
_SEGMENT_MEMO: "dict[Tuple, PackedVitSegments]" = {}
_SEGMENT_MEMO_CAP = 8


def _cached_segments(cfg, params, use_tdm) -> "PackedVitSegments":
    # r_t / tdm_layers only matter through the segment plan (the executor
    # always receives k explicitly), so cfgs differing only in keep rate —
    # the per-request-r_t reference loop — share one executor
    import dataclasses as _dc
    plan = vit_segments(cfg, use_tdm)
    cfg_norm = cfg.replace(pruning=_dc.replace(cfg.pruning, r_t=1.0,
                                               tdm_layers=()))
    key = (plan, cfg_norm, id(params))
    runner = _SEGMENT_MEMO.get(key)
    if runner is None:
        runner = PackedVitSegments(cfg, params, use_tdm=use_tdm)
        if len(_SEGMENT_MEMO) >= _SEGMENT_MEMO_CAP:
            _SEGMENT_MEMO.pop(next(iter(_SEGMENT_MEMO)))
        _SEGMENT_MEMO[key] = runner
    return runner


def forward_vit_packed(cfg: ModelConfig, params: Dict, patches: jax.Array,
                       use_tdm: bool | None = None,
                       segments: "Optional[PackedVitSegments]" = None,
                       schedule: Optional[Sequence[float]] = None,
                       soft: bool = False) -> M.Output:
    """ViT forward through the jitted segment executor, one request at a
    time: the engine's segments walked unbatched and unpadded.

    ``params`` is the MASKED tree (``PG.apply_pruning``): the attention
    projections run as XLA matmuls by the block-masked weights and the
    MLPs masked-dense (the paper's DBMM path).

    The serving engine's logits match this walk to rounding (the tests'
    ``assert_logits_close`` tolerance): a batched, merged or fused program
    is a different XLA program and may reduce in another order. Pass
    ``segments`` to reuse an already-compiled executor (e.g. an engine's);
    otherwise one is memoized per (cfg, params, use_tdm) so repeated calls
    — batch evaluation loops, parity tests — compile once.

    ``schedule`` is a per-TDM-segment keep schedule (``None`` broadcasts
    ``cfg.pruning.r_t``) and ``soft`` selects the package-token soft TDM —
    together the offline walk of the serving engine's quality-elastic and
    soft-pruning paths."""
    runner = segments if segments is not None else _cached_segments(
        cfg, params, use_tdm)
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
                                         pkg_mass=pkg_mass)
            else:
                k = tdm_keep_count(n, r)
                x = runner.run(seg, x, k=k)
            n = k + 2
            ordinal += 1
        elif seg[0] == "head":
            return M.Output(runner.run(seg, x))
        else:
            x = runner.run(seg, x)
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
    """Owns the jitted per-segment step functions for one (cfg, masked
    params) pair, behind a compile ledger.

    Shape discipline mirrors ``serving.runner.ModelRunner``: each distinct
    (segment, batch tile, token tile, masked?) combination compiles once;
    ``compile_count`` is our ledger and ``jit_compile_count()`` asks the
    jit caches themselves. The ragged batcher bounds the distinct
    combinations to its bucket set."""

    def __init__(self, cfg: ModelConfig, params: Dict,
                 use_tdm: Optional[bool] = None,
                 donate_activations: bool = False):
        self.cfg = cfg
        self.params = params
        self.plan = vit_segments(cfg, use_tdm)
        self.donate_activations = donate_activations
        # Only the "layers" segment preserves the activation shape
        # [B, n, D] input->output, so only its input tile is donatable
        # (embed/tdm/head change shapes — donating them would just warn
        # and allocate anyway). Donation requires callers never to re-read
        # a dispatched tile: the serving engine's tiles are fresh padded
        # batches, or an output passed through whole whose rows all move
        # on, and forward_vit_packed rebinds x each segment, so both
        # satisfy it; keep the default off for ad-hoc callers
        # that reuse inputs across calls (e.g. timing probes).
        don = dict(donate_argnums=(1,)) if donate_activations else {}
        # each segment program under its own name, so the device trace
        # names its module jit_<name> (jit_vit_layers, jit_vit_lane, ...)
        self._embed = _program(
            "vit_embed",
            lambda params, patches: vit_embed(cfg, params, patches))
        self._layers = _program(
            "vit_layers",
            lambda params, x, n_valid, lo, hi: vit_layers(
                cfg, params, x, lo, hi, n_valid=n_valid),
            static_argnames=("lo", "hi"), **don)
        self._tdm = _program(
            "vit_tdm",
            lambda params, x, n_valid, layer, k: vit_tdm_layer(
                cfg, params, x, layer, k=k, n_valid=n_valid),
            static_argnames=("layer", "k"))
        self._tdm_soft = _program(
            "vit_tdm_soft",
            lambda params, x, n_valid, pkg_mass, layer, k:
            vit_tdm_soft_layer(cfg, params, x, layer, k=k,
                               pkg_mass=pkg_mass, n_valid=n_valid),
            static_argnames=("layer", "k"))
        self._head = _program(
            "vit_head", lambda params, x: vit_head(cfg, params, x))
        self._fused = _program(
            "vit_lane",
            lambda params, x, pkg_mass, steps: run_fused_steps(
                cfg, params, x, steps, pkg_mass=pkg_mass),
            static_argnames=("steps",))
        self._compiled: set = set()
        self._fused_trajectories: set = set()

    def run(self, seg: Segment, x: jax.Array,
            n_valid: Optional[np.ndarray] = None,
            k: Optional[int] = None, soft: bool = False,
            pkg_mass: Optional[jax.Array] = None):
        """Execute one segment on a dense tile ``x``. ``n_valid`` ([B]) is
        required whenever rows are token-padded; ``k`` is required for
        ``tdm`` segments (uniform across the tile by batcher construction).
        ``soft`` selects the package-token TDM variant: the call takes the
        tile's accumulated package masses (``None`` before the first TDM)
        and returns ``(y, new_mass)`` instead of ``y``."""
        kind = seg[0]
        nv = None if n_valid is None else jnp.asarray(n_valid, jnp.int32)
        key = ((seg, tuple(x.shape), nv is not None, k, "soft") if soft
               else (seg, tuple(x.shape), nv is not None, k))
        if kind == "embed":
            self._compiled.add(key)
            return self._embed(self.params, x)
        if kind == "layers":
            self._compiled.add(key)
            return self._layers(self.params, x, nv, lo=seg[1], hi=seg[2])
        if kind == "tdm":
            if k is None:
                raise ValueError("tdm segments need an explicit static k "
                                 "(per-request keep count)")
            self._compiled.add(key)
            if soft:
                return self._tdm_soft(self.params, x, nv, pkg_mass,
                                      layer=seg[1], k=k)
            return self._tdm(self.params, x, nv, layer=seg[1], k=k)
        if kind == "head":
            self._compiled.add(key)
            return self._head(self.params, x)
        raise ValueError(f"unknown segment {seg!r}")

    def run_fused(self, steps: Tuple[Tuple, ...], x: jax.Array,
                  pkg_mass: Optional[jax.Array] = None) -> jax.Array:
        """Express lane: execute ``steps`` — consecutive ``(segment, k)``
        pairs, or ``(segment, k, soft)`` triples for soft TDM steps — as
        ONE jitted trajectory program (one dispatch for the whole remaining
        forward of a bucket-singleton request). ``pkg_mass`` ([1]) seeds
        the package mass when the lane starts after a soft request's first
        TDM. Compiles once per distinct (steps, entry shape); the
        per-trajectory ledger is ``fused_trajectory_count`` and its keys
        bound the extra jit entries beyond the tile bucket set."""
        steps = tuple(
            (tuple(s[0]), None if s[1] is None else int(s[1]))
            + ((True,) if len(s) > 2 and s[2] else ())
            for s in steps)
        if not steps:
            raise ValueError("fused run needs at least one step")
        self._fused_trajectories.add((steps, tuple(x.shape)))
        self._compiled.add((("fused",) + steps, tuple(x.shape), False, None))
        return self._fused(self.params, jnp.asarray(x), pkg_mass,
                           steps=steps)
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
