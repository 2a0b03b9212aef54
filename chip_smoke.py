#!/usr/bin/env python3
"""Serve full-width DeiT-Small through VisionEngine on one TPU and check it.

    python3 chip_smoke.py

Builds DeiT-Small at its published widths (12 layers, d_model 384, 6 heads,
224 px, TDM at layers 2/6/9, r_b 0.5) from a seed, prunes it, serves a
mixed stream of 8 requests (49/169/196 patches, keep rates
0.5/0.7/default) through ``VisionEngine`` with kernels compiled, checks
that the encoder segment runs its projections as XLA matmuls with no
Pallas kernel, and compares every request's
logits with the masked-dense reference run at ``highest`` matmul
precision. A request that misses the tolerance passes only if a TDM top-k
near-tie made the served path keep other tokens and the logits agree with
a reference that keeps those tokens. Runs in one process and starts no
other.

Exits non-zero, printing no result line, when JAX finds no TPU or any check
fails. On success the last line of standard output is the JSON result.
"""
from __future__ import annotations

import dataclasses
import json
import os
import sys
import time

SEED = 0
N_REQUESTS = 8
MAX_BATCH = 4
ARRIVAL_SPREAD = 4
# The served path's XLA matmuls run at the TPU's default precision, which
# rounds f32 operands to bf16 (relative 2^-9 per operand); the reference
# runs at "highest". On a TPU v5e, requests whose TDMs kept the
# reference's tokens differed by at most 0.0196 (logits std 0.78); one
# swapped token moves them by ~0.1, and a wrong block, header or
# permutation by O(1).
ATOL = 5e-2
RTOL = 5e-2
# Where logits miss that tolerance, a TDM that kept a different token set
# explains it only as a near-tie: the served TDM scores must agree with the
# reference's within SCORE_ATOL, and each token swap must need a reference
# score gap of at most twice that layer's score difference. The token sets
# are read from an unbatched walk through the served segments; its logits'
# distance from the engine's is reported, not judged: the verdict needs only
# that some near-tie selection explains the served logits.
SCORE_ATOL = 1e-4


def _requests(cfg):
    from repro.launch.serve_vision import make_requests
    return make_requests(cfg, N_REQUESTS, ARRIVAL_SPREAD, SEED)


def _with_keep_rate(cfg, r_t):
    return cfg.replace(pruning=dataclasses.replace(cfg.pruning, r_t=r_t))


def _served_walk(engine, patches, r_t):
    """Walk one request through the engine's own jitted segments, unbatched
    (the walk ``forward_vit_packed`` makes), and read at each TDM the scores
    and the tokens that segment keeps. Returns the logits and
    ``{layer: (kept token indices, scores)}``."""
    import jax
    import numpy as np

    from repro.core import packed_runner as PR

    segs = engine.segments
    scores_at = jax.jit(lambda params, x, layer: PR._encoder_attn(
        engine.cfg, params, x, layer, collect_scores=True)[1],
        static_argnames="layer")
    x, n, kept = patches, patches.shape[1] + 1, {}
    for seg in segs.plan:
        if seg[0] == "tdm":
            k = PR.tdm_keep_count(n, r_t)
            s = scores_at(segs.params, x, layer=seg[1])
            kept[seg[1]] = (np.asarray(jax.lax.top_k(s[0, 1:], k)[1]),
                            np.asarray(s[0]))
            x, n = segs.run(seg, x, k=k), k + 2
        elif seg[0] == "head":
            return np.asarray(segs.run(seg, x)[0]), kept
        else:
            x = segs.run(seg, x)
    raise AssertionError("the segment plan ends with the head")


def _reference_walk(cfg, masked, patches, r_t, served_kept):
    """Masked-dense forward at the caller's matmul precision that keeps, at
    each TDM, the tokens the served walk kept. Returns the logits and, per
    TDM where the reference's own top-k differs, ``(layer, tokens swapped,
    reference score gap the swap needs, max |served - reference| score)``.
    """
    import numpy as np

    from repro.core import packed_runner as PR
    from repro.core import token_pruning as TP
    from repro.models import attention as A
    from repro.models import layers as L

    x = PR.vit_embed(cfg, masked, patches)
    flips = []
    for i, lp in enumerate(masked["layers"]):
        tdm = i in cfg.pruning.tdm_layers
        h = L.layer_norm(x, lp["ln1_s"], lp["ln1_b"], cfg.norm_eps)
        h, _, s = A.attention_block(h, lp["attn"], cfg, causal=False,
                                    use_rope=False, collect_scores=tdm,
                                    score_row=0)
        x = x + h
        if tdm:
            k = PR.tdm_keep_count(x.shape[1], r_t)
            srv_idx, srv_s = served_kept[i]
            body = np.asarray(s[0, 1:])
            ref_idx = np.argsort(-body, kind="stable")[:k]
            out = np.setdiff1d(ref_idx, srv_idx)
            if out.size:
                into = np.setdiff1d(srv_idx, ref_idx)
                flips.append((i, int(out.size),
                              float(body[out].max() - body[into].min()),
                              float(np.abs(srv_s - np.asarray(s[0])).max())))
            # the served tokens win top-k, in the served order, so token
            # positions after this TDM match the served walk's
            s = s.at[0, 1 + srv_idx].set(2.0 + np.arange(k, 0, -1))
            x, _ = TP.tdm(x, s, r_t, has_cls=True, k=k)
        h = L.layer_norm(x, lp["ln2_s"], lp["ln2_b"], cfg.norm_eps)
        x = x + L.gelu_mlp(h, lp["mlp"])
    return np.asarray(PR.vit_head(cfg, masked, x)[0]), flips


def _explain_by_flips(engine, masked, r, r_t, served):
    """Tell a TDM top-k near-tie flip apart from a numeric error: compare
    the tokens the served walk keeps at each TDM with the reference's, then
    compare the served logits with a reference that keeps the served
    tokens. Returns ``(verdict, detail)``."""
    import jax
    import numpy as np

    walk, kept = _served_walk(engine, r.patches[None], r_t)
    with jax.default_matmul_precision("highest"):
        forced, flips = _reference_walk(_with_keep_rate(engine.cfg, r_t),
                                        masked, r.patches[None], r_t, kept)
    walk_err = float(np.abs(walk - served).max())
    detail = (f"max|walk-served|={walk_err:.6g} "
              f"max|served-ref(served tokens)|="
              f"{np.abs(served - forced).max():.6g} flips(layer, swapped, "
              f"gap, max score diff)={flips}")
    near_ties = all(diff <= SCORE_ATOL and gap <= 2 * diff
                    for _, _, gap, diff in flips)
    if flips and near_ties and np.allclose(served, forced, atol=ATOL,
                                           rtol=RTOL):
        return "ok (top-k near-tie flip)", detail
    return "FAIL", detail


def _no_kernel_in_segment(engine) -> bool:
    """Lower and compile one served encoder segment at B=MAX_BATCH, N=197
    and look for a compiled Pallas kernel: its projections are XLA
    matmuls, so there must be none."""
    import jax.numpy as jnp

    segs = engine.segments
    seg = next(s for s in segs.plan if s[0] == "layers")
    n = (engine.cfg.image_size // engine.cfg.patch_size) ** 2 + 1
    x = jnp.zeros((MAX_BATCH, n, engine.cfg.d_model), jnp.float32)
    nv = jnp.full((MAX_BATCH,), n, jnp.int32)
    lowered = segs._layers.lower(segs.params, x, nv, lo=seg[1], hi=seg[2])
    in_lowered = "tpu_custom_call" in lowered.as_text()
    in_compiled = "tpu_custom_call" in lowered.compile().as_text()
    print(f"segment {seg} at B={MAX_BATCH} N={n}: tpu_custom_call in "
          f"lowered={in_lowered} compiled={in_compiled} (expected False)")
    return not (in_lowered or in_compiled)


def smoke(cfg) -> bool:
    """Serve ``cfg`` and check the kernel mode and every request's logits.
    Returns whether every check passed."""
    import jax
    import numpy as np

    from repro.core import packed_runner as PR
    from repro.kernels import backend
    from repro.models import model as M
    from repro.models import pruning_glue as PG
    from repro.serving import VisionEngine, VisionEngineConfig

    ok = True
    interpret = backend.default_interpret()
    print(f"kernels interpreted: {interpret}")
    if interpret:
        print("FAIL: Pallas kernels would run in the interpreter")
        return False

    key = jax.random.PRNGKey(SEED)
    params = M.init_params(cfg, key)
    scores = PG.init_scores(cfg, params, jax.random.fold_in(key, 7))
    vc = VisionEngineConfig(max_batch=MAX_BATCH, planner="full",
                            pipeline_depth=1)
    engine = VisionEngine.from_pruned(cfg, params, scores, vc=vc)

    t0 = time.perf_counter()
    first = engine.serve(_requests(cfg))
    t1 = time.perf_counter()
    out = engine.serve(_requests(cfg))
    t2 = time.perf_counter()
    st = engine.stats()
    print(f"wall clock: first serve (compiles every shape) {t1 - t0:.3f} s, "
          f"second serve (warm) {t2 - t1:.3f} s, compile (first minus "
          f"second) {(t1 - t0) - (t2 - t1):.3f} s")
    print(f"served {len(out)} requests per pass, {st['steps']} engine "
          f"steps, {st['jit_compile_count']} jit compiles")
    if len(out) != N_REQUESTS or any(
            not np.array_equal(first[u], out[u]) for u in out):
        print("FAIL: the warm pass did not reproduce the first pass")
        ok = False

    if not _no_kernel_in_segment(engine):
        print("FAIL: a served segment calls a Pallas kernel")
        ok = False

    masked = engine.segments.params
    print(f"tolerance: |served - reference| <= {ATOL} + {RTOL} * "
          f"|reference| (reference at highest matmul precision)")
    for r in _requests(cfg):
        r_t = cfg.pruning.r_t if r.r_t is None else r.r_t
        served = np.asarray(out[r.uid])
        with jax.default_matmul_precision("highest"):
            ref = np.asarray(PR.masked_dense_reference(
                _with_keep_rate(cfg, r_t), params, scores,
                r.patches[None]).logits[0])
        line = (f"uid {r.uid}: patches={r.n_patches} r_t={r_t} "
                f"max|served-ref|={np.abs(served - ref).max():.6g}")
        if not np.all(np.isfinite(served)) or served.shape != ref.shape:
            verdict = "FAIL (shape or non-finite)"
        elif np.allclose(served, ref, atol=ATOL, rtol=RTOL):
            verdict = "ok"
        else:
            verdict, detail = _explain_by_flips(engine, masked, r, r_t,
                                                served)
            line += " " + detail
        ok &= verdict.startswith("ok")
        print(f"{line} -> {verdict}")
    print(f"wall clock: reference checks {time.perf_counter() - t2:.3f} s")
    return ok


def main() -> int:
    import jax

    dev = jax.devices()[0]
    print(f"device: platform={dev.platform} kind={dev.device_kind} "
          f"count={jax.device_count()}", flush=True)
    if dev.platform != "tpu":
        print("chip_smoke: JAX finds no TPU; nothing was run",
              file=sys.stderr)
        return 1
    sys.path.insert(0, os.path.join(os.path.dirname(
        os.path.abspath(__file__)), "src"))
    from repro.configs import get_config
    from repro.launch.compile_cache import enable_compile_cache

    print(f"compile cache: {enable_compile_cache()}")
    cfg = get_config("deit-small")
    print(f"config: {cfg.name} layers={cfg.num_layers} d={cfg.d_model} "
          f"heads={cfg.num_heads} image={cfg.image_size} "
          f"tdm_layers={cfg.pruning.tdm_layers} r_b={cfg.pruning.r_b}")
    if not smoke(cfg):
        return 1
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": jax.device_count()}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
