"""Benchmark: Fig. 9/10 analog measured on THIS host — wall-clock latency of
the actual JAX ViT forward, dense vs simultaneous-pruned (reduced config so
it runs on CPU), plus the SBMM kernel vs dense matmul at the packed sizes.

The FPGA numbers are reproduced analytically in perf_model_bench; this file
shows the pruning speedup materializes in the real implementation too."""
from __future__ import annotations

import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs import DEIT_SMALL, PruningConfig
from repro.core import block_pruning as bp
from repro.core import packing
from repro.kernels.sbmm import sbmm
from repro.models import model as M


def _time(fn, *args, iters=5):
    fn(*args)[0].block_until_ready() if isinstance(fn(*args), tuple) else None
    out = fn(*args)
    jax.block_until_ready(out)
    t0 = time.perf_counter()
    for _ in range(iters):
        out = fn(*args)
    jax.block_until_ready(out)
    return (time.perf_counter() - t0) / iters * 1e6  # us


def run() -> list:
    rows = []
    key = jax.random.PRNGKey(0)

    # real ViT forward: dense vs token-pruned (same weights)
    cfg_d = DEIT_SMALL.replace(
        num_layers=4, pruning=PruningConfig())
    cfg_p = cfg_d.replace(pruning=PruningConfig(
        block_size=16, r_b=0.5, r_t=0.5, tdm_layers=(1, 2)))
    params = M.init_params(cfg_d, key)
    n = (cfg_d.image_size // cfg_d.patch_size) ** 2
    patches = jax.random.normal(key, (1, n, cfg_d.patch_size ** 2 * 3))

    f_dense = jax.jit(lambda p, x: M.forward_vit(cfg_d, p, x).logits)
    f_tdm = jax.jit(lambda p, x: M.forward_vit(cfg_p, p, x).logits)
    t_dense = _time(f_dense, params, patches)
    t_tdm = _time(f_tdm, params, patches)
    rows.append(("fig9.jax_vit4L_dense_us", round(t_dense, 1), "CPU wall"))
    rows.append(("fig9.jax_vit4L_tdm_rt0.5_us", round(t_tdm, 1),
                 f"speedup={t_dense/t_tdm:.2f}x"))

    # SBMM kernel vs dense matmul at a pruned-weight operating point
    K, N, b, rb = 384, 1536, 16, 0.5
    w = np.asarray(jax.random.normal(key, (K, N)), np.float32)
    sc = np.asarray(jax.random.normal(key, bp.score_shape((K, N), b)))
    keep = max(1, int(np.ceil(sc.size * rb)))
    mask = np.asarray(bp._hard_topk(jnp.asarray(sc), keep))
    pk = packing.pack_weight(w, mask, b)
    x = jax.random.normal(key, (128, K))
    dense_w = pk.to_dense()
    t_dense_mm = _time(jax.jit(lambda a, b: a @ b), x, dense_w)
    rows.append(("sbmm.dense_matmul_us", round(t_dense_mm, 1),
                 f"{128}x{K}x{N}"))
    rows.append(("sbmm.packed_blocks", int(np.asarray(pk.counts).sum()),
                 f"of {sc.size} ({rb:.0%} kept)"))
    # NOTE: the Pallas kernel runs in interpret mode on CPU (orders of
    # magnitude slower than compiled TPU execution); we report its VALIDATED
    # numerical match instead of a misleading CPU wall time.
    y1 = np.asarray(sbmm(x, pk))
    y2 = np.asarray(x @ dense_w)
    rows.append(("sbmm.kernel_max_abs_err", float(np.abs(y1 - y2).max()),
                 "interpret-mode validation"))
    return rows
