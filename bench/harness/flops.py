"""Operation and byte counts: the model's required FLOPs and the SBMM
kernel's least work.

``image_macs`` is the paper's count (Tables I, II, VI), copied from the
program's ``repro.core.complexity.model_macs`` for one image at its own keep
rate: weight pruning contributes alpha = r_b of each projection and MLP
matmul, and work over masked zeros is not counted. One MAC is two FLOPs.

``sbmm_call`` is the least work of one SBMM call y[M, N] = x[M, K] . W,
where W keeps ``kept`` blocks of ``b x b``: ``2 * M * kept * b * b``
operations, and the bytes of reading x and the kept blocks (with their
int32 headers) once and writing y once.
"""
from __future__ import annotations

import math
from typing import Dict, Tuple


def _pruned_encoder_macs(B, N, H, Dp, D, Dmlp, *, alpha, alpha_proj, h_kept,
                         n_kept, alpha_mlp, has_tdm):
    ln1 = B * N * D
    ln2 = B * n_kept * D
    res1 = B * N * D
    res2 = B * n_kept * D
    msa = (B * h_kept * N * Dp * D * (3 * alpha + alpha_proj)
           + 2 * B * h_kept * N ** 2 * Dp)
    tdm = B * N * (H + N + D) if has_tdm else 0
    mlp = 2 * B * n_kept * D * Dmlp * alpha_mlp
    return ln1 + ln2 + res1 + res2 + msa + tdm + mlp


def _dense_encoder_macs(B, N, H, Dp, D, Dmlp):
    ln = B * N * D
    res = B * N * D
    msa = 4 * B * H * N * D * Dp + 2 * B * H * N ** 2 * Dp
    mlp = 2 * B * N * D * Dmlp
    return 2 * ln + 2 * res + msa + mlp


def image_macs(model: dict, pruning: dict, r_t: float,
               n_patches: int | None = None) -> float:
    """MACs of one image of ``n_patches`` (default: the full image) at keep
    rate ``r_t`` under the configuration's block pruning."""
    if n_patches is None:
        n_patches = (model["image_size"] // model["patch_size"]) ** 2
    r_b = pruning["r_b"]
    weight_pruned = r_b < 1.0
    token_pruned = r_t < 1.0 and bool(pruning["tdm_layers"])
    H, D, Dmlp = model["num_heads"], model["d_model"], model["d_ff"]
    Dp = D // H
    alpha = r_b if weight_pruned else 1.0
    n = n_patches + 1
    total = 0.0
    for layer in range(model["num_layers"]):
        has_tdm = token_pruned and layer in pruning["tdm_layers"]
        n_kept = 1 + max(1, math.ceil((n - 1) * r_t)) + 1 if has_tdm else n
        if weight_pruned or token_pruned:
            total += _pruned_encoder_macs(
                1, n, H, Dp, D, Dmlp, alpha=alpha, alpha_proj=alpha,
                h_kept=H, n_kept=n_kept, alpha_mlp=alpha, has_tdm=has_tdm)
        else:
            total += _dense_encoder_macs(1, n, H, Dp, D, Dmlp)
        n = n_kept
    embed = n_patches * (model["patch_size"] ** 2 * 3) * D
    head = model["num_classes"] * D
    return total + embed + head


def image_flops(model: dict, pruning: dict, r_t: float) -> float:
    return 2.0 * image_macs(model, pruning, r_t)


def sbmm_call(m_rows: int, k_in: int, n_out: int, kept: int, b: int,
              elem_bytes: int = 4) -> Tuple[float, float]:
    """(operations, bytes) of one SBMM call at its least."""
    ops = 2.0 * m_rows * kept * b * b
    nbytes = elem_bytes * (m_rows * k_in + kept * b * b + m_rows * n_out)
    nbytes += 4 * kept  # int32 header entries
    return ops, float(nbytes)


def sbmm_segment_work(model: dict, pruning: dict, seg: Tuple, batch: int,
                      n_tokens: int, k: int | None) -> Dict[str, float]:
    """Least operations and bytes of the SBMM calls of one segment run:
    every encoder layer of a ``layers`` or ``tdm`` segment runs four
    projections (q, k, v, o) over ``batch * n_tokens`` rows; the embedding
    and the head run none."""
    D, b, r_b = model["d_model"], pruning["block_size"], pruning["r_b"]
    layers = (seg[2] - seg[1] if seg[0] == "layers"
              else 1 if seg[0] == "tdm" else 0)
    m = -(-D // b)
    kept = max(1, math.ceil(m * m * r_b))
    ops, nbytes = sbmm_call(batch * n_tokens, D, D, kept, b)
    calls = 4 * layers
    return {"ops": calls * ops, "bytes": calls * nbytes, "calls": calls}
