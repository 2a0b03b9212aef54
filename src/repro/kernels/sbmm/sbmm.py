"""SBMM — Sparse Block-wise Matrix Multiplication Pallas kernel.

TPU-native realization of the paper's MPCA/SBMM (Algorithm 2): a dense
activation matrix multiplies a block-compressed weight. The weight is stored
column-major as gathered blocks with a per-column header of surviving
row-block indices (core/packing.py — the direct analog of the FPGA's CB
header format).

Mapping onto TPU:
  * the kernel works on the TRANSPOSED problem, yᵀ = Wᵀ·xᵀ: activations
    enter as xᵀ [K, M] so the reduction axis K sits on sublanes and the
    tokens on the 128-wide lanes. A kept block's row-block index then
    selects ``b`` sublane rows of xᵀ (a b-aligned sublane slice the TPU
    tiling admits), where the untransposed layout would need a b-lane
    slice at a dynamic offset, which Mosaic rejects for b < 128.
  * grid = (M/TM, n_block_cols) — token strips play the role of the p_t PE
    rows; block-columns play the p_c lanes (the offline column balancing
    in packing.py equalizes work across grid columns).
  * the activation strip xᵀ [K, TM] is VMEM-resident (the GFB analog); the
    per-column gathered blocks [max_kept, b, b] stream through VMEM (the CB
    analog); the header rides in scalar memory (prefetched — SMEM analog).
  * each output tile yᵀ [b, TM] is lane-dense (TM a multiple of 128 on
    TPU); accumulation is fp32; padding entries (idx < 0) contribute
    exactly zero, which is how load imbalance manifests as *skipped work*
    rather than wrong results.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.backend import resolve_interpret

LANES = 128  # TPU lane width: the compiled kernel's token tile must be a
#              multiple of it (the output tile's last dimension)


FP16_UNSUPPORTED = ("float16 SBMM blocks do not compile on the TPU: Mosaic "
                    "cannot load float16 vectors (the fp16 precision tier); "
                    "serve fp32 or int8 there")


def check_compiled(tm: int, block_dtype, interpret: bool) -> None:
    """Refuse, with a clear error, what Mosaic would reject. Compiled
    kernels put ``tm`` tokens on the lanes of every tile, so the TPU needs
    a multiple of 128, and it cannot load float16 blocks. The interpreter
    takes any tile and dtype."""
    if interpret:
        return
    if tm % LANES:
        raise ValueError(
            f"compiled SBMM needs a token tile that is a multiple of "
            f"{LANES}, got tm={tm}")
    if jnp.dtype(block_dtype) == jnp.float16:
        raise ValueError(FP16_UNSUPPORTED)


def _sbmm_kernel(header_ref, xt_ref, blocks_ref, yt_ref, *, block_size: int,
                 max_kept: int, tm: int):
    """One (token-strip, block-column) grid cell.

    header_ref : [n_cols, max_kept] int32 (scalar prefetch)
    xt_ref     : [K, TM]  transposed activation strip (VMEM)
    blocks_ref : [1, max_kept, b, b] this column's blocks, each transposed
                 to [out, in]
    yt_ref     : [b, TM]  transposed output tile
    """
    j = pl.program_id(1)
    b = block_size

    def body(s, acc):
        idx = header_ref[j, s]
        start = pl.multiple_of(jnp.maximum(idx, 0) * b, b)
        x_blk = xt_ref[pl.ds(start, b), :]                 # [b, TM] gather
        w_blk = blocks_ref[0, s]                           # [b, b]
        contrib = jnp.dot(w_blk, x_blk, preferred_element_type=jnp.float32)
        return acc + jnp.where(idx >= 0, contrib, 0.0)

    acc = jax.lax.fori_loop(
        0, max_kept, body, jnp.zeros((b, tm), jnp.float32))
    yt_ref[...] = acc.astype(yt_ref.dtype)


def sbmm_pallas(x: jax.Array, blocks: jax.Array, header: jax.Array,
                *, tm: int = 128,
                interpret: "bool | None" = None) -> jax.Array:
    """x: [M, K] (K padded to n_row_blocks·b); blocks: [C, S, b, b];
    header: [C, S] int32 (-1 padding). Returns y: [M, C·b].

    ``M`` must be a multiple of ``tm`` (ops.py pads), and on a TPU ``tm``
    a multiple of 128. ``interpret=None`` auto-detects the backend
    (kernels.backend)."""
    interpret = resolve_interpret(interpret)
    M, K = x.shape
    C, S, b, _ = blocks.shape
    assert M % tm == 0, (M, tm)
    check_compiled(tm, blocks.dtype, interpret)

    grid = (M // tm, C)
    kernel = functools.partial(_sbmm_kernel, block_size=b, max_kept=S, tm=tm)
    yt = pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=grid,
            in_specs=[
                pl.BlockSpec((K, tm), lambda i, j, hdr: (0, i)),
                pl.BlockSpec((1, S, b, b), lambda i, j, hdr: (j, 0, 0, 0)),
            ],
            out_specs=pl.BlockSpec((b, tm), lambda i, j, hdr: (j, i)),
        ),
        out_shape=jax.ShapeDtypeStruct((C * b, M), x.dtype),
        interpret=interpret,
    )(header, x.T, jnp.swapaxes(blocks, 2, 3))
    return yt.T
