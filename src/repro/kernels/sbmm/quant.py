"""Dequant-in-kernel SBMM — int8 gathered blocks × float activations.

Same transposed layout and grid as the fp32 kernel (``sbmm.py``): one
(token-strip, block-column) cell per grid step, header-driven sublane
gather of xᵀ strips, fp32 accumulation into a lane-dense yᵀ tile. The
difference is the weight stream: blocks arrive as int8 and are dequantized
in registers right before the MXU — ``w = q.astype(f32) * scale``. The
scales stream through VMEM next to their blocks as one column per block,
``[C, S, b, 1]`` (the block's output channels sit on sublanes in the
transposed layout): per-output-channel scales ([C, S, b]) fill it as they
are, per-block scales ([C, S]) are broadcast over the b channels first —
the same product per element, so both granularities share one kernel.

``sbmm_quant_ref`` is the jnp dequant oracle, written to mirror the
kernel's per-column accumulation order exactly so interpret-mode runs
bit-match it (tests assert ``array_equal``, not atol).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.backend import resolve_interpret
from repro.kernels.sbmm.sbmm import check_compiled


def _sbmm_quant_kernel(header_ref, xt_ref, blocks_ref, scales_ref, yt_ref, *,
                       block_size: int, max_kept: int, tm: int):
    """One (token-strip, block-column) grid cell with in-register dequant.

    header_ref : [n_cols, max_kept] int32 (scalar prefetch)
    xt_ref     : [K, TM]  transposed activation strip (VMEM)
    blocks_ref : [1, max_kept, b, b] int8 blocks of this column, each
                 transposed to [out, in]
    scales_ref : [1, max_kept, b, 1] f32 per-output-channel scales
    yt_ref     : [b, TM]  transposed output tile
    """
    j = pl.program_id(1)
    b = block_size

    def body(s, acc):
        idx = header_ref[j, s]
        start = pl.multiple_of(jnp.maximum(idx, 0) * b, b)
        x_blk = xt_ref[pl.ds(start, b), :]                 # [b, TM] gather
        w_blk = blocks_ref[0, s].astype(jnp.float32) * scales_ref[0, s]
        contrib = jnp.dot(w_blk, x_blk.astype(jnp.float32),
                          preferred_element_type=jnp.float32)
        return acc + jnp.where(idx >= 0, contrib, 0.0)

    acc = jax.lax.fori_loop(
        0, max_kept, body, jnp.zeros((b, tm), jnp.float32))
    yt_ref[...] = acc.astype(yt_ref.dtype)


def _channel_scales(scales: jax.Array, b: int) -> jax.Array:
    """[C, S] (per block) or [C, S, b] (per channel) -> [C, S, b, 1]."""
    if scales.ndim == 2:
        scales = jnp.broadcast_to(scales[:, :, None], scales.shape + (b,))
    return scales.astype(jnp.float32)[..., None]


def sbmm_quant_pallas(x: jax.Array, blocks: jax.Array, header: jax.Array,
                      scales: jax.Array, *, tm: int = 128,
                      interpret: "bool | None" = None) -> jax.Array:
    """x: [M, K]; blocks: [C, S, b, b] int8; header: [C, S] int32;
    scales: [C, S] or [C, S, b] f32. Returns y: [M, C·b] in x.dtype.

    ``M`` must be a multiple of ``tm`` (ops.py pads), and on a TPU ``tm``
    a multiple of 128. The header goes through scalar prefetch; the scales
    stream with their column's blocks."""
    interpret = resolve_interpret(interpret)
    M, K = x.shape
    C, S, b, _ = blocks.shape
    assert M % tm == 0, (M, tm)
    check_compiled(tm, blocks.dtype, interpret)

    grid = (M // tm, C)
    kernel = functools.partial(_sbmm_quant_kernel, block_size=b, max_kept=S,
                               tm=tm)
    yt = pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=grid,
            in_specs=[
                pl.BlockSpec((K, tm), lambda i, j, hdr: (0, i)),
                pl.BlockSpec((1, S, b, b), lambda i, j, hdr: (j, 0, 0, 0)),
                pl.BlockSpec((1, S, b, 1), lambda i, j, hdr: (j, 0, 0, 0)),
            ],
            out_specs=pl.BlockSpec((b, tm), lambda i, j, hdr: (j, i)),
        ),
        out_shape=jax.ShapeDtypeStruct((C * b, M), x.dtype),
        interpret=interpret,
    )(header, x.T, jnp.swapaxes(blocks, 2, 3), _channel_scales(scales, b))
    return yt.T


def sbmm_quant_ref(x: jnp.ndarray, blocks: jnp.ndarray, header: jnp.ndarray,
                   scales: jnp.ndarray) -> jnp.ndarray:
    """jnp dequant oracle, accumulation-order-matched to the kernel: per
    block-column, walk kept slots in header order, dequantize the block,
    multiply it (transposed, as the kernel does) with the gathered xᵀ rows
    in f32, and sum in slot order — bit-identical to an interpret-mode
    kernel run."""
    M, K = x.shape
    C, S, b, _ = blocks.shape
    hdr = np.asarray(header)
    scl = np.asarray(scales, np.float32)
    per_channel = scl.ndim == 3
    xt = jnp.asarray(x, jnp.float32).T
    cols = []
    for c in range(C):
        acc = jnp.zeros((b, M), jnp.float32)
        for s in range(S):
            r = int(hdr[c, s])
            if r < 0:
                continue  # adds exactly 0.0 in the kernel — bit-neutral
            w_q = jnp.asarray(blocks[c, s], jnp.float32).T   # [out, in]
            w = w_q * (scl[c, s][:, None] if per_channel else scl[c, s])
            acc = acc + jnp.dot(w, xt[r * b:(r + 1) * b],
                                preferred_element_type=jnp.float32)
        cols.append(acc)
    return jnp.concatenate(cols, axis=0).T.astype(x.dtype)
