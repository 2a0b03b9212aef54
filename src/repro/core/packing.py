"""Block-compressed weight format + offline load balancing (paper §V-A, §V-D1).

The FPGA stores pruned weights column-major as *blocks*: per block-column, a
header of surviving row-block indices followed by the blocks themselves. The
MPCA's PE columns then gather the matching input row-blocks by header index.

TPU adaptation (DESIGN.md §2): the MXU wants lane-aligned tiles, so we keep
the *logical* pruning granularity ``b×b`` (16/32 — the accuracy-relevant knob)
but pack the surviving blocks into a dense **gathered** tensor

    blocks  : [n_cols, max_kept, b, b]   (zero-padded per column)
    header  : [n_cols, max_kept] int32   (row-block index, -1 = padding)
    counts  : [n_cols]          int32

so the SBMM Pallas kernel streams contiguous VMEM tiles and uses the header
to gather input row-blocks — the exact analog of the paper's CB/GFB flow.

Offline load balancing: block-wise top-k is *global*, so per-column block
counts differ. ``balance_columns`` computes a column permutation that snake-
assigns columns (sorted by block count) across the ``p_c``-analog lanes so
each lane's total work is near-equal; the permutation is folded into the
output layout, and the inverse permutation is fused into the next operator's
input gather (free at runtime).
"""
from __future__ import annotations

import dataclasses
import math
from typing import Tuple

import numpy as np
import jax
import jax.numpy as jnp


@dataclasses.dataclass
class PackedWeight:
    """Block-compressed representation of a pruned weight matrix."""

    blocks: jnp.ndarray   # [n_cols, max_kept, b, b]
    header: jnp.ndarray   # [n_cols, max_kept] int32; -1 padding
    counts: jnp.ndarray   # [n_cols] int32
    col_perm: np.ndarray  # permutation applied to block-columns
    shape: Tuple[int, int]
    block_size: int

    @property
    def n_cols(self) -> int:
        return self.blocks.shape[0]

    @property
    def max_kept(self) -> int:
        return self.blocks.shape[1]

    def nbytes(self) -> int:
        """Model-size contribution: stored blocks + headers (paper metric),
        each at its actual dtype width — fp16-quantized blocks halve the
        block term; the header term follows the header dtype rather than
        assuming 4 bytes."""
        kept = int(np.asarray(self.counts).sum())
        b = self.block_size
        return (kept * b * b * self.blocks.dtype.itemsize
                + kept * self.header.dtype.itemsize)

    def to_dense(self) -> jnp.ndarray:
        """Reconstruct the (masked) dense weight — the packing oracle."""
        m1, m2 = self.shape
        b = self.block_size
        n_rows = math.ceil(m1 / b)
        n_cols = self.n_cols
        dense = np.zeros((n_rows * b, n_cols * b), dtype=self.blocks.dtype)
        blocks = np.asarray(self.blocks)
        header = np.asarray(self.header)
        for pc in range(n_cols):
            c = int(self.col_perm[pc])  # logical column stored at slot pc
            for s in range(self.max_kept):
                r = int(header[pc, s])
                if r < 0:
                    continue
                dense[r * b:(r + 1) * b, c * b:(c + 1) * b] = blocks[pc, s]
        return jnp.asarray(dense[:m1, :m2])


# PackedWeight is a jit-traversable pytree: the device tensors are children,
# the host-side layout metadata (permutation, logical shape, granularity)
# rides as hashable static aux data — so {path: PackedWeight} dicts can be
# passed straight into jitted segment runners (serving.vision) instead of
# being baked into the trace as constants.
def _pw_flatten(pw: "PackedWeight"):
    children = (pw.blocks, pw.header, pw.counts)
    aux = (tuple(int(c) for c in np.asarray(pw.col_perm)),
           tuple(pw.shape), pw.block_size)
    return children, aux


def _pw_unflatten(aux, children) -> "PackedWeight":
    col_perm, shape, block_size = aux
    blocks, header, counts = children
    return PackedWeight(blocks=blocks, header=header, counts=counts,
                        col_perm=np.asarray(col_perm, dtype=np.int64),
                        shape=tuple(shape), block_size=block_size)


jax.tree_util.register_pytree_node(PackedWeight, _pw_flatten, _pw_unflatten)


def balance_columns(col_counts: np.ndarray, lanes: int = 8) -> np.ndarray:
    """Offline workload assignment (paper §V-D1): a deterministic column
    permutation such that processing columns in ``perm`` order with
    round-robin lane assignment (lane ``i`` handles ``perm[i::lanes]``)
    balances the per-lane block totals.

    Heaviest-first ordering + round-robin is the classic LPT heuristic: the
    max lane load is within (4/3 − 1/3·lanes) of optimal. ``lane_loads``
    audits the result in tests."""
    return np.argsort(-np.asarray(col_counts), kind="stable")


def lane_loads(col_counts: np.ndarray, perm: np.ndarray, lanes: int) -> np.ndarray:
    """Per-lane total blocks when columns are processed in ``perm`` order with
    round-robin lane assignment — the balance audit used in tests."""
    loads = np.zeros(lanes, dtype=np.int64)
    for i, col in enumerate(perm):
        loads[i % lanes] += col_counts[col]
    return loads


def pack_weight(w: np.ndarray, block_mask: np.ndarray, block_size: int,
                lanes: int = 8) -> PackedWeight:
    """Pack ``w`` under ``block_mask`` (shape ``score_shape(w.shape, b)``)."""
    m1, m2 = w.shape
    b = block_size
    n_rows, n_cols = block_mask.shape
    pad = np.zeros((n_rows * b, n_cols * b), dtype=w.dtype)
    pad[:m1, :m2] = w

    col_counts = block_mask.sum(axis=0).astype(np.int64)
    perm = balance_columns(col_counts, lanes)
    max_kept = max(1, int(col_counts.max()))

    blocks = np.zeros((n_cols, max_kept, b, b), dtype=w.dtype)
    header = np.full((n_cols, max_kept), -1, dtype=np.int32)
    counts = np.zeros((n_cols,), dtype=np.int32)
    for pc, c in enumerate(perm):
        rows = np.nonzero(block_mask[:, c])[0]
        counts[pc] = len(rows)
        for s, r in enumerate(rows):
            header[pc, s] = r
            blocks[pc, s] = pad[r * b:(r + 1) * b, c * b:(c + 1) * b]
    return PackedWeight(
        blocks=jnp.asarray(blocks),
        header=jnp.asarray(header),
        counts=jnp.asarray(counts),
        col_perm=np.asarray(perm),
        shape=(m1, m2),
        block_size=b,
    )


def packed_model_size_bytes(masks_and_weights, block_size: int,
                            dtype_bytes: int = 2,
                            header_bytes: int = 4,
                            scale_bytes: int = 0,
                            scales_per_block: int = 1) -> int:
    """Aggregate paper-style model size: only surviving blocks + headers for
    pruned tensors, full size for dense tensors.

    ``masks_and_weights``: iterable of (w_shape, block_mask or None).
    ``dtype_bytes`` is the stored element width (2 = the paper's int16
    weights; 4/2/1 for the kernel library's fp32/fp16/int8 blocks —
    ``repro.core.quant.PRECISION_BYTES``); ``header_bytes`` the per-kept-
    block index width; ``scale_bytes`` (× ``scales_per_block`` per kept
    block, e.g. ``block_size`` for per-output-channel scales) accounts for
    quantization scales, so the model-size columns stay honest across
    precisions."""
    total = 0
    per_block_meta = header_bytes + scale_bytes * scales_per_block
    for w_shape, mask in masks_and_weights:
        if mask is None:
            total += int(np.prod(w_shape)) * dtype_bytes
        else:
            kept = int(np.asarray(mask).sum())
            total += (kept * block_size * block_size * dtype_bytes
                      + kept * per_block_meta)
    return total
