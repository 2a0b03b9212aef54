"""Quantized packed weights — the serving-era twin of the paper's pruning.

The paper prunes weights so the FPGA streams less and computes denser; the
deployment-side analog of "smaller weights, denser compute" is
quantization (HeatViT pairs 8-bit quantization with token pruning;
EdgeVisionTransformer applies float16 to pruned ViTs). This module extends
the block-compressed format (``core.packing.PackedWeight``) with symmetric
int8 quantization: the int8 blocks keep the exact ``blocks``/``header``
layout the SBMM kernel streams, and per-block (or per-output-channel)
float scales ride alongside as one extra pytree child the dequant-in-kernel
variant (``kernels.sbmm.sbmm_quant``) streams next to the blocks.

Precisions (the ``precision`` axis the serving stack threads through):

* ``fp32``  — the reference path, bit-exact with everything before it.
* ``fp16``  — weights stored as float16 (the fast path: the existing SBMM
  kernel already accumulates in fp32 via ``preferred_element_type``, so
  fp16 blocks ride it unchanged); attention runs on fp16-cast q/k/v.
  Interpret mode only: compiled Pallas cannot load float16 blocks on a
  TPU v5e, so the engine refuses this tier where the kernels compile.
* ``int8``  — symmetric per-block/per-channel int8 blocks + f32 scales,
  dequantized inside the kernel.

Scale granularities:

* ``"block"``   — one scale per kept b×b block (``scales [C, S]``).
* ``"channel"`` — one scale per output channel of each kept block
  (``scales [C, S, b]``, axis over the block's output columns) — tighter
  error bounds, the serving default.

Symmetric quantization: ``scale = max|w| / 127`` (1.0 where the block is
all-zero, so dequant stays exact there), ``q = clip(round(w / scale))``.
The roundtrip error is bounded by ``scale / 2`` per element — the property
tests assert exactly that bound across block sizes and granularities.
"""
from __future__ import annotations

import dataclasses
from typing import Dict, Tuple, Union

import numpy as np
import jax
import jax.numpy as jnp

from repro.core.packing import PackedWeight

__all__ = ["PRECISIONS", "PRECISION_BYTES", "GRANULARITIES",
           "QuantizedPackedWeight", "quantize_packed", "dequantize_packed",
           "quantization_error", "quantize_packed_dict",
           "packed_dict_nbytes", "max_abs_error"]

PRECISIONS = ("fp32", "fp16", "int8")
PRECISION_BYTES = {"fp32": 4, "fp16": 2, "int8": 1}
GRANULARITIES = ("block", "channel")

_QMAX = 127.0  # symmetric int8: [-127, 127] (keeps -128 unused; |q| <= 127)


@dataclasses.dataclass
class QuantizedPackedWeight:
    """Block-compressed weight with int8 blocks + float dequant scales.

    Same gathered layout as :class:`PackedWeight` (``blocks [C, S, b, b]``,
    ``header [C, S]``, ``counts [C]``, load-balancing ``col_perm``), plus
    ``scales`` — ``[C, S]`` for per-block granularity or ``[C, S, b]`` for
    per-output-channel. Registered as a pytree so {path: weight} dicts pass
    straight into jitted segment runners, exactly like PackedWeight."""

    blocks: jnp.ndarray   # [n_cols, max_kept, b, b] int8
    scales: jnp.ndarray   # [n_cols, max_kept] or [n_cols, max_kept, b] f32
    header: jnp.ndarray   # [n_cols, max_kept] int32; -1 padding
    counts: jnp.ndarray   # [n_cols] int32
    col_perm: np.ndarray
    shape: Tuple[int, int]
    block_size: int
    granularity: str = "block"

    @property
    def n_cols(self) -> int:
        return self.blocks.shape[0]

    @property
    def max_kept(self) -> int:
        return self.blocks.shape[1]

    def nbytes(self) -> int:
        """Model-size contribution: int8 blocks + headers + dequant scales,
        each at its actual dtype width (kept entries only)."""
        kept = int(np.asarray(self.counts).sum())
        b = self.block_size
        scales_per_block = b if self.granularity == "channel" else 1
        return (kept * b * b * self.blocks.dtype.itemsize
                + kept * self.header.dtype.itemsize
                + kept * scales_per_block * self.scales.dtype.itemsize)

    def to_dense(self) -> jnp.ndarray:
        """Dequantized dense reconstruction (the quantization oracle)."""
        return dequantize_packed(self).to_dense()


def _qpw_flatten(q: "QuantizedPackedWeight"):
    children = (q.blocks, q.scales, q.header, q.counts)
    aux = (tuple(int(c) for c in np.asarray(q.col_perm)),
           tuple(q.shape), q.block_size, q.granularity)
    return children, aux


def _qpw_unflatten(aux, children) -> "QuantizedPackedWeight":
    col_perm, shape, block_size, granularity = aux
    blocks, scales, header, counts = children
    return QuantizedPackedWeight(
        blocks=blocks, scales=scales, header=header, counts=counts,
        col_perm=np.asarray(col_perm, dtype=np.int64),
        shape=tuple(shape), block_size=block_size, granularity=granularity)


jax.tree_util.register_pytree_node(QuantizedPackedWeight, _qpw_flatten,
                                   _qpw_unflatten)


def _expand_scales(scales: np.ndarray) -> np.ndarray:
    """Broadcast scales over block elements: [C,S] -> [C,S,1,1] (block) or
    [C,S,b] -> [C,S,1,b] (per-output-channel — axis 3 is the block's
    output-column axis, matching ``x_blk @ w_blk``'s column scaling)."""
    if scales.ndim == 2:
        return scales[:, :, None, None]
    return scales[:, :, None, :]


def _symmetric_scales(blocks: np.ndarray, granularity: str) -> np.ndarray:
    if granularity not in GRANULARITIES:
        raise ValueError(f"granularity must be one of {GRANULARITIES}, "
                         f"got {granularity!r}")
    if granularity == "block":
        amax = np.abs(blocks).max(axis=(2, 3))        # [C, S]
    else:
        amax = np.abs(blocks).max(axis=2)             # [C, S, b]
    return np.where(amax > 0.0, amax / _QMAX, 1.0).astype(np.float32)


def quantize_packed(pw: PackedWeight, precision: str = "int8",
                    granularity: str = "block"
                    ) -> Union[PackedWeight, "QuantizedPackedWeight"]:
    """Quantize a packed weight to ``precision``.

    ``fp32`` returns ``pw`` unchanged; ``fp16`` returns a
    :class:`PackedWeight` with float16 blocks (rides the existing SBMM
    kernel — fp32 accumulation via ``preferred_element_type``); ``int8``
    returns a :class:`QuantizedPackedWeight` with symmetric scales at
    ``granularity``."""
    if precision not in PRECISIONS:
        raise ValueError(f"precision must be one of {PRECISIONS}, "
                         f"got {precision!r}")
    if precision == "fp32":
        return pw
    if precision == "fp16":
        return PackedWeight(
            blocks=jnp.asarray(pw.blocks, jnp.float16),
            header=pw.header, counts=pw.counts, col_perm=pw.col_perm,
            shape=pw.shape, block_size=pw.block_size)
    blocks = np.asarray(pw.blocks, np.float32)
    scales = _symmetric_scales(blocks, granularity)
    q = np.clip(np.rint(blocks / _expand_scales(scales)),
                -_QMAX, _QMAX).astype(np.int8)
    return QuantizedPackedWeight(
        blocks=jnp.asarray(q), scales=jnp.asarray(scales),
        header=pw.header, counts=pw.counts, col_perm=pw.col_perm,
        shape=pw.shape, block_size=pw.block_size, granularity=granularity)


def dequantize_packed(qpw) -> PackedWeight:
    """Reference dequantization back to an fp32 :class:`PackedWeight` —
    the jnp oracle the dequant-in-kernel Pallas variant is tested against.
    Accepts an fp16-blocks PackedWeight too (plain upcast)."""
    if isinstance(qpw, PackedWeight):
        return PackedWeight(
            blocks=jnp.asarray(qpw.blocks, jnp.float32),
            header=qpw.header, counts=qpw.counts, col_perm=qpw.col_perm,
            shape=qpw.shape, block_size=qpw.block_size)
    scales = _expand_scales(np.asarray(qpw.scales, np.float32))
    blocks = np.asarray(qpw.blocks, np.float32) * scales
    return PackedWeight(
        blocks=jnp.asarray(blocks), header=qpw.header, counts=qpw.counts,
        col_perm=qpw.col_perm, shape=qpw.shape, block_size=qpw.block_size)


def quantization_error(pw: PackedWeight, qpw) -> float:
    """Max-abs weight delta between the fp32 packed weight and the
    dequantized ``qpw`` (the stats-line honesty number)."""
    a = np.asarray(pw.blocks, np.float32)
    b = np.asarray(dequantize_packed(qpw).blocks, np.float32)
    return float(np.abs(a - b).max()) if a.size else 0.0


def quantize_packed_dict(packed: Dict[str, PackedWeight],
                         precision: str = "int8",
                         granularity: str = "block") -> Dict[str, object]:
    """Quantize every weight of a ``pack_model`` dict to ``precision``."""
    return {k: quantize_packed(v, precision, granularity)
            for k, v in packed.items()}


def max_abs_error(packed: Dict[str, PackedWeight],
                  qpacked: Dict[str, object]) -> float:
    """Max-abs weight delta across a whole quantized model dict."""
    return max((quantization_error(packed[k], qpacked[k])
                for k in packed), default=0.0)


def packed_dict_nbytes(packed: Dict[str, object]) -> int:
    """Total packed model bytes (blocks + headers + scales) of a
    {path: PackedWeight | QuantizedPackedWeight} dict."""
    return sum(w.nbytes() for w in packed.values())
