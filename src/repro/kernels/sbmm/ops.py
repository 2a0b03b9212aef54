"""Jit'd public wrapper for the SBMM kernel: padding, permutation handling,
and backend selection (real Pallas on TPU, interpret mode elsewhere)."""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.packing import PackedWeight
from repro.core.quant import QuantizedPackedWeight
from repro.kernels.backend import resolve_interpret
from repro.kernels.sbmm.quant import sbmm_quant_pallas
from repro.kernels.sbmm.sbmm import sbmm_pallas


@functools.partial(jax.jit, static_argnames=("tm", "interpret"))
def _sbmm_raw_jit(x: jax.Array, blocks: jax.Array, header: jax.Array,
                  tm: int, interpret: bool) -> jax.Array:
    C, S, b, _ = blocks.shape
    M, K = x.shape
    k_pad = (-K) % b
    m_pad = (-M) % tm
    if k_pad or m_pad:
        x = jnp.pad(x, ((0, m_pad), (0, k_pad)))
    y = sbmm_pallas(x, blocks, header, tm=tm, interpret=interpret)
    return y[:M]


@functools.partial(jax.jit, static_argnames=("tm", "interpret"))
def _sbmm_quant_raw_jit(x: jax.Array, blocks: jax.Array, header: jax.Array,
                        scales: jax.Array, tm: int,
                        interpret: bool) -> jax.Array:
    C, S, b, _ = blocks.shape
    M, K = x.shape
    k_pad = (-K) % b
    m_pad = (-M) % tm
    if k_pad or m_pad:
        x = jnp.pad(x, ((0, m_pad), (0, k_pad)))
    y = sbmm_quant_pallas(x, blocks, header, scales, tm=tm,
                          interpret=interpret)
    return y[:M]


def sbmm_quant_raw(x: jax.Array, blocks: jax.Array, header: jax.Array,
                   scales: jax.Array, tm: int = 128,
                   interpret: bool | None = None) -> jax.Array:
    """Pad rows/cols and run the dequant-in-kernel variant. Backend
    auto-detection matches :func:`sbmm_raw` (resolved outside the jit)."""
    return _sbmm_quant_raw_jit(x, blocks, header, scales, tm,
                               resolve_interpret(interpret))


def sbmm_raw(x: jax.Array, blocks: jax.Array, header: jax.Array,
             tm: int = 128, interpret: bool | None = None) -> jax.Array:
    """Pad rows/cols and run the kernel. x: [M, K_logical].

    ``interpret=None`` auto-detects (compiled on TPU, interpreter on CPU
    CI; ``REPRO_KERNEL_INTERPRET`` overrides) — resolved here, outside the
    jit, so the resolved value is a static argument."""
    return _sbmm_raw_jit(x, blocks, header, tm, resolve_interpret(interpret))


def sbmm(x: jax.Array, packed: "PackedWeight | QuantizedPackedWeight",
         tm: int = 128, interpret: bool | None = None) -> jax.Array:
    """Full SBMM: y = x @ W_masked, undoing the load-balancing column
    permutation so callers see logical column order. A
    :class:`QuantizedPackedWeight` dispatches the dequant-in-kernel
    variant (int8 blocks, scales streamed with them); an fp16-blocks
    PackedWeight rides the standard kernel in interpret mode only (fp32
    accumulation either way).

    x: [..., M1_any, K]; returns [..., M1_any, M2]."""
    lead = x.shape[:-1]
    x2 = x.reshape(-1, x.shape[-1])
    if isinstance(packed, QuantizedPackedWeight):
        y = sbmm_quant_raw(x2, packed.blocks, packed.header, packed.scales,
                           tm=tm, interpret=interpret)
    else:
        y = sbmm_raw(x2, packed.blocks, packed.header, tm=tm,
                     interpret=interpret)
    b = packed.block_size
    m2 = packed.shape[1]
    # slot pc holds logical column perm[pc] -> scatter back
    C = packed.n_cols
    inv = np.empty(C, dtype=np.int64)
    inv[np.asarray(packed.col_perm)] = np.arange(C)
    y_blocks = y.reshape(x2.shape[0], C, b)
    y_logical = y_blocks[:, jnp.asarray(inv), :].reshape(x2.shape[0], C * b)
    return y_logical[:, :m2].reshape(lead + (m2,))
