"""Token-package Pallas kernel — the soft-pruning (SPViT-style) sibling of
``token_drop``.

Where the TDHM fuses dropped tokens with pre-normalized weights, the soft
TDM carries a persistent *package token* whose accumulated score mass must
re-enter the aggregation at its raw scale. So this kernel is a weighted
scatter-reduce over UN-normalized weights, normalized in-VMEM:

    package = (w · Z) / (Σ w + eps)

with ``w`` holding raw dropped-token scores, the carried package mass at
the package row, and exactly 0 at kept rows — one [1, N] × [N, TD] matmul
plus a row-sum per column tile, fused with the k kept-row gathers in a
single VMEM-resident pass over Z (one HBM read instead of gather + mask +
reduce + divide in the unfused jnp path).

grid = (D / TD,): each cell owns a [N, TD] column slice of the token
matrix, same layout as ``token_drop``.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.backend import resolve_interpret


def _token_package_kernel(keep_idx_ref, z_ref, w_ref, out_ref, *, k: int):
    """keep_idx_ref: [k] int32 (scalar prefetch)
    z_ref  : [N, TD] column slice of tokens
    w_ref  : [1, N] RAW weights (dropped scores + package mass; 0 at kept)
    out_ref: [k + 1, TD] — kept rows then the normalized package token."""

    def gather_row(r, _):
        idx = keep_idx_ref[r]
        row = z_ref[pl.dslice(idx, 1), :]
        out_ref[pl.dslice(r, 1), :] = row.astype(out_ref.dtype)
        return 0

    jax.lax.fori_loop(0, k, gather_row, 0)
    w = w_ref[...].astype(jnp.float32)
    acc = jnp.dot(w, z_ref[...].astype(jnp.float32),
                  preferred_element_type=jnp.float32)  # [1, TD]
    package = acc / (jnp.sum(w) + 1e-9)
    out_ref[pl.dslice(k, 1), :] = package.astype(out_ref.dtype)


def token_package_pallas(z: jax.Array, keep_idx: jax.Array,
                         weights: jax.Array, *, td: int = 128,
                         interpret: "bool | None" = None) -> jax.Array:
    """z: [N, D]; keep_idx: [k] int32; weights: [N] RAW (un-normalized —
    dropped scores plus the carried package mass; zero at kept rows).
    Returns [k + 1, D]: kept tokens followed by the package token
    ``(weights · z) / (Σ weights + 1e-9)``. ``D`` must be a multiple of
    ``td`` (ops.py pads). ``interpret=None`` auto-detects the backend
    (kernels.backend)."""
    interpret = resolve_interpret(interpret)
    N, D = z.shape
    (k,) = keep_idx.shape
    assert D % td == 0, (D, td)
    kernel = functools.partial(_token_package_kernel, k=k)
    return pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(D // td,),
            in_specs=[
                pl.BlockSpec((N, td), lambda j, idx: (0, j)),
                pl.BlockSpec((1, N), lambda j, idx: (0, 0)),
            ],
            out_specs=pl.BlockSpec((k + 1, td), lambda j, idx: (0, j)),
        ),
        out_shape=jax.ShapeDtypeStruct((k + 1, D), z.dtype),
        interpret=interpret,
    )(keep_idx, z, weights.reshape(1, N))
