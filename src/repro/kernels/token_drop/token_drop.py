"""Token-drop Pallas kernel — the TDHM (Token Dropping Hardware Module)
adapted to TPU.

The FPGA TDHM sorts token scores with a bitonic network, then routes tokens
through index-shuffle networks into a new token buffer, fusing the non-top-k
tokens into one weighted-average token. On TPU the sort/top-k is native
(jax.lax.top_k, done outside), and the interesting fusion is the *single
VMEM-resident pass* that (a) gathers the kept rows and (b) reduces the
dropped rows into the fused token — one HBM read of Z instead of three
(gather + mask + reduce) in the unfused jnp path.

grid = (D / TD,): each cell owns a [N, TD] column slice of the token matrix.
  * kept rows: k dynamic-slice row gathers driven by prefetched indices
    (the index-shuffle network analog)
  * fused row: one [1, N] × [N, TD] matmul with the normalized drop weights
    (the weighted-aggregation tree analog).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.backend import resolve_interpret


def _token_drop_kernel(keep_idx_ref, z_ref, w_ref, out_ref, *, k: int):
    """keep_idx_ref: [k] int32 (scalar prefetch)
    z_ref  : [N, TD] column slice of tokens
    w_ref  : [1, N] normalized drop weights (0 at kept rows)
    out_ref: [k + 1, TD] — kept rows then the fused token."""

    def gather_row(r, _):
        idx = keep_idx_ref[r]
        row = z_ref[pl.dslice(idx, 1), :]
        out_ref[pl.dslice(r, 1), :] = row.astype(out_ref.dtype)
        return 0

    jax.lax.fori_loop(0, k, gather_row, 0)
    fused = jnp.dot(w_ref[...], z_ref[...].astype(jnp.float32),
                    preferred_element_type=jnp.float32)  # [1, TD]
    out_ref[pl.dslice(k, 1), :] = fused.astype(out_ref.dtype)


def token_drop_pallas(z: jax.Array, keep_idx: jax.Array,
                      drop_weights: jax.Array, *, td: int = 128,
                      interpret: "bool | None" = None) -> jax.Array:
    """z: [N, D]; keep_idx: [k] int32; drop_weights: [N] (normalized, zero at
    kept rows). Returns [k + 1, D]: kept tokens followed by the fused token.
    ``D`` must be a multiple of ``td`` (ops.py pads). ``interpret=None``
    auto-detects the backend (kernels.backend)."""
    interpret = resolve_interpret(interpret)
    N, D = z.shape
    (k,) = keep_idx.shape
    assert D % td == 0, (D, td)
    kernel = functools.partial(_token_drop_kernel, k=k)
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
    )(keep_idx, z, drop_weights.reshape(1, N))
