"""Property tests for the quantized packing format and dequant kernels.

Requires ``hypothesis`` (the optional 'test' extra); the deterministic
fallbacks for the same invariants live in tests/test_quant.py.
"""
import pytest

pytest.importorskip(
    "hypothesis", reason="property tests need the optional 'test' extra")

import jax.numpy as jnp
import numpy as np
from hypothesis import given, settings
from hypothesis import strategies as st

from conftest import assert_logits_close
from repro.core import packing
from repro.core import quant as Q
from repro.kernels.sbmm import sbmm_quant_raw, sbmm_quant_ref

_fast = settings(max_examples=20, deadline=None)


def _pack(rng, b, keep, n_rows=3, n_cols=4, amp=1.0):
    w = (rng.standard_normal((n_rows * b, n_cols * b)) * amp
         ).astype(np.float32)
    mask = np.zeros((n_rows, n_cols), bool)
    total = n_rows * n_cols
    flat = rng.choice(total, size=min(keep, total), replace=False)
    mask[flat // n_cols, flat % n_cols] = True
    return packing.pack_weight(w, mask, b)


@_fast
@given(b=st.sampled_from([8, 16, 32]),
       granularity=st.sampled_from(Q.GRANULARITIES),
       keep=st.integers(1, 12), seed=st.integers(0, 2 ** 16),
       scale_pow=st.integers(-6, 6))
def test_roundtrip_error_within_half_scale(b, granularity, keep, seed,
                                           scale_pow):
    """|w - dequant(quant(w))| <= scale/2 per element, across block sizes,
    scale granularities, keep counts and weight magnitudes 2^-6..2^6 (the
    scale must adapt, not clip), and the int8 payload stays in [-127, 127]
    (symmetric: -128 never emitted)."""
    rng = np.random.default_rng(seed)
    pw = _pack(rng, b, keep, amp=2.0 ** scale_pow)
    qpw = Q.quantize_packed(pw, "int8", granularity)
    err = np.abs(np.asarray(pw.blocks, np.float32)
                 - np.asarray(Q.dequantize_packed(qpw).blocks, np.float32))
    bound = np.asarray(Q._expand_scales(np.asarray(qpw.scales)),
                       np.float32) / 2.0
    assert np.all(err <= np.broadcast_to(bound, err.shape)
                  * (1 + 1e-6) + 1e-12)
    assert np.all(np.abs(np.asarray(qpw.blocks, np.int64)) <= 127)


@_fast
@given(granularity=st.sampled_from(Q.GRANULARITIES),
       keep=st.integers(1, 12), seed=st.integers(0, 2 ** 16))
def test_channel_granularity_refines_block(granularity, keep, seed):
    """Channel scales partition each block's columns, so the max-abs
    roundtrip error can only shrink relative to one scale per block."""
    rng = np.random.default_rng(seed)
    pw = _pack(rng, 16, keep)
    e_block = Q.quantization_error(pw, Q.quantize_packed(pw, "int8",
                                                         "block"))
    e_chan = Q.quantization_error(pw, Q.quantize_packed(pw, "int8",
                                                        "channel"))
    assert e_chan <= e_block + 1e-7


@_fast
@given(m=st.integers(1, 40), keep=st.integers(1, 6),
       seed=st.integers(0, 2 ** 16),
       granularity=st.sampled_from(Q.GRANULARITIES))
def test_quant_kernel_bit_matches_ref(m, keep, seed, granularity):
    """Interpret-mode dequant SBMM kernel matches the jnp reference
    ``sbmm_quant_ref`` within ``assert_logits_close`` at arbitrary row
    counts (exercises the ops.py pad-to-tile path whenever m % tm != 0):
    the kernel's tiled dots may round differently from the reference's."""
    rng = np.random.default_rng(seed)
    pw = _pack(rng, 16, keep, n_rows=2, n_cols=3)
    qpw = Q.quantize_packed(pw, "int8", granularity)
    x = jnp.asarray(rng.standard_normal((m, 32)), jnp.float32)
    y = sbmm_quant_raw(x, qpw.blocks, qpw.header, qpw.scales, tm=16)
    y_ref = sbmm_quant_ref(x, qpw.blocks, qpw.header, qpw.scales)
    assert_logits_close(y, y_ref)
