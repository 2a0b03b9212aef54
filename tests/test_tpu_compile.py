"""Compile the served kernels for a described TPU v5e at DeiT-Small widths.

Nothing runs: each test lowers and compiles for a v5e chip that is
described, not attached, so Mosaic refuses here what it would refuse on the
chip (unaligned tiles, unsupported loads). The topology is described inside
a fixture, never at import time, and these tests skip where it cannot be.
The persistent compilation cache is off around them: such a compile cannot
be read back without a chip.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import SingleDeviceSharding

from repro.configs import get_config
from repro.core import packed_runner as PR
from repro.core import packing
from repro.core import quant as Q
from repro.kernels import backend
from repro.kernels.sbmm import ops
from repro.models import model as M
from repro.models import pruning_glue as PG

CFG = get_config("deit-small")
B, N = 4, 197  # the engine's max_batch tile at 224 px (196 patches + CLS)


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler or topology support here
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture
def compiled_kernels(one_chip, monkeypatch):
    """Kernels resolve to compiled Pallas (the host backend here is the
    CPU), with the persistent cache off for the duration."""
    from jax.experimental.compilation_cache import compilation_cache as cc
    monkeypatch.setenv(backend.ENV_VAR, "compiled")
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    try:
        yield one_chip
    finally:
        jax.config.update("jax_enable_compilation_cache", was)
        cc.reset_cache()


def _spec(a, sharding):
    return jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=sharding)


def _deit_attention_weight():
    """One 384x384 attention weight packed at r_b=0.5, block 16."""
    b = CFG.pruning.block_size
    d = CFG.d_model
    rng = np.random.default_rng(0)
    n_blocks = (d // b) ** 2
    mask = np.zeros(n_blocks, bool)
    mask[rng.choice(n_blocks, int(n_blocks * CFG.pruning.r_b),
                    replace=False)] = True
    w = rng.standard_normal((d, d)).astype(np.float32)
    return packing.pack_weight(w, mask.reshape(d // b, d // b), b)


@pytest.mark.parametrize("precision,granularity", [
    ("fp32", None), ("int8", "block"), ("int8", "channel")])
def test_sbmm_compiles_for_v5e(compiled_kernels, precision, granularity):
    pw = _deit_attention_weight()
    x = jax.ShapeDtypeStruct((B * N, CFG.d_model), jnp.float32,
                             sharding=compiled_kernels)
    if precision == "fp32":
        fn = jax.jit(lambda x, blocks, header: ops._sbmm_raw_jit(
            x, blocks, header, tm=128, interpret=False))
        args = (pw.blocks, pw.header)
    else:
        qpw = Q.quantize_packed(pw, precision, granularity)
        fn = jax.jit(lambda x, blocks, header, scales: ops._sbmm_quant_raw_jit(
            x, blocks, header, scales, tm=128, interpret=False))
        args = (qpw.blocks, qpw.header, qpw.scales)
    compiled = fn.lower(
        x, *(_spec(a, compiled_kernels) for a in args)).compile()
    assert "tpu_custom_call" in compiled.as_text()


@pytest.mark.parametrize("tier", ["fp32"])
def test_packed_encoder_segment_compiles_for_v5e(compiled_kernels, tier):
    """A jitted PackedVitSegments encoder segment of full-width DeiT-Small,
    at the engine's largest tile, compiles for v5e with its attention
    projections as XLA dots by the block-masked weights and no Pallas
    kernel (float32 is the one serving tier)."""
    key = jax.random.PRNGKey(0)
    params = M.init_params(CFG, key)
    scores = PG.init_scores(CFG, params, jax.random.fold_in(key, 7))
    segs = PR.PackedVitSegments(CFG, PG.apply_pruning(CFG, params, scores))
    spec = lambda t: jax.tree_util.tree_map(
        lambda a: _spec(a, compiled_kernels), t)
    x = jax.ShapeDtypeStruct((B, N, CFG.d_model), jnp.float32,
                             sharding=compiled_kernels)
    nv = jax.ShapeDtypeStruct((B,), jnp.int32, sharding=compiled_kernels)
    seg = next(s for s in segs.plan if s[0] == "layers")
    compiled = segs._layers.lower(spec(segs.params), x, nv, lo=seg[1],
                                  hi=seg[2]).compile().as_text()
    assert "tpu_custom_call" not in compiled
    assert "convolution(" in compiled
