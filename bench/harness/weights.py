"""Weights and pruning structure of a configuration, made from seeds.

The weight values come from ``--seed``, on the device, in one jitted call,
in float32 (the type they are served in). The pruned structure (block and
MLP-unit scores) comes from the configuration's fixed ``structure_seed``:
every run of a cell then serves the same shapes, so the compiled programs
of one seed serve every other.

The tree follows the layout the program's ViT takes (``patch_embed``,
``patch_bias``, ``cls``, ``pos``, ``layers[i]`` with ``ln1_*``/``ln2_*``,
``attn`` q/k/v/o weights and biases, ``mlp`` wi/bi/wo/bo, ``ln_f_*``,
``head``). Norm scales and every bias are drawn too, so a path that drops
one of them reads differently from the reference.
"""
from __future__ import annotations

import math

import numpy as np


def seed_key(seed: int):
    """A JAX key for any whole ``seed`` up to 64 bits."""
    import jax
    seed = int(seed) % (1 << 64)
    return jax.random.fold_in(jax.random.key(seed & 0xFFFFFFFF), seed >> 32)


def make_params(model: dict, seed: int) -> dict:
    import jax
    import jax.numpy as jnp

    D, F, C = model["d_model"], model["d_ff"], model["num_classes"]
    P = model["patch_size"] ** 2 * 3
    n = (model["image_size"] // model["patch_size"]) ** 2 + 1
    L = model["num_layers"]

    # (shape, std, offset) of every leaf, in tree order
    leaves = {"patch_embed": ((P, D), math.sqrt(2.0 / (P + D)), 0.0),
              "patch_bias": ((D,), 0.02, 0.0),
              "cls": ((1, 1, D), 0.02, 0.0),
              "pos": ((n, D), 0.02, 0.0),
              "ln_f_s": ((D,), 0.1, 1.0), "ln_f_b": ((D,), 0.02, 0.0),
              "head": ((D, C), math.sqrt(2.0 / (D + C)), 0.0)}
    xd, xf = math.sqrt(1.0 / D), math.sqrt(2.0 / (D + F))
    layer = {"ln1_s": ((D,), 0.1, 1.0), "ln1_b": ((D,), 0.02, 0.0),
             "ln2_s": ((D,), 0.1, 1.0), "ln2_b": ((D,), 0.02, 0.0),
             "attn": {"wq": ((D, D), xd, 0.0), "wk": ((D, D), xd, 0.0),
                      "wv": ((D, D), xd, 0.0), "wo": ((D, D), xd, 0.0),
                      "bq": ((D,), 0.02, 0.0), "bk": ((D,), 0.02, 0.0),
                      "bv": ((D,), 0.02, 0.0), "bo": ((D,), 0.02, 0.0)},
             "mlp": {"wi": ((D, F), xf, 0.0), "bi": ((F,), 0.02, 0.0),
                     "wo": ((F, D), xf, 0.0), "bo": ((D,), 0.02, 0.0)}}
    spec = {**leaves, "layers": [layer] * L}
    is_leaf = lambda t: isinstance(t, tuple)
    flat, tree = jax.tree_util.tree_flatten(spec, is_leaf=is_leaf)
    sizes = [math.prod(shape) for shape, _, _ in flat]

    def build(key):
        # one draw for every weight: a small program to compile
        z = jax.random.normal(key, (sum(sizes),), jnp.float32)
        out, at = [], 0
        for (shape, std, off), size in zip(flat, sizes):
            out.append(off + std * z[at:at + size].reshape(shape))
            at += size
        return jax.tree_util.tree_unflatten(tree, out)

    return jax.jit(build)(seed_key(seed))


def make_scores(model: dict, pruning: dict, structure_seed: int) -> dict:
    """Block scores of every attention projection and one score per MLP
    hidden unit, as host arrays keyed ``layers/<i>/attn/<w>`` and
    ``layers/<i>/mlp/units``."""
    rng = np.random.default_rng(structure_seed)
    b = pruning["block_size"]
    D, F = model["d_model"], model["d_ff"]
    m = -(-D // b)
    out = {}
    for i in range(model["num_layers"]):
        for w in ("wq", "wk", "wv", "wo"):
            out[f"layers/{i}/attn/{w}"] = rng.standard_normal(
                (m, m)).astype(np.float32)
        out[f"layers/{i}/mlp/units"] = rng.standard_normal(F).astype(
            np.float32)
    return out


def program_scores(scores: dict) -> dict:
    """The score tree in the program's keys: the MLP unit scores serve as
    wi's column scores and wo's row scores."""
    import jax.numpy as jnp
    out = {}
    for key, s in scores.items():
        if key.endswith("/mlp/units"):
            base = key[: -len("units")]
            out[base + "wi"] = jnp.asarray(s)
            out[base + "wo"] = jnp.asarray(s)
        else:
            out[key] = jnp.asarray(s)
    return out
