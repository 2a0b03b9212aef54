"""Plain reference for the pruned ViT configurations (DeiT-S, DeiT-B).

Straight ``jax.numpy``, written from the published description and the
configuration file alone; it imports nothing of the program under test.

    patches [B, n, P*P*3] -> patch embedding + bias, CLS prepended,
    learned positions added -> 12 pre-norm encoder layers -> final norm ->
    the CLS row through the head.

Each encoder layer: ``x += W_o . attn(LN1(x) W_q, LN1(x) W_k, LN1(x) W_v)``
with 1/sqrt(head_dim) softmax over all tokens, then at a TDM layer the token
dropping module, then ``x += gelu(LN2(x) W_i + b_i) W_o + b_o``.

Static block pruning (paper section IV-A): every attention projection keeps
the ``ceil(m * n * r_b)`` highest-scored of its ``m x n`` blocks of
``b x b`` (top-k over the whole matrix); the MLP keeps the
``ceil(d_ff * r_b)`` highest-scored hidden units (columns of W_i, rows of
W_o).

Token dropping (paper section IV-B): the score of a token is the CLS row of
the attention probabilities averaged over heads. The ``k = ceil((n - 1) *
r_t)`` highest-scored non-CLS tokens are kept; the others are fused into one
token, their sum weighted by score over the sum of their scores (plus 1e-9);
the layer then carries CLS + k + 1 tokens into its MLP.

Numerics follow the configuration's ``numerics`` block: the GELU is the
tanh form and the head has no bias (the departures from DeiT it lists).
Every matrix product takes an explicit ``precision``.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp
import numpy as np


def block_keep(m: int, n: int, r_b: float) -> int:
    return max(1, math.ceil(m * n * r_b))


def unit_keep(d_ff: int, r_b: float) -> int:
    return max(1, math.ceil(d_ff * r_b))


def tdm_keep(n_tokens: int, r_t: float) -> int:
    """Non-CLS tokens kept by a TDM applied to ``n_tokens`` (CLS included)."""
    return max(1, math.ceil((n_tokens - 1) * r_t))


def _topk_mask(scores: np.ndarray, keep: int) -> np.ndarray:
    flat = np.sort(scores.reshape(-1))[::-1]
    return (scores >= flat[keep - 1]).astype(np.float32)


def masks(model: dict, pruning: dict, scores: dict) -> dict:
    """Element masks for every pruned weight, from the score tree
    (``layers/<i>/attn/<w>`` block scores, ``layers/<i>/mlp/units``)."""
    b, r_b = pruning["block_size"], pruning["r_b"]
    out = {}
    for i in range(model["num_layers"]):
        for w in ("wq", "wk", "wv", "wo"):
            s = np.asarray(scores[f"layers/{i}/attn/{w}"])
            bm = _topk_mask(s, block_keep(*s.shape, r_b))
            out[f"layers/{i}/attn/{w}"] = np.kron(bm, np.ones((b, b),
                                                              np.float32))
        u = np.asarray(scores[f"layers/{i}/mlp/units"])
        out[f"layers/{i}/mlp/units"] = _topk_mask(
            u, unit_keep(model["d_ff"], r_b))
    return out


def masked_params(params: dict, model: dict, mask: dict, dtype) -> dict:
    """The weights with their masks applied, cast to ``dtype``."""
    cast = lambda a: jnp.asarray(a, jnp.float32).astype(dtype)
    layers = []
    for i, lp in enumerate(params["layers"]):
        a, m = lp["attn"], lp["mlp"]
        d_in, d_out = a["wq"].shape
        unit = jnp.asarray(mask[f"layers/{i}/mlp/units"])
        layers.append({
            **{k: cast(lp[k]) for k in ("ln1_s", "ln1_b", "ln2_s", "ln2_b")},
            "attn": {
                **{w: cast(a[w] * mask[f"layers/{i}/attn/{w}"][
                    :a[w].shape[0], :a[w].shape[1]])
                   for w in ("wq", "wk", "wv", "wo")},
                **{bn: cast(a[bn]) for bn in ("bq", "bk", "bv", "bo")}},
            "mlp": {"wi": cast(m["wi"] * unit[None, :]), "bi": cast(m["bi"]),
                    "wo": cast(m["wo"] * unit[:, None]), "bo": cast(m["bo"])},
        })
    return {**{k: cast(params[k]) for k in
               ("patch_embed", "patch_bias", "cls", "pos", "ln_f_s",
                "ln_f_b", "head")},
            "layers": layers}


def _ln(x, s, b, eps):
    mu = x.mean(-1, keepdims=True)
    var = ((x - mu) ** 2).mean(-1, keepdims=True)
    return (x - mu) / jnp.sqrt(var + eps) * s + b


def _gelu_tanh(x):
    c = math.sqrt(2.0 / math.pi)
    return 0.5 * x * (1.0 + jnp.tanh(c * (x + 0.044715 * x ** 3)))


def forward(p: dict, patches, model: dict, pruning: dict, r_t: float,
            precision) -> jax.Array:
    """Logits [B, classes] of a batch of images that share ``r_t`` and a
    patch count. ``p`` comes from :func:`masked_params`."""
    H = model["num_heads"]
    D = model["d_model"]
    dh = D // H
    eps = model["norm_eps"]
    mm = lambda a, w: jnp.einsum("...i,io->...o", a, w, precision=precision)
    dt = p["pos"].dtype
    x = mm(patches.astype(dt), p["patch_embed"]) + p["patch_bias"]
    B, n, _ = x.shape
    x = jnp.concatenate([jnp.broadcast_to(p["cls"], (B, 1, D)), x], axis=1)
    x = x + p["pos"][None, : n + 1]
    tdm_layers = set(pruning["tdm_layers"]) if r_t < 1.0 else set()
    for i, lp in enumerate(p["layers"]):
        a = lp["attn"]
        h = _ln(x, lp["ln1_s"], lp["ln1_b"], eps)
        N = h.shape[1]
        q = (mm(h, a["wq"]) + a["bq"]).reshape(B, N, H, dh)
        k = (mm(h, a["wk"]) + a["bk"]).reshape(B, N, H, dh)
        v = (mm(h, a["wv"]) + a["bv"]).reshape(B, N, H, dh)
        logits = jnp.einsum("bqhd,bkhd->bhqk", q, k,
                            precision=precision) / math.sqrt(dh)
        probs = jax.nn.softmax(logits, axis=-1)
        o = jnp.einsum("bhqk,bkhd->bqhd", probs, v, precision=precision)
        x = x + mm(o.reshape(B, N, D), a["wo"]) + a["bo"]
        if i in tdm_layers:
            score = probs[:, :, 0, 1:].mean(axis=1)            # [B, N-1]
            keep = tdm_keep(N, r_t)
            _, idx = jax.lax.top_k(score, keep)
            body = x[:, 1:]
            kept = jnp.take_along_axis(body, idx[..., None], axis=1)
            dropped = jnp.ones_like(score, bool).at[
                jnp.arange(B)[:, None], idx].set(False)
            w = jnp.where(dropped, score, 0.0)
            w = w / (w.sum(axis=1, keepdims=True) + 1e-9)
            fused = jnp.einsum("bn,bnd->bd", w.astype(dt), body,
                               precision=precision)
            x = jnp.concatenate([x[:, :1], kept, fused[:, None]], axis=1)
        m = lp["mlp"]
        h = _ln(x, lp["ln2_s"], lp["ln2_b"], eps)
        x = x + mm(_gelu_tanh(mm(h, m["wi"]) + m["bi"]), m["wo"]) + m["bo"]
    x = _ln(x, p["ln_f_s"], p["ln_f_b"], eps)
    return mm(x[:, 0], p["head"]).astype(jnp.float32)
