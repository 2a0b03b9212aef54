"""The comparison that decides ``correct``.

Once the window has closed and the program's state is freed, a sample of
the requests the window completed, drawn from the seed, is run through the
configuration's plain reference at ``highest`` matmul precision, and the
served logits are compared with the reference's. The sample covers every
keep rate of the mix and, where the engine served some requests as fused
express lanes, both lanes and tiles.

Per request, the error is the largest absolute logit difference over the
RMS of the reference's logits. Two numbers are compared, each against its
limit in the configuration file's ``check`` block:

* ``group_median_err``: the largest, over the sample's groups (keep rate
  x express lane or tile), of the group's median error. A lower matmul
  precision moves every request; a fault of one keep rate or of one path
  moves a whole group.
* ``n_err_over_tol``: how many sampled requests have an error above the
  block's ``tol``. A fault that alters some requests of every group (one
  slot of a tile) moves only these. A sound run reads here only the rare
  request on which a top-k near-tie at a TDM layer, broken the other way
  by rounding, keeps another token than the reference.
"""
from __future__ import annotations

import functools
from collections import defaultdict
from typing import Dict, List, Tuple

import numpy as np

from . import traffic as T

BLOCK = 32  # reference batch: requests of one keep rate, padded by repeats


def served_by(req, segments_per_request: int) -> str:
    """``lane`` when the request ran its remaining segments as one fused
    program (it finished in fewer engine steps than it has segments)."""
    steps = req.done_step - req.admitted_step + 1
    return "lane" if steps < segments_per_request else "tile"


def sample(records, size: int, seed: int) -> List[int]:
    """Up to ``size`` completed requests, drawn from the seed evenly over
    the (keep rate, served by) groups."""
    groups: Dict = defaultdict(list)
    for uid, r in sorted(records.requests.items()):
        if uid not in records.out or r.admitted_step is None:
            continue
        groups[(r.r_t, served_by(r, records.segments_per_request))].append(
            uid)
    rng = T.seed_rng(seed, 5)
    pools = []
    for key in sorted(groups):
        uids = list(groups[key])
        rng.shuffle(uids)
        pools.append(uids)
    out: List[int] = []
    while len(out) < size and any(pools):
        for p in pools:
            if p and len(out) < size:
                out.append(p.pop())
    return sorted(out)


def reference_logits(reference, params, model: dict, pruning: dict,
                     scores: dict, pool: np.ndarray, reqs: List,
                     dtype: str = "float32",
                     precision: str = "highest") -> Dict[int, np.ndarray]:
    """{uid: logits} of the plain reference over ``reqs`` (each with
    ``image`` and ``r_t``), in blocks of :data:`BLOCK` per keep rate."""
    import jax
    import jax.numpy as jnp

    masks = reference.masks(model, pruning, scores)
    p = reference.masked_params(params, model, masks, jnp.dtype(dtype))
    prec = {"highest": jax.lax.Precision.HIGHEST,
            "high": jax.lax.Precision.HIGH,
            "default": jax.lax.Precision.DEFAULT}[precision]
    out: Dict[int, np.ndarray] = {}
    by_rate: Dict[float, List] = defaultdict(list)
    for r in reqs:
        by_rate[r.r_t].append(r)
    for r_t, group in sorted(by_rate.items()):
        fwd = jax.jit(functools.partial(reference.forward, model=model,
                                        pruning=pruning, r_t=r_t,
                                        precision=prec))
        for i in range(0, len(group), BLOCK):
            blk = group[i:i + BLOCK]
            idx = [q.image for q in blk]
            idx += [idx[-1]] * (BLOCK - len(idx))
            x = jnp.asarray(pool[np.asarray(idx)]).astype(jnp.dtype(dtype))
            y = np.asarray(fwd(p, x))
            for j, q in enumerate(blk):
                out[q.uid] = y[j]
    return out


# the control for each matmul precision a configuration can state: the
# reference one step lower, as (dtype, matmul precision)
CONTROL = {"highest": ("float32", "high")}


def errors(served: Dict[int, np.ndarray], ref: Dict[int, np.ndarray],
           norm=np.max) -> Dict[int, float]:
    """Per request: ``norm`` of |served - reference| (default the largest
    difference) over the RMS of the reference's logits."""
    out = {}
    for uid, r in ref.items():
        s = np.asarray(served[uid], np.float64)
        r = np.asarray(r, np.float64)
        if s.shape != r.shape or not np.all(np.isfinite(s)):
            out[uid] = float("inf")
        else:
            out[uid] = float(norm(np.abs(s - r)) / np.sqrt((r * r).mean()))
    return out


def candidates(served, ref) -> Dict[str, float]:
    """Statistics a limit could be set on, for ``bench/limits.py``."""
    rms = lambda d: np.sqrt((d * d).mean())
    out = {}
    for kind, norm in (("max", np.max), ("rms", rms)):
        e = np.asarray(list(errors(served, ref, norm).values()))
        for q in (10, 25, 50, 90, 100):
            out[f"{kind}_q{q}"] = float(np.percentile(e, q))
    return out


NUMBERS = ("group_median_err", "n_err_over_tol")


def groups_of(records, uids) -> Dict[int, Tuple]:
    """{uid: (keep rate, served by)} of sampled requests."""
    return {u: (records.requests[u].r_t,
                served_by(records.requests[u], records.segments_per_request))
            for u in uids}


def numbers(err: Dict[int, float], groups: Dict[int, Tuple],
            tol: float) -> Dict[str, float]:
    by: Dict[Tuple, List[float]] = defaultdict(list)
    for uid, e in err.items():
        by[groups[uid]].append(e)
    return {"group_median_err": max((float(np.median(v))
                                     for v in by.values()), default=np.inf),
            "n_err_over_tol": int(sum(1 for e in err.values()
                                      if not e <= tol))}


def verdict(nums: Dict[str, float], limits: Dict[str, float]) -> bool:
    return all(np.isfinite(nums[k]) and nums[k] <= limits[k]
               for k in limits)
