"""Quickstart: the paper's technique end to end on a small ViT.

1. Build a reduced DeiT config with BOTH prunings enabled.
2. Run simultaneous fine-pruning (Algorithm 1) for a few steps with a
   teacher, watching the loss recover while the cubic schedule tightens r_b.
3. Harden the masks, pack the pruned weights into the block-compressed
   format, and run the SBMM kernel against the masked-dense oracle.

Run: PYTHONPATH=src python examples/quickstart.py
"""
import numpy as np
import jax
import jax.numpy as jnp

from repro.configs import DEIT_SMALL
from repro.core import simultaneous as SIM
from repro.core import packing
from repro.data import DataConfig, synthetic_vit_batch
from repro.kernels.sbmm import sbmm
from repro.models import model as M
from repro.models import pruning_glue as PG
from repro.optim import AdamW


def main():
    key = jax.random.PRNGKey(0)
    cfg = DEIT_SMALL.reduced()
    print(f"config: {cfg.name} (reduced) L={cfg.num_layers} D={cfg.d_model} "
          f"r_b={cfg.pruning.r_b} r_t={cfg.pruning.r_t} "
          f"TDM layers={cfg.pruning.tdm_layers}")

    # --- Algorithm 1: simultaneous fine-pruning with distillation --------
    state, opt = SIM.init_state(cfg, key, AdamW(lr=2e-3))
    teacher = M.init_params(cfg, jax.random.fold_in(key, 1))
    step = jax.jit(SIM.make_simultaneous_step(cfg, cfg, opt, total_steps=30))
    dc = DataConfig(seed=0)
    for i in range(10):
        batch = {k: jnp.asarray(v)
                 for k, v in synthetic_vit_batch(cfg, 8, dc, i).items()}
        state, m = step(state, teacher, batch)
        if i % 3 == 0:
            print(f"  step {i}: loss={float(m['loss']):.4f} "
                  f"ce={float(m['ce']):.4f} distill={float(m['distill']):.4f} "
                  f"r_b(t)={float(m['r_b']):.3f}")

    # --- harden masks + pack one weight for the accelerator path ---------
    masks = PG.hard_masks(cfg, state.params, state.scores)
    path = next(p for p in masks if p.endswith("attn/wq"))
    layer_idx = int(path.split("/")[1])
    w = np.asarray(state.params["layers"][layer_idx]["attn"]["wq"],
                   np.float32)
    mask = np.asarray(masks[path])
    pk = packing.pack_weight(w, mask, cfg.pruning.block_size)
    kept = int(np.asarray(pk.counts).sum())
    print(f"packed {path}: {kept}/{mask.size} blocks kept "
          f"({kept/mask.size:.0%}), {pk.nbytes()/1e3:.1f} KB packed")

    # --- SBMM kernel vs masked-dense oracle ------------------------------
    x = jax.random.normal(key, (32, w.shape[0]), jnp.float32)
    y_kernel = sbmm(x, pk)
    y_oracle = x @ pk.to_dense()
    err = float(jnp.abs(y_kernel - y_oracle).max())
    print(f"SBMM kernel vs oracle: max |err| = {err:.2e}")
    assert err < 1e-3
    print("quickstart OK")


if __name__ == "__main__":
    main()
