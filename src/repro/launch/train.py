"""Training launcher: end-to-end driver tying together configs, data,
sharding, the fault-tolerant loop, checkpointing, and (optionally) the
paper's simultaneous pruning.

    PYTHONPATH=src python -m repro.launch.train --arch minitron-4b \\
        --reduced --steps 50 --batch 8 --seq 128 --ckpt /tmp/ckpt

On a real cluster the same driver runs un-``--reduced`` against the
production mesh; on this CPU container the reduced path is the runnable
end-to-end example (examples/train_lm.py wraps it).
"""
from __future__ import annotations

import argparse
import time
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

from repro.checkpoint import CheckpointManager
from repro.configs import get_config
from repro.configs.base import ShapeConfig
from repro.data import DataConfig, synthetic_lm_batch
from repro.dist.fault import FaultConfig, RestartableLoop
from repro.launch.compile_cache import enable_compile_cache
from repro.models import model as M
from repro.models import steps as ST
from repro.models import pruning_glue as PG
from repro.optim import AdamW


def make_state_factory(cfg, opt, with_scores: bool):
    def make_state():
        key = jax.random.PRNGKey(0)
        params = M.init_params(cfg, key)
        scores = (PG.init_scores(cfg, params, jax.random.fold_in(key, 7))
                  if with_scores else None)
        tr = {"params": params, "scores": scores} if with_scores else params
        return {"params": params, "scores": scores,
                "opt": opt.init(tr), "step": 0}
    return make_state


def train(arch: str, steps: int = 50, batch: int = 8, seq: int = 128,
          lr: float = 1e-3, ckpt_dir: str | None = None, reduced: bool = True,
          checkpoint_every: int = 20, prune: bool = False,
          log_every: int = 10, seed: int = 0):
    cfg = get_config(arch)
    if reduced:
        cfg = cfg.reduced()
    if prune:
        pr = cfg.pruning
        cfg = cfg.replace(pruning=pr.__class__(
            block_size=16, r_b=0.5, r_t=1.0, lambda_reg=pr.lambda_reg))
    shape = ShapeConfig("custom", seq_len=seq, global_batch=batch,
                        kind="train")
    opt = AdamW(lr=lr)
    dc = DataConfig(seed=seed)

    if cfg.family == "vit":
        from repro.data import synthetic_vit_batch
        vstep = jax.jit(ST.make_vit_train_step(cfg, opt))

        def step_wrap(state, batch_np):
            b = {k: jnp.asarray(v) for k, v in batch_np.items()}
            params, opt_state, metrics = vstep(state["params"], state["opt"], b)
            return ({"params": params, "scores": None, "opt": opt_state,
                     "step": state["step"] + 1}, metrics)

        data_fn = lambda step: synthetic_vit_batch(cfg, batch, dc, step)
    else:
        step_fn = ST.make_train_step(cfg, opt, with_pruning=prune)
        jstep = jax.jit(step_fn)

        def step_wrap(state, batch_np):
            b = {k: jnp.asarray(v) for k, v in batch_np.items()}
            params, scores, opt_state, metrics = jstep(
                state["params"], state["opt"], b, state["scores"])
            return ({"params": params, "scores": scores, "opt": opt_state,
                     "step": state["step"] + 1}, metrics)

        data_fn = partial(synthetic_lm_batch, cfg, shape, dc,
                          local_batch=batch)

    losses = []
    if ckpt_dir:
        loop = RestartableLoop(
            CheckpointManager(ckpt_dir, keep=2),
            FaultConfig(checkpoint_every=checkpoint_every),
            make_state=make_state_factory(cfg, opt, prune),
            step_fn=step_wrap,
            data_fn=lambda s: data_fn(step=s),
            state_to_tree=lambda s: {"params": s["params"],
                                     "opt": s["opt"]},
            tree_to_state=lambda t, s: {**s, **t})
        out = loop.run(steps)
        return out

    state = make_state_factory(cfg, opt, prune)()
    t0 = time.time()
    for i in range(steps):
        state, metrics = step_wrap(state, data_fn(step=i))
        losses.append(float(metrics["loss"]))
        if i % log_every == 0 or i == steps - 1:
            print(f"step {i:4d} loss {losses[-1]:.4f} "
                  f"({(time.time()-t0)/(i+1):.2f}s/step)")
    return {"losses": losses, "state": state}


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--steps", type=int, default=50)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=128)
    ap.add_argument("--lr", type=float, default=1e-3)
    ap.add_argument("--ckpt", default=None)
    ap.add_argument("--reduced", action="store_true", default=True)
    ap.add_argument("--full", dest="reduced", action="store_false")
    ap.add_argument("--prune", action="store_true",
                    help="enable the paper's block weight pruning")
    args = ap.parse_args()
    enable_compile_cache()
    out = train(args.arch, args.steps, args.batch, args.seq, args.lr,
                args.ckpt, args.reduced, prune=args.prune)
    if "losses" in out:
        print(f"final loss: {out['losses'][-1]:.4f}")


if __name__ == "__main__":
    main()
