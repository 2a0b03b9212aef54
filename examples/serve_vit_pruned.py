"""Serving the simultaneously-pruned ViT — walkthrough of the vision
serving engine (the paper's system-level claim as software).

Pipeline:
  1. simultaneous pruning, hardened: score init -> hard block masks ->
     masked params (block-masked attention weights, the DBMM path for
     the MLPs);
  2. a continuous-batching ``VisionEngine``: image requests of mixed
     resolutions and per-request token keep rates admitted through the
     shared ``Scheduler`` (prune-pressure-aware policy), executed as
     per-stage segments; the ``TilePlanner`` (planner='full') prices the
     ragged population with the accelerator cost model each step and
     emits an ``ExecutionPlan`` — dense token-count tiles (bucket-merged
     when the model says padding is cheaper than a dispatch) plus fused
     express lanes for bucket-singleton requests;
  3. verification: every served logit vector matches the single-request
     offline path (``forward_vit_packed``) to float32 rounding, regardless
     of what else was in flight and of what the planner merged or fused;
  4. quality elasticity: the same stream re-served through a
     ``QualityController`` (quality='degrade') with per-request
     accuracy/latency preferences — consenting requests are tightened
     onto the controller's quantized keep-level grid (here the 0.55
     floor), ``quality='strict'`` requests are pinned to their base
     schedule, and soft-pruning requests fold dropped tokens into a
     weighted package token instead of discarding them. Every degraded
     logit still matches the offline path run at the schedule the
     controller resolved.

Run: PYTHONPATH=src python examples/serve_vit_pruned.py
"""
import dataclasses

import jax
import numpy as np

from repro.configs import get_config
from repro.core import packed_runner as PR
from repro.models import model as M
from repro.models import pruning_glue as PG
from repro.serving import VisionEngine, VisionEngineConfig, VisionRequest

# served logits match the offline walk to float32 rounding: batched,
# merged and fused programs may sum in another order
RTOL = ATOL = 1e-5


def main():
    cfg = get_config("deit-small").reduced()
    key = jax.random.PRNGKey(0)
    params = M.init_params(cfg, key)
    scores = PG.init_scores(cfg, params, jax.random.fold_in(key, 7))

    # --- 1. harden the pruning (offline, once per model) ------------------
    masked = PG.apply_pruning(cfg, params, scores)   # block-masked weights
    print(f"masked {4 * cfg.num_layers} attention weights "
          f"(block_size={cfg.pruning.block_size}, r_b={cfg.pruning.r_b}); "
          f"segment plan: {PR.vit_segments(cfg)}")

    # --- 2. a mixed request stream ----------------------------------------
    rng = np.random.default_rng(0)
    side = cfg.image_size // cfg.patch_size
    pdim = cfg.patch_size ** 2 * 3
    mixes = [(side ** 2, None), ((side - 1) ** 2, 0.5),
             ((side // 2) ** 2, 0.7), (side ** 2, 0.5),
             ((side - 1) ** 2, None), ((side // 2) ** 2, 0.5)]
    reqs = [VisionRequest(
        uid=i, patches=rng.standard_normal((n, pdim)).astype(np.float32),
        r_t=r_t, arrival_step=i // 2)
        for i, (n, r_t) in enumerate(mixes)]

    engine = VisionEngine(cfg, masked,
                          VisionEngineConfig(max_batch=3, planner="full"),
                          policy="prune_pressure_aware")
    out = engine.serve(reqs)
    st = engine.stats()
    print(f"served {st['images_served']} images in {st['steps']} engine "
          f"steps over {st['batcher_tiles']} tiles + "
          f"{st['plan_lanes']} express lanes "
          f"(merges {st['plan_merges']}, padding waste "
          f"{st['batcher_padding_waste']:.1%}, jit compiles "
          f"{st['jit_compile_count']} <= buckets+trajectories "
          f"{st['compile_budget']}, modeled saving "
          f"{st['plan_modeled_saving_ms']:.2f}ms)")
    admit_order = [uid for kind, uid in engine.events if kind == "admit"]
    print(f"admission order (prune-pressure-aware): {admit_order}")

    # --- 3. served logits vs the offline single-request path -------------
    for r in reqs:
        c = cfg if r.r_t is None else cfg.replace(
            pruning=dataclasses.replace(cfg.pruning, r_t=r.r_t))
        ref = PR.forward_vit_packed(c, masked, r.patches[None],
                                    segments=engine.segments)
        close = np.allclose(out[r.uid], np.asarray(ref.logits[0]),
                            rtol=RTOL, atol=ATOL)
        print(f"  uid {r.uid} ({r.n_patches:2d} patches, "
              f"r_t={r.r_t if r.r_t is not None else cfg.pruning.r_t}): "
              f"top-1 class {int(np.argmax(out[r.uid]))}, "
              f"matches offline: {close}")
        assert close, "batched serving must not change logits"

    # --- 4. quality-elastic serving ---------------------------------------
    # The controller maps scheduler pressure + per-request preference to a
    # per-step keep schedule at plan time. 'degrade' sheds load: every
    # consenting request drops to the grid floor; a request that asks for
    # quality='strict' keeps its base schedule; soft_prune=True swaps the
    # hard top-k drop for the package-token kernel (dropped tokens live on
    # as one score-weighted summary row).
    print("\nquality-elastic re-serve (degrade controller, floor 0.55):")
    qreqs = [VisionRequest(
        uid=i, patches=r.patches.copy(), r_t=r.r_t,
        arrival_step=r.arrival_step) for i, r in enumerate(reqs)]
    qreqs[1].quality = "strict"     # accuracy-critical: opts out
    qreqs[3].soft_prune = True      # latency-tolerant: package token
    qeng = VisionEngine(cfg, masked,
                        VisionEngineConfig(
                            max_batch=3, planner="full", quality="degrade",
                            keep_levels=(1.0, 0.85, 0.7, 0.55),
                            keep_floor=0.55),
                        policy="prune_pressure_aware")
    qout = qeng.serve(qreqs)
    qst = qeng.stats()
    print(f"tightened {qst['quality_tightened']}/"
          f"{qst['quality_decisions']} keep decisions onto grid levels "
          f"{qst['quality_levels_used']} (jit compiles "
          f"{qst['jit_compile_count']} <= {qst['compile_budget']})")
    for r in qreqs:
        # the reference schedule is whatever the controller resolved (the
        # resolution is pure, so we can replay it: strict preference pins
        # the base schedule; everyone else tightens down the grid — a
        # base rate already below the floor level is left untouched)
        base = PR.keep_schedule(cfg, r_t=r.r_t)
        sched = qeng.planner.quality.resolve(base, preference=r.quality)
        ref = PR.forward_vit_packed(cfg, masked, r.patches[None],
                                    schedule=sched, soft=r.soft_prune,
                                    segments=qeng.segments)
        close = np.allclose(qout[r.uid], np.asarray(ref.logits[0]),
                            rtol=RTOL, atol=ATOL)
        tag = ("strict" if r.quality == "strict"
               else "soft" if r.soft_prune else "hard")
        print(f"  uid {r.uid} ({tag:6s}): schedule {sched} -> top-1 "
              f"{int(np.argmax(qout[r.uid]))}, matches offline "
              f"at the resolved schedule: {close}")
        assert close, "the controller changes WHICH schedule runs, " \
                      "never the math"


if __name__ == "__main__":
    main()
