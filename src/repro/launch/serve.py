"""Serving launcher: batched requests through the layered serving API
(Scheduler / KVCacheManager / ModelRunner composed by ServeEngine).

    PYTHONPATH=src python -m repro.launch.serve --arch qwen3-14b --reduced \\
        --requests 8 --max-new 16 --kv-prune 0.5

``--continuous`` serves through the slot-based continuous-batching path
(admission prefills only the admitted prompt via per-slot cache writes);
``--no-slot-prefill`` forces the PR-2 whole-batch re-prefill for A/B runs.
``--elastic-drop N`` additionally simulates losing half the devices after
``N`` engine steps, exercising the degradation_path replan + re-shard
(meaningful with >1 device, e.g. under
``XLA_FLAGS=--xla_force_host_platform_device_count=4``).

Demonstrates the beyond-paper dynamic KV-cache pruning (the paper's token
scoring adapted to decode) on a runnable reduced model.
"""
from __future__ import annotations

import argparse
import json
import tempfile
import time

import jax
import numpy as np

from repro.configs import get_config
from repro.launch.compile_cache import enable_compile_cache
from repro.models import model as M
from repro.obs import MetricsRegistry, Tracer
from repro.serving import ElasticContext, EngineConfig, Request, ServeEngine


def simulated_loss_context(params, drop_after: int,
                           directory: str) -> ElasticContext:
    """ElasticContext that reports full capacity for ``drop_after`` probes,
    then half the devices forever after (the checkpoint holding ``params``
    is written into ``directory``)."""
    from repro.checkpoint import CheckpointManager
    from repro.dist.elastic import MeshPlan

    ndev = jax.device_count()
    manager = CheckpointManager(directory, keep=1)
    manager.save(0, params)
    degraded = max(ndev // 2, 1)
    probes = {"n": 0}

    def device_count() -> int:
        probes["n"] += 1
        return ndev if probes["n"] <= drop_after else degraded

    return ElasticContext(
        manager=manager,
        plan=MeshPlan((ndev, 1), ("data", "model")),
        budgets=[degraded, 1],
        device_count=device_count)


def serve(arch: str, num_requests: int = 8, prompt_len: int = 16,
          max_new: int = 16, kv_prune: float = 1.0, reduced: bool = True,
          max_batch: int = 4, seed: int = 0, continuous: bool = False,
          elastic_drop: int = 0, per_slot_prefill: bool = True,
          policy: str = "fifo", pipeline_depth: int = 1,
          trace_out: str = "", metrics_out: str = ""):
    if elastic_drop and not continuous:
        raise ValueError("--elastic-drop requires --continuous: only the "
                         "slot path probes device_count() between steps")
    cfg = get_config(arch)
    if reduced:
        cfg = cfg.reduced()
    params = M.init_params(cfg, jax.random.PRNGKey(seed))
    ec = EngineConfig(
        max_batch=max_batch,
        max_len=prompt_len + 2 * max_new + 8,
        kv_prune_interval=4 if kv_prune < 1.0 else 0,
        kv_prune_keep=kv_prune,
        per_slot_prefill=per_slot_prefill,
        pipeline_depth=pipeline_depth)
    rng = np.random.default_rng(seed)
    reqs = [Request(uid=i,
                    prompt=rng.integers(0, cfg.vocab_size, prompt_len,
                                        dtype=np.int32),
                    max_new_tokens=max_new)
            for i in range(num_requests)]
    tracer = Tracer() if trace_out else None
    with tempfile.TemporaryDirectory(prefix="elastic_") as ckpt_dir:
        elastic = (simulated_loss_context(params, elastic_drop, ckpt_dir)
                   if elastic_drop else None)
        engine = ServeEngine(cfg, params, ec, elastic=elastic,
                             policy=policy, tracer=tracer)
        t0 = time.time()
        out = engine.serve(reqs, continuous=continuous)
        dt = time.time() - t0
    if trace_out:
        tracer.write_chrome_trace(trace_out)
    if metrics_out:
        engine.export_metrics(MetricsRegistry()).write_json(metrics_out)
    total_tokens = sum(len(v) for v in out.values())
    return {"outputs": out, "seconds": dt,
            "tokens_per_s": total_tokens / dt,
            "events": list(engine.events),
            "stats": engine.stats()}


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--requests", type=int, default=8)
    ap.add_argument("--prompt-len", type=int, default=16)
    ap.add_argument("--max-new", type=int, default=16)
    ap.add_argument("--kv-prune", type=float, default=1.0)
    ap.add_argument("--max-batch", type=int, default=4)
    ap.add_argument("--reduced", action=argparse.BooleanOptionalAction,
                    default=True,
                    help="serve the tiny CPU-test preset of --arch; "
                         "--no-reduced serves its published widths")
    ap.add_argument("--continuous", action="store_true",
                    help="serve through the slot-based continuous path")
    ap.add_argument("--no-slot-prefill", action="store_true",
                    help="force PR-2 whole-batch re-prefill on admission")
    ap.add_argument("--elastic-drop", type=int, default=0, metavar="N",
                    help="simulate losing half the devices after N steps")
    ap.add_argument("--policy", default="fifo",
                    help="admission policy: fifo | shortest_prompt_first "
                         "| prune_pressure_aware (shared with the vision "
                         "path)")
    ap.add_argument("--pipeline-depth", type=int, default=1,
                    help="StepPipeline depth for the continuous path: 1 "
                         "= synchronous stepping (the reference path), 2 "
                         "= stage step N+1 while the device executes "
                         "step N (bit-exact)")
    ap.add_argument("--trace-out", default="", metavar="PATH",
                    help="write a Chrome trace_event JSON (Perfetto-"
                         "loadable) of the run's plan/stage/dispatch/"
                         "complete spans to PATH at exit")
    ap.add_argument("--metrics-out", default="", metavar="PATH",
                    help="write the engine's metrics-registry snapshot "
                         "(JSON) to PATH at exit")
    ap.add_argument("--json", action="store_true",
                    help="print a machine-readable result line")
    args = ap.parse_args()
    enable_compile_cache()
    out = serve(args.arch, args.requests, args.prompt_len, args.max_new,
                args.kv_prune, args.reduced, max_batch=args.max_batch,
                continuous=args.continuous, elastic_drop=args.elastic_drop,
                per_slot_prefill=not args.no_slot_prefill,
                policy=args.policy, pipeline_depth=args.pipeline_depth,
                trace_out=args.trace_out, metrics_out=args.metrics_out)
    if args.json:
        print(json.dumps({
            "outputs": {str(k): v for k, v in out["outputs"].items()},
            "tokens_per_s": out["tokens_per_s"],
            "events": out["events"],
            "stats": out["stats"]}))
        return
    st = out["stats"]
    print(f"served {args.requests} requests in {out['seconds']:.2f}s "
          f"({out['tokens_per_s']:.1f} tok/s)")
    print(f"  admissions: {st['admissions']}, prefilled "
          f"{st['prefill_tokens_per_admission']:.1f} tok/admission, "
          f"{st['jit_compile_count']} jit compiles, "
          f"{st['prune_events']} KV prunes")
    for uid, toks in sorted(out["outputs"].items()):
        print(f"  req {uid}: {toks[:8]}{'...' if len(toks) > 8 else ''}")
    for ev in out["events"]:
        if ev[0] == "degrade":
            print(f"  degraded to mesh {ev[1]}")


if __name__ == "__main__":
    main()
