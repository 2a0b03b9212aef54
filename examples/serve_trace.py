"""Traffic & SLOs — replaying a bundled bursty trace through both engines.

Walkthrough of the ``repro.traffic`` subsystem:

  1. **Load a trace.** ``examples/traces/bursty_vision.jsonl`` is a
     12-request bursty (Markov-modulated Poisson) arrival stream with a
     0.05 ms virtual SLO per request, committed to the repo. Traces store
     request *descriptions* plus a content seed — not pixels — so the
     file is a few KB and replays byte-for-byte: its sha256 fingerprint
     is the workload's identity (bench artifacts record it).
  2. **Replay it on the virtual clock.** The ``TrafficHarness`` drives
     the ``VisionEngine`` tick by tick; time advances by the cost model's
     price of each dispatched step, so every latency / deadline verdict
     below is deterministic — same on any machine, any pipeline depth.
     With admission off, the served logits match calling
     ``engine.serve()`` directly, to float32 rounding (asserted).
  3. **Turn on admission control.** The burst overruns the engine;
     the cost-model ``AdmissionController`` bounds the modeled backlog,
     degrading consenting requests to the quality floor before rejecting
     (QualityController composition) — the queue stays bounded and the
     accepted requests keep their SLOs.
  4. **Same interface, LM engine.** The bundled
     ``examples/traces/bursty_lm.jsonl`` replays through ``ServeEngine``
     (continuous batching) with dispatched tokens priced onto the same
     virtual clock.

Run: PYTHONPATH=src python examples/serve_trace.py
"""
import os

import jax
import numpy as np

from repro.configs import get_config
from repro.models import model as M
from repro.models import pruning_glue as PG
from repro.serving import (EngineConfig, ServeEngine, VisionEngine,
                           VisionEngineConfig)
from repro.traffic import (LMDriver, TrafficHarness, VisionDriver,
                           load_trace, trace_fingerprint)

TRACE_DIR = os.path.join(os.path.dirname(__file__), "traces")


def show(tag, rep):
    print(f"  [{tag}] completed {rep['completed']}/{rep['offered']} "
          f"(rejected {rep['rejected']}) goodput={rep['goodput_rps']:.0f}/s "
          f"p50={rep['latency_p50_ms']:.3f}ms "
          f"p99={rep['latency_p99_ms']:.3f}ms "
          f"miss={rep['deadline_miss_rate']:.0%} "
          f"peak_queue={rep['peak_queue_depth']}")


def main():
    # --- 1. the bundled vision trace --------------------------------------
    trace = load_trace(os.path.join(TRACE_DIR, "bursty_vision.jsonl"))
    print(f"vision trace: {len(trace.requests)} requests, "
          f"offered {trace.offered_load_rps:.0f}/s, "
          f"fingerprint {trace_fingerprint(trace)[:16]}...")

    cfg = get_config("deit-small").reduced()
    key = jax.random.PRNGKey(0)
    params = M.init_params(cfg, key)
    scores = PG.init_scores(cfg, params, jax.random.fold_in(key, 7))
    masked = PG.apply_pruning(cfg, params, scores)

    def vision_engine(quality="strict"):
        return VisionEngine(cfg, masked, VisionEngineConfig(
            max_batch=2, planner="full", quality=quality))

    # --- 2. unbounded replay == the plain engine ---------------------------
    h = TrafficHarness(VisionDriver(vision_engine()))
    rep = h.run(trace)
    show("vision, unbounded", rep)

    eng = vision_engine()
    direct = eng.serve([VisionDriver(eng).materialize(t)
                        for t in trace.requests])
    assert sorted(direct) == sorted(h.outputs)
    for uid, logits in direct.items():
        np.testing.assert_allclose(h.outputs[uid], logits, rtol=1e-5,
                                   atol=1e-5)
    print("  unbounded replay serves engine.serve()'s logits")

    # --- 3. admission control: bounded backlog, degrade before reject -----
    h2 = TrafficHarness(VisionDriver(vision_engine(quality="auto")),
                        admission_limit_ms=0.03)
    rep2 = h2.run(trace)
    show("vision, admission", rep2)
    a = rep2["admission"]
    print(f"  admission verdicts: {a['accepts']} accepted, "
          f"{a['degrades']} degraded to the quality floor, "
          f"{a['rejects']} rejected "
          f"(queue {rep2['peak_queue_depth']} vs "
          f"{rep['peak_queue_depth']} unbounded)")
    assert rep2["peak_queue_depth"] <= rep["peak_queue_depth"]

    # --- 4. the LM engine behind the same interface -----------------------
    lm_trace = load_trace(os.path.join(TRACE_DIR, "bursty_lm.jsonl"))
    print(f"lm trace: {len(lm_trace.requests)} requests, "
          f"offered {lm_trace.offered_load_rps:.0f}/s")
    lm_cfg = get_config("stablelm-1.6b").reduced()
    lm = ServeEngine(lm_cfg, M.init_params(lm_cfg, jax.random.PRNGKey(0)),
                     EngineConfig(max_batch=2, max_len=128))
    rep3 = TrafficHarness(LMDriver(lm, per_token_ms=1.0)).run(lm_trace)
    show("lm, unbounded", rep3)


if __name__ == "__main__":
    main()
