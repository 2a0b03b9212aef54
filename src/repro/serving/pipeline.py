"""StepPipeline — pipelined step execution shared by both serving engines.

The paper's accelerator overlaps on-the-fly token pruning with compute via
multi-level parallelism; the software engines used to run every step
synchronously (plan -> dispatch -> block), leaving the host idle while the
device ran and vice versa. This module is the runtime half of the fix: a
step is split into three phases and only the last one ever waits.

    stage     (engine) build the ExecutionPlan and the padded input
              buffers for step N. Pure host bookkeeping plus data-movement
              ops on device handles — it mutates no shared engine state it
              cannot roll back, so a staged step can still be dropped and
              replanned (e.g. a request was submitted mid-step and belongs
              in this plan).
    dispatch  (pipeline) enqueue step N's jitted segment calls. JAX's
              async dispatch returns pending arrays immediately; nothing
              here blocks. Host mirrors (seg_idx, cache lengths, token
              chains) advance now, because they are deterministic given
              the plan — the enabler for computing plan N+1 while the
              device still executes plan N.
    complete  (pipeline) block on the outputs step N's ``complete``
              callback reads on the host (logits, generated tokens), then
              run the callback.

What ``complete`` waits for: only the handles ``dispatch`` returns, which
are the outputs the host reads. Outputs that stay on the device for a
later step (``StagedStep.outputs``: a vision tile's activations, which the
next step takes whole) are not waited on, because the device orders the
consuming step after them anyway. A step with no host-read output
completes without waiting: its callback still runs (counters, records),
and the host goes on to stage the next step while the device works. A
later step that is waited on (a head's logits) waits for the whole chain
before it.

Run-ahead bound: after ``max_unwaited`` unwaited steps in a row, the next
step with nothing to read waits on its device outputs, so the host never
queues unread steps without limit. The vision engine sets the bound to
the length of its model's segment plan, so a request's whole trajectory
fits; the default 0 waits on every step.

``depth`` bounds how many dispatched-but-incomplete steps may be in
flight. Depth 1 completes each step inside :meth:`submit`: a step that
carries logits has them on the host when ``submit`` returns. Depth 2
double-buffers: while the device executes step N, the host stages step
N+1, and step N is completed only when N+1's dispatch has been enqueued.
Results are bit-exact at any depth — the pipeline reorders *waiting*, not
math: every step's inputs are fully determined at its stage time.

This module still owns the engines' ONLY ``jax.block_until_ready`` call
site (CI greps for strays); everything upstream must hand the pipeline
handles instead of blocking.
"""
from __future__ import annotations

import dataclasses
import time
from collections import deque
from typing import Any, Callable, Deque, Dict, Optional

import jax

from repro.obs.trace import NULL_TRACER, Tracer

__all__ = ["StagedStep", "StepPipeline", "StepReport"]


@dataclasses.dataclass(frozen=True)
class StepReport:
    """What one engine ``tick`` did, in host-deterministic terms.

    Both engines' incremental step APIs (``VisionEngine.tick``,
    ``ServeEngine.tick_continuous``) return one of these so external
    drivers — the trace-replay harness in ``repro.traffic`` foremost — can
    account request lifecycles on a *virtual* clock: every field is known
    at dispatch time from host bookkeeping alone (no device sync), and is
    identical at every pipeline depth for the same request stream.

    ``dispatched``   — whether the tick put a step on the device (False =
                       idle bookkeeping tick: nothing admitted/running).
    ``modeled_ms``   — the cost model's price of the dispatched step
                       (vision: the committed ``ExecutionPlan``'s modeled
                       cycles; LM engines leave it 0 and report
                       ``work_tokens`` for the driver to price).
    ``work_tokens``  — tokens this step dispatched (LM: prefilled +
                       decoded; vision engines leave it 0).
    ``admitted``     — uids that entered slots this tick (their first
                       segment/prefill dispatches in this very step).
    ``completed``    — uids whose final segment/token was dispatched this
                       tick; their host-visible outputs materialize when
                       the pipeline completes the step.
    """

    dispatched: bool
    modeled_ms: float = 0.0
    work_tokens: int = 0
    admitted: tuple = ()
    completed: tuple = ()


@dataclasses.dataclass
class StagedStep:
    """One fully-staged engine step awaiting dispatch.

    ``dispatch`` enqueues the device work and returns the handles of the
    outputs the host reads (empty when it reads none), which completion
    blocks on; ``outputs`` (optional) holds the step's other device
    outputs, which a later step consumes on the device and completion
    waits on only at the run-ahead bound; ``complete`` runs after any
    block and materializes host-visible results (``waited`` says whether
    the pipeline blocked first); ``rollback`` (optional) undoes any host-mirror
    mutations staging made, so the step can be dropped pre-dispatch when a
    replan invalidates it (mid-step submission). Once dispatched, a step
    can no longer be dropped — device work is in flight."""

    dispatch: Callable[[], Any]
    complete: Callable[[Any], None]
    rollback: Optional[Callable[[], None]] = None
    label: str = ""
    handles: Any = None
    outputs: Any = None
    dispatched: bool = False
    completed: bool = False
    waited: bool = False
    modeled_ms: float = 0.0   # the cost model's price of this step (vision
    # engines set it from the staged ExecutionPlan; 0 = unmodeled). Paired
    # with the measured dispatch+block wall time at completion, this is
    # the per-step modeled-vs-measured sample behind the calibration-drift
    # metric (pipeline stats: modeled_ms_total / measured_ms_total); an
    # unwaited step blocks 0 s, and a later waited step's block covers
    # what of its device work the host did not overlap.
    dispatch_wall_s: float = 0.0  # wall seconds this step's dispatch took
    # (pipeline-recorded; the complete phase adds its block time to form
    # the measured cost)


class StepPipeline:
    """Bounded in-flight window of engine steps.

    ``depth`` = max steps dispatched but not yet completed. ``submit``
    dispatches the new step, then completes the oldest in-flight steps
    until at most ``depth - 1`` remain — so depth 1 completes every step
    inside ``submit`` and depth 2 keeps exactly one step on the device
    while the host stages the next.

    Completing a step blocks only on its host-read handles; a step with
    none goes unwaited unless ``max_unwaited`` steps in a row already
    did, and then waits on its device ``outputs`` (module docstring).
    """

    def __init__(self, depth: int = 1, tracer: Optional[Tracer] = None,
                 max_unwaited: int = 0):
        if depth < 1:
            raise ValueError(f"pipeline depth must be >= 1, got {depth}")
        if max_unwaited < 0:
            raise ValueError(f"max_unwaited must be >= 0, got "
                             f"{max_unwaited}")
        self.depth = depth
        self.max_unwaited = max_unwaited
        self._unwaited_run = 0   # unwaited completions since the last wait
        # wall-clock span tracer (repro.obs): dispatch/complete spans on
        # the "pipeline" track, complete split into block (waiting for the
        # device) and to_host (the step's complete callback). Disabled by
        # default — one attribute check per phase; it observes timing
        # only, never reorders work
        self.tracer = tracer if tracer is not None else NULL_TRACER
        self._inflight: Deque[StagedStep] = deque()
        # accounting (the bench's wall_vs_device column reads these)
        self.steps = 0           # steps dispatched
        self.drops = 0           # staged steps dropped pre-dispatch
        self.overlap_hits = 0    # waited completions whose handles were
        #                          already ready — the device finished
        #                          while the host was staging (overlap
        #                          realized)
        self.steps_unwaited = 0  # completions that blocked on nothing:
        #                          the host read no output of the step
        self.block_s = 0.0       # wall seconds inside block_until_ready
        self.dispatch_s = 0.0    # wall seconds enqueueing device work
        self.modeled_ms_total = 0.0   # sum of completed steps' cost-model
        #                               prices (steps with modeled_ms > 0)
        self.measured_ms_total = 0.0  # their measured dispatch+block wall
        #                               ms — modeled vs measured is the
        #                               calibration-drift signal
        self.starved_s = 0.0     # wall seconds the device spent with NO
        #                          step in flight — the host was planning/
        #                          staging while the device sat idle. This
        #                          is the quantity double-buffering
        #                          removes, and it is meaningful even when
        #                          host and device share cores (CPU): it
        #                          measures queue emptiness, not wall
        #                          speedup.
        self._idle_since = time.perf_counter()

    # -- lifecycle ----------------------------------------------------------
    def submit(self, step: StagedStep) -> None:
        """Dispatch ``step`` and drain completions down to ``depth - 1``
        in-flight steps."""
        tr = self.tracer
        t0 = time.perf_counter()
        if not self._inflight and self._unwaited_run == 0:
            # the device queue was empty for the whole host-side gap since
            # it last drained — that gap is device starvation (after an
            # unwaited completion the device may still be busy with it)
            self.starved_s += t0 - self._idle_since
        if tr.enabled:
            tr.begin("dispatch", track="pipeline", label=step.label)
        step.handles = step.dispatch()
        step.dispatched = True
        if tr.enabled:
            tr.end("dispatch", track="pipeline")
        step.dispatch_wall_s = time.perf_counter() - t0
        self.dispatch_s += step.dispatch_wall_s
        self.steps += 1
        self._inflight.append(step)
        while len(self._inflight) > self.depth - 1:
            self._complete_oldest()

    def drop(self, step: StagedStep) -> None:
        """Discard a staged-but-not-dispatched step (a replan invalidated
        it); runs its rollback so staged host-mirror state resets."""
        if step.dispatched:
            raise RuntimeError("cannot drop a dispatched step: its device "
                               "work is already in flight")
        if step.rollback is not None:
            step.rollback()
        self.drops += 1

    def flush(self) -> None:
        """Complete every in-flight step (end of serve, or before an
        operation that must observe fully-materialized state, e.g. an
        elastic rebuild). As in :meth:`submit`, a step with no host-read
        output completes without waiting for the device."""
        while self._inflight:
            self._complete_oldest()

    def _complete_oldest(self) -> None:
        step = self._inflight.popleft()
        tr = self.tracer
        # block on what the host reads; at the run-ahead bound, on the
        # step's device outputs, or, where later in-flight steps already
        # consumed them, on theirs (the device runs those after this one)
        leaves = _live_leaves(step.handles)
        if not leaves and self._unwaited_run >= self.max_unwaited:
            leaves = (_live_leaves(step.outputs)
                      or _live_leaves([s.outputs for s in self._inflight]))
        step.waited = bool(leaves)
        if step.waited and all(l.is_ready() for l in leaves
                               if hasattr(l, "is_ready")):
            self.overlap_hits += 1
        if tr.enabled:
            tr.begin("complete", track="pipeline", label=step.label)
        block = 0.0
        if step.waited:
            self._unwaited_run = 0
            if tr.enabled:
                tr.begin("block", track="pipeline")
            t0 = time.perf_counter()
            jax.block_until_ready(leaves)
            block = time.perf_counter() - t0
            self.block_s += block
            if tr.enabled:
                tr.end("block", track="pipeline")
        else:
            self._unwaited_run += 1
            self.steps_unwaited += 1
        if tr.enabled:
            tr.begin("to_host", track="pipeline")
        step.complete(step.handles)
        step.completed = True
        if tr.enabled:
            tr.end("to_host", track="pipeline")
            tr.end("complete", track="pipeline")
        if step.modeled_ms > 0.0:
            # dispatch wall + block wall brackets the device's work for
            # this step (exactly the bench's device-busy proxy), measured
            # per step so drift against the cost model is attributable
            self.modeled_ms_total += step.modeled_ms
            self.measured_ms_total += (step.dispatch_wall_s + block) * 1e3
        if not self._inflight:
            self._idle_since = time.perf_counter()

    # -- observability ------------------------------------------------------
    @property
    def in_flight(self) -> int:
        return len(self._inflight)

    def stats(self) -> Dict[str, Any]:
        return {
            "depth": self.depth,
            "steps": self.steps,
            "drops": self.drops,
            "overlap_hits": self.overlap_hits,
            "steps_unwaited": self.steps_unwaited,
            "block_s": self.block_s,
            "dispatch_s": self.dispatch_s,
            "starved_s": self.starved_s,
            "modeled_ms_total": self.modeled_ms_total,
            "measured_ms_total": self.measured_ms_total,
            # signed relative drift of the cost model against measured
            # wall time ((modeled - measured) / measured): the closed-loop
            # adaptation signal; 0.0 until a modeled step completes
            "cost_error": ((self.modeled_ms_total - self.measured_ms_total)
                           / self.measured_ms_total
                           if self.measured_ms_total > 0.0 else 0.0),
        }


def _live_leaves(tree) -> list:
    """The array leaves of ``tree`` that still hold a buffer: a later step
    may already have consumed (donated) an output, and the device orders
    that step's work after this one's, so the rest say when it is done."""
    return [l for l in jax.tree_util.tree_leaves(tree)
            if not (hasattr(l, "is_deleted") and l.is_deleted())]
