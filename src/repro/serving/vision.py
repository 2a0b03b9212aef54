"""VisionEngine — continuous-batching inference for the packed, pruned ViT.

The paper's headline system claim is an accelerator that *serves* the
simultaneously-pruned ViT: multi-level parallelism plus load balancing for
the irregular work left by block-pruned weights and on-the-fly token
pruning. This engine is the software twin of that serving layer:

* Admission rides the same ``Scheduler`` as the LM path (one unified
  admit/retire/degrade event stream, policy-pluggable — FIFO,
  shortest-prompt-first, prune-pressure-aware).
* Execution walks the per-stage segmentation of ``forward_vit_packed``
  (``core.packed_runner.vit_segments``): prune boundaries are batching
  boundaries. Each engine step advances every in-flight image one segment.
* Between segments the ``TilePlanner`` (``serving.planner``) prices the
  ragged population with the accelerator cost model and emits an
  ``ExecutionPlan``: dense token-count tiles (grouped by the
  ``RaggedBatcher``, optionally bin-packed/merged when the modeled padding
  cost is below the dispatch saving), express-lane fused trajectories for
  bucket-singleton requests, and deadline-driven tile splits/ordering for
  requests carrying a ``deadline_ms``. Jit recompiles are bounded by the
  bucket ∪ trajectory set. ``VisionEngineConfig.planner="off"`` (default)
  is the identity plan — exactly PR 4's ``RaggedBatcher.plan`` behavior.

Bit-exactness: in the default ``balanced`` mode with ``token_tile=1``,
buckets hold requests at *identical* token counts, the batch dimension is
padded with don't-care rows (rows are computationally independent), and the
jitted segment bodies are the same pure functions the offline
single-request path composes — so every request's logits are bit-exact
against ``forward_vit_packed`` regardless of batch composition
(tests/test_vision_engine.py). ``token_tile > 1`` and ``naive`` mode
token-pad rows inside masked kernels: same math, FP reduction order may
differ.

Requests may carry per-request keep rates (``r_t``) and arbitrary patch
counts (images of different resolutions) — both are sources of raggedness;
``arrival_step`` staggers admission so the population mixes stages, the
continuous-batching scenario.
"""
from __future__ import annotations

import dataclasses
import math
import time
from typing import Any, Callable, Dict, List, Optional, Sequence, Tuple

import numpy as np
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.core import packed_runner as PR
from repro.core import quant as Q
from repro.kernels.backend import default_interpret
from repro.kernels.sbmm.sbmm import FP16_UNSUPPORTED
from repro.obs.metrics import MetricsRegistry
from repro.obs.trace import NULL_TRACER, Tracer
from repro.serving.planner import (PLANNER_MODES, PlanItem, TileCostModel,
                                   TilePlanner)
from repro.serving.pipeline import StagedStep, StepPipeline, StepReport
from repro.serving.quality import (QUALITY_MODES, QualityConfig,
                                   QualityController)
from repro.serving.ragged_batcher import RaggedBatcher
from repro.serving.scheduler import Scheduler

__all__ = ["VisionRequest", "VisionEngineConfig", "VisionEngine"]


@dataclasses.dataclass
class VisionRequest:
    uid: int
    patches: np.ndarray              # [n_patches, patch²·3] float32
    r_t: Optional[float] = None      # per-request TDM keep rate (None = cfg)
    arrival_step: int = 0            # engine step at which it may be admitted
    deadline_ms: Optional[float] = None  # wall-clock SLO from admission; the
    # planner carves the request into smaller, first-dispatched tiles when
    # its modeled slack runs out, and the admission annotation below shrinks
    # so prune_pressure_aware admits tight-deadline requests earlier
    keep_schedule: Optional[Tuple[float, ...]] = None  # explicit per-TDM
    # keep schedule (one entry per TDM segment, in segment order) —
    # overrides r_t; None broadcasts r_t over every TDM step
    quality: Optional[str] = None    # accuracy/latency preference for the
    # QualityController: "strict" pins the base schedule even under load,
    # "degrade" invites maximum tightening, None follows the engine mode.
    # Ignored (bit-exactly) while the engine controller is off.
    soft_prune: bool = False         # serve with the soft-pruning TDM:
    # dropped tokens fold into a persistent package token instead of being
    # re-fused per layer (keeps accuracy honest at aggressive keep rates)
    logits: Optional[np.ndarray] = None
    done: bool = False
    prune_load: Optional[float] = None   # predicted post-prune token load
    # (sum of the per-segment token counts, deadline-discounted; set at
    # submit and REFRESHED each admission pass for waiting deadline
    # requests — the prune_pressure_aware admission policy reads it)
    prune_load_base: Optional[float] = None  # undiscounted load (engine-set)
    solo_ms: Optional[float] = None  # modeled solo latency (engine-set)
    submit_t: Optional[float] = None  # monotonic submit time (engine-set;
    # waiting time consumes deadline slack in the refresh)
    enqueue_s: Optional[float] = None  # perf_counter enqueue time
    # (engine-set while tracing: the start of the request's queued span)

    @property
    def n_patches(self) -> int:
        return int(self.patches.shape[0])


@dataclasses.dataclass
class VisionEngineConfig:
    max_batch: int = 8        # in-flight image slots
    token_tile: int = 1       # bucket quantization (1 = exact, bit-exact)
    mode: str = "balanced"    # 'balanced' buckets | 'naive' pad-to-max
    planner: str = "off"      # TilePlanner mode: off|merge|fuse|full
    use_tdm: Optional[bool] = None   # None = cfg.pruning.token_pruning_enabled
    pipeline_depth: int = 1   # StepPipeline depth: 1 = synchronous,
    # 2 = double-buffered (host plans/stages step N+1 while the device
    # executes step N; results bit-exact at any depth)
    quality: str = "strict"   # QualityController mode: strict = off
    # (bit-exact with the pre-controller path), auto = tighten keep rates
    # with queue/deadline pressure, degrade = shed-load floor
    keep_levels: Tuple[float, ...] = (1.0, 0.85, 0.7, 0.55, 0.4)
    # quantized keep-rate grid the controller resolves onto (bounds the
    # distinct TDM k values, hence recompiles)
    keep_floor: float = 0.4   # no request is ever tightened below this
    precision: str = "fp32"   # serving precision tier: "fp32" is the
    # bit-exact reference path; "fp16"/"int8" make that tier available to
    # the planner, which prices each request's trajectory at both fp32 and
    # the tier and picks the cheaper (fp32 ties win). Requests with
    # quality="strict" are always pinned to fp32. Encoder segments only —
    # embed and head run fp32 at every tier.
    quant_granularity: str = "channel"  # int8 scale granularity:
    # "block" = one scale per kept block, "channel" = per output channel

    def __post_init__(self):
        if self.precision not in Q.PRECISIONS:
            raise ValueError(f"VisionEngineConfig.precision must be one of "
                             f"{Q.PRECISIONS}, got {self.precision!r}")
        if self.quant_granularity not in Q.GRANULARITIES:
            raise ValueError(f"VisionEngineConfig.quant_granularity must be "
                             f"one of {Q.GRANULARITIES}, "
                             f"got {self.quant_granularity!r}")
        if self.max_batch <= 0:
            raise ValueError(f"VisionEngineConfig.max_batch must be a "
                             f"positive slot count, got {self.max_batch}")
        if self.pipeline_depth <= 0:
            raise ValueError(f"VisionEngineConfig.pipeline_depth must be "
                             f">= 1, got {self.pipeline_depth}")
        if self.token_tile <= 0:
            raise ValueError(f"VisionEngineConfig.token_tile must be "
                             f"positive, got {self.token_tile}")
        if self.mode not in ("balanced", "naive"):
            raise ValueError(f"VisionEngineConfig.mode must be 'balanced' "
                             f"or 'naive', got {self.mode!r}")
        if self.planner not in PLANNER_MODES:
            raise ValueError(f"VisionEngineConfig.planner must be one of "
                             f"{PLANNER_MODES}, got {self.planner!r}")
        if self.planner != "off" and self.mode != "balanced":
            raise ValueError(f"planner {self.planner!r} requires "
                             f"mode='balanced' (got {self.mode!r})")
        # delegate grid/floor/mode validation to the config the controller
        # is built from (one source of truth for the constraints)
        self.quality_config = QualityConfig(mode=self.quality,
                                            keep_levels=self.keep_levels,
                                            keep_floor=self.keep_floor)


@dataclasses.dataclass
class DeviceOps:
    """Device work the engine issues outside its segment programs, for one
    step or in total. Each eager ``jnp`` call is a device program of its
    own (``jit_<primitive>`` on the device trace), so the counts follow
    what each call launches:

    * ``jnp.pad``: one program, and its fill value is one put;
    * ``jnp.zeros`` of a row: two programs (convert the fill value,
      broadcast it), one put; of a scalar: one program, one put;
    * ``jnp.stack`` of m arrays: one ``expand_dims`` each, then
      concatenates in groups of 16 (:func:`_stack_programs`);
    * ``y[b, :n]`` (or ``y[b]``): a ``dynamic_slice`` and a ``squeeze``,
      and each start index (one per axis of ``y``) is one put;
    * ``x[None]`` and ``reshape``: one program;
    * a host array made a device array (a batch of patches stacked on the
      host, a lane's patches, ``n_valid``): one put.

    A tile passed through (the previous tile's output, whole) launches
    nothing. A member's rows are sliced out of a tile's output only when
    a later tile or lane cannot take that output whole, so the slice is
    counted in the step that stages it. Logits reach the host as one
    copy of each head or lane output, which launches nothing either.
    """
    eager_ops: int = 0   # eager device programs
    h2d_puts: int = 0    # host-to-device copies
    to_host: int = 0     # logit rows delivered to the host
    passthrough_tiles: int = 0  # tiles staged as the previous output whole

    def add(self, other: "DeviceOps") -> None:
        self.eager_ops += other.eager_ops
        self.h2d_puts += other.h2d_puts
        self.to_host += other.to_host
        self.passthrough_tiles += other.passthrough_tiles


def _stack_programs(m: int) -> int:
    """Device programs ``jnp.stack`` launches for ``m`` arrays: an
    ``expand_dims`` per array, then rounds of concatenates over groups of
    16 until one array is left (a group of one passes through)."""
    n = m
    while m > 1:
        n += m // 16 + (m % 16 > 1)
        m = -(-m // 16)
    return n


@dataclasses.dataclass(frozen=True)
class _Rows:
    """Row ``b`` of the output ``y`` of a tile: where a member's value
    lives once its tile ran. Kept as a reference, so that a tile made of
    exactly that output's rows takes ``y`` whole, and other paths slice
    it only when they need the member on its own."""
    y: Any
    b: int

    def take(self, n: Optional[int], ops: DeviceOps):
        """``y[b, :n]`` (``y[b]`` for ``n=None``), counted into ``ops``."""
        ops.eager_ops += 2
        ops.h2d_puts += self.y.ndim
        return self.y[self.b] if n is None else self.y[self.b, :n]


@dataclasses.dataclass
class _Live:
    """Per-slot in-flight state: the request, its current activation
    (unpadded — padding is a per-tile concern) and where it is in the
    segment plan."""
    req: VisionRequest
    seg_idx: int
    x: Any               # host patches (pre-embed), or the _Rows of the
    # tile output holding its [n_tokens, D] activations
    n_tokens: int        # real rows of x (grouping key)
    schedule: Tuple[float, ...]  # BASE per-TDM keep schedule (static per
    # request; the QualityController resolves the *effective* schedule
    # from it at every staging pass — already-executed entries are baked
    # into n_tokens and never revisited)
    soft: bool = False   # package-token soft TDM for this request
    pkg_mass: Any = None  # accumulated package mass (_Rows of a soft TDM
    # tile's [B] mass) after the first soft TDM; updated at dispatch like
    # x/n_tokens
    admit_t: float = 0.0  # monotonic admission time (deadline slack base)
    admit_s: float = 0.0  # perf_counter admission time (tracing only)
    precision: str = "fp32"  # execution precision chosen at admission
    # (planner-priced; "strict" quality pins fp32) — static per request so
    # its stage keys, and therefore its tiles, stay precision-uniform


class VisionEngine:
    """Single-host reference engine for packed-ViT serving. Exposes the
    layers as ``.scheduler`` / ``.planner`` (owning ``.batcher``) /
    ``.segments`` for tests, policies, and telemetry (mirroring
    ``ServeEngine``'s three layers)."""

    def __init__(self, cfg: ModelConfig, params: Dict, packed: Dict,
                 vc: Optional[VisionEngineConfig] = None,
                 policy: "str | Callable" = "fifo",
                 cost_model: Optional[TileCostModel] = None,
                 tracer: Optional[Tracer] = None):
        if cfg.family != "vit":
            raise ValueError(f"VisionEngine serves the 'vit' family, "
                             f"got {cfg.family!r}")
        self.cfg = cfg
        self.vc = vc if vc is not None else VisionEngineConfig()
        if self.vc.precision == "fp16" and not default_interpret():
            raise ValueError(FP16_UNSUPPORTED)
        # the engine never re-reads a dispatched tile's batch: it is a
        # fresh padded stack, or an output passed through whole, whose
        # every row belongs to the tile's members (each then moves on to
        # the new output). So layers tiles can donate their input buffers
        # to the output allocation
        self.segments = PR.PackedVitSegments(
            cfg, params, packed, use_tdm=self.vc.use_tdm,
            donate_activations=True,
            quant_granularity=self.vc.quant_granularity)
        self.scheduler = Scheduler(self.vc.max_batch, policy=policy)
        self.batcher = RaggedBatcher(token_tile=self.vc.token_tile,
                                     mode=self.vc.mode,
                                     max_batch=self.vc.max_batch)
        self.planner = TilePlanner(
            self.batcher,
            cost_model if cost_model is not None else TileCostModel(cfg),
            mode=self.vc.planner,
            quality=QualityController(self.vc.quality_config,
                                      num_slots=self.vc.max_batch))
        self._live: Dict[int, _Live] = {}   # slot -> state
        # not-yet-arrived requests as (absolute arrival step, request):
        # arrival_step is relative to the serve() call that submitted it,
        # so identical request streams replay identically (warmup == run)
        self._pending: List[Any] = []
        # wall-clock span tracer (repro.obs): plan/stage spans here, the
        # pipeline adds dispatch/complete. NULL_TRACER default = one
        # attribute check per guarded region; traces observe wall time
        # only and never perturb the dispatched math (CI asserts digest
        # equality traced vs untraced)
        self.tracer = tracer if tracer is not None else NULL_TRACER
        self.pipeline = StepPipeline(self.vc.pipeline_depth,
                                     tracer=self.tracer)
        # speculative next-step plan from plan_ahead: (population
        # fingerprint it is valid for, plan). Consumed on fingerprint
        # match; dropped (and replanned) when admissions/retirements made
        # the prediction stale.
        self._plan_cache: Optional[Any] = None
        # bucket keys of tiles dispatched from a stacked batch: the first
        # tile of a bucket is always stacked, so the eager programs a
        # ragged tile may later need (row slices of this bucket's inputs,
        # pads, the stack) compile with its segment program, at warm-up,
        # and not in a later step
        self._stacked: set = set()
        self.plan_ahead_hits = 0
        self.plan_ahead_drops = 0
        self.steps = 0
        self.images_served = 0
        # device work outside the segment programs, summed over completed
        # steps (always counted; a traced step also logs its own)
        self.device_ops = DeviceOps()
        # quantization observability: tiles+lanes dispatched per precision,
        # and how many of those went through the dequant-in-kernel int8
        # SBMM path (counted at the dispatch phase, like planner.commit)
        self.precision_dispatches: Dict[str, int] = {
            p: 0 for p in Q.PRECISIONS}
        self.dequant_dispatches = 0
        self._n_patches_max = (cfg.image_size // cfg.patch_size) ** 2
        self._use_tdm = (cfg.pruning.token_pruning_enabled
                         if self.vc.use_tdm is None else self.vc.use_tdm)
        # TDM ordinal bookkeeping: _tdm_before[si] = how many TDM segments
        # precede plan index si — the keep-schedule index of the NEXT TDM
        # a request at seg_idx=si will hit (executed entries are history)
        self._tdm_before: List[int] = []
        n_tdm = 0
        for seg in self.segments.plan:
            self._tdm_before.append(n_tdm)
            if seg[0] == "tdm":
                n_tdm += 1
        self._tdm_before.append(n_tdm)  # seg_idx == len(plan) (finished)
        self._n_tdm = n_tdm

    @classmethod
    def from_pruned(cls, cfg: ModelConfig, params: Dict, scores: Dict,
                    vc: Optional[VisionEngineConfig] = None,
                    policy: "str | Callable" = "fifo",
                    tracer: Optional[Tracer] = None) -> "VisionEngine":
        """Harden the pruning and build the engine: masks the dense params
        (the DBMM path) and SBMM-packs the attention weights."""
        from repro.models import pruning_glue as PG
        masked = PG.apply_pruning(cfg, params, scores)
        packed = PR.pack_model(cfg, params, scores)
        return cls(cfg, masked, packed, vc=vc, policy=policy,
                   tracer=tracer)

    # -- events / compat ---------------------------------------------------
    @property
    def events(self):
        return self.scheduler.events

    # -- public API --------------------------------------------------------
    def serve(self, requests: Sequence[VisionRequest]
              ) -> Dict[int, np.ndarray]:
        """Serve ``requests`` to completion; returns {uid: logits}. Requests
        with ``arrival_step > 0`` join the waiting queue only once the
        engine has taken that many steps (staggered admission — the
        continuous-batching scenario)."""
        out: Dict[int, np.ndarray] = {}
        self.enqueue(requests)
        while self._pending or self.scheduler.has_work():
            self.tick(out)
        self.finish()
        return out

    def enqueue(self, requests: Sequence[VisionRequest]) -> None:
        """Validate + annotate ``requests`` and queue them for admission
        (``arrival_step`` relative to the CURRENT engine step). ``serve``
        is ``enqueue`` + ``tick`` until idle + ``finish``; external
        drivers (``repro.traffic.harness``) call the pieces themselves to
        interleave submission with stepping on their own clock."""
        base = self.steps
        # validate ALL before enqueueing ANY: a bad request must not leak
        # its siblings into the engine (they'd surface next serve())
        for r in requests:
            self._validate(r)
        if self.tracer.enabled:
            now_s = time.perf_counter()
            for r in requests:
                r.enqueue_s = now_s
        for r in requests:
            if r.prune_load is None:
                sched = self._base_schedule(r)
                traj = PR.token_trajectory(
                    self.cfg, r.n_patches, use_tdm=self._use_tdm,
                    schedule=sched if self._use_tdm else None,
                    soft=r.soft_prune)
                r.prune_load_base = float(sum(traj))
                r.prune_load = r.prune_load_base
                r.submit_t = time.monotonic()
                if r.deadline_ms is not None:
                    # deadline-aware admission annotation: discount the
                    # post-prune load by how tight the deadline is relative
                    # to the request's modeled solo latency, so the SAME
                    # prune_pressure_aware policy admits urgent requests
                    # earlier (no new policy needed). Recomputed every
                    # admission pass (_refresh_prune_loads): waiting time
                    # consumes slack, so urgency RISES while queued.
                    cm = self.planner.cost_model
                    r.solo_ms = cm.ms(cm.trajectory_cycles(
                        self._traj_from(0, r.n_patches, sched, r.soft_prune,
                                        precision=self._precision_for(r))))
                    r.prune_load *= min(1.0, r.deadline_ms
                                        / max(r.solo_ms, 1e-9))
            self._pending.append((base + r.arrival_step, r))
        self._pending.sort(key=lambda ar: ar[0])
        self._plan_cache = None  # stale speculation from a previous batch

    def tick(self, out: Dict[int, np.ndarray]) -> StepReport:
        """One serve-loop iteration: retire finished slots, admit due
        arrivals, stage + dispatch one engine step through the pipeline.
        Returns a :class:`StepReport` of host-deterministic facts about
        the step (dispatched plan's modeled cost, admitted/completed
        uids) — identical at every pipeline depth for the same request
        stream, which is what lets the traffic harness keep a virtual
        clock that doesn't depend on wall time."""
        # retire bookkeeping for the step in flight: trajectories are
        # deterministic, so which slots finished is host-known before
        # their logits materialize (the pipeline completion fills out)
        self._retire_finished()
        self._admit_arrivals()
        self._refresh_prune_loads(time.monotonic())
        live_before = {st.req.uid for st in self._live.values()}
        cycles_before = self.planner.modeled_cycles
        staged = None
        while True:
            # requests submitted after staging began belong in THIS
            # plan: drop the staged step (rolls back, leaks nothing)
            # and replan with the admissions included
            sub_mark = self.scheduler.submitted_total
            self.scheduler.schedule()
            self._sync_admissions()
            if not self._live:
                break
            staged = self._stage_step(out)
            if self.scheduler.submitted_total == sub_mark:
                break
            self.pipeline.drop(staged)
            staged = None
        admitted = tuple(sorted(
            {st.req.uid for st in self._live.values()} - live_before))
        if staged is None:
            if self._pending or self.scheduler.has_work():
                # nothing admitted yet (future arrivals): advance time
                self.steps += 1
            return StepReport(dispatched=False, admitted=admitted)
        self.pipeline.submit(staged)
        n_segs = len(self.segments.plan)
        completed = tuple(sorted(
            st.req.uid for st in self._live.values()
            if st.seg_idx >= n_segs))
        return StepReport(
            dispatched=True,
            # planner.commit ran inside the dispatch above, so the ledger
            # delta is exactly this step's ExecutionPlan modeled cost
            modeled_ms=self.planner.cost_model.ms(
                self.planner.modeled_cycles - cycles_before),
            admitted=admitted, completed=completed)

    def finish(self) -> None:
        """Drain the pipeline (materializing every in-flight step's
        outputs) and retire the finished slots."""
        self.pipeline.flush()
        self._retire_finished()

    def modeled_request_ms(self, r: VisionRequest,
                           schedule: Optional[Sequence[float]] = None
                           ) -> float:
        """Cost-model price (ms) of serving ``r`` solo from scratch under
        ``schedule`` (default: its own base keep schedule). The admission
        controller prices marginal cost with this — including the
        quality-degraded variant (pass the floored schedule)."""
        sched = (tuple(float(v) for v in schedule) if schedule is not None
                 else self._base_schedule(r))
        cm = self.planner.cost_model
        return cm.ms(cm.trajectory_cycles(
            self._traj_from(0, r.n_patches, sched, r.soft_prune,
                            precision=self._precision_for(r))))

    def modeled_backlog_ms(self) -> float:
        """Modeled time to drain the engine's current commitment: the
        remaining trajectories of every live slot plus the full
        trajectories of every waiting request — the capacity term the
        admission controller compares offered work against."""
        cm = self.planner.cost_model
        ms = sum(self.modeled_request_ms(r) for r in self.scheduler.waiting)
        for st in self._live.values():
            ms += cm.ms(cm.trajectory_cycles(self._traj_from(
                st.seg_idx, st.n_tokens, st.schedule, st.soft,
                precision=st.precision)))
        return ms

    def stats(self) -> Dict[str, Any]:
        buckets = self.batcher.bucket_count
        trajectories = self.planner.trajectory_count
        return {
            "images_served": self.images_served,
            "steps": self.steps,
            "admissions": self.scheduler.num_admissions,
            "compile_count": self.segments.compile_count,
            "jit_compile_count": self.segments.jit_compile_count(),
            "bucket_count": buckets,
            "trajectory_count": trajectories,
            # the recompile bound: jit_compile_count <= compile_budget
            "compile_budget": buckets + trajectories,
            "plan_ahead_hits": self.plan_ahead_hits,
            "plan_ahead_drops": self.plan_ahead_drops,
            # quantized-serving counters: the engine tier, tile+lane
            # dispatches per execution precision, and how many dispatches
            # ran the dequant-in-kernel int8 SBMM
            "precision": self.vc.precision,
            **{f"dispatch_{p}": n
               for p, n in self.precision_dispatches.items()},
            "dequant_dispatches": self.dequant_dispatches,
            # eager_ops / h2d_puts / to_host / passthrough_tiles of every
            # completed step
            **dataclasses.asdict(self.device_ops),
            **{f"sched_{k}": v for k, v in self.scheduler.stats().items()},
            **{f"pipeline_{k}": v for k, v in self.pipeline.stats().items()},
            **{f"batcher_{k}": v for k, v in self.batcher.stats().items()},
            **{f"plan_{k}": v for k, v in self.planner.stats().items()},
            **{f"quality_{k}": v
               for k, v in self.planner.quality.stats().items()},
        }

    def export_metrics(self, registry: MetricsRegistry,
                       prefix: str = "vision") -> MetricsRegistry:
        """Fold this engine's observable state into ``registry``: every
        numeric ``stats()`` entry as a ``<prefix>.<key>`` gauge (compile
        ledgers, planner merge/fuse/deadline counters, padding waste,
        device idle, backlog), plus the signals the flat dicts cannot
        carry — the modeled-vs-measured plan cost error (calibration
        drift) and the quality controller's tighten count per keep
        level. The quantization counters (``dispatch_<precision>``,
        ``dequant_dispatches``, the planner's ``plan_precision_*``
        decisions) ride the absorb like every other numeric stat."""
        registry.absorb(prefix, self.stats())
        p = self.pipeline.stats()
        registry.gauge(f"{prefix}.plan_cost_error").set(p["cost_error"])
        for lvl, n in sorted(self.planner.quality.level_counts.items()):
            registry.gauge(
                f"{prefix}.quality_tightened_level_{lvl:g}").set(n)
        return registry

    def quantization_report(self) -> Dict[str, Any]:
        """Weight-quantization accounting at the engine's precision tier:
        the max-abs weight delta vs the fp32 packed dict (the launcher's
        quantization-error stat) and the packed model size at both tiers
        (``PackedWeight.nbytes`` semantics — surviving blocks + headers +
        scales, at actual dtype widths). fp32 engines report a zero error
        without ever building a quantized dict."""
        fp32_bytes = Q.packed_dict_nbytes(self.segments.packed)
        rep = {"precision": self.vc.precision,
               "granularity": self.vc.quant_granularity,
               "packed_bytes_fp32": fp32_bytes,
               "packed_bytes": fp32_bytes,
               "quant_max_abs_error": 0.0}
        if self.vc.precision != "fp32":
            qd = self.segments.packed_for(self.vc.precision)
            rep["packed_bytes"] = Q.packed_dict_nbytes(qd)
            rep["quant_max_abs_error"] = Q.max_abs_error(
                self.segments.packed, qd)
        return rep

    # -- engine internals --------------------------------------------------
    def _validate(self, r: VisionRequest) -> None:
        n = r.n_patches
        if not 1 <= n <= self._n_patches_max:
            raise ValueError(
                f"request {r.uid}: {n} patches outside "
                f"[1, {self._n_patches_max}] (pos-table capacity for "
                f"image_size={self.cfg.image_size}, "
                f"patch_size={self.cfg.patch_size})")
        pdim = self.cfg.patch_size ** 2 * 3
        if r.patches.shape[-1] != pdim:
            raise ValueError(f"request {r.uid}: patch dim "
                             f"{r.patches.shape[-1]} != {pdim}")
        r_t = self.cfg.pruning.r_t if r.r_t is None else r.r_t
        # explicit isfinite: NaN fails every comparison, so `not a < x <= b`
        # happens to catch it, but inf/NaN deserve their own message and
        # deadline_ms's `<= 0.0` test would WAVE A NaN THROUGH
        if not (math.isfinite(r_t) and 0.0 < r_t <= 1.0):
            raise ValueError(f"request {r.uid}: r_t must be finite in "
                             f"(0, 1], got {r_t}")
        if r.deadline_ms is not None and not (
                math.isfinite(r.deadline_ms) and r.deadline_ms > 0.0):
            raise ValueError(f"request {r.uid}: deadline_ms must be finite "
                             f"and positive, got {r.deadline_ms}")
        if r.keep_schedule is not None:
            ks = tuple(float(v) for v in r.keep_schedule)
            if self._use_tdm and len(ks) != self._n_tdm:
                raise ValueError(
                    f"request {r.uid}: keep_schedule has {len(ks)} entries, "
                    f"model has {self._n_tdm} TDM segments")
            for v in ks:
                if not (math.isfinite(v) and 0.0 < v <= 1.0):
                    raise ValueError(f"request {r.uid}: keep_schedule "
                                     f"entries must be finite in (0, 1], "
                                     f"got {v}")
        if r.quality is not None and r.quality not in QUALITY_MODES:
            raise ValueError(f"request {r.uid}: quality must be one of "
                             f"{QUALITY_MODES}, got {r.quality!r}")

    def _admit_arrivals(self) -> None:
        arrived = [r for at, r in self._pending if at <= self.steps]
        if arrived:
            self._pending = [(at, r) for at, r in self._pending
                             if at > self.steps]
            self.scheduler.submit(arrived)

    def _sync_admissions(self) -> None:
        """Initialize in-flight state for slots the Scheduler filled."""
        for slot, req in self.scheduler.running.items():
            if slot in self._live:
                continue
            self._live[slot] = _Live(
                req=req, seg_idx=0,
                x=np.asarray(req.patches, np.float32),
                n_tokens=req.n_patches,
                schedule=self._base_schedule(req),
                soft=req.soft_prune,
                admit_t=time.monotonic(),
                admit_s=time.perf_counter() if self.tracer.enabled else 0.0,
                precision=self._precision_for(req, record=True))

    def _precision_for(self, r: VisionRequest, record: bool = False) -> str:
        """Execution precision for ``r`` — the planner's third knob. fp32
        engines short-circuit (no planner call, no counters: the fp32 path
        stays byte-identical to the pre-quantization engine), and
        quality="strict" requests pin fp32 on any engine. Otherwise the
        planner prices the request's full trajectory at fp32 AND at the
        engine tier and takes the strict argmin (fp32 listed first, so
        ties keep full precision). ``record=True`` only at admission —
        pricing probes (modeled_request_ms / backlog) must not inflate the
        decision counters."""
        if self.vc.precision == "fp32" or r.quality == "strict":
            return "fp32"
        sched = self._base_schedule(r)
        cands = [(p, self._traj_from(0, r.n_patches, sched, r.soft_prune,
                                     precision=p))
                 for p in ("fp32", self.vc.precision)]
        return self.planner.choose_precision(cands, record=record)

    def _base_schedule(self, r: VisionRequest) -> Tuple[float, ...]:
        """The request's own per-TDM keep schedule BEFORE any controller
        tightening: an explicit ``keep_schedule`` verbatim, else its
        ``r_t`` (else the config's) broadcast over the TDM segments."""
        if r.keep_schedule is not None:
            return tuple(float(v) for v in r.keep_schedule)
        return PR.keep_schedule(self.cfg, r_t=r.r_t, use_tdm=self._use_tdm)

    def _refresh_prune_loads(self, now: float) -> None:
        """Re-discount waiting deadline requests' ``prune_load`` by their
        CURRENT slack each admission pass (not once at submit): waiting
        time consumes slack, so a queued deadline request's urgency rises
        until ``prune_pressure_aware`` prefers it."""
        for req in self.scheduler.waiting:
            if (req.deadline_ms is None or req.prune_load_base is None
                    or req.solo_ms is None or req.submit_t is None):
                continue
            left = req.deadline_ms - (now - req.submit_t) * 1e3
            req.prune_load = req.prune_load_base * min(
                1.0, max(left, 0.0) / max(req.solo_ms, 1e-9))

    def _traj_from(self, seg_idx: int, n_tokens: int,
                   schedule: Sequence[float], soft: bool = False,
                   precision: str = "fp32"):
        """Remaining (stage key, entry token count) trajectory from segment
        ``seg_idx`` at ``n_tokens`` real tokens under ``schedule`` (full
        per-TDM keep schedule; entries before this point are history —
        already baked into ``n_tokens``). A stage key is the batcher
        grouping identity — the segment (weights + static layer range)
        plus, at TDM segments, the static keep count (tiles must be
        k-uniform because k is a compile-time top-k width); soft-pruning
        TDM stages append a ``"soft"`` marker (different kernel, and the
        package row makes padded-batch membership semantics different), so
        soft and hard requests never share a TDM tile while non-TDM
        segments still batch together. Non-fp32 ``precision`` appends the
        precision string to the weight-bearing (layers/tdm) stage keys
        (after the soft marker) — different weights and kernels, so
        precisions never share an encoder tile and the cost model prices
        them at their own throughput; embed/head keys stay unmarked (those
        tiles run fp32 at every tier and batch across precisions), and
        fp32 keys are byte-identical to the pre-quantization ones. Offsets
        align with engine steps, which is what the planner's fusion and
        deadline logic rely on."""
        mark = () if precision == "fp32" else (precision,)
        entries = []
        n = n_tokens
        ti = self._tdm_before[seg_idx]
        for si in range(seg_idx, len(self.segments.plan)):
            seg = self.segments.plan[si]
            if seg[0] == "tdm":
                r = schedule[ti]
                if soft:
                    k = PR.tdm_soft_keep_count(n, r, has_pkg=ti > 0)
                    entries.append(((si, seg, k, "soft") + mark, n))
                else:
                    k = PR.tdm_keep_count(n, r)
                    entries.append(((si, seg, k) + mark, n))
                n = k + 2
                ti += 1
            elif seg[0] == "layers":
                entries.append(((si, seg, None) + mark, n))
            else:
                entries.append(((si, seg, None), n))
                if seg[0] == "embed":
                    n += 1  # + CLS
        return tuple(entries)

    def _resolve_schedule(self, st: _Live, now: float) -> Tuple[float, ...]:
        """The EFFECTIVE keep schedule for this staging pass: the request's
        base schedule run through the planner's QualityController with the
        current queue pressure and deadline slack. Pure (controller
        counters fold in at dispatch) — safe under staging drop/replan,
        and an exact identity when the controller is off."""
        q = self.planner.quality
        if not q.enabled:
            return st.schedule
        done = self._tdm_before[st.seg_idx]
        left = rem = None
        if st.req.deadline_ms is not None:
            left = st.req.deadline_ms - (now - st.admit_t) * 1e3
            cm = self.planner.cost_model

            def rem(sched, _st=st, _cm=cm):
                return _cm.ms(_cm.trajectory_cycles(self._traj_from(
                    _st.seg_idx, _st.n_tokens, sched, _st.soft,
                    precision=_st.precision)))

        # backlog pressure comes from the Scheduler's first-class counter —
        # the same number its stats() block (and the traffic harness) report
        return q.resolve(st.schedule, done=done,
                         preference=st.req.quality,
                         queue_depth=self.scheduler.queue_depth,
                         deadline_left_ms=left, remaining_ms=rem)

    def _plan_item(self, st: _Live, now: float,
                   schedule: Sequence[float]) -> PlanItem:
        traj = self._traj_from(st.seg_idx, st.n_tokens, schedule, st.soft,
                               precision=st.precision)
        left = None
        if st.req.deadline_ms is not None:
            left = st.req.deadline_ms - (now - st.admit_t) * 1e3
        return PlanItem(stage=traj[0][0], n_tokens=st.n_tokens,
                        cap=self._token_cap(st), trajectory=traj,
                        deadline_left_ms=left)

    @staticmethod
    def _parse_stage(stage) -> Tuple[Tuple, Optional[int], bool, str]:
        """Decompose an engine stage key into ``(segment, k, soft,
        precision)`` — the inverse of ``_traj_from``'s key construction:
        ``(si, segment, k[, "soft"][, precision])`` with both trailing
        markers optional ("soft" is not a precision string, so membership
        in ``Q.PRECISIONS`` disambiguates)."""
        seg, k = stage[1], stage[2]
        rest = stage[3:]
        soft = "soft" in rest
        precision = next((m for m in rest if m in Q.PRECISIONS), "fp32")
        return seg, k, soft, precision

    def _token_cap(self, st: _Live) -> Optional[int]:
        """Hard bound on the padded token tile: the embed stage indexes the
        position table, so its tile must never quantize past the table's
        patch capacity (later stages have no positional shape bound)."""
        if self.segments.plan[st.seg_idx][0] == "embed":
            return self._n_patches_max
        return None

    def step(self, out: Dict[int, np.ndarray]) -> None:
        """Synchronously advance the in-flight population one step
        (compat wrapper: stage + dispatch + complete + retire in one
        call). The serve loop goes through the pipeline instead, where
        stage/dispatch/complete are allowed to overlap across steps."""
        self.pipeline.submit(self._stage_step(out))
        self.pipeline.flush()
        self._retire_finished()

    def _next_plan(self, items: List[PlanItem]):
        """This step's ExecutionPlan, via the plan-ahead cache when the
        population matches the prediction (the common case between
        admissions at depth >= 2): plans are deterministic values of the
        item population, so the speculative plan IS the plan a fresh
        ``plan_ahead(items, 1)[0]`` would build — bit-identical behavior,
        planning cost hidden behind the previous step's device work."""
        key = self._items_fingerprint(items)
        cached, self._plan_cache = self._plan_cache, None
        if cached is not None:
            ckey, cplan = cached
            if key is not None and ckey == key:
                self.plan_ahead_hits += 1
                return cplan
            self.plan_ahead_drops += 1
        plans = self.planner.plan_ahead(items, self.pipeline.depth)
        if len(plans) > 1 and key is not None:
            nxt = self.planner.advance_items(items, plans[0])
            if nxt:
                self._plan_cache = (self._items_fingerprint(nxt), plans[1])
        return plans[0]

    @staticmethod
    def _items_fingerprint(items: List[PlanItem]):
        """Population identity the plan cache keys on; ``None`` (never
        cache) when any item carries a deadline — urgency depends on the
        wall clock, so deadline plans must be rebuilt at dispatch time."""
        if any(it.deadline_left_ms is not None for it in items):
            return None
        return tuple((it.stage, it.n_tokens, it.cap, it.trajectory)
                     for it in items)

    def _passthrough(self, tile, states: List[_Live]):
        """The batch a lock-step tile already is, else ``None``: a tile of
        a bucket stacked before, whose members are rows ``0..m-1`` of one
        tile output ``y``, in tile order, and fill every row and token of
        the tile (no zero row, no token pad). Then ``y`` is bit for bit
        what pad and stack would build, and staging it launches
        nothing."""
        y = states[0].x.y if isinstance(states[0].x, _Rows) else None
        if (y is None or tile.bucket_key not in self._stacked
                or len(states) != tile.b_tile
                or y.shape != (tile.b_tile, tile.n_tile, self.cfg.d_model)):
            return None
        for b, st in enumerate(states):
            if not (isinstance(st.x, _Rows) and st.x.y is y and st.x.b == b
                    and st.n_tokens == tile.n_tile):
                return None
        return y

    def _stage_step(self, out: Dict[int, np.ndarray]) -> StagedStep:
        """Stage one engine step: plan the population, build every tile's
        padded input batch and every lane's entry activation, and close
        over them in a :class:`StagedStep`. Staging mutates NO engine
        state (plans fold into the ledgers only at dispatch, via
        ``planner.commit``) — a staged step can be dropped for a replan
        and leaks nothing.

        A tile is staged in the cheapest of three ways: the previous
        tile's output passed through whole (:meth:`_passthrough`), host
        patches padded and stacked on the host in one put, or each
        member's rows sliced, padded and stacked on the device.

        Exactness: all three are pure data movement, so the staged
        buffers are bitwise the batches the synchronous path built
        host-side; the same jitted segment bodies then make the logits
        independent of pipeline depth."""
        slots = sorted(self._live)
        now = time.monotonic()
        tr = self.tracer
        step_no = self.steps
        ops = DeviceOps()  # this step's device work outside its segments
        t_step, compiled0 = 0.0, 0
        if tr.enabled:
            t_step = time.perf_counter()
            compiled0 = self.segments.jit_compile_count()
            tr.begin("plan", track="engine", step=self.steps,
                     population=len(slots))
        # quality resolution happens ONCE per staging pass, before planning:
        # the effective schedules shape the trajectories the planner prices,
        # so the plan, the stage keys and the dispatched k values all agree
        eff = {s: self._resolve_schedule(self._live[s], now) for s in slots}
        items = [self._plan_item(self._live[s], now, eff[s]) for s in slots]
        plan = self._next_plan(items)
        if tr.enabled:
            tr.end("plan", track="engine")
        n_urgent = plan.urgent_tile_count()
        n_segs = len(self.segments.plan)

        # controller accounting for this step (folded in at dispatch only —
        # a dropped staging pass leaves no trace)
        q_dec = q_tight = q_dl = 0
        q_levels: List[float] = []
        q = self.planner.quality
        if q.enabled:
            depth = self.scheduler.queue_depth
            for s in slots:
                st = self._live[s]
                done = self._tdm_before[st.seg_idx]
                pairs = list(zip(st.schedule[done:], eff[s][done:]))
                q_dec += len(pairs)
                hit = [e for b, e in pairs if e < b - 1e-12]
                q_tight += len(hit)
                q_levels.extend(hit)
                if st.req.deadline_ms is not None and hit:
                    # how much of the tightening came from the deadline
                    # loop (vs queue pressure alone)
                    e0 = q.resolve(st.schedule, done=done,
                                   preference=st.req.quality,
                                   queue_depth=depth)
                    q_dl += sum(1 for a, b in zip(e0[done:], eff[s][done:])
                                if b < a - 1e-12)

        if tr.enabled:
            tr.begin("stage", track="engine", step=self.steps,
                     tiles=len(plan.tiles), lanes=len(plan.lanes))
        tile_runs = []
        for tile in plan.tiles:
            member_slots = [slots[i] for i in tile.members]
            states = [self._live[s] for s in member_slots]
            # the tile's stage key is the source of truth for what runs:
            # (si, segment, k[, "soft"][, precision]) — states[0] only
            # supplies data
            seg, k, soft, prec = self._parse_stage(tile.stage)
            batch = self._passthrough(tile, states)
            passed = batch is not None
            if passed:
                ops.passthrough_tiles += 1
            elif all(isinstance(st.x, np.ndarray) for st in states):
                # host patches: pad and stack on the host, one put
                batch = np.zeros((tile.b_tile, tile.n_tile,
                                  states[0].x.shape[-1]), np.float32)
                for b, st in enumerate(states):
                    batch[b, : st.n_tokens] = st.x
                batch = jnp.asarray(batch)
                ops.h2d_puts += 1
            else:
                # token/batch padding is exactness-neutral; building the
                # batch from device handles (pad + stack) keeps staging
                # async — a host-side scatter would block on the
                # previous step
                rows = [jnp.pad(st.x.take(st.n_tokens, ops),
                                ((0, tile.n_tile - st.n_tokens), (0, 0)))
                        for st in states]
                # a pad per member (its fill value is a put)
                ops.eager_ops += len(states)
                ops.h2d_puts += len(states)
                if tile.b_tile > len(states):
                    zero = jnp.zeros((tile.n_tile, rows[0].shape[-1]),
                                     jnp.float32)
                    rows += [zero] * (tile.b_tile - len(states))
                    ops.eager_ops += 2
                    ops.h2d_puts += 1
                batch = jnp.stack(rows)
                ops.eager_ops += _stack_programs(tile.b_tile)
            n_valid = None
            if tile.needs_mask and seg[0] in ("layers", "tdm"):
                n_valid = np.fromiter(
                    (st.n_tokens for st in states), np.int32, len(states))
                n_valid = np.concatenate(
                    [n_valid, np.full(tile.b_tile - len(states), tile.n_tile,
                                      np.int32)])
                ops.h2d_puts += 1  # segments.run puts it on the device
            pkg_mass = None
            if soft and self._tdm_before[tile.stage[0]] > 0:
                # every member past its first soft TDM carries a package
                # mass; batch-pad rows get 0 (their packages are don't-care)
                pkg_mass = jnp.stack(
                    [st.pkg_mass.take(None, ops).reshape(())
                     for st in states]
                    + [jnp.zeros((), jnp.float32)]
                    * (tile.b_tile - len(states)))
                ops.eager_ops += (len(states) + 1
                                  + _stack_programs(tile.b_tile))
                ops.h2d_puts += 1
            tile_runs.append((tile, member_slots, seg, k, soft, prec, batch,
                              n_valid, pkg_mass, passed))

        lane_runs = []
        for lane in plan.lanes:
            slot = slots[lane.member]
            st = self._live[slot]
            steps = []
            for stage, _ in lane.trajectory:
                seg, k, soft, _prec = self._parse_stage(stage)
                steps.append((seg, k, True) if soft else (seg, k))
            steps = tuple(steps)
            seed = None
            if st.pkg_mass is not None:
                seed = st.pkg_mass.take(None, ops).reshape(1)
                ops.eager_ops += 1
            if isinstance(st.x, np.ndarray):
                # a lane from admission: its patches, one put
                x1 = jnp.asarray(st.x[None])
                ops.h2d_puts += 1
            else:
                x1 = st.x.take(st.n_tokens, ops)[None]
                ops.eager_ops += 1
            lane_runs.append((slot, steps, x1, seed))
        if tr.enabled:
            tr.end("stage", track="engine")

        # (state, y handle, row, "lane" | "tile") of head and lane outputs
        produced: List[Any] = []

        def run_tile(run):
            (tile, member_slots, seg, k, soft, prec, batch, n_valid,
             pkg_mass, passed) = run
            if not passed:
                self._stacked.add(tile.bucket_key)
            self.precision_dispatches[prec] += 1
            if prec == "int8":
                self.dequant_dispatches += 1
            if tr.enabled:
                tr.begin("tile", track="pipeline", seg=seg,
                         batch=tile.b_tile, n=tile.n_tile, k=k)
            mass = None
            if soft:
                y, mass = self.segments.run(seg, batch, n_valid=n_valid,
                                            k=k, soft=True,
                                            pkg_mass=pkg_mass,
                                            precision=prec)
            else:
                y = self.segments.run(seg, batch, n_valid=n_valid, k=k,
                                      precision=prec)
            if tr.enabled:
                tr.end("tile", track="pipeline")
                tr.begin("unstage", track="pipeline")
            kind = seg[0]
            # members keep references to their rows of the output; the
            # next staging pass slices them only where it must
            for b, slot in enumerate(member_slots):
                st = self._live[slot]
                if kind == "head":
                    produced.append((st, y, b, "tile"))
                else:
                    st.x = _Rows(y, b)
                if kind == "embed":
                    st.n_tokens += 1          # + CLS
                elif kind == "tdm":
                    st.n_tokens = k + 2       # CLS + k kept + fused/package
                    if soft:
                        st.pkg_mass = _Rows(mass, b)
                st.seg_idx += 1
            if tr.enabled:
                tr.end("unstage", track="pipeline")
            return y

        def dispatch():
            # urgent tiles (the plan's leading tiles) dispatch BEFORE
            # lanes: a fused lane is the most expensive single dispatch of
            # the step and must not sit on a deadline-urgent request's
            # critical path
            handles = [run_tile(run) for run in tile_runs[:n_urgent]]
            for slot, steps, x1, seed in lane_runs:
                st = self._live[slot]
                self.precision_dispatches[st.precision] += 1
                if st.precision == "int8":
                    self.dequant_dispatches += 1
                if tr.enabled:
                    tr.begin("lane", track="pipeline", steps=steps, batch=1,
                             n=x1.shape[1])
                y = self.segments.run_fused(steps, x1, pkg_mass=seed,
                                            precision=st.precision)
                if tr.enabled:
                    tr.end("lane", track="pipeline")
                produced.append((st, y, 0, "lane"))
                st.seg_idx = n_segs
                handles.append(y)
            handles += [run_tile(run) for run in tile_runs[n_urgent:]]
            self.planner.commit(plan)
            if q.enabled:
                q.record(q_dec, q_tight, q_levels,
                         deadline_tightened=q_dl)
            self.steps += 1
            return handles

        def complete(handles):
            # one copy to the host per head or lane output, rows taken
            # there
            host: Dict[int, np.ndarray] = {}
            for st, y, row, path in produced:
                req = st.req
                if id(y) not in host:
                    host[id(y)] = np.asarray(y)
                req.logits = host[id(y)][row]
                req.done = True
                out[req.uid] = req.logits
                ops.to_host += 1
                if tr.enabled:
                    self._record_request(st, path, time.perf_counter())
            self.device_ops.add(ops)
            if tr.enabled:
                tr.record("step", t_step, time.perf_counter(), track="engine",
                          step=step_no, tiles=len(plan.tiles),
                          passthrough_tiles=ops.passthrough_tiles,
                          lanes=len(plan.lanes), eager_ops=ops.eager_ops,
                          h2d_puts=ops.h2d_puts, to_host=ops.to_host,
                          compiled=(self.segments.jit_compile_count()
                                    - compiled0))

        return StagedStep(dispatch=dispatch, complete=complete,
                          label=f"vit-step-{self.steps}",
                          modeled_ms=self.planner.cost_model.ms(
                              plan.stats.modeled_cycles))

    def _record_request(self, st: _Live, path: str, done_s: float) -> None:
        """Log a served request's ``queued`` (enqueue to admission) and
        ``served`` (admission to its logits in the result dict) spans on
        the ``requests`` track, sharing its uid."""
        req = st.req
        attrs = dict(uid=req.uid, path=path,
                     r_t=self.cfg.pruning.r_t if req.r_t is None
                     else req.r_t)
        if req.enqueue_s is not None:
            self.tracer.record("queued", req.enqueue_s, st.admit_s,
                               track="requests", **attrs)
        self.tracer.record("served", st.admit_s, done_s, track="requests",
                           **attrs)

    def _retire_finished(self) -> None:
        """Free slots whose trajectory completed. Host-deterministic given
        the dispatched plans, so it runs at the NEXT step's build even
        while the finishing step is still on the device; the logits
        materialize in that step's pipeline completion."""
        n_segs = len(self.segments.plan)
        for slot in sorted(self._live):
            st = self._live[slot]
            if st.seg_idx >= n_segs:
                self.scheduler.retire(slot)
                del self._live[slot]
                self.images_served += 1
