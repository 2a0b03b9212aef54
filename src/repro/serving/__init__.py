"""Layered serving API.

LM path: ``Scheduler`` (admission policy) / ``KVCacheManager`` (per-slot
cache state) / ``ModelRunner`` (jitted steps + compile cache) compose into
``ServeEngine``; ``prune_kv_caches`` is the standalone KV compaction.

Vision path: the same ``Scheduler`` + ``TilePlanner`` (cost-model-driven
execution planning over the ``RaggedBatcher``'s token-count buckets:
bucket merging, express-lane fusion, deadline-aware tiling; owns the
``QualityController`` that resolves per-request keep schedules under
load) + ``core.packed_runner.PackedVitSegments`` compose into
``VisionEngine`` —
continuous-batching inference for the simultaneously-pruned ViT.

Both engines drive their step loops through the ``StepPipeline``
(``repro.serving.pipeline``): steps are staged (plan + input buffers),
dispatched asynchronously, and completed (blocked + materialized) as
separate phases, so at ``pipeline_depth`` 2 the host plans and stages step
N+1 while the device executes step N. Depth 1 reproduces the synchronous
path step for step.
"""
from repro.serving.cache_manager import (KVCacheManager, bucket_length,
                                         prune_kv_caches)
from repro.serving.engine import (ElasticContext, EngineConfig, Request,
                                  ServeEngine)
from repro.serving.pipeline import StagedStep, StepPipeline, StepReport
from repro.serving.planner import (PLANNER_MODES, ExecutionPlan, FusedLane,
                                   PlanItem, PlanStats, TileCostModel,
                                   TilePlanner)
from repro.serving.quality import (QUALITY_MODES, QualityConfig,
                                   QualityController)
from repro.serving.ragged_batcher import RaggedBatcher, Tile
from repro.serving.runner import ModelRunner, build_padded_batch
from repro.serving.scheduler import Scheduler
from repro.serving.vision import (VisionEngine, VisionEngineConfig,
                                  VisionRequest)

__all__ = ["ServeEngine", "EngineConfig", "ElasticContext", "Request",
           "Scheduler", "KVCacheManager", "ModelRunner", "prune_kv_caches",
           "bucket_length", "build_padded_batch",
           "StepPipeline", "StagedStep", "StepReport",
           "VisionEngine", "VisionEngineConfig", "VisionRequest",
           "RaggedBatcher", "Tile",
           "TilePlanner", "TileCostModel", "ExecutionPlan", "PlanItem",
           "FusedLane", "PlanStats", "PLANNER_MODES",
           "QualityController", "QualityConfig", "QUALITY_MODES"]
