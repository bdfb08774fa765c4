"""Online serving subsystem: score individual requests against a trained
GAME model at low latency.

The offline path (``cli/score_game.py``) reloads the Avro model and scores a
static dataset in one pass; this package is the other half of the stack —
the Photon-ML GLMix design (fixed-effect prior + per-entity random-effect
corrections) was built for per-member online serving, and the pieces here
map onto that design:

- :mod:`photon_ml_tpu.serving.artifact` — pack a trained ``GameModel`` into
  a serving artifact: dense FE coefficient arrays plus per-coordinate RE
  coefficient tables as contiguous ``(n_entities, dim)`` matrices behind an
  entity-id → row off-heap index (the PHIX store from ``indexmap/offheap``).
- :mod:`photon_ml_tpu.serving.scorer` — a jit'd fixed-shape score function:
  ``mean(x·β_FE + Σ_re x·β_RE[entity])`` with gathered RE rows; cold
  entities degrade to the FE-only score (RE prior mean = 0).
- :mod:`photon_ml_tpu.serving.batcher` — a microbatcher coalescing
  ``ScoreRequest``s into padded batches drawn from a small set of bucket
  sizes, so XLA compiles once per bucket and never per request.
- :mod:`photon_ml_tpu.serving.cache` — an LRU device-resident cache of hot
  RE coefficient rows over a host-side backing store.
- :mod:`photon_ml_tpu.serving.metrics` — latency percentiles, queue depth,
  batch fill ratio and cache hit rate as a dict snapshot.
- :mod:`photon_ml_tpu.serving.replay` — turn a scoring dataset into a
  request stream and pump it through the batcher (the CLI's driver).
- :mod:`photon_ml_tpu.serving.hotswap` — apply nearline delta artifacts
  (``photon_ml_tpu.incremental``) to a live scorer between batches: in-place
  table mutation with no retrace, per-row cache invalidation, AUC validation
  gate with rollback to the previous generation.
- :mod:`photon_ml_tpu.serving.routing` /
  :mod:`photon_ml_tpu.serving.sharded` — the device-resident hot path: RE
  tables partitioned across a serving mesh behind an entity→(shard, slot)
  routing index, one jitted gather per shard per batch.
- :mod:`photon_ml_tpu.serving.admission` — asynchronous admission of the
  cold long tail into device headroom slots (double-buffered host→device
  copies off the request path).
- :mod:`photon_ml_tpu.serving.continuous` — continuous microbatching:
  requests join in-flight buckets up to a deadline, scored by per-replica
  threads with backpressure-bounded queues.
- :mod:`photon_ml_tpu.serving.deltawatch` — the ``--watch-deltas`` poll as
  a supervised daemon (``photon_ml_tpu.resilience``): crashes restart with
  backoff, corrupt deltas are skipped without advancing the generation.
- :mod:`photon_ml_tpu.serving.requestplane` — sampled per-request
  lifecycle tracing: a seeded sampler tags ~1/N requests, stage
  boundaries (queue → featurize → route → dispatch → device → reply) are
  stamped through the batcher/scorer, hot-swap and admission stalls are
  folded in as interference, and records drain to the run ledger for
  ``analyze_run --requests`` tail attribution.
- :mod:`photon_ml_tpu.serving.slo` — availability + latency objectives
  over a rolling window with error-budget burn-rate accounting
  (``/healthz`` degraded reason + ``serving.slo.*`` gauges).
- :mod:`photon_ml_tpu.serving.overload` — closed-loop overload control:
  SLO burn rate drives batch-deadline shrink and FE-only shedding with
  hysteresis (``serving.overload.*`` gauges).
- :mod:`photon_ml_tpu.serving.scenarios` — seeded traffic-shape scenarios
  (steady, diurnal, burst storm, cold-entity flood, hot-swap under load,
  plus the tenancy trio: tenant isolation, ramped rollout, nearline loop)
  driving ``replay_requests``.
- :mod:`photon_ml_tpu.serving.tenancy` — the tenancy plane: N GLMix model
  variants as fingerprint-chained delta overlays on ONE shared sharded
  scorer, seeded deterministic variant routing with hot ramp percentages,
  per-tenant admission quotas with priority-aware shedding, and per-tenant
  SLO error budgets (tenant-labeled ``serving.slo.*`` series).
"""

from photon_ml_tpu.serving.artifact import (
    ServingArtifact,
    ServingTable,
    load_artifact,
    load_tuned_config,
    pack_game_model,
    save_artifact,
    save_tuned_config,
)
from photon_ml_tpu.serving.introspect import IntrospectionServer, prometheus_text
from photon_ml_tpu.serving.admission import AdmissionController
from photon_ml_tpu.serving.batcher import MicroBatcher
from photon_ml_tpu.serving.cache import HotEntityCache
from photon_ml_tpu.serving.continuous import ContinuousBatcher, PendingResult
from photon_ml_tpu.serving.deltawatch import DeltaWatcher
from photon_ml_tpu.serving.hotswap import (
    CoordinatedHotSwap,
    HotSwapManager,
    SwapReport,
    ValidationGate,
)
from photon_ml_tpu.serving.metrics import ServingMetrics
from photon_ml_tpu.serving.replay import replay_requests, requests_from_game_data
from photon_ml_tpu.serving.requestplane import REQUEST_STAGES, RequestPlane
from photon_ml_tpu.serving.scenarios import (
    DEFAULT_TENANTS,
    SCENARIO_NAMES,
    TENANCY_SCENARIOS,
    build_scenario,
    run_scenario,
)
from photon_ml_tpu.serving.tenancy import (
    TenancyPlane,
    TenantBudget,
    TenantQuota,
    VariantRegistry,
    VariantRouter,
    VariantScorer,
    build_tenant_slos,
    make_nearline_fn,
    tag_requests,
)
from photon_ml_tpu.serving.overload import OverloadController
from photon_ml_tpu.serving.slo import SLOTracker
from photon_ml_tpu.serving.routing import (
    CoordinateRouting,
    RoutingIndex,
    build_routing,
)
from photon_ml_tpu.serving.scorer import GameScorer, ScoreRequest, ScoreResult
from photon_ml_tpu.serving.sharded import (
    ShardedGameScorer,
    ShardedReTable,
    serving_mesh,
)

__all__ = [
    "AdmissionController",
    "ContinuousBatcher",
    "DEFAULT_TENANTS",
    "REQUEST_STAGES",
    "RequestPlane",
    "SCENARIO_NAMES",
    "SLOTracker",
    "TENANCY_SCENARIOS",
    "TenancyPlane",
    "TenantBudget",
    "TenantQuota",
    "VariantRegistry",
    "VariantRouter",
    "VariantScorer",
    "build_scenario",
    "build_tenant_slos",
    "make_nearline_fn",
    "run_scenario",
    "tag_requests",
    "CoordinateRouting",
    "CoordinatedHotSwap",
    "DeltaWatcher",
    "GameScorer",
    "HotEntityCache",
    "HotSwapManager",
    "MicroBatcher",
    "OverloadController",
    "PendingResult",
    "RoutingIndex",
    "ScoreRequest",
    "ScoreResult",
    "ShardedGameScorer",
    "ShardedReTable",
    "ServingArtifact",
    "ServingMetrics",
    "ServingTable",
    "SwapReport",
    "ValidationGate",
    "IntrospectionServer",
    "build_routing",
    "load_artifact",
    "load_tuned_config",
    "pack_game_model",
    "prometheus_text",
    "replay_requests",
    "requests_from_game_data",
    "save_artifact",
    "save_tuned_config",
    "serving_mesh",
]
