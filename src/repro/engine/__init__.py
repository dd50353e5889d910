"""SpGEMM execution-plan engine: cached plans, batched executor, telemetry.

The reusable execution layer between the one-shot ``repro.core.spgemm``
API and the serving/analytics front-ends:

  plan.py      — immutable :class:`SpgemmPlan` over operand signatures
                 (everything derivable before data arrives).
  autotune.py  — :class:`AdaptivePolicy` / :class:`PolicyState`:
                 telemetry-driven shard-count selection (AUTO_SHARDS),
                 tracked-jitter hash-schedule headroom, and the
                 :class:`EstimatorState` calibration loop behind
                 ``plan_mode="estimate"`` cold planning.
  partition.py — :class:`ShardSpec` row-block partitioning (flop-balanced
                 bounds, pow-2 shard buckets) + mesh placement helpers.
  cache.py     — LRU :class:`PlanCache` of plans + jitted executables
                 (hit/miss/evict counters; the §5.4 recompile analog),
                 with JSON ``dump``/``load`` cross-process persistence.
  executor.py  — :class:`SpgemmEngine`: streaming submit/drain with
                 plan-grouped batching, completion-order finalize, and
                 sharded fan-out; ``execute`` backs ``spgemm()``.
  stats.py     — trace accounting and registry-backed engine/plan
                 counters (one source of truth with telemetry.py).
  telemetry.py — structured spans (also profiler annotations), the
                 phase vocabulary of the device scopes, metrics
                 registry, ring-buffer event log, and the JSONL /
                 Prometheus exporters.

Lifecycle::

    signature -> plan (cold) -> first execution learns capacity buckets
              -> specialized plan + jitted executable cached
              -> steady-state requests: pad to bucket, dispatch async,
                 one verify sync; overflow grows buckets and re-plans.
    shards=N  -> parent plan learns a flop-balanced ShardSpec; requests
                 fan out into per-shard sub-dispatches (ordinary plans on
                 the slice signatures) and a jitted merge concatenation.
"""
from repro.core.spgemm import AUTO_SHARDS
from repro.core.workspace import (Arena, ArenaPressureError, Lease,
                                  LeaseSpec, default_arena,
                                  reset_default_arena)

from .autotune import (AdaptivePolicy, EstimatorState, MemoryGovernor,
                       PolicyState, choose_shards, revise_shards,
                       trim_schedule)
from .cache import CacheEntry, PlanCache
from .executor import (SpgemmEngine, SpgemmRequest, StepTimer,
                       default_engine, reset_default_engine)
from .partition import (ShardSpec, balanced_bounds, clamp_shards,
                        plan_shards, shard_devices)
from .plan import (HashSchedule, MatrixSig, PlanKey, SpgemmPlan, plan,
                   plan_key)
from .stats import (EngineStats, PlanStats, plan_label, render,
                    total_traces, traces_for)
from .telemetry import (LATENCY_BUCKETS_S, EventLog, MetricsRegistry, Span,
                        Telemetry, engine_sample_blocks, git_rev,
                        histogram_quantile, merge_sample_blocks,
                        prometheus_text, resolve_telemetry, utc_now_iso)

__all__ = [
    "AUTO_SHARDS", "AdaptivePolicy", "EstimatorState", "PolicyState",
    "choose_shards", "revise_shards", "trim_schedule",
    "Arena", "ArenaPressureError", "Lease", "LeaseSpec", "MemoryGovernor",
    "default_arena", "reset_default_arena",
    "CacheEntry", "PlanCache", "SpgemmEngine", "SpgemmRequest", "StepTimer",
    "default_engine", "reset_default_engine", "ShardSpec", "balanced_bounds",
    "clamp_shards", "plan_shards", "shard_devices", "HashSchedule",
    "MatrixSig", "PlanKey", "SpgemmPlan", "plan", "plan_key", "EngineStats",
    "PlanStats", "plan_label", "render", "total_traces", "traces_for",
    "LATENCY_BUCKETS_S", "EventLog", "MetricsRegistry", "Span", "Telemetry",
    "engine_sample_blocks", "git_rev", "histogram_quantile",
    "merge_sample_blocks", "prometheus_text", "resolve_telemetry",
    "utc_now_iso",
]
