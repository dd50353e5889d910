"""Batched, plan-cached SpGEMM executor — the engine behind ``spgemm()``.

This module owns BOTH execution paths for the OpSparse two-phase flow
(paper Fig. 2):

``_execute_steps``
    The faithful host-orchestrated six-step pipeline (setup, sym-bin,
    symbolic, alloc, num-bin, numeric) moved here from ``core/spgemm.py``.
    It serves cold calls (capacity buckets / hash launch schedule still
    unknown) and ``timing`` runs.

``_build_hot_executable`` / ``_build_hash_executable``
    The steady-state paths: ONE jitted closure per specialized plan.  With
    the product/nnz buckets — and, for the hash method, the per-rung
    bin-count buckets of the :class:`~repro.engine.plan.HashSchedule` —
    already learned there is nothing left for the host to decide
    mid-flight, so the paper's mandatory host syncs collapse into a single
    post-dispatch read that merely *verifies* the buckets — the
    recompile/allocation analog of §5.4's alloc/exec overlap.  For hash
    plans that read also covers the bin sizes and the fallback rung's
    sub-product totals (still one ``device_get``).

The :class:`SpgemmEngine` streams requests through a plan cache
(``cache.py``): requests are grouped by plan signature, operands are padded
to the signature's pow-2 storage buckets (so every group member reuses one
executable), and the drain loop keeps a bounded window of dispatches in
flight — request ``k+1`` is planned and dispatched on the host while
earlier requests still execute on device — finalizing pending records in
COMPLETION order (whichever device work finishes first gets its one host
sync first; ``drain_ordered=True`` restores dispatch-order finalize).

``shards=N`` fans each request out into flop-balanced row-block
sub-dispatches of A (``partition.py``) that reuse the same plan machinery,
merged back by a per-plan jitted concatenation.
"""
from __future__ import annotations

import dataclasses
import itertools
import threading
import time
from collections import OrderedDict
from functools import partial
from typing import Callable, Dict, List, Optional, Tuple, Union

import jax
import jax.numpy as jnp

from repro import phases
from repro.core import esc
from repro.core.analysis import (estimate_result, exclusive_sum_in_place,
                                 nprod_into_rpt, row_flops)
from repro.core.binning import bin_rows, bin_rows_for_ladder
from repro.core.csr import CSR
from repro.core.spgemm import (AUTO_SHARDS, SpgemmConfig, SpgemmResult,
                               next_bucket)
from repro.core.faults import FaultPlan, InjectedFault, resolve_faults
from repro.core.workspace import (Arena, ArenaPressureError, Lease,
                                  default_arena)
from repro.kernels import spgemm_hash
from repro.launch.mesh import data_axis_devices

from . import autotune, stats as stats_mod
from .autotune import AdaptivePolicy, MemoryGovernor, PolicyState
from .cache import CacheEntry, PlanCache
from .partition import ShardSpec, plan_shards, shard_devices
from .plan import HashSchedule, MatrixSig, SpgemmPlan, plan as make_plan
from .stats import EngineStats
from .telemetry import Span, Telemetry, resolve_telemetry

_exclusive_sum = jax.jit(exclusive_sum_in_place, donate_argnums=0)

# Capacity buckets (product expansion / C storage) get a smaller margin:
# it only moves the learned pow-2 bucket when the observed total sits in
# the top fifth of one, exactly where same-signature jitter would
# otherwise flip buckets call over call (sharded sub-problems halve the
# totals, putting them near boundaries far more often than whole
# matrices).  Elsewhere it is absorbed by the pow-2 rounding for free.
_CAPACITY_HEADROOM = 1.25


class StepTimer:
    """Per-step wall-clock instrumentation (blocks only when enabled).

    With an ENABLED ``tracer`` each measured step also emits a telemetry
    span (nested under the tracer's current ``with``-span — the cold
    ``cold_steps`` span in practice), giving the trace per-kernel-phase
    attribution on exactly the paths that already host-sync.  A step that
    is one phase takes the phase's name (``repro.phases``: ``nprod``,
    ``bin``, ``rowptr``); ``symbolic`` and ``numeric`` span several.  The
    ``timings`` dict keeps its historical block-time-only semantics (the
    two binnings add up under ``bin``).
    """

    def __init__(self, enabled: bool, tracer: Optional[Telemetry] = None,
                 uid: Optional[int] = None):
        self.tracer = tracer if (tracer is not None
                                 and tracer.enabled) else None
        self.enabled = enabled or self.tracer is not None
        self.uid = uid
        self.timings: Dict[str, float] = {}

    def measure(self, name: str, value):
        """Block on `value` and charge the elapsed time to `name`."""
        if self.enabled:
            span = (self.tracer.start_span(name, uid=self.uid)
                    if self.tracer is not None else None)
            t0 = time.perf_counter()
            jax.block_until_ready(value)
            self.timings[name] = self.timings.get(name, 0.0) + (
                time.perf_counter() - t0)
            if span is not None:
                self.tracer.end_span(span)
        return value


# ---------------------------------------------------------------------------
# Path 1: the faithful six-step host-orchestrated flow (paper Fig. 2).
# ---------------------------------------------------------------------------

def _floor_schedule(row_buckets, fall_cap, plan_buckets, plan_fall):
    """Floor a freshly-derived phase schedule at the plan's learned one so
    repeat shapes keep hitting the same per-kernel executables (and the
    schedule only ever grows)."""
    if plan_buckets is None:
        return row_buckets, fall_cap
    return (tuple(max(a, b) for a, b in zip(row_buckets, plan_buckets)),
            max(fall_cap, plan_fall))


def _execute_steps(A: CSR, B: CSR, plan: SpgemmPlan,
                   timer: StepTimer, *, headroom: float = 2.0):
    """Cold / timing path.  Returns (result, prod_cap, nnz_cap, hash_sched).

    Identical math to the pre-engine ``core.spgemm`` flow, except the
    capacity buckets are floored at the plan's learned buckets so repeat
    shapes keep hitting the same per-kernel executables.  For the hash
    method each phase derives its launch schedule ONCE (``host_schedule``,
    with headroom, floored at the plan's), runs the schedule-driven
    kernels with it, and the combined :class:`HashSchedule` is returned
    for the caller to specialize the plan with (``None`` for ESC).

    ``headroom`` over-provisions the learned bin-count buckets so
    steady-state bin-size jitter stays inside the schedule: padding rows
    are masked grid steps, far cheaper than the steps-redo + recompile an
    overflow costs (the §5.1/§5.6 memory-vs-retrace trade-off).  It is no
    longer a fixed 2x: the engine passes the plan's adaptive-policy value
    (``engine/autotune``) — grown after overflows, shrunk on stable
    streams.
    """
    config = plan.config
    m = A.nrows
    sym_ladder, num_ladder = plan.sym_ladder, plan.num_ladder
    sched = plan.hash_schedule

    # ---- step1: setup -----------------------------------------------------
    rpt_buf = nprod_into_rpt(A, B)               # n_prod lives in C.rpt (§5.3)
    timer.measure(phases.NPROD, rpt_buf)
    nprod = rpt_buf[:m]
    total_nprod = int(jnp.sum(nprod))            # host sync #1 (sizes launches)

    # ---- step2: symbolic binning -------------------------------------------
    sym_binning = bin_rows_for_ladder(nprod, sym_ladder)
    timer.measure(phases.BIN, sym_binning.bins)

    prod_capacity = max(plan.prod_bucket or 0,
                        next_bucket(max(int(total_nprod
                                            * _CAPACITY_HEADROOM), 1)))

    # ---- step3: symbolic ----------------------------------------------------
    sym_buckets = sym_fall = None
    if config.method == "hash":
        # Packed configs need pack-aligned sym buckets (the packed kernels
        # batch rows_per_block rows per grid step); learning them aligned
        # here keeps every later union/floor aligned too.  The standalone
        # symbolic kernel packs just like the fused one, so the alignment
        # is needed whether or not the numeric phase fuses.
        sym_packs = (sym_ladder.rows_per_block
                     if config.row_packing else None)
        sym_buckets, sym_fall = _floor_schedule(
            *spgemm_hash.host_schedule(A, B, sym_binning, sym_ladder,
                                       headroom=headroom,
                                       packs=sym_packs),
            sched.sym_row_buckets if sched else None,
            sched.fall_prod_bucket if sched else 0)
        nnz_buf, _, _ = spgemm_hash.symbolic_scheduled(
            A, B, sym_binning, sym_ladder,
            row_buckets=sym_buckets, fallback_prod_capacity=sym_fall,
            single_access=config.hash_single_access,
            interpret=config.interpret, row_packing=config.row_packing)
    else:
        nnz_buf = esc.symbolic(A, B, prod_capacity=prod_capacity)
    timer.measure("symbolic", nnz_buf)

    # ---- step4: alloc -------------------------------------------------------
    nnz = nnz_buf[:m]
    # Numeric binning is dispatched BEFORE the host reads total_nnz: the
    # launch-early / allocate-later ordering of §5.4.
    num_binning = bin_rows_for_ladder(nnz, num_ladder)
    total_nnz = int(jnp.sum(nnz))                # host sync #2 (alloc C)
    nnz_capacity = max(plan.nnz_bucket or 0,
                       next_bucket(max(int(total_nnz
                                           * _CAPACITY_HEADROOM), 1)))
    rpt = _exclusive_sum(nnz_buf)                # in-place on the rpt buffer
    timer.measure(phases.ROWPTR, rpt)
    timer.measure(phases.BIN, num_binning.bins)

    # ---- step6: numeric -----------------------------------------------------
    hash_sched = None
    if config.method == "hash":
        num_buckets, num_fall = _floor_schedule(
            *spgemm_hash.host_schedule(A, B, num_binning, num_ladder,
                                       headroom=headroom),
            sched.num_row_buckets if sched else None,
            sched.fall_prod_bucket if sched else 0)
        # Both phases share ONE fallback expansion capacity (one arena
        # bucket per plan): each eager phase runs with the shared max.
        fall = max(sym_fall, num_fall)
        C, _, _ = spgemm_hash.numeric_scheduled(
            A, B, rpt, num_binning, num_ladder,
            row_buckets=num_buckets, nnz_capacity=nnz_capacity,
            fallback_prod_capacity=fall,
            single_access=config.hash_single_access,
            interpret=config.interpret)
        # The plan learns what its steady executable needs, which for a
        # fused plan is less (webbase-1M's numeric fallback rung expands
        # 16x more than its symbolic one).
        hash_sched = HashSchedule(sym_buckets, num_buckets,
                                  autotune.fallback_need(
                                      sym_fall, num_fall,
                                      config.fuse_numeric))
    elif config.fuse_esc:
        C = esc.spgemm_fused(A, B, prod_capacity=prod_capacity,
                             nnz_capacity=nnz_capacity)
    else:
        C = esc.numeric(A, B, rpt, prod_capacity=prod_capacity,
                        nnz_capacity=nnz_capacity)
    timer.measure("numeric", C.val)

    result = SpgemmResult(
        C=C, total_nprod=total_nprod, total_nnz=total_nnz,
        sym_binning=sym_binning, num_binning=num_binning,
        timings=timer.timings)
    return result, prod_capacity, nnz_capacity, hash_sched


# ---------------------------------------------------------------------------
# Path 2: the steady-state jitted executable (one trace per plan).
# ---------------------------------------------------------------------------

def _donate_workspace(body: Callable) -> Callable:
    """Wrap a steady-state pipeline so it carries an arena lease through
    the trace: the leased buffers are DONATED into the executable and
    returned as outputs, so XLA aliases the outputs onto the donated HBM
    blocks — the same physical workspace serves request after request
    instead of each dispatch allocating fresh expansion buffers (§5.4's
    alloc/exec overlap, generalized arena-wide).  The engine rebinds the
    plan's lease to the RETURNED arrays at finalize (the donated inputs
    are consumed and must not be touched again)."""
    @partial(jax.jit, donate_argnums=(2, 3))
    def run(A: CSR, B: CSR, ws_i32: jax.Array, ws_val: jax.Array):
        return body(A, B) + (ws_i32, ws_val)
    return run


def _finish_executable(plan: SpgemmPlan, body: Callable) -> Callable:
    """Jit a builder's pipeline body, threading the arena lease through
    when the plan holds one (``workspace_spec() is not None``)."""
    if plan.workspace_spec() is not None:
        return _donate_workspace(body)
    return jax.jit(body)


def _build_hot_executable(plan: SpgemmPlan) -> Callable:
    """Jit the whole two-phase flow against a specialized plan.

    Every shape is static (the plan's buckets), so the full pipeline —
    setup, both binnings, symbolic, alloc, numeric — fuses into one
    executable with zero mid-flight host syncs.  The totals come back as
    device scalars; the engine's finalize step reads them once to verify
    the buckets still hold (growing them on overflow).
    """
    assert plan.is_specialized and plan.config.method == "esc"
    m = plan.a_sig.nrows
    config = plan.config
    sym_upper = plan.sym_ladder.upper
    sym_nb = plan.sym_ladder.num_bins
    num_upper = plan.num_ladder.upper
    num_nb = plan.num_ladder.num_bins
    prod_cap, nnz_cap = plan.prod_bucket, plan.nnz_bucket
    key = plan.signature

    def body(A: CSR, B: CSR):
        stats_mod.record_trace(key)      # fires once per trace (recompile)
        with phases.scope(phases.NPROD):
            rpt_buf = nprod_into_rpt(A, B)
            nprod = rpt_buf[:m]
            total_nprod = jnp.sum(nprod)
        with phases.scope(phases.BIN):
            sym_binning = bin_rows(nprod, upper=sym_upper, num_bins=sym_nb)
        nnz_buf = esc.symbolic(A, B, prod_capacity=prod_cap)
        nnz = nnz_buf[:m]
        with phases.scope(phases.BIN):
            num_binning = bin_rows(nnz, upper=num_upper, num_bins=num_nb)
        with phases.scope(phases.ROWPTR):
            total_nnz = jnp.sum(nnz)
            rpt = exclusive_sum_in_place(nnz_buf)
        if config.fuse_esc:
            C = esc.spgemm_fused(A, B, prod_capacity=prod_cap,
                                 nnz_capacity=nnz_cap)
        else:
            C = esc.numeric(A, B, rpt, prod_capacity=prod_cap,
                            nnz_capacity=nnz_cap)
        return C, total_nprod, total_nnz, sym_binning, num_binning

    return _finish_executable(plan, body)


def _fallback_nnz(binning, nnz, ladder, row_buckets) -> jax.Array:
    """nnz of the rows on the ESC fallback rung (0 when the schedule has
    none): the entries the hash epilogue does not write."""
    if not row_buckets[-1]:
        return jnp.int32(0)
    fallback = binning.bin_of_row == len(ladder.table_sizes)
    return jnp.sum(jnp.where(fallback, nnz, 0))


def _build_hash_executable(plan: SpgemmPlan) -> Callable:
    """Jit the whole hash pipeline against a specialized plan (§5.1–§5.5).

    The plan's :class:`HashSchedule` makes the per-rung launch loop a
    static schedule (fixed-capacity ``pallas_call`` per populated rung,
    largest rung first), so the two binnings, every hash kernel, and the
    ESC fallback rung all trace into ONE executable — the hash method's
    zero-retrace steady state.  The returned device scalars (totals, bin
    sizes via the binnings, fallback sub-products) let finalize verify
    the whole schedule in its single host sync.
    """
    assert plan.is_specialized and plan.config.method == "hash"
    m = plan.a_sig.nrows
    config = plan.config
    sym_ladder, num_ladder = plan.sym_ladder, plan.num_ladder
    sched = plan.hash_schedule
    nnz_cap = plan.nnz_bucket
    key = plan.signature

    def body(A: CSR, B: CSR):
        stats_mod.record_trace(key)      # fires once per trace (recompile)
        with phases.scope(phases.NPROD):
            rpt_buf = nprod_into_rpt(A, B)
            nprod = rpt_buf[:m]
            total_nprod = jnp.sum(nprod)
        with phases.scope(phases.BIN):
            sym_binning = bin_rows(nprod, upper=sym_ladder.upper,
                                   num_bins=sym_ladder.num_bins)
        nnz_buf, sym_fall_prod, _ = spgemm_hash.symbolic_scheduled(
            A, B, sym_binning, sym_ladder,
            row_buckets=sched.sym_row_buckets,
            fallback_prod_capacity=sched.fall_prod_bucket,
            single_access=config.hash_single_access,
            interpret=config.interpret)
        nnz = nnz_buf[:m]
        with phases.scope(phases.BIN):
            num_binning = bin_rows(nnz, upper=num_ladder.upper,
                                   num_bins=num_ladder.num_bins)
        with phases.scope(phases.ROWPTR):
            total_nnz = jnp.sum(nnz)
            fall_nnz = _fallback_nnz(num_binning, nnz, num_ladder,
                                     sched.num_row_buckets)
            rpt = exclusive_sum_in_place(nnz_buf)
        # Both phases expand into the SAME shared fallback capacity (one
        # arena bucket, one traced expansion shape per plan).
        C, num_fall_prod, _ = spgemm_hash.numeric_scheduled(
            A, B, rpt, num_binning, num_ladder,
            row_buckets=sched.num_row_buckets,
            nnz_capacity=nnz_cap,
            fallback_prod_capacity=sched.fall_prod_bucket,
            single_access=config.hash_single_access,
            interpret=config.interpret)
        return (C, total_nprod, total_nnz, sym_binning, num_binning,
                sym_fall_prod, num_fall_prod, fall_nnz)

    return _finish_executable(plan, body)


def _build_fused_hash_executable(plan: SpgemmPlan) -> Callable:
    """Jit the FUSED hash pipeline against a specialized plan.

    ``fuse_numeric`` steady state: one n_prod binning (symbolic ladder),
    one table build per row (``spgemm_hash.fused_scheduled``) emitting
    nnz AND accumulated values, so the paper's symbolic/numeric table
    double-build collapses to a single probe pass — roughly half the
    per-row table transactions of the two-pass executable (the cold
    steps path, which stays the parity oracle).  The finalize sync
    verifies only the sym schedule + fallback product + nnz bucket
    (there is no numeric binning to check).
    """
    assert (plan.is_specialized and plan.config.method == "hash"
            and plan.config.fuse_numeric)
    m = plan.a_sig.nrows
    config = plan.config
    sym_ladder, num_ladder = plan.sym_ladder, plan.num_ladder
    sched = plan.hash_schedule
    nnz_cap = plan.nnz_bucket
    key = plan.signature

    def body(A: CSR, B: CSR):
        stats_mod.record_trace(key)      # fires once per trace (recompile)
        with phases.scope(phases.NPROD):
            rpt_buf = nprod_into_rpt(A, B)
            nprod = rpt_buf[:m]
            total_nprod = jnp.sum(nprod)
        with phases.scope(phases.BIN):
            sym_binning = bin_rows(nprod, upper=sym_ladder.upper,
                                   num_bins=sym_ladder.num_bins)
        C, nnz, sym_fall_prod, _ = spgemm_hash.fused_scheduled(
            A, B, sym_binning, sym_ladder,
            row_buckets=sched.sym_row_buckets,
            nnz_capacity=nnz_cap,
            fallback_prod_capacity=sched.fall_prod_bucket,
            single_access=config.hash_single_access,
            interpret=config.interpret,
            row_packing=config.row_packing)
        with phases.scope(phases.ROWPTR):
            total_nnz = jnp.sum(nnz)
            fall_nnz = _fallback_nnz(sym_binning, nnz, sym_ladder,
                                     sched.sym_row_buckets)
        # No numeric phase runs, but the n_nz binning stays part of the
        # result so fused steady-state calls report the same telemetry
        # shape as cold calls (it's a cheap histogram, not a probe pass).
        with phases.scope(phases.BIN):
            num_binning = bin_rows(nnz, upper=num_ladder.upper,
                                   num_bins=num_ladder.num_bins)
        return (C, total_nprod, total_nnz, sym_binning, num_binning,
                sym_fall_prod, fall_nnz)

    return _finish_executable(plan, body)


def _build_merge_executable(spec: ShardSpec, m: int, n: int) -> Callable:
    """Jit the per-shard CSR concatenation for a sharded plan's partition.

    Row-block sub-products are disjoint in row space, so the merged C is a
    pure concatenation: shard row pointers rebased by the running nnz
    offsets (on device — no host math touches the arrays) and each shard's
    packed entries scattered at its offset.  Shapes are static (the real
    row counts come from the spec's pinned bounds; storage from the shard
    results' capacities), so one trace serves the steady state; a shard
    plan's nnz-bucket growth changes an input shape and retraces once.
    """
    real_rows = tuple(spec.rows(s) for s in range(spec.n_shards))
    key = ("merge", spec.bounds, m, n)

    @jax.jit
    def run(parts):
        stats_mod.record_trace(key)      # fires once per trace (recompile)
        nnzs = jnp.stack([C.rpt[r] for C, r in zip(parts, real_rows)])
        offs = jnp.concatenate(
            [jnp.zeros(1, jnp.int32),
             jnp.cumsum(nnzs).astype(jnp.int32)])
        rpt = jnp.concatenate(
            [C.rpt[:r] + offs[i]
             for i, (C, r) in enumerate(zip(parts, real_rows))]
            + [offs[-1:]])
        out_cap = sum(C.capacity for C in parts)
        col = jnp.zeros(out_cap, jnp.int32)
        val = jnp.zeros(out_cap, parts[0].val.dtype)
        for i, C in enumerate(parts):
            idx = jnp.arange(C.capacity, dtype=jnp.int32)
            tgt = jnp.where(idx < nnzs[i], offs[i] + idx, out_cap)  # drop pad
            col = col.at[tgt].set(C.col, mode="drop")
            val = val.at[tgt].set(C.val, mode="drop")
        return CSR(rpt=rpt, col=col, val=val, shape=(m, n))

    return run


# ---------------------------------------------------------------------------
# Request records.
# ---------------------------------------------------------------------------

@dataclasses.dataclass
class SpgemmRequest:
    """One queued (A, B) product awaiting drain()."""

    uid: int
    A: CSR
    B: CSR
    config: SpgemmConfig


@dataclasses.dataclass
class _Finished:
    """Synchronously-completed dispatch (steps path)."""

    uid: int
    result: SpgemmResult
    auto_entry: Optional[CacheEntry] = None  # AUTO_SHARDS policy entry
    span: Optional[Span] = None   # open request/shard span (ends at finalize)
    t0: Optional[float] = None    # dispatch wall-clock (latency histogram)


@dataclasses.dataclass
class _Pending:
    """Asynchronously-dispatched hot-path call awaiting its one host sync."""

    uid: int
    entry: CacheEntry
    plan: SpgemmPlan    # the plan the run was dispatched against: the
                        # entry may be re-specialized while we're in flight
    A: CSR
    B: CSR
    handles: tuple      # (C, total_nprod, total_nnz, sym_binning, num_binning
                        #  [, ...phase scalars][, ws_i32, ws_val when leased])
    t0: float
    auto_entry: Optional[CacheEntry] = None  # AUTO_SHARDS policy entry
    span: Optional[Span] = None   # open request/shard span (ends at finalize)
    lease: Optional[Lease] = None  # arena workspace checked out at dispatch
    # Host-side phase wall-clocks captured at dispatch (estimate-mode cold
    # calls: estimate/build/compile_dispatch) — merged into the finalized
    # SpgemmResult.timings so benchmarks see the cold-phase breakdown.
    timings: Dict[str, float] = dataclasses.field(default_factory=dict)


@dataclasses.dataclass
class _ShardedPending:
    """A request fanned out into per-shard sub-dispatches awaiting merge.

    Each element of ``shard_recs`` is an ordinary record (_Finished from a
    cold shard, _Pending from a hot one) with its own verify sync; the
    merge finalizer verifies the slice storage buckets (redoing any
    truncated shard), then concatenates the per-shard CSRs."""

    uid: int
    entry: CacheEntry   # the PARENT (sharded) plan's cache entry
    spec: ShardSpec     # the partition the shards were sliced with
    shard_recs: List["_Record"]
    A: CSR              # the canonicalized operands, kept for slice
    B: CSR              # verification and overflowed-shard redo
    config: SpgemmConfig
    t0: float
    auto_entry: Optional[CacheEntry] = None  # AUTO_SHARDS policy entry
    span: Optional[Span] = None   # open request span (ends at finalize)


_Record = Union[_Finished, _Pending, _ShardedPending]


def _record_ready(rec: _Record) -> bool:
    """Whether a record's device work has completed (non-blocking probe).

    Backends whose arrays lack ``is_ready`` report True — the completion-
    order drain then degrades gracefully to dispatch order."""
    if isinstance(rec, _Finished):
        return True
    if isinstance(rec, _ShardedPending):
        return all(_record_ready(r) for r in rec.shard_recs)
    return all(leaf.is_ready() for leaf in jax.tree_util.tree_leaves(rec.handles)
               if hasattr(leaf, "is_ready"))


class SpgemmEngine:
    """Streaming SpGEMM front-end: plan cache + batched async executor.

    Usage::

        engine = SpgemmEngine()
        r = engine.execute(A, B)                 # synchronous, plan-cached

        engine.submit(A1, B1); engine.submit(A2, B2)
        results = engine.drain()    # batched, completion-order finalize

    ``execute`` is what ``repro.core.spgemm`` wraps; ``submit``/``drain``
    is the serving-path API: requests grouped by plan, a bounded window
    of dispatches in flight, pending work finalized as it completes
    (``drain(drain_ordered=True)`` restores dispatch-order finalize).

    ``shards=N`` makes every plan partition-aware: requests fan out into N
    flop-balanced row-block sub-dispatches of A (pow-2-bucketed slice
    signatures, so shard plans hit the cache) whose CSR results a jitted
    merge finalizer concatenates back — one plan, N shards.  ``mesh``
    optionally places shard s on the s-th data-axis device of a
    ``launch/mesh.py`` mesh (replicated B, row-sharded A).

    ``shards="auto"`` replaces the static knob with the adaptive policy
    (``engine/autotune.py``): N is learned per plan from the cold flop
    estimate bounded by device occupancy, and revised from finalize
    telemetry when the stream's flop mean drifts (tiny products collapse
    to N=1).  ``policy`` tunes the :class:`AdaptivePolicy` knobs — it
    also governs the tracked-jitter hash-schedule headroom (grow on
    overflow, trim on sustained eviction-free streaks).
    """

    def __init__(self, config: Optional[SpgemmConfig] = None, *,
                 cache_capacity: int = 64,
                 shards: Union[int, str] = 1, mesh=None,
                 policy: Optional[AdaptivePolicy] = None,
                 telemetry: Union[Telemetry, bool, None] = None,
                 arena: Optional[Arena] = None,
                 governor: Optional[MemoryGovernor] = None,
                 faults: Optional[FaultPlan] = None):
        assert shards == "auto" or shards >= 1, shards
        self.config = config or SpgemmConfig()
        self.shards = shards
        self.mesh = mesh
        self.policy = policy or AdaptivePolicy()
        # Workspace arena + memory governor: by default every engine in
        # the process shares ONE arena (multi-tenant traffic is bounded
        # together); pass an explicit Arena for isolation.  The governor
        # default is unbounded — set ``MemoryGovernor(cap_bytes=...)`` to
        # turn the degradation ladder on.
        self.arena = arena if arena is not None else default_arena()
        self.governor = governor or MemoryGovernor()
        # Structured tracing/metrics (telemetry.py).  Disabled by default:
        # spans/events no-op, but the registry still backs EngineStats /
        # the cache counters, so there is exactly ONE set of numbers.
        self.telemetry = resolve_telemetry(telemetry)
        # Deterministic fault injection (core/faults.py), threaded the
        # same way: the disabled default costs one attribute read per
        # site.  Sites: lease_denial (workspace acquisition), verify_
        # overflow (finalize), executor_raise + slow_dispatch (dispatch).
        self.faults = resolve_faults(faults)
        self.cache = PlanCache(cache_capacity, telemetry=self.telemetry,
                               arena=self.arena)
        self.stats = EngineStats(registry=self.telemetry.registry)
        # Engine-level estimator calibration (plan_mode="estimate"): the
        # tail-quantile headroom is learned ACROSS plans from observed
        # confirm/retrace telemetry — misses are a property of the traffic
        # distribution, not of one signature.
        self.est_state = autotune.EstimatorState(self.policy)
        reg = self.telemetry.registry
        self._hist_request = reg.histogram("opsparse_request_latency_seconds")
        self._hist_cold = reg.histogram("opsparse_cold_steps_seconds")
        self._hist_finalize = reg.histogram("opsparse_finalize_seconds")
        # The hash epilogue's work and yield (see _note_epilogue).
        self._epilogue_slots = reg.counter("opsparse_epilogue_slots_total")
        self._epilogue_gathered = reg.counter(
            "opsparse_epilogue_gathered_slots_total")
        self._epilogue_entries = reg.counter(
            "opsparse_epilogue_entries_total")
        # The hash products and the part of them on the ESC fallback
        # rung (see _note_products).
        self._hash_products = reg.counter("opsparse_hash_products_total")
        self._fallback_products = reg.counter(
            "opsparse_fallback_products_total")
        # Arena gauges/counters: snapshot-set from the (possibly shared)
        # arena's own accounting on every lease transition, so multiple
        # engines publishing into their own registries agree.
        self._arena_gauges = {
            "opsparse_arena_bytes_in_use": reg.gauge(
                "opsparse_arena_bytes_in_use"),
            "opsparse_arena_bytes_reserved": reg.gauge(
                "opsparse_arena_bytes_reserved"),
            "opsparse_arena_peak_bytes": reg.gauge(
                "opsparse_arena_peak_bytes"),
            "opsparse_arena_lease_hits_total": reg.gauge(
                "opsparse_arena_lease_hits_total"),
            "opsparse_arena_lease_misses_total": reg.gauge(
                "opsparse_arena_lease_misses_total"),
            "opsparse_arena_pressure_events_total": reg.gauge(
                "opsparse_arena_pressure_events_total"),
        }
        self._queue: List[SpgemmRequest] = []
        self._uids = itertools.count()
        # Per-device replicated-B memo for the mesh path.  Streams reuse
        # the same B request after request (the repeated-adjacency
        # pattern), so B ships to each non-home device ONCE, not once per
        # dispatch.  A new B clears the WHOLE memo (identity check on the
        # source array) so stale replicas don't pin device memory.
        self._b_src = None
        self._b_placed: Dict = {}

    # -- public API ---------------------------------------------------------
    def _effective_config(self, config: Optional[SpgemmConfig]) -> SpgemmConfig:
        """Resolve the per-call config.  The engine-level ``shards`` knob
        (an int, or ``"auto"`` = AUTO_SHARDS adaptive selection) only
        folds into the engine's own default config — an explicitly passed
        config is taken verbatim, so ``SpgemmConfig(shards=1)`` opts a
        single call out of engine-level sharding."""
        if config is not None:
            return config
        config = self.config
        if self.shards != 1 and config.shards == 1:
            shards = AUTO_SHARDS if self.shards == "auto" else self.shards
            config = dataclasses.replace(config, shards=shards)
        return config

    def execute(self, A: CSR, B: CSR,
                config: Optional[SpgemmConfig] = None) -> SpgemmResult:
        """Plan-then-execute one product (the ``spgemm()`` backend)."""
        rec = self._dispatch(next(self._uids), A, B,
                             self._effective_config(config))
        return self._finalize(rec)

    def prewarm(self, A: CSR, B: CSR,
                config: Optional[SpgemmConfig] = None, *,
                prod_bucket: Optional[int] = None,
                nnz_bucket: Optional[int] = None) -> SpgemmPlan:
        """Ahead-of-time plan specialization (no execution).

        Seeds the plan for (A, B)'s signatures with caller-provided
        capacity buckets — Liu & Vinter-style ahead-of-time allocation
        for workloads whose product sizes are known (or bounded) up
        front, e.g. a BFS whose frontiers grow hop over hop.  The first
        real request then goes straight to the jitted hot path instead
        of paying a cold discovery call plus progressive regrows.

        With NO buckets supplied the sampling estimator sizes the plan
        instead (``core/analysis.estimate_result``): capacities, and for
        hash configs the full launch schedule — so an estimator prewarm
        fully specializes even hash plans, which explicit buckets alone
        cannot (they lack the schedule).

        Capacity buckets are per-(sub-)problem state, which a sharded
        parent plan doesn't hold — its partition needs data the caller
        can't supply here.  On a sharded engine, pass an explicit
        unsharded config (or prewarm via :meth:`PlanCache.load`).
        """
        config = self._effective_config(config)
        if config.shards != 1:       # not assert: must survive python -O
            raise ValueError(
                "prewarm seeds capacity buckets, which sharded (or "
                "AUTO_SHARDS) plans don't use; pass SpgemmConfig(shards=1) "
                "or PlanCache.load() a dump")
        a_sig, b_sig = MatrixSig.of(A), MatrixSig.of(B)
        entry = self.cache.get((a_sig, b_sig, config))
        if entry is None:
            entry = self.cache.insert(make_plan(a_sig, b_sig, config))
        if prod_bucket is None and nnz_bucket is None:
            if not entry.plan.is_specialized:
                uid = next(self._uids)
                with self.telemetry.span("estimate", uid=uid,
                                         prewarm=True):
                    self._estimate_specialize(
                        entry, A.with_capacity(a_sig.cap_bucket),
                        B.with_capacity(b_sig.cap_bucket), uid)
            return entry.plan
        if prod_bucket is None or nnz_bucket is None:
            raise ValueError(
                "pass both prod_bucket and nnz_bucket, or neither "
                "(estimator-sized prewarm)")
        self.cache.specialize(entry, entry.plan.with_capacities(
            max(entry.plan.prod_bucket or 0,
                next_bucket(max(prod_bucket, 1))),
            max(entry.plan.nnz_bucket or 0,
                next_bucket(max(nnz_bucket, 1)))))
        return entry.plan

    def _estimate_specialize(self, entry: CacheEntry, A: CSR, B: CSR,
                             uid: int) -> Dict[str, float]:
        """Specialize a cold plan from the sampled estimator
        (``plan_mode="estimate"`` — the Ocean-style cold path).

        The exact cold path runs the FULL symbolic phase (and, two-pass,
        a second probe pass) just to size buckets.  Here the per-row
        n_prod fetch — the same host sync the flop partitioner pays —
        yields the EXACT symbolic-side schedule, and a small measured row
        sample bands the compression ratio to predict the nnz bucket and
        the numeric-side rung counts.  The plan is specialized in one
        step (capacities + hash launch schedule) with its policy marked
        ``estimated=True``; the finalize verify confirms it on the first
        admitted call, and an under-estimate pays one overflow-grow
        retrace (bitwise-equal result via the steps oracle) while the
        engine-level :class:`~repro.engine.autotune.EstimatorState`
        grows the tail headroom for the next cold plan.

        Returns the host wall-clock as a timings fragment
        (``{"estimate": seconds}``) for the cold-phase breakdown.
        """
        plan = entry.plan
        config = plan.config
        t0 = time.perf_counter()
        est = estimate_result(
            A, B,
            sym_upper=plan.sym_ladder.upper,
            num_upper=plan.num_ladder.upper,
            n_sample=self.policy.est_sample_rows,
            quantile=self.policy.est_quantile,
            headroom=self.est_state.headroom)
        self.stats.estimates += 1
        self.telemetry.event(
            "estimate", uid=uid, sampled_rows=est.sampled_rows,
            r_lo=est.r_lo, r_hi=est.r_hi, total_nprod=est.total_nprod,
            total_nnz_high=est.total_nnz_high,
            est_headroom=self.est_state.headroom)
        prod_cap = max(plan.prod_bucket or 0,
                       next_bucket(max(int(est.total_nprod
                                           * _CAPACITY_HEADROOM), 1)))
        nnz_cap = max(plan.nnz_bucket or 0,
                      next_bucket(max(int(est.total_nnz_high
                                          * _CAPACITY_HEADROOM), 1)))
        state = plan.policy or PolicyState(
            headroom=self.policy.headroom_init)
        specialized = plan.with_capacities(prod_cap, nnz_cap)
        if config.method == "hash":
            # Same bucket math as host_schedule/trim_schedule (the ONE
            # shared copy in spgemm_hash), fed estimated counts: exact
            # rows per sym rung, band-high rows per num rung, and the
            # band-high fallback products of both phases, merged by
            # autotune.fallback_need.
            m_cap = next_bucket(plan.a_sig.nrows,
                                minimum=spgemm_hash._ROW_BUCKET_MIN)
            packs = (plan.sym_ladder.rows_per_block
                     if config.row_packing else None)
            sym_buckets = tuple(
                spgemm_hash.schedule_bucket(
                    c, m_cap=m_cap, headroom=state.headroom,
                    pack=(packs[b] if packs is not None and b < len(packs)
                          else 1))
                for b, c in enumerate(est.sym_counts))
            num_buckets = tuple(
                spgemm_hash.schedule_bucket(c, m_cap=m_cap,
                                            headroom=state.headroom)
                for c in est.num_counts)
            fall = autotune.fallback_need(est.sym_fall_prod,
                                          est.num_fall_prod,
                                          config.fuse_numeric)
            fall_bucket = (spgemm_hash.fallback_capacity_bucket(
                fall, headroom=state.headroom) if fall else 0)
            sched = HashSchedule(sym_buckets, num_buckets, fall_bucket)
            if plan.hash_schedule is not None:
                sched = sched.union(plan.hash_schedule)
            specialized = specialized.with_hash_schedule(sched)
        self.cache.specialize(
            entry, specialized.with_policy(state.with_estimated(True)))
        return {"estimate": time.perf_counter() - t0}

    def submit(self, A: CSR, B: CSR,
               config: Optional[SpgemmConfig] = None) -> int:
        """Queue a request; returns its uid (resolved by ``drain``)."""
        assert A.ncols == B.nrows, (A.shape, B.shape)
        uid = next(self._uids)
        self._queue.append(
            SpgemmRequest(uid, A, B, self._effective_config(config)))
        return uid

    def drain(self, *, drain_ordered: bool = False,
              window: int = 4) -> Dict[int, SpgemmResult]:
        """Run all queued requests; returns {uid: result}.

        Requests are grouped by plan signature (group members share one
        executable) and pipelined: up to ``window`` dispatches stay in
        flight, and pending records are finalized in COMPLETION order —
        whichever device work finishes first gets its verify sync first,
        so a slow mixed-size request no longer head-of-line-blocks the
        small ones dispatched after it.  ``drain_ordered=True`` restores
        the PR-1 dispatch-order double-buffered finalize (compat flag; the
        return type is identical either way).
        """
        queue, self._queue = self._queue, []
        self.stats.drains += 1
        groups: "OrderedDict[tuple, List[SpgemmRequest]]" = OrderedDict()
        for req in queue:
            key = (MatrixSig.of(req.A), MatrixSig.of(req.B), req.config)
            groups.setdefault(key, []).append(req)
        ordered = itertools.chain.from_iterable(groups.values())

        # The drain span parents every request span opened inside it (via
        # the tracer's thread-local stack), so the Perfetto view groups a
        # whole batch — including finalizes the completion-order loop
        # reordered — under one interval.
        results: Dict[int, SpgemmResult] = {}
        with self.telemetry.span("drain", n_requests=len(queue),
                                 ordered=drain_ordered):
            if drain_ordered:
                inflight: Optional[_Record] = None
                for req in ordered:
                    try:
                        rec = self._dispatch(req.uid, req.A, req.B,
                                             req.config)
                    except ArenaPressureError:
                        # Backpressure: finalize the in-flight record
                        # (returning its lease) and retry once; with
                        # nothing in flight the cap is simply too small.
                        if inflight is None:
                            raise
                        results[inflight.uid] = self._finalize(inflight)
                        inflight = None
                        rec = self._dispatch(req.uid, req.A, req.B,
                                             req.config)
                    if inflight is not None:
                        if not isinstance(inflight, _Finished):
                            self.stats.overlapped += 1  # planned k+1, k ran
                        results[inflight.uid] = self._finalize(inflight)
                    inflight = rec
                if inflight is not None:
                    results[inflight.uid] = self._finalize(inflight)
                return results

            pending: List[_Record] = []
            window = max(1, int(window))
            for req in ordered:
                # Reap down BEFORE dispatching: appending first would hold
                # window+1 concurrent dispatches (off-by-one — the window
                # is a device-memory bound, so it must hold at dispatch).
                while len(pending) >= window:
                    self._reap_one(pending, results)
                while True:
                    try:
                        rec = self._dispatch(req.uid, req.A, req.B,
                                             req.config)
                        break
                    except ArenaPressureError:
                        # Backpressure: finalize one in-flight record
                        # (returning its lease) and retry; with nothing
                        # in flight the cap is simply too small.
                        if not pending:
                            raise
                        self._reap_one(pending, results)
                if any(not isinstance(r, _Finished) for r in pending):
                    self.stats.overlapped += 1   # planned k+1 while k ran
                pending.append(rec)
                self.stats.peak_inflight = max(self.stats.peak_inflight,
                                               len(pending))
            while pending:
                self._reap_one(pending, results)
        return results

    def _reap_one(self, pending: List[_Record],
                  results: Dict[int, SpgemmResult]) -> None:
        """Finalize ONE pending record, preferring completed device work;
        with nothing complete yet, fall back to the oldest dispatch."""
        for i, rec in enumerate(pending):
            if _record_ready(rec):
                if i:
                    self.stats.reordered += 1
                pending.pop(i)
                results[rec.uid] = self._finalize(rec)
                return
        rec = pending.pop(0)
        results[rec.uid] = self._finalize(rec)

    def report(self) -> str:
        return stats_mod.render(self)

    # -- internals ----------------------------------------------------------
    def _update_arena_gauges(self) -> None:
        """Snapshot the (possibly shared) arena's accounting into this
        engine's registry gauges.  Called on every lease transition and
        by ``prometheus_text`` just before rendering, so scrapes see
        fresh numbers even for engines idle since their last lease."""
        a = self.arena
        g = self._arena_gauges
        g["opsparse_arena_bytes_in_use"].set(a.bytes_in_use)
        g["opsparse_arena_bytes_reserved"].set(a.bytes_reserved)
        g["opsparse_arena_peak_bytes"].set(a.peak_bytes)
        g["opsparse_arena_lease_hits_total"].set(a.lease_hits)
        g["opsparse_arena_lease_misses_total"].set(a.lease_misses)
        g["opsparse_arena_pressure_events_total"].set(a.pressure_events)

    # -- fault-injection site shims (core/faults.py) ------------------------
    def _note_fault(self, site: str, uid: int) -> None:
        self.stats.faults_injected += 1
        self.telemetry.event("fault_injected", uid=uid, site=site)

    def _consult_dispatch_faults(self, uid: int) -> None:
        """``executor_raise`` + ``slow_dispatch`` sites, consulted once
        per user-visible request (shard sub-dispatches excluded — the
        consult rides the same guard as ``stats.requests``)."""
        faults = self.faults
        if not faults.enabled:
            return
        spec = faults.fire("executor_raise", uid=uid)
        if spec is not None:
            self._note_fault("executor_raise", uid)
            raise InjectedFault(
                spec.message or f"injected executor fault (uid={uid})",
                site="executor_raise", transient=spec.transient)
        spec = faults.fire("slow_dispatch", uid=uid)
        if spec is not None and spec.delay_s > 0:
            self._note_fault("slow_dispatch", uid)
            time.sleep(spec.delay_s)

    def _try_lease(self, spec, cap, device, uid: int) -> Optional[Lease]:
        """Arena acquisition with the ``lease_denial`` site in front: an
        injected denial is indistinguishable from the cap binding, so the
        governor ladder (and the drain/service backpressure above it)
        runs for real without real memory pressure.  Each acquisition
        attempt — including post-reclaim and post-trim retries — is one
        site visit, so a spec's ``at`` indices control ladder depth."""
        if self.faults.enabled \
                and self.faults.fire("lease_denial", uid=uid) is not None:
            self._note_fault("lease_denial", uid)
            return None
        return self.arena.try_acquire(spec, cap, device)

    def _forced_overflow(self, uid: int) -> bool:
        """``verify_overflow`` site: one visit per hot-path finalize."""
        if not self.faults.enabled:
            return False
        if self.faults.fire("verify_overflow", uid=uid) is None:
            return False
        self._note_fault("verify_overflow", uid)
        return True

    def _lease_workspace(self, entry: CacheEntry, uid: int,
                         device=None) -> Tuple[Optional[Lease], bool]:
        """Check the plan's workspace out of the arena, walking the
        governor's degradation ladder under pressure.

        Returns ``(lease, spill)``: ``lease`` is ``None`` for plans with
        nothing leasable (``workspace_spec() is None``) and under a spill;
        ``spill=True`` routes THIS call through the unleased two-pass
        steps path.  Raises :class:`ArenaPressureError` when the ladder is
        exhausted (``drain`` answers it with backpressure: finalize one
        in-flight record — returning its lease — then retry)."""
        spec = entry.plan.workspace_spec()
        if spec is None:
            return None, False
        cap = self.governor.cap_bytes
        lease = self._try_lease(spec, cap, device, uid)
        if lease is None:
            # rung 0: the cap is binding — count pressure, drop idle
            # pooled buffers, retry.
            self.arena.note_pressure()
            self.stats.arena_pressure += 1
            self.telemetry.event("arena_pressure", uid=uid,
                                 want_bytes=spec.nbytes, cap_bytes=cap,
                                 reserved=self.arena.bytes_reserved)
            self.arena.reclaim()
            lease = self._try_lease(spec, cap, device, uid)
        if lease is None and self.governor.trim_under_pressure:
            # rung 1: forced headroom trim — re-derive the hash schedule
            # at the policy floor from the streak's observed maxima,
            # shrinking this plan's lease spec (drops the executable for
            # one rebuild; the trace is against the smaller shapes).
            plan = entry.plan
            state = plan.policy
            if (plan.config.method == "hash" and plan.hash_schedule is not None
                    and state is not None and state.sym_max is not None):
                forced = dataclasses.replace(
                    state, headroom=self.policy.headroom_min)
                trimmed = autotune.trim_schedule(
                    forced, plan.hash_schedule, m=plan.a_sig.nrows,
                    sym_ladder=plan.sym_ladder,
                    packed=plan.config.row_packing,
                    fused=plan.config.fuse_numeric, policy=self.policy)
                if trimmed is not None:
                    self.stats.arena_trims += 1
                    entry.stats.schedule_trims += 1
                    self.telemetry.event("arena_trim", uid=uid)
                    self.cache.specialize(
                        entry,
                        plan.with_hash_schedule(HashSchedule(*trimmed))
                        .with_policy(forced.after_trim(self.policy)))
                    spec = entry.plan.workspace_spec()
                    if spec is None:
                        return None, False
                    lease = self._try_lease(spec, cap, device, uid)
        if lease is None and self.governor.spill_fused \
                and entry.plan.config.method == "hash" \
                and entry.plan.config.fuse_numeric:
            # rung 2: spill the fused plan to the two-pass steps oracle
            # for this call — no lease, no arena growth, result parity.
            # Hash-fused only: an ESC "spill" would still allocate the
            # same workspace per call, just outside arena accounting.
            self.stats.arena_spills += 1
            self.telemetry.event("arena_spill", uid=uid)
            return None, True
        if lease is None:
            # rung 3: refuse — the caller must return leases first.
            raise ArenaPressureError(
                f"workspace lease of {spec.nbytes} bytes exceeds the "
                f"governor cap ({cap} bytes; "
                f"{self.arena.bytes_reserved} reserved)")
        self._update_arena_gauges()
        return lease, False

    def _release_ws(self, rec: "_Pending") -> None:
        """Finalize-side half of the donation loop: rebind the lease to
        the workspace arrays the executable RETURNED (the donated inputs
        were consumed; XLA aliased the outputs onto their blocks) and
        return them to the arena's free lists."""
        if rec.lease is not None:
            lease, rec.lease = rec.lease, None
            self.arena.release(lease, rebind=rec.handles[-2:])
            if lease in rec.entry.leases:
                rec.entry.leases.remove(lease)
            self._update_arena_gauges()

    def _dispatch(self, uid: int, A: CSR, B: CSR, config: SpgemmConfig, *,
                  _sub: bool = False,
                  _parent: Optional[Span] = None) -> _Record:
        assert A.ncols == B.nrows, (A.shape, B.shape)
        if config.shards == AUTO_SHARDS:
            auto_entry, config = self._resolve_auto_shards(A, B, config)
            rec = self._dispatch(uid, A, B, config, _sub=_sub,
                                 _parent=_parent)
            rec.auto_entry = auto_entry   # finalize feeds telemetry back
            return rec
        if config.shards > 1:
            if A.nrows >= 2:
                return self._dispatch_sharded(uid, A, B, config)
            # Nothing to partition: run (and key the plan) unsharded so
            # the request still reaches the jitted steady state.
            config = dataclasses.replace(config, shards=1)
        if not _sub:       # shard sub-dispatches aren't user requests
            self.stats.requests += 1
            self._consult_dispatch_faults(uid)
        t0 = time.perf_counter()
        tel = self.telemetry
        # The request (or, under the sharded fan-out, per-shard) span
        # stays OPEN across the async dispatch->finalize split: it rides
        # the record and _finalize closes it after the verify sync.
        span = tel.start_span("shard" if _sub else "request",
                              parent=_parent, uid=uid, method=config.method)
        a_sig, b_sig = MatrixSig.of(A), MatrixSig.of(B)
        with tel.span("plan_lookup", parent=span, uid=uid) as lookup:
            entry = self.cache.get((a_sig, b_sig, config))
            lookup.set(hit=entry is not None)
            if entry is None:
                entry = self.cache.insert(make_plan(a_sig, b_sig, config))
        entry.stats.calls += 1

        # Canonicalize operand storage to the signature buckets so every
        # request in the bucket presents identical static shapes.
        A = A.with_capacity(a_sig.cap_bucket)
        B = B.with_capacity(b_sig.cap_bucket)

        plan = entry.plan
        est_timings: Optional[Dict[str, float]] = None
        if (config.plan_mode == "estimate" and not plan.is_specialized
                and config.method in ("esc", "hash") and not config.timing):
            # Estimation-based cold path: specialize straight from the
            # sampled estimator and fall through to the jitted hot path —
            # the full symbolic sizing pass never runs.  The finalize
            # verify (+ overflow-grow retrace) is the correctness net.
            with tel.span("estimate", parent=span, uid=uid):
                est_timings = self._estimate_specialize(entry, A, B, uid)
            plan = entry.plan
        hot_eligible = (plan.is_specialized
                        and config.method in ("esc", "hash")
                        and not config.timing)
        if not hot_eligible:
            state = plan.policy or PolicyState(
                headroom=self.policy.headroom_init)
            # StepTimer carries the tracer, so the six paper steps (nprod,
            # the binnings, symbolic, rowptr, numeric) emit kernel-phase spans
            # nested under cold_steps — attribution on exactly the path
            # that already host-syncs per step.  Truly-cold calls keep the
            # timer on even untraced so benchmarks get the cold-phase
            # breakdown (the steps path host-syncs per step anyway).
            with tel.span("cold_steps", parent=span, uid=uid,
                          specialized=plan.is_specialized) as cold:
                result, prod_cap, nnz_cap, hash_sched = _execute_steps(
                    A, B, plan,
                    StepTimer(config.timing or not plan.is_specialized,
                              tracer=tel, uid=uid),
                    headroom=state.headroom)
            if tel.enabled:
                self._hist_cold.observe(cold.dur)
            if not plan.is_specialized:
                # Progressive allocation: learn the buckets (and, for the
                # hash method, the launch schedule the run just used) for
                # steady state.
                specialized = plan.with_capacities(prod_cap, nnz_cap)
                if hash_sched is not None:
                    specialized = specialized.with_hash_schedule(hash_sched)
                    specialized = specialized.with_policy(state)
                self.cache.specialize(entry, specialized)
            entry.stats.steps_calls += 1
            entry.stats.time_s += time.perf_counter() - t0
            return _Finished(uid, result, span=span, t0=t0)

        # Check the workspace out of the arena BEFORE touching the
        # executable: a forced pressure trim re-specializes the entry
        # (shrinking the traced shapes), so the build below must see the
        # post-ladder plan.
        devs = A.val.devices()
        lease, spill = self._lease_workspace(
            entry, uid, device=next(iter(devs)) if len(devs) == 1 else None)
        if spill:
            # Fused->two-pass spill: this call runs the unleased steps
            # oracle (bitwise-identical result); the plan and its fused
            # executable stay cached for when pressure clears.
            state = entry.plan.policy or PolicyState(
                headroom=self.policy.headroom_init)
            with tel.span("arena_spill_steps", parent=span, uid=uid):
                result, _, _, _ = _execute_steps(
                    A, B, entry.plan,
                    StepTimer(config.timing, tracer=tel, uid=uid),
                    headroom=state.headroom)
            entry.stats.steps_calls += 1
            entry.stats.time_s += time.perf_counter() - t0
            return _Finished(uid, result, span=span, t0=t0)
        plan = entry.plan
        if lease is not None:
            entry.leases.append(lease)   # eviction forfeits outstanding ones
        if entry.executable is None:
            with tel.span("build_executable", parent=span, uid=uid):
                t_build = time.perf_counter()
                if config.method != "hash":
                    builder = _build_hot_executable
                elif config.fuse_numeric:
                    builder = _build_fused_hash_executable
                else:
                    builder = _build_hash_executable
                entry.executable = builder(plan)
                if est_timings is not None:
                    est_timings["build"] = time.perf_counter() - t_build
        with tel.span("dispatch", parent=span, uid=uid):
            t_disp = time.perf_counter()
            if lease is None:
                handles = entry.executable(A, B)   # async dispatch, no sync
            else:
                handles = entry.executable(A, B, lease.i32, lease.val)
            if est_timings is not None:
                # First call through a fresh executable: the jit dispatch
                # blocks on trace+compile, so this IS the compile cost.
                est_timings["compile_dispatch"] = time.perf_counter() - t_disp
        entry.stats.hot_calls += 1
        return _Pending(uid, entry, plan, A, B, handles, t0, span=span,
                        lease=lease, timings=est_timings or {})

    def _dispatch_sharded(self, uid: int, A: CSR, B: CSR,
                          config: SpgemmConfig) -> _Record:
        """Fan one request out into per-shard row-block sub-dispatches.

        The parent plan owns the learned :class:`ShardSpec`; each shard's
        A slice is padded to the spec's pow-2 row/storage buckets and
        dispatched through the ordinary (unsharded) plan machinery, so
        shards reuse the existing ESC/hash executables — and shards whose
        buckets coincide share ONE sub-plan.  Per-shard slice overflow
        grows only that shard's bucket (and hence only that shard's plan).
        """
        self.stats.requests += 1
        self.stats.sharded_requests += 1
        self._consult_dispatch_faults(uid)
        t0 = time.perf_counter()
        tel = self.telemetry
        span = tel.start_span("request", uid=uid, method=config.method,
                              shards=config.shards)
        a_sig, b_sig = MatrixSig.of(A), MatrixSig.of(B)
        with tel.span("plan_lookup", parent=span, uid=uid) as lookup:
            entry = self.cache.get((a_sig, b_sig, config))
            lookup.set(hit=entry is not None)
            if entry is None:
                entry = self.cache.insert(make_plan(a_sig, b_sig, config))
        entry.stats.calls += 1

        spec = entry.plan.shard_spec
        if spec is None:
            # Cold call: ONE host read of the flop estimate balances the
            # row blocks; the partition is then pinned so steady-state
            # shard signatures never move.  Steady-state dispatch stays
            # sync-free — whether this request's slices FIT the learned
            # storage buckets is checked in the finalize sync (an
            # overflowed slice would be silently truncated, which the
            # sub-plans can't detect themselves).
            with tel.span("partition", parent=span, uid=uid):
                flops = row_flops(A, B)        # host int64 (its one sync)
                rpt = jax.device_get(A.rpt)
                spec = plan_shards(rpt, flops, config.shards, telemetry=tel)
                self.cache.specialize(entry,
                                      entry.plan.with_shard_spec(spec))

        if entry.executable is None:
            with tel.span("build_executable", parent=span, uid=uid):
                entry.executable = _build_merge_executable(
                    spec, m=A.nrows, n=B.ncols)

        devices = (shard_devices(self.mesh, spec.n_shards)
                   if self.mesh is not None else None)
        sub_cfg = dataclasses.replace(config, shards=1)
        shard_recs: List[_Record] = []
        for s in range(spec.n_shards):
            A_s = A.row_slice(spec.bounds[s], spec.bounds[s + 1],
                              nrows=spec.row_buckets[s],
                              capacity=spec.cap_buckets[s])
            B_s = B
            if devices is not None:
                dev = devices[s]
                A_s = jax.device_put(A_s, dev)          # row-sharded A
                if self._b_src is not B.val:            # new B: drop replicas
                    self._b_src = B.val
                    self._b_placed = {}
                if dev not in self._b_placed:
                    self._b_placed[dev] = (B if dev in B.val.devices()
                                           else jax.device_put(B, dev))
                B_s = self._b_placed[dev]
            try:
                rec = self._dispatch(uid, A_s, B_s, sub_cfg, _sub=True,
                                     _parent=span)
            except ArenaPressureError:
                # Unwind the fan-out: finalize the shards already in
                # flight so their leases return, then re-raise — drain's
                # backpressure handler redispatches the whole request.
                for r in shard_recs:
                    self._finalize(r)
                if tel.enabled and isinstance(span, Span):
                    tel.end_span(span)
                raise
            if rec.span is not None:
                rec.span.set(shard=s)
            shard_recs.append(rec)
        return _ShardedPending(uid, entry, spec, shard_recs, A, B,
                               config, t0, span=span)

    # -- adaptive shard count (AUTO_SHARDS) ---------------------------------
    def _device_count(self) -> int:
        """Per-shard occupancy bound: the devices shards could land on."""
        if self.mesh is not None:
            return len(data_axis_devices(self.mesh))
        return jax.local_device_count()

    def _resolve_auto_shards(self, A: CSR, B: CSR, config: SpgemmConfig):
        """Turn an AUTO_SHARDS config into a concrete one via the policy.

        The decision lives on the AUTO plan entry (keyed by the unresolved
        config), so it is learned once per signature — ONE host read of
        the flop estimate on the cold request, like the shard partitioner
        — then pinned; finalize-side telemetry (:meth:`_note_auto`) can
        revise it when the stream's flop mean drifts out of the
        hysteresis band (shrinking to 1 for tiny products where the merge
        finalizer dominates).
        """
        self.stats.auto_requests += 1
        a_sig, b_sig = MatrixSig.of(A), MatrixSig.of(B)
        entry = self.cache.get((a_sig, b_sig, config))
        if entry is None:
            entry = self.cache.insert(make_plan(a_sig, b_sig, config))
        state = entry.plan.policy
        if state is None or state.shard_decision is None:
            flops = row_flops(A, B)          # host int64 (the one sync)
            total = int(flops.sum())
            n = autotune.choose_shards(total, A.nrows, self._device_count(),
                                       self.policy,
                                       telemetry=self.telemetry)
            state = ((state or PolicyState(headroom=self.policy.headroom_init))
                     .with_shard_decision(n, total))
            self.cache.update_policy(entry, state)
        n = state.shard_decision
        return entry, dataclasses.replace(config, shards=max(n, 1))

    def _note_auto(self, entry: CacheEntry, result: SpgemmResult) -> None:
        """Feed one finalized request's flop estimate back to its AUTO
        plan's policy, revising the shard decision on sustained drift."""
        state = entry.plan.policy
        if state is None:
            return
        state = state.note_flops(2 * result.total_nprod)
        state, revised = autotune.revise_shards(
            state, entry.plan.a_sig.nrows, self._device_count(), self.policy,
            telemetry=self.telemetry)
        if revised:
            self.stats.policy_revisions += 1
        self.cache.update_policy(entry, state)

    def _finalize(self, rec: _Record) -> SpgemmResult:
        tel = self.telemetry
        with tel.span("finalize", parent=rec.span, uid=rec.uid) as fin:
            result = self._finalize_record(rec)
        if rec.auto_entry is not None:
            self._note_auto(rec.auto_entry, result)
        if tel.enabled:
            self._hist_finalize.observe(fin.dur)
            span = rec.span
            if isinstance(span, Span):
                # Close the open request/shard span the dispatch left on
                # the record (idempotent under redo paths).
                tel.end_span(span)
                if span.name == "request" and rec.t0 is not None:
                    self._hist_request.observe(span.t1 - rec.t0)
        return result

    def _finalize_record(self, rec: _Record) -> SpgemmResult:
        if isinstance(rec, _ShardedPending):
            return self._finalize_sharded(rec)
        if isinstance(rec, _Finished):
            return rec.result

        # Verify against the DISPATCH-TIME plan: a concurrent overflow may
        # have re-specialized the entry with larger buckets than this run
        # actually executed with, and passing its check would return a
        # silently truncated C.
        plan = rec.plan
        handles = (rec.handles[:-2] if rec.lease is not None
                   else rec.handles)   # the lease rides as the last pair
        if plan.config.method == "hash" and plan.config.fuse_numeric:
            (C, tnp, tnz, sym_binning, num_binning, sym_fall,
             fall_nnz) = handles
            # The ONE host sync: totals + sym bin sizes + fallback product
            # (num_binning is telemetry only — no numeric pass to verify).
            with self.telemetry.span("verify_sync", uid=rec.uid):
                fetched = jax.device_get(
                    (tnp, tnz, sym_binning.bin_size, sym_fall, fall_nnz))
            self._release_ws(rec)    # sync done: the workspace is idle
            total_nprod, total_nnz = int(fetched[0]), int(fetched[1])
            schedule_ok = plan.hash_schedule.admits_fused(
                fetched[2], int(fetched[3]))
            if not schedule_ok:
                self.stats.bin_overflows += 1
                rec.entry.stats.bin_overflows += 1
            if not schedule_ok or total_nnz > plan.nnz_bucket \
                    or self._forced_overflow(rec.uid):
                return self._grow_and_redo(rec, total_nprod, total_nnz,
                                           schedule_overflow=not schedule_ok)
            self._note_hash_admit(rec, fetched[2], fetched[3])
            self._note_epilogue(plan, total_nnz - int(fetched[4]))
            self._note_products(total_nprod, int(fetched[3]))
        elif plan.config.method == "hash":
            (C, tnp, tnz, sym_binning, num_binning,
             sym_fall, num_fall, fall_nnz) = handles
            # The ONE host sync: totals + bin sizes + fallback products.
            with self.telemetry.span("verify_sync", uid=rec.uid):
                fetched = jax.device_get(
                    (tnp, tnz, sym_binning.bin_size, num_binning.bin_size,
                     sym_fall, num_fall, fall_nnz))
            self._release_ws(rec)    # sync done: the workspace is idle
            total_nprod, total_nnz = int(fetched[0]), int(fetched[1])
            schedule_ok = plan.hash_schedule.admits(
                fetched[2], fetched[3], int(fetched[4]), int(fetched[5]))
            if not schedule_ok:
                self.stats.bin_overflows += 1
                rec.entry.stats.bin_overflows += 1
            if not schedule_ok or total_nnz > plan.nnz_bucket \
                    or self._forced_overflow(rec.uid):
                return self._grow_and_redo(rec, total_nprod, total_nnz,
                                           schedule_overflow=not schedule_ok)
            self._note_hash_admit(rec, fetched[2], fetched[4],
                                  num_sizes=fetched[3], num_fall=fetched[5])
            self._note_epilogue(plan, total_nnz - int(fetched[6]))
            self._note_products(total_nprod, int(fetched[4]))
        else:
            C, tnp, tnz, sym_binning, num_binning = handles
            with self.telemetry.span("verify_sync", uid=rec.uid):
                total_nprod, total_nnz = (            # the ONE host sync
                    int(x) for x in jax.device_get((tnp, tnz)))
            self._release_ws(rec)    # sync done: the workspace is idle
            if (total_nprod > plan.prod_bucket
                    or total_nnz > plan.nnz_bucket
                    or self._forced_overflow(rec.uid)):
                return self._grow_and_redo(rec, total_nprod, total_nnz)
            # ESC plans carry no hash schedule, so the estimate
            # confirmation doesn't ride _note_hash_admit — clear the
            # provenance flag here.
            state = rec.entry.plan.policy
            if state is not None and state.estimated:
                self._note_estimate_confirmed(rec.uid)
                self.cache.update_policy(rec.entry,
                                         state.with_estimated(False))

        rec.entry.stats.time_s += time.perf_counter() - rec.t0
        return SpgemmResult(
            C=C, total_nprod=total_nprod, total_nnz=total_nnz,
            sym_binning=sym_binning, num_binning=num_binning,
            timings=dict(rec.timings))

    def _finalize_sharded(self, rec: _ShardedPending) -> SpgemmResult:
        """Merge finalizer: one verify sync per shard (each sub-record's
        ordinary finalize, overflow redo and all), then the jitted
        device-side concatenation of the per-shard CSRs.

        The slice-storage check happens HERE, not at dispatch: a slice
        whose nnz outgrew its learned bucket was silently truncated (the
        sub-plan can't tell — the truncated slice is self-consistent), so
        the boundary gather below is part of the request's verify sync.
        Keeping it out of dispatch keeps sharded dispatch sync-free, so
        drain()'s in-flight window genuinely overlaps sharded requests.
        An overflow grows only the offending shard's bucket and redoes
        only that shard."""
        t_fin = time.perf_counter()
        tel = self.telemetry
        spec = rec.spec
        with tel.span("verify_slices", uid=rec.uid):
            slice_nnz = jax.device_get(
                rec.A.rpt[jnp.asarray(spec.bounds, dtype=jnp.int32)])
        sizes = [int(slice_nnz[s + 1]) - int(slice_nnz[s])
                 for s in range(spec.n_shards)]
        overflowed = [s for s in range(spec.n_shards)
                      if sizes[s] > spec.cap_buckets[s]]
        if overflowed:
            tel.event("shard_grow", uid=rec.uid, shards=tuple(overflowed))
            grown = spec
            for s in overflowed:
                grown = grown.with_cap_bucket(s, 2 * sizes[s])  # headroom
                self.stats.shard_grows += 1
            rec.entry.stats.capacity_grows += len(overflowed)
            current = rec.entry.plan.shard_spec
            if current is not None:     # keep any concurrent growth
                grown = grown.union(current)
            self.cache.specialize(
                rec.entry, rec.entry.plan.with_shard_spec(grown))
            sub_cfg = dataclasses.replace(rec.config, shards=1)
            for s in overflowed:        # redo ONLY the truncated shards
                A_s = rec.A.row_slice(spec.bounds[s], spec.bounds[s + 1],
                                      nrows=grown.row_buckets[s],
                                      capacity=grown.cap_buckets[s])
                rec.shard_recs[s] = self._dispatch(
                    rec.uid, A_s, rec.B, sub_cfg, _sub=True,
                    _parent=rec.span)
        shard_results = [self._finalize(r) for r in rec.shard_recs]
        merge = rec.entry.executable
        if merge is None:     # entry re-specialized while we were in flight
            merge = _build_merge_executable(
                rec.spec, m=rec.spec.bounds[-1], n=rec.B.ncols)
            rec.entry.executable = merge
        parts = tuple(r.C for r in shard_results)
        with tel.span("shard_merge", uid=rec.uid,
                      n_shards=spec.n_shards) as merge_span:
            if self.mesh is not None:
                # Mesh placement commits each shard's result to its shard
                # device; one jitted computation can't mix committed
                # devices, so gather the parts home first.
                merge_span.set(devices=tuple(
                    next(iter(C.val.devices())).id for C in parts))
                home = next(iter(parts[0].val.devices()))
                parts = tuple(C if C.val.devices() == {home}
                              else jax.device_put(C, home) for C in parts)
            C = merge(parts)
        timings: Dict[str, float] = {}
        for r in shard_results:
            for k, v in r.timings.items():
                timings[k] = timings.get(k, 0.0) + v
        # Book only the merge/verify overhead on the parent plan — the
        # shard work is already charged to the shard plans, and the
        # overhead-vs-shard-work split is exactly what an adaptive shard
        # count would tune on.
        rec.entry.stats.time_s += time.perf_counter() - t_fin
        return SpgemmResult(
            C=C,
            total_nprod=sum(r.total_nprod for r in shard_results),
            total_nnz=sum(r.total_nnz for r in shard_results),
            sym_binning=None, num_binning=None, timings=timings)

    def _note_estimate_confirmed(self, uid: int) -> None:
        """One ADMITTED finalize just verified an estimated plan: count
        the hit and let the engine-level estimator headroom decay toward
        its floor (sustained accuracy should not keep paying day-one
        conservatism)."""
        self.stats.estimate_hits += 1
        self.est_state.note_hit()
        self.telemetry.event("estimate_confirmed", uid=uid,
                             est_headroom=self.est_state.headroom)

    def _note_hash_admit(self, rec: _Pending, sym_sizes, sym_fall,
                         num_sizes=None, num_fall=0) -> None:
        """Adaptive-headroom telemetry for one ADMITTED hash finalize.

        Folds the bin sizes the verify sync already fetched into the
        plan's policy state (streak maxima — capture is free, no extra
        sync).  Once the eviction-free streak reaches the policy
        threshold, re-derive the schedule from the observed maxima at a
        shrunken headroom and swap it in iff that actually removes
        padded grid steps or whole rungs — ONE deliberate retrace that
        stops a stable stream paying for day-one jitter margins.  At most
        one trim fires per overflow epoch (``PolicyState.trimmed``).
        """
        entry = rec.entry
        plan = entry.plan      # CURRENT plan: maxima fold monotonically
        if plan.hash_schedule is None:
            return
        state = plan.policy or PolicyState(headroom=self.policy.headroom_init)
        if state.estimated:
            # First admitted finalize under an estimated schedule:
            # the prediction held — promote the plan to verified.
            self._note_estimate_confirmed(rec.uid)
            state = state.with_estimated(False)
        state = state.note_admit(sym_sizes, sym_fall, num_sizes, num_fall)
        if state.wants_trim(self.policy):
            trimmed = autotune.trim_schedule(
                state, plan.hash_schedule, m=plan.a_sig.nrows,
                sym_ladder=plan.sym_ladder, packed=plan.config.row_packing,
                fused=plan.config.fuse_numeric, policy=self.policy)
            state = state.after_trim(self.policy)
            if trimmed is not None:
                self.stats.schedule_trims += 1
                entry.stats.schedule_trims += 1
                self.telemetry.event("schedule_trim", uid=rec.uid,
                                     headroom=state.headroom)
                self.cache.specialize(entry, plan.with_hash_schedule(
                    HashSchedule(*trimmed)).with_policy(state))
                return
        self.cache.update_policy(entry, state)

    def _note_epilogue(self, plan: SpgemmPlan, entries: int) -> None:
        """Count one admitted hot hash product's epilogue, from its static
        schedule: the table slots it sorted (padded rows and empty slots
        included), those of them in rungs that gathered C's entries
        rather than scattering every slot, and the entries of C it wrote.
        Entries over slots is the epilogue's useful share of its work;
        gathered over all slots, how much of it took the gather path."""
        sched = plan.hash_schedule
        if plan.config.fuse_numeric:       # fused: the symbolic ladder
            ladder, buckets = plan.sym_ladder, sched.sym_row_buckets
            packing = plan.config.row_packing
        else:                              # two-pass numeric: unpacked
            ladder, buckets, packing = (plan.num_ladder,
                                        sched.num_row_buckets, False)
        self._epilogue_slots.inc(spgemm_hash.epilogue_slots(
            ladder, buckets, row_packing=packing))
        self._epilogue_gathered.inc(spgemm_hash.epilogue_gathered_slots(
            ladder, buckets, nnz_capacity=plan.nnz_bucket,
            row_packing=packing))
        self._epilogue_entries.inc(entries)

    def _note_products(self, total_nprod: int, fallback_prod: int) -> None:
        """Count one admitted hot hash product's intermediate products,
        and those of them in rows that the n_prod binning puts on the ESC
        fallback rung, from the totals the verify sync fetched."""
        self._hash_products.inc(total_nprod)
        self._fallback_products.inc(fallback_prod)

    def _grow_and_redo(self, rec: _Pending, total_nprod: int,
                       total_nnz: int, *,
                       schedule_overflow: bool = False) -> SpgemmResult:
        """Overflow recovery (rare: a same-signature request outgrew the
        learned plan).  Grow the buckets, redo via the steps path, and
        re-specialize the entry so the NEXT request is hot again.

        ``schedule_overflow`` marks a hash BIN-SCHEDULE overflow (a rung
        or fallback capacity evicted rows) — the only signal the adaptive
        headroom tracks.  A pure nnz/prod capacity overflow with an
        admitting schedule grows the pow-2 buckets but must NOT inflate
        the bin headroom: the bins never jittered."""
        plan = rec.plan
        self.stats.capacity_grows += 1
        rec.entry.stats.capacity_grows += 1
        tel = self.telemetry
        tel.event("capacity_grow", uid=rec.uid,
                  schedule_overflow=schedule_overflow,
                  total_nprod=total_nprod, total_nnz=total_nnz)
        # NB: an overflowed hot run truncates its expansion (or drops rows
        # past a bin bucket), so its totals are only lower bounds; the
        # steps redo reports the true capacities to respecialize with.
        # Floor at the entry's CURRENT buckets so a concurrent grow is kept.
        current = rec.entry.plan
        grown = plan.with_capacities(
            max(plan.prod_bucket, current.prod_bucket or 0,
                next_bucket(max(total_nprod, 1))),
            max(plan.nnz_bucket, current.nnz_bucket or 0,
                next_bucket(max(total_nnz, 1))))
        # Tracked-jitter headroom: the stream just proved it jitters more
        # than the schedule allowed — the redo re-derives with a grown
        # headroom (and a fresh streak/trim epoch).
        state = current.policy or PolicyState(
            headroom=self.policy.headroom_init)
        if state.estimated:
            # An estimated plan under-provisioned: the steps redo below
            # re-derives EXACT buckets (clearing the provenance flag),
            # and the engine-level estimator headroom grows so the next
            # cold estimate is more conservative.
            self.stats.estimate_misses += 1
            self.est_state.note_miss()
            tel.event("estimate_miss", uid=rec.uid,
                      schedule_overflow=schedule_overflow)
            state = state.with_estimated(False)
        if schedule_overflow:
            state = state.note_overflow(self.policy)
        grown = grown.with_policy(state)
        with tel.span("grow_redo", uid=rec.uid):
            result, prod_cap, nnz_cap, hash_sched = _execute_steps(
                rec.A, rec.B, grown,
                StepTimer(False, tracer=tel, uid=rec.uid),
                headroom=state.headroom)
        rec.entry.stats.steps_calls += 1   # the redo ran the steps oracle
        respecialized = grown.with_capacities(prod_cap, nnz_cap)
        if hash_sched is not None:
            # The redo floored at the DISPATCH plan's schedule; union with
            # the entry's CURRENT one so a concurrent grow is kept too.
            if current.hash_schedule is not None:
                hash_sched = hash_sched.union(current.hash_schedule)
            respecialized = respecialized.with_hash_schedule(hash_sched)
        self.cache.specialize(rec.entry, respecialized)
        rec.entry.stats.time_s += time.perf_counter() - rec.t0
        return result


# ---------------------------------------------------------------------------
# The process-wide default engine behind ``repro.core.spgemm``.
# ---------------------------------------------------------------------------

_DEFAULT: Optional[SpgemmEngine] = None
_DEFAULT_LOCK = threading.Lock()


def default_engine() -> SpgemmEngine:
    """Shared engine serving every ``spgemm()`` call in the process."""
    global _DEFAULT
    with _DEFAULT_LOCK:
        if _DEFAULT is None:
            _DEFAULT = SpgemmEngine()
        return _DEFAULT


def reset_default_engine() -> None:
    """Drop the shared engine (tests that need a cold cache)."""
    global _DEFAULT
    _DEFAULT = None
