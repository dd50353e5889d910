"""Execution plans: everything derivable about a SpGEMM call BEFORE data.

OpSparse overlaps result-matrix allocation with kernel execution (§5.4) and
fuses all metadata into one allocation (§5.3) because on a GPU the per-call
setup cost is ``cudaMalloc`` + launch configuration.  In the JAX port the
analogous per-call cost is *trace + compile*: every distinct static shape
is a new executable.  An :class:`SpgemmPlan` therefore captures the full
static configuration of a call — the ladder pair, the accumulator method,
the pow-2 capacity buckets, and the donated fused-metadata buffer layout —
keyed by *signatures* of the operands rather than the operands themselves,
so that every request landing in the same shape bucket shares one plan
(and, via :mod:`repro.engine.cache`, one compiled executable).

Plans are progressive (Liu & Vinter-style ahead-of-time allocation): a
fresh plan has no product/nnz capacity buckets (they depend on data); the
first execution *learns* them and :meth:`SpgemmPlan.with_capacities`
produces the specialized plan that steady-state traffic runs against.
"""
from __future__ import annotations

import dataclasses
from typing import Optional, Tuple

from repro.core.binning_ranges import BinLadder
from repro.core.csr import CSR
from repro.core.spgemm import SpgemmConfig, next_bucket
from repro.core.workspace import LeaseSpec, WorkspacePlan

from .autotune import PolicyState
from .partition import ShardSpec


@dataclasses.dataclass(frozen=True)
class MatrixSig:
    """Shape/nnz-bucket signature of one CSR operand.

    Two matrices with the same signature are interchangeable for planning:
    same static shapes after padding ``col``/``val`` to ``cap_bucket``
    (pow-2 — the recompile analog of §5.4's cudaMalloc bucketing), hence
    the same traced executables.
    """

    nrows: int
    ncols: int
    cap_bucket: int     # pow-2 bucket of the col/val storage capacity
    dtype: str          # value dtype name

    @classmethod
    def of(cls, M: CSR) -> "MatrixSig":
        return cls(nrows=M.nrows, ncols=M.ncols,
                   cap_bucket=next_bucket(M.capacity),
                   dtype=str(M.val.dtype))


# The cache key.  Partition-awareness threads through it via
# ``SpgemmConfig.shards``: a sharded parent plan (shards=N) and the
# unsharded plan of the same operands are distinct cache entries, and each
# per-shard sub-dispatch keys on its SLICE's signature (pow-2 row/storage
# buckets from the plan's ShardSpec) with shards=1 — so shard plans are
# ordinary plans, shared across shards/requests whose buckets coincide.
PlanKey = Tuple[MatrixSig, MatrixSig, SpgemmConfig]


@dataclasses.dataclass(frozen=True)
class HashSchedule:
    """Learned static launch schedule for the hash method (§5.1, §5.5).

    The paper's per-call host decision — which bin kernels to launch, with
    how many rows each — becomes part of the specialized plan: a pow-2
    row-count bucket per rung of each ladder (last entry = the ESC
    fallback rung; 0 = rung statically absent) plus pow-2 capacities for
    the fallback rung's sub-product expansions.  With these static, the
    whole hash pipeline traces into one executable; the engine's finalize
    sync verifies the actual bin sizes fit and grows the schedule
    (monotonically, via :meth:`union`) on overflow.
    """

    sym_row_buckets: Tuple[int, ...]
    num_row_buckets: Tuple[int, ...]
    fall_prod_bucket: int   # fallback expansion capacity (autotune.fallback_need)

    def union(self, other: "HashSchedule") -> "HashSchedule":
        """Elementwise max — schedules only ever grow (progressive
        allocation; keeps every previously-admitted request admitted)."""
        return HashSchedule(
            sym_row_buckets=tuple(
                max(a, b) for a, b in zip(self.sym_row_buckets,
                                          other.sym_row_buckets)),
            num_row_buckets=tuple(
                max(a, b) for a, b in zip(self.num_row_buckets,
                                          other.num_row_buckets)),
            fall_prod_bucket=max(self.fall_prod_bucket,
                                 other.fall_prod_bucket),
        )

    def admits(self, sym_bin_sizes, num_bin_sizes, sym_fall_prod: int,
               num_fall_prod: int) -> bool:
        """Whether an executed run's observed bin metadata fit the static
        schedule it was dispatched with (rows beyond a bucket — or
        fallback products beyond their capacity — were truncated).  Both
        phases share ``fall_prod_bucket`` (one arena bucket, one traced
        expansion shape), so the bound is on their max."""
        return (
            self.admits_fused(sym_bin_sizes, sym_fall_prod)
            and all(int(s) <= b for s, b in zip(num_bin_sizes,
                                                self.num_row_buckets))
            and int(num_fall_prod) <= self.fall_prod_bucket)

    def admits_fused(self, sym_bin_sizes, sym_fall_prod: int) -> bool:
        """Fused-pipeline admission (``SpgemmConfig.fuse_numeric``): the
        one table build is scheduled off the SYMBOLIC ladder alone — there
        is no numeric binning/probe pass to verify.  When a packed config
        learned this schedule the sym buckets are additionally multiples
        of each rung's ``rows_per_block`` (``host_schedule(packs=...)``;
        pow-2 unions preserve the alignment)."""
        return (
            all(int(s) <= b for s, b in zip(sym_bin_sizes,
                                            self.sym_row_buckets))
            and int(sym_fall_prod) <= self.fall_prod_bucket)


@dataclasses.dataclass(frozen=True)
class SpgemmPlan:
    """Immutable pre-data execution plan for one (A_sig, B_sig, config).

    Fields derivable before any data arrives:
      a_sig / b_sig    operand signatures (shapes + storage buckets).
      config           the full SpgemmConfig (method, multipliers, ...).
      sym_ladder       symbolic bin ladder (paper Table 1 ranges).
      num_ladder       numeric bin ladder (paper Table 2 ranges).
      sym_workspace    donated fused-metadata buffer layout for the
      num_workspace    symbolic/numeric binning steps (§5.3 analog).

    Learned on first execution (progressive allocation):
      prod_bucket      pow-2 capacity for the intermediate-product
                       expansion (``None`` until learned).
      nnz_bucket       pow-2 capacity for C.col/C.val (``None`` until
                       learned).
      hash_schedule    static per-rung launch schedule (hash method only;
                       ``None`` until learned — ESC plans never set it).
      shard_spec       learned row-block partition (sharded plans only,
                       ``config.shards > 1``; ``None`` until the cold call
                       balances the blocks by cumulative flop estimate).
      policy           adaptive-policy state (``engine/autotune``): the
                       tracked headroom/jitter for hash plans, the shard
                       decision for AUTO_SHARDS plans.  Updated without
                       dropping executables (it never enters a trace);
                       persisted by ``PlanCache.dump/load``.
    """

    a_sig: MatrixSig
    b_sig: MatrixSig
    config: SpgemmConfig
    sym_ladder: BinLadder
    num_ladder: BinLadder
    sym_workspace: WorkspacePlan
    num_workspace: WorkspacePlan
    prod_bucket: Optional[int] = None
    nnz_bucket: Optional[int] = None
    hash_schedule: Optional[HashSchedule] = None
    shard_spec: Optional[ShardSpec] = None
    policy: Optional[PolicyState] = None

    @property
    def signature(self) -> PlanKey:
        """The cache key: ladders/workspaces are derived from it."""
        return (self.a_sig, self.b_sig, self.config)

    @property
    def is_specialized(self) -> bool:
        """True once everything the jitted steady state needs is learned —
        the capacity buckets, plus the launch schedule for hash plans.
        A sharded parent plan only needs its partition: the capacities
        live on the per-shard sub-plans."""
        if self.config.shards > 1:
            return self.shard_spec is not None
        caps = self.prod_bucket is not None and self.nnz_bucket is not None
        if self.config.method == "hash":
            return caps and self.hash_schedule is not None
        return caps

    def with_capacities(self, prod_bucket: int,
                        nnz_bucket: int) -> "SpgemmPlan":
        """Specialized plan with learned (or grown) capacity buckets."""
        return dataclasses.replace(self, prod_bucket=int(prod_bucket),
                                   nnz_bucket=int(nnz_bucket))

    def with_hash_schedule(self, schedule: HashSchedule) -> "SpgemmPlan":
        """Plan with a learned (or grown) static hash launch schedule."""
        return dataclasses.replace(self, hash_schedule=schedule)

    def with_shard_spec(self, spec: ShardSpec) -> "SpgemmPlan":
        """Plan with a learned (or per-shard-grown) row-block partition."""
        return dataclasses.replace(self, shard_spec=spec)

    def with_policy(self, state: PolicyState) -> "SpgemmPlan":
        """Plan carrying updated adaptive-policy state (same signature,
        same traced shapes — cached executables stay valid)."""
        return dataclasses.replace(self, policy=state)

    def admits(self, A: CSR, B: CSR) -> bool:
        """Whether (A, B) land in this plan's shape buckets."""
        return MatrixSig.of(A) == self.a_sig and MatrixSig.of(B) == self.b_sig

    def workspace_spec(self) -> Optional[LeaseSpec]:
        """Size class of the arena lease this plan's steady state wants,
        or ``None`` when the plan allocates nothing leasable: not yet
        specialized, a sharded parent (leases live on the per-shard
        sub-plans), or a hash plan whose fallback rung is statically
        absent (``fall_prod_bucket == 0`` — nothing to expand).

        ESC leases the intermediate-product expansion (row ids + col ids
        as one int32 buffer, values separately); hash plans lease the
        fallback rung's sub-expansion with the same 2:1 int32:value cell
        split.  Both phases of a two-pass hash plan share ONE lease —
        the shared ``fall_prod_bucket`` is what makes that sound."""
        if not self.is_specialized or self.config.shards > 1:
            return None
        dtype = self.a_sig.dtype
        if self.config.method == "hash":
            fall = self.hash_schedule.fall_prod_bucket
            if not fall:
                return None
            return LeaseSpec(i32_cells=2 * fall, val_cells=fall,
                             val_dtype=dtype)
        return LeaseSpec(i32_cells=2 * self.prod_bucket,
                         val_cells=self.prod_bucket, val_dtype=dtype)


def plan(a_sig: MatrixSig, b_sig: MatrixSig,
         config: SpgemmConfig = SpgemmConfig()) -> SpgemmPlan:
    """Construct the pre-data plan for a signature pair.

    Everything here is derivable without looking at values: the ladders
    come from the config's multipliers, the workspace layouts from
    (M, NUM_BIN) alone.  Capacity buckets stay unlearned (``None``).
    """
    assert a_sig.ncols == b_sig.nrows, (a_sig, b_sig)
    if config.plan_mode not in ("exact", "estimate"):
        raise ValueError(
            f"unknown plan_mode {config.plan_mode!r} "
            "(expected 'exact' or 'estimate')")
    sym_ladder, num_ladder = config.ladders()
    return SpgemmPlan(
        a_sig=a_sig, b_sig=b_sig, config=config,
        sym_ladder=sym_ladder, num_ladder=num_ladder,
        sym_workspace=WorkspacePlan(a_sig.nrows, sym_ladder.num_bins),
        num_workspace=WorkspacePlan(a_sig.nrows, num_ladder.num_bins),
    )


def plan_key(A: CSR, B: CSR, config: SpgemmConfig) -> PlanKey:
    """Cache key for a concrete request — signatures, not arrays."""
    return (MatrixSig.of(A), MatrixSig.of(B), config)
