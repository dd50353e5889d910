"""Plan-cache effectiveness on a streaming request pipeline.

Acceptance targets (ISSUE 1, extended by ISSUE 2 to the hash method): on
a stream of >=20 same-bucket SpGEMM requests, steady-state per-call
wall-clock must be >=5x lower than the first (cold-trace) call, with a
reported plan-cache hit rate >=90% and ZERO retraces after warmup.

The stream models serving traffic: distinct matrices whose storage lands
in one pow-2 capacity bucket, so every request after the first reuses the
cached specialized plan and its jitted executable.  ``--method hash``
exercises the bin-count-bucketed hash steady state: the warmup prefix may
grow the learned launch schedule (rung discovery), after which the gate
requires the jitted path to serve every request without recompiling.  A
second phase pushes the same stream through ``submit``/``drain`` to
exercise the batched, completion-order-finalized path.

``--shards N`` (ISSUE 3) runs the whole stream through the partition-
aware engine: every request fans out into N flop-balanced row-block
shards whose plans must come from the cache (hit rate >=90% across shard
plans, zero retraces after warmup), and the merged result must be
bitwise-identical in nnz/structure to the unsharded path.

``--fused`` (ISSUE 4, hash only) routes steady-state traffic through the
fused symbolic->numeric executable with multi-row VMEM packing: one table
build per row instead of two.  Extra gates: bitwise parity with the
two-pass path on nnz/structure/values, and a measured per-row hash-table
access reduction >= 1.5x vs symbolic+numeric.

``--adaptive`` (ISSUE 5, hash only) runs the stream with NO static
execution knobs: the shard count comes from the AUTO_SHARDS telemetry
policy, the hash-schedule headroom is tracked-jitter (the trim's one
deliberate retrace must land inside warmup, then zero retraces), the
fused path is the default, and steady-state latency must be no worse
than 2x the fixed-2x-headroom baseline previously recorded in
``BENCH_engine.json`` by the plain ``--method hash`` run.

``--arena`` (ISSUE 7) gates the shared workspace arena under a memory
governor: K distinct shape-bucket plans (``--plans``, >= 4) run
concurrently through interleaved ``submit``/``drain`` windows with the
governor capped at 0.6x the per-plan-buffer baseline (the bytes K
private workspaces would pin).  Gates: peak arena bytes <= the cap and
strictly below the baseline, zero retraces after warmup, and bitwise
result parity against a fresh uncapped engine.  Records
``peak_workspace_bytes`` / ``arena_hit_rate`` into the trajectory.

``--estimate`` (ISSUE 8) gates estimation-based cold planning: the same
stream runs twice in ONE process — first under ``plan_mode="estimate"``
(sampled nnz/flop estimator specializes the cold plan; the full symbolic
sizing pass never runs), then under exact planning on a fresh engine.
The ordering biases AGAINST the gate (the exact baseline inherits the
estimate stream's shared jit warmth).  Gates: the estimator must beat
the exact symbolic sizing pass it replaces by >=3x, the full first call
(which fronts the hot-executable compile) must still be no slower than
exact's cold call, zero estimate-stream retraces after warmup
(estimates confirmed, not corrected), steady state no worse than exact,
and bitwise result parity across every request.  Records an
``_estimate``-suffixed trajectory key with the cold-phase breakdown.

``--trace PATH`` enables the engine's structured telemetry layer
(``repro.engine.telemetry``) for the whole run, checks that its spans
cover the full nested pipeline, and exports the event log as JSON Lines
at PATH.  (A profiler trace, ``jax.profiler.trace``, holds the same spans
as ``opsparse.*`` annotations on the device trace's clock.)  Traced runs
record under a ``_traced``-suffixed trajectory key and gate their
steady-state latency at <5% over the tracing-disabled baseline for the
same configuration (the observability tax must stay in the noise).

Every run also records a perf-trajectory artifact at the repo root
(``BENCH_engine.json``): per-configuration steady-state latency (mean
and min of the tail), the cold call's phase breakdown (``phases_ms`` —
span aggregates when traced, the cold request's own per-step timings
otherwise), retrace count, git revision, and — for the hash method —
table-access totals, so future PRs have a baseline to compare against.

Run:  PYTHONPATH=src python benchmarks/bench_engine.py [--smoke]
          [--method hash] [--fused] [--adaptive] [--shards 2]
          [--trace /tmp/events.jsonl]
"""
from __future__ import annotations

import argparse
import json
import sys
import time
from pathlib import Path

import jax
import numpy as np

from repro.core import (SpgemmConfig, bin_rows_for_ladder, next_bucket,
                        nprod_into_rpt, random_csr, spgemm_reference)
from repro.core.analysis import exclusive_sum_in_place
from repro.core.faults import FaultPlan, FaultSpec
from repro.engine import (AdaptivePolicy, Arena, MatrixSig, MemoryGovernor,
                          SpgemmEngine, Telemetry, git_rev, total_traces,
                          utc_now_iso)
from repro.kernels import spgemm_hash, use_compile_cache
from repro.serve import SpgemmService

BENCH_JSON = Path(__file__).resolve().parent.parent / "BENCH_engine.json"


def build_stream(n_requests: int, m: int, k: int, n: int, avg: float):
    """Distinct matrices canonicalized to ONE shape-bucket signature."""
    pairs = []
    for s in range(n_requests):
        A = random_csr(jax.random.PRNGKey(2 * s), m, k, avg_nnz_per_row=avg)
        B = random_csr(jax.random.PRNGKey(2 * s + 1), k, n,
                       avg_nnz_per_row=avg)
        pairs.append((A, B))
    # Same-bucket premise: pad every operand to the stream-wide pow-2
    # bucket (the serving tier's batching discipline).
    cap_a = next_bucket(max(A.capacity for A, _ in pairs))
    cap_b = next_bucket(max(B.capacity for _, B in pairs))
    return [(A.with_capacity(cap_a), B.with_capacity(cap_b))
            for A, B in pairs]


def measure_hash_accesses(A, B, config: SpgemmConfig, *,
                          with_fused: bool = True):
    """Fig.-9 access counters on one pair: two-pass vs fused table builds.

    Returns ``(sym, num, fused)`` total table-transaction counts; the
    fused build replaces sym+num, so ``(sym + num) / fused`` is the
    measured per-call access reduction.  ``with_fused=False`` skips the
    fused counter (None) so non-fused gates never touch the fused kernels.
    """
    m = A.nrows
    sym_lad, num_lad = config.ladders()
    nprod = nprod_into_rpt(A, B)[:m]
    sym_bn = bin_rows_for_ladder(nprod, sym_lad)
    nnz_buf, acc_s = spgemm_hash.symbolic_binned(
        A, B, sym_bn, sym_lad, single_access=config.hash_single_access,
        interpret=config.interpret, collect_accesses=True)
    num_bn = bin_rows_for_ladder(nnz_buf[:m], num_lad)
    cap = next_bucket(max(int(nnz_buf[:m].sum()), 1))
    rpt = exclusive_sum_in_place(nnz_buf)
    _, acc_n = spgemm_hash.numeric_binned(
        A, B, rpt, num_bn, num_lad, nnz_capacity=cap,
        single_access=config.hash_single_access,
        interpret=config.interpret, collect_accesses=True)
    if not with_fused:
        return int(acc_s), int(acc_n), None
    _, acc_f = spgemm_hash.fused_binned(
        A, B, sym_bn, sym_lad, nnz_capacity=cap,
        single_access=config.hash_single_access,
        interpret=config.interpret, row_packing=config.row_packing,
        collect_accesses=True)
    return int(acc_s), int(acc_n), int(acc_f)


def record_trajectory(key: str, entry: dict) -> None:
    """Merge one configuration's results into ``BENCH_engine.json``.

    An unparseable file (e.g. a run killed mid-write) is set aside as
    ``BENCH_engine.json.corrupt`` instead of silently clobbered — the
    trajectory is the baseline future PRs compare against.
    """
    payload = {}
    if BENCH_JSON.exists():
        try:
            payload = json.loads(BENCH_JSON.read_text())
        except (ValueError, OSError):
            corrupt = BENCH_JSON.with_suffix(".json.corrupt")
            BENCH_JSON.rename(corrupt)
            print(f"WARNING: unreadable {BENCH_JSON.name} preserved as "
                  f"{corrupt.name}; starting a fresh trajectory",
                  file=sys.stderr)
    payload[key] = entry
    BENCH_JSON.write_text(json.dumps(payload, indent=1, sort_keys=True)
                          + "\n")


def result_parity(base, res, *, bitwise_val: bool) -> bool:
    """nnz/rpt/col/val parity of two SpgemmResults (bitwise structure;
    values bitwise or allclose — sharded merges may reorder FP sums)."""
    nnz = base.total_nnz
    val_eq = np.array_equal if bitwise_val else np.allclose
    return (
        res.total_nnz == nnz
        and np.array_equal(np.asarray(res.C.rpt), np.asarray(base.C.rpt))
        and np.array_equal(np.asarray(res.C.col)[:nnz],
                           np.asarray(base.C.col)[:nnz])
        and val_eq(np.asarray(res.C.val)[:nnz],
                   np.asarray(base.C.val)[:nnz]))


def _lease_bytes(spec) -> int:
    """Bucketed bytes one plan's workspace lease pins (the per-plan-
    buffer baseline sums these: without the arena each plan would hold
    its own pair for its whole cache lifetime)."""
    return sum(Arena._bucket_bytes(k) for k in Arena._buckets(spec))


def run_arena_gate(args) -> int:
    """ISSUE 7 acceptance: K distinct shape-bucket plans served
    concurrently out of one governor-capped arena.

    The per-plan-buffer baseline is what the pre-arena engine pinned:
    every cached plan holding a private workspace pair sized to its own
    bucket.  The arena gate runs the same K plans through interleaved
    submit/drain windows with the governor capped at 0.6x that baseline
    and requires the measured peak to stay under the cap — lease reuse
    across requests (and across same-bucket plans) is what makes the
    window, not the plan count, the working-set bound.
    """
    cfg = SpgemmConfig(method=args.method)
    K, rounds, window = args.plans, 3, 3
    # Distinct nrows => distinct MatrixSigs => K separate cached plans.
    pairs = []
    for i in range(K):
        m = args.m + 8 * i
        A = random_csr(jax.random.PRNGKey(2 * i), m, args.k,
                       avg_nnz_per_row=args.avg)
        B = random_csr(jax.random.PRNGKey(2 * i + 1), args.k, args.n,
                       avg_nnz_per_row=args.avg)
        pairs.append((A, B))

    engine = SpgemmEngine(cfg, arena=Arena())
    for A, B in pairs:                    # cold (steps) + hot (first lease)
        engine.execute(A, B)
        jax.block_until_ready(engine.execute(A, B).C.val)

    entries = [engine.cache.get((MatrixSig.of(A), MatrixSig.of(B), cfg))
               for A, B in pairs]
    specs = [e.plan.workspace_spec() for e in entries]
    assert all(s is not None for s in specs), "unleasable plan in the gate"
    baseline = sum(_lease_bytes(s) for s in specs)
    cap = int(0.6 * baseline)
    engine.governor = MemoryGovernor(cap_bytes=cap)
    engine.arena.reclaim()               # drop warmup leases: cap must bind
    engine.arena.reset_peak()
    hits0 = engine.arena.lease_hits
    misses0 = engine.arena.lease_misses
    warm_traces = total_traces()

    last = None
    t0 = time.perf_counter()
    for _ in range(rounds):
        uids = [engine.submit(A, B) for A, B in pairs]
        results = engine.drain(window=window)
        jax.block_until_ready([results[u].C.val for u in uids])
        last = [results[u] for u in uids]
    traffic_s = time.perf_counter() - t0
    n_reqs = rounds * K

    peak = engine.arena.peak_bytes
    retraces = total_traces() - warm_traces
    hits = engine.arena.lease_hits - hits0
    misses = engine.arena.lease_misses - misses0
    hit_rate = hits / max(hits + misses, 1)

    # Bitwise parity: an uncapped fresh engine (own arena) must produce
    # byte-identical results — governor pressure and lease recycling are
    # not allowed to change a single bit of the output.
    fresh = SpgemmEngine(cfg, arena=Arena())
    parity = True
    for (A, B), res in zip(pairs, last):
        fresh.execute(A, B)
        base = fresh.execute(A, B)       # hot path, like the gated stream
        parity = parity and result_parity(base, res, bitwise_val=True)

    cap_ok = peak <= cap
    base_ok = peak < baseline
    print(f"plans:         {K:9d} distinct shape buckets "
          f"({rounds} rounds, window {window})")
    print(f"baseline:      {baseline:9d} B  (per-plan private workspaces)")
    print(f"governor cap:  {cap:9d} B  (0.6x baseline)")
    print(f"arena peak:    {peak:9d} B  "
          f"({peak / baseline:.2f}x baseline, "
          f"{'OK' if cap_ok and base_ok else 'OVER'})")
    print(f"lease reuse:   {hits:9d} hits / {misses} misses "
          f"({hit_rate * 100:.1f}% hit rate, "
          f"{engine.stats.arena_pressure} pressure events)")
    print(f"hot traces:    {total_traces():9d}  "
          f"({retraces} after warmup, target 0)")
    print(f"parity:        {'OK' if parity else 'MISMATCH':>9s}  "
          f"(capped arena vs fresh engine: nnz/rpt/col/val bitwise)")
    print(f"traffic:       {traffic_s * 1e3:9.1f} ms for {n_reqs} requests "
          f"({traffic_s / n_reqs * 1e3:.2f} ms/req)")
    print()
    print(engine.report())

    key = f"{args.method}_arena@{args.m}x{args.k}x{args.n}k{K}"
    record_trajectory(key, {
        "plans": K,
        "rounds": rounds,
        "window": window,
        "shape": [args.m, args.k, args.n],
        "baseline_workspace_bytes": baseline,
        "governor_cap_bytes": cap,
        "peak_workspace_bytes": peak,
        "peak_over_baseline": round(peak / baseline, 4),
        "arena_hit_rate": round(hit_rate, 4),
        "pressure_events": engine.stats.arena_pressure,
        "retraces_after_warmup": retraces,
        "traffic_ms_per_request": round(traffic_s / n_reqs * 1e3, 4),
        "git_rev": git_rev(BENCH_JSON.parent),
        "recorded_at": utc_now_iso(),
    })
    print(f"trajectory:    {BENCH_JSON.name} <- {key}")

    ok = cap_ok and base_ok and retraces == 0 and parity
    print()
    print("PASS" if ok else "FAIL",
          f"(peak {peak} B vs cap {cap} B / baseline {baseline} B, "
          f"{retraces} retraces, hit rate {hit_rate * 100:.1f}%"
          + ("" if cap_ok else ", peak over governor cap")
          + ("" if base_ok else ", peak not below per-plan baseline")
          + ("" if parity else ", parity MISMATCH")
          + ")")
    return 0 if ok else 1


def run_estimate_gate(args) -> int:
    """ISSUE 8 acceptance: estimation-based cold-path planning.

    The SAME request stream runs twice in one process, ordered so the
    measurement bias runs AGAINST the gate: the ``plan_mode="estimate"``
    stream goes FIRST (truly cold — its first call pays every shared
    one-time cost), then the exact-planning baseline runs on a fresh
    engine SECOND, inheriting whatever kernel-cache warmth the estimate
    stream built.  The exact cold call still compiles the standalone
    six-step jits the estimate path never touches, which is precisely
    the cost the estimator exists to skip.
    """
    stream = build_stream(args.requests, args.m, args.k, args.n, args.avg)

    def run_stream(config):
        engine = SpgemmEngine(config)
        times, results = [], []
        warm = total_traces()
        for i, (A, B) in enumerate(stream):
            t0 = time.perf_counter()
            res = engine.execute(A, B)
            jax.block_until_ready(res.C.val)
            times.append(time.perf_counter() - t0)
            results.append(res)
            if i == args.warmup - 1:
                # Absorb any pending schedule rebuild before the gate arms
                # (same discipline as the main stream gate).
                jax.block_until_ready(engine.execute(A, B).C.val)
                warm = total_traces()
            if args.check:
                ref = np.asarray(spgemm_reference(A, B))
                np.testing.assert_allclose(np.asarray(res.C.to_dense()),
                                           ref, rtol=1e-4, atol=1e-4)
        return engine, times, results, total_traces() - warm

    est_engine, est_t, est_res, retraces = run_stream(
        SpgemmConfig(method=args.method, plan_mode="estimate"))
    exact_engine, ex_t, ex_res, _ = run_stream(
        SpgemmConfig(method=args.method))

    est_cold, ex_cold = est_t[0], ex_t[0]
    est_tail = est_t[len(est_t) // 2:]
    ex_tail = ex_t[len(ex_t) // 2:]
    est_steady, ex_steady = min(est_tail), min(ex_tail)
    parity = all(result_parity(b, r, bitwise_val=True)
                 for b, r in zip(ex_res, est_res))
    phases_ms = {n: round(t * 1e3, 3)
                 for n, t in sorted(est_res[0].timings.items())}

    # The tentpole gate compares the sizing pass against its replacement:
    # the exact cold call IS the full symbolic sizing pass (its per-step
    # kernels exist only to size the plan; the hot executable both modes
    # compile afterwards is common cost), and the "estimate" phase is
    # what stands in for it.  The full first-call walls are gated too —
    # the estimate path fronts the hot-executable compile into call one,
    # and that must still not make the first call slower than exact's.
    plan_ms = phases_ms.get("estimate", 0.0)
    plan_ratio = ex_cold * 1e3 / max(plan_ms, 1e-6)
    plan_ok = plan_ratio >= 3.0 and plan_ms > 0.0
    cold_ok = est_cold <= ex_cold
    retrace_ok = retraces == 0
    # min-of-tail with tolerance: the steady executables are IDENTICAL in
    # shape (only planning differed), so any gap is ambient-load jitter —
    # which on a shared CI host routinely exceeds a strict bound.
    steady_ok = est_steady <= 1.5 * ex_steady
    # Every estimated plan must resolve: confirmed by an admitted
    # finalize or (inside warmup) corrected by the overflow retrace.
    s = est_engine.stats
    resolved_ok = s.estimates > 0 and (
        s.estimate_hits + s.estimate_misses >= s.estimates)

    print(f"method:        {args.method:>9s}  (plan_mode=estimate vs exact)")
    print(f"sizing pass:   {plan_ms:9.1f} ms estimate vs "
          f"{ex_cold * 1e3:.1f} ms exact symbolic sizing = "
          f"{plan_ratio:.1f}x ({'OK' if plan_ok else 'BELOW 3x'})")
    print(f"cold call:     {est_cold * 1e3:9.1f} ms estimate "
          f"(plan + hot compile) vs {ex_cold * 1e3:.1f} ms exact "
          f"(sizing only; hot compile lands on call 2) "
          f"({'OK' if cold_ok else 'WORSE'})")
    print(f"cold phases:   " + ", ".join(
        f"{n} {t:.1f} ms" for n, t in phases_ms.items()))
    print(f"steady state:  {est_steady * 1e3:9.2f} ms estimate vs "
          f"{ex_steady * 1e3:.2f} ms exact min-of-tail "
          f"({'OK' if steady_ok else 'WORSE'})")
    print(f"estimates:     {s.estimates:9d} plans "
          f"({s.estimate_hits} confirmed / {s.estimate_misses} retraced, "
          f"headroom {est_engine.est_state.headroom:.2f})")
    print(f"retraces:      {retraces:9d} after {args.warmup}-request "
          f"warmup (target 0)")
    print(f"parity:        {'OK' if parity else 'MISMATCH':>9s}  "
          f"(estimate vs exact stream: nnz/rpt/col/val bitwise, "
          f"{len(stream)} requests)")
    print()
    print(est_engine.report())

    key = (f"{args.method}_estimate"
           f"@{args.m}x{args.k}x{args.n}r{args.requests}")
    record_trajectory(key, {
        "requests": args.requests,
        "shape": [args.m, args.k, args.n],
        "cold_ms": round(est_cold * 1e3, 3),
        "exact_cold_ms": round(ex_cold * 1e3, 3),
        "plan_ms": round(plan_ms, 3),
        "plan_speedup": round(plan_ratio, 2),
        "steady_min_ms": round(est_steady * 1e3, 4),
        "exact_steady_min_ms": round(ex_steady * 1e3, 4),
        "phases_ms": phases_ms,
        "estimates": s.estimates,
        "estimate_hits": s.estimate_hits,
        "estimate_misses": s.estimate_misses,
        "retraces_after_warmup": retraces,
        "git_rev": git_rev(BENCH_JSON.parent),
        "recorded_at": utc_now_iso(),
    })
    print(f"trajectory:    {BENCH_JSON.name} <- {key}")

    ok = (plan_ok and cold_ok and retrace_ok and steady_ok and parity
          and resolved_ok)
    print()
    print("PASS" if ok else "FAIL",
          f"(sizing {plan_ratio:.1f}x vs exact, {retraces} retraces, "
          f"{s.estimate_hits}/{s.estimates} estimates confirmed"
          + ("" if plan_ok else ", sizing advantage < 3x")
          + ("" if cold_ok else ", first call slower than exact cold")
          + ("" if steady_ok else ", steady state worse than exact")
          + ("" if parity else ", parity MISMATCH")
          + ("" if resolved_ok else ", unresolved estimated plans")
          + ")")
    return 0 if ok else 1


def run_serve_gate(args) -> int:
    """ISSUE 9 acceptance: the fault-tolerant serving front-end (chaos
    gate).

    A mixed-tenant request stream runs twice: fault-free, then under a
    seeded :class:`FaultPlan` arming lease denials and verify overflows
    probabilistically across the whole stream.  The gate requires ZERO
    failed well-formed requests under chaos, every chaos result bitwise
    identical to its fault-free twin, and the chaos p99 latency bounded
    relative to fault-free (recovery redos cost about a cold call, not
    more).  Two targeted scenarios then check the structured-failure
    contract — a poisoned (non-transient) request errors WITHOUT a
    retry, a stalled request under a deadline returns a timeout — and
    the per-tenant counters are asserted on a live ``/metrics`` scrape.
    """
    import urllib.request

    cfg = SpgemmConfig(method=args.method)
    stream = build_stream(args.requests, args.m, args.k, args.n, args.avg)
    tenants = ["alpha", "beta"]
    assign = [tenants[i % 2] for i in range(len(stream))]

    def run_service(faults=None):
        svc = SpgemmService(cfg, arena=Arena(), faults=faults,
                            backoff_base_s=1e-3, backoff_cap_s=0.05)
        outs, lats = [], []
        for (A, B), ten in zip(stream, assign):
            t0 = time.perf_counter()
            r = svc.call(A, B, tenant=ten, deadline_s=60.0)
            if r.ok:
                jax.block_until_ready(r.value.C.val)
            lats.append(time.perf_counter() - t0)
            outs.append(r)
        return svc, outs, lats

    def p99(lats):
        return sorted(lats)[min(len(lats) - 1, int(0.99 * len(lats)))]

    # ---- phase 1: chaos stream vs fault-free twin -------------------------
    _, clean, clean_lats = run_service()
    chaos_plan = FaultPlan([
        # Deterministic double denial: visits 5 and 6 are one request's
        # initial + post-reclaim acquisition attempts (or two requests'
        # worth under earlier probabilistic denials) — either way at
        # least one ArenaPressureError reaches the service retry loop.
        FaultSpec(site="lease_denial", at=(5, 6)),
        FaultSpec(site="lease_denial", probability=0.25),
        FaultSpec(site="verify_overflow", probability=0.15),
    ], seed=args.seed)
    svc, chaos, chaos_lats = run_service(chaos_plan)

    failed = [i for i, r in enumerate(chaos) if not r.ok]
    parity = all(
        r.ok and result_parity(c.value, r.value, bitwise_val=True)
        for c, r in zip(clean, chaos))
    retries = sum(r.retries for r in chaos)
    survived = sum(r.faults_survived for r in chaos)
    injected = chaos_plan.total_injected
    p99_clean, p99_chaos = p99(clean_lats), p99(chaos_lats)
    # Injected overflows redo through the steps oracle (~a cold call) and
    # denials add backoff sleeps; the clean p99 is ALSO a cold call, so a
    # generous multiple plus a wall-clock floor absorbs CI timer noise.
    p99_bound = max(5.0 * p99_clean, 0.5)
    p99_ok = p99_chaos <= p99_bound

    # ---- phase 2: structured-failure contract -----------------------------
    A0, B0 = stream[0]
    svc_poison = SpgemmService(cfg, arena=Arena(), faults=FaultPlan(
        [FaultSpec(site="executor_raise", at=(0,), message="poisoned")]))
    r_poison = svc_poison.call(A0, B0, tenant="alpha")
    poison_ok = (r_poison.status == "error" and r_poison.retries == 0
                 and "poisoned" in r_poison.error)

    svc_slow = SpgemmService(cfg, arena=Arena(), faults=FaultPlan(
        [FaultSpec(site="slow_dispatch", at=(1,), delay_s=0.3)]))
    svc_slow.call(A0, B0, tenant="alpha")        # warm: latency history
    r_slow = svc_slow.call(A0, B0, tenant="alpha", deadline_s=0.05)
    deadline_ok = r_slow.status == "timeout" and r_slow.value is None

    # ---- phase 3: live /metrics scrape ------------------------------------
    server = svc.serve_http()
    try:
        body = urllib.request.urlopen(server.url, timeout=10).read().decode()
    finally:
        svc.close()
    scrape_ok = all(
        f'opsparse_service_requests_total{{tenant="{t}"}}' in body
        for t in tenants) and all(
        name in body for name in (
            "opsparse_service_retries_total",
            "opsparse_service_timeouts_total",
            "opsparse_service_sheds_total",
            "opsparse_service_faults_survived_total",
            "opsparse_engine_faults_injected_total"))

    n = len(stream)
    print(f"stream:        {n:9d} requests over {len(tenants)} tenants "
          f"(seed {args.seed})")
    print(f"chaos:         {injected:9d} faults injected "
          f"({retries} service retries, {survived} survived on ok paths)")
    print(f"failures:      {len(failed):9d} failed well-formed requests "
          f"(target 0){'' if not failed else ' -> ' + str(failed)}")
    print(f"parity:        {'OK' if parity else 'MISMATCH':>9s}  "
          f"(chaos vs fault-free twin: nnz/rpt/col/val bitwise)")
    print(f"p99 latency:   {p99_chaos * 1e3:9.1f} ms under chaos vs "
          f"{p99_clean * 1e3:.1f} ms clean "
          f"(bound {p99_bound * 1e3:.0f} ms, "
          f"{'OK' if p99_ok else 'OVER'})")
    print(f"poisoned req:  {r_poison.status:>9s}  "
          f"({r_poison.retries} retries, target error/0)")
    print(f"deadline req:  {r_slow.status:>9s}  (injected stall vs 50 ms "
          f"budget, target timeout)")
    print(f"scrape:        {'OK' if scrape_ok else 'MISSING':>9s}  "
          f"(per-tenant series on live /metrics)")

    key = f"{args.method}_serve@{args.m}x{args.k}x{args.n}"
    record_trajectory(key, {
        "requests": n,
        "tenants": tenants,
        "shape": [args.m, args.k, args.n],
        "seed": args.seed,
        "faults_injected": injected,
        "fault_sites": chaos_plan.snapshot()["injected"],
        "service_retries": retries,
        "faults_survived": survived,
        "failed_requests": len(failed),
        "p99_clean_ms": round(p99_clean * 1e3, 3),
        "p99_chaos_ms": round(p99_chaos * 1e3, 3),
        "git_rev": git_rev(BENCH_JSON.parent),
        "recorded_at": utc_now_iso(),
    })
    print(f"trajectory:    {BENCH_JSON.name} <- {key}")

    ok = (not failed and parity and p99_ok and poison_ok and deadline_ok
          and scrape_ok and injected > 0)
    print()
    print("PASS" if ok else "FAIL",
          f"({n} requests, {injected} faults, {len(failed)} failures"
          + ("" if parity else ", parity MISMATCH")
          + ("" if p99_ok else ", p99 over bound")
          + ("" if poison_ok else ", poisoned-request contract broken")
          + ("" if deadline_ok else ", deadline contract broken")
          + ("" if scrape_ok else ", /metrics series missing")
          + ("" if injected > 0 else ", no faults injected — gate inert")
          + ")")
    return 0 if ok else 1


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--smoke", action="store_true",
                    help="small sizes for CI (~30 s)")
    ap.add_argument("--method", choices=("esc", "hash"), default="esc",
                    help="accumulator method for the whole stream")
    ap.add_argument("--fused", action="store_true",
                    help="hash only: fused one-build steady state with "
                         "row packing (gates access reduction + parity)")
    ap.add_argument("--adaptive", action="store_true",
                    help="hash only: telemetry-driven policy — AUTO shard "
                         "count, tracked-jitter headroom (trim inside "
                         "warmup), fused-by-default; gates zero steady-"
                         "state retraces and steady latency no worse than "
                         "the fixed-2x baseline in BENCH_engine.json")
    ap.add_argument("--requests", type=int, default=24)
    ap.add_argument("--warmup", type=int, default=None,
                    help="requests before the zero-retrace gate arms "
                         "(cold call + schedule/rung discovery; default 4, "
                         "or 12 under --adaptive so the headroom trim "
                         "lands inside warmup)")
    ap.add_argument("--m", type=int, default=256)
    ap.add_argument("--k", type=int, default=256)
    ap.add_argument("--n", type=int, default=256)
    ap.add_argument("--avg", type=float, default=4.0)
    ap.add_argument("--shards", type=int, default=1,
                    help="row-block shards per request (partition-aware "
                         "engine; 1 = unsharded)")
    ap.add_argument("--arena", action="store_true",
                    help="workspace-arena gate: K distinct shape-bucket "
                         "plans (--plans) under a governor cap of 0.6x "
                         "the per-plan-buffer baseline; gates peak bytes, "
                         "zero retraces, bitwise parity")
    ap.add_argument("--plans", type=int, default=8,
                    help="arena gate: number of distinct shape buckets "
                         "(>= 4)")
    ap.add_argument("--estimate", action="store_true",
                    help="estimation-based cold-planning gate: run the "
                         "stream under plan_mode='estimate' first (cold), "
                         "then an exact-planning baseline on a fresh "
                         "engine in the same process; gates cold-call "
                         ">=3x, zero post-warmup retraces, steady state "
                         "no worse, bitwise parity")
    ap.add_argument("--serve", action="store_true",
                    help="chaos gate for the fault-tolerant serving "
                         "front-end: a mixed-tenant stream under a seeded "
                         "FaultPlan; gates zero failed requests, bitwise "
                         "parity vs a fault-free run, bounded p99 "
                         "inflation, structured error/timeout contracts, "
                         "and per-tenant /metrics series")
    ap.add_argument("--seed", type=int, default=0,
                    help="serve gate: FaultPlan seed (same seed => same "
                         "injections)")
    ap.add_argument("--check", action="store_true",
                    help="verify every result against the dense oracle")
    ap.add_argument("--trace", metavar="PATH", default=None,
                    help="enable telemetry, check its spans cover the "
                         "pipeline and export the event log as JSON Lines "
                         "to PATH; gates traced steady latency at <5%% "
                         "over tracing off in the same process")
    args = ap.parse_args(argv)
    use_compile_cache()
    if args.requests < 1:
        ap.error("--requests must be >= 1")
    if args.smoke:
        args.requests, args.m, args.k, args.n = 20, 64, 64, 64
    if args.warmup is None:
        args.warmup = 12 if args.adaptive else 4
    if not 0 < args.warmup < args.requests:
        ap.error("--warmup must be in [1, effective --requests)")
    if args.fused and args.method != "hash":
        ap.error("--fused requires --method hash")
    if args.adaptive and args.method != "hash":
        ap.error("--adaptive requires --method hash")
    if args.adaptive and args.shards > 1:
        ap.error("--adaptive picks the shard count itself; drop --shards")
    if args.adaptive and args.fused:
        ap.error("--adaptive already runs the fused-by-default config; "
                 "drop --fused (its packing/access gates assume a static "
                 "row_packing setup)")
    if args.arena:
        if args.fused or args.adaptive or args.shards > 1 or args.estimate \
                or args.serve:
            ap.error("--arena is its own gate; drop --fused/--adaptive/"
                     "--shards/--estimate/--serve")
        if args.plans < 4:
            ap.error("--plans must be >= 4 (the gate is about concurrent "
                     "shape buckets)")
        return run_arena_gate(args)
    if args.estimate:
        if args.fused or args.adaptive or args.shards > 1 or args.trace \
                or args.serve:
            ap.error("--estimate is its own gate; drop --fused/--adaptive/"
                     "--shards/--trace/--serve")
        return run_estimate_gate(args)
    if args.serve:
        if args.fused or args.adaptive or args.shards > 1 or args.trace \
                or args.estimate:
            ap.error("--serve is its own gate; drop --fused/--adaptive/"
                     "--shards/--trace/--estimate")
        return run_serve_gate(args)

    stream = build_stream(args.requests, args.m, args.k, args.n, args.avg)
    # --trace flips the engine's telemetry layer on for the WHOLE stream
    # (cold calls included: the span check covers cold and steady paths).
    # The ring is sized to hold a full run so the export isn't truncated.
    telemetry = (Telemetry(enabled=True, events_capacity=1 << 16)
                 if args.trace else None)
    if args.adaptive:
        # No static knobs: fused-by-default config, AUTO shard count, and
        # a trim streak short enough that the headroom shrink (one
        # deliberate retrace) lands inside the warmup window.
        config = SpgemmConfig(method="hash")
        engine = SpgemmEngine(config, shards="auto",
                              policy=AdaptivePolicy(trim_streak=6),
                              telemetry=telemetry)
    else:
        config = SpgemmConfig(method=args.method, fuse_numeric=args.fused,
                              row_packing=args.fused)
        engine = SpgemmEngine(config, shards=args.shards,
                              telemetry=telemetry)

    # ---- phase 1: per-call wall-clock over the stream ---------------------
    times = []
    warm_traces = 0
    cold_phases = None
    for i, (A, B) in enumerate(stream):
        t0 = time.perf_counter()
        res = engine.execute(A, B)
        jax.block_until_ready(res.C.val)
        times.append(time.perf_counter() - t0)
        if i == 0 and res.timings:
            # The truly-cold call keeps its StepTimer on even untraced, so
            # the trajectory gets the cold-phase breakdown for free.
            cold_phases = {n: round(t * 1e3, 3)
                           for n, t in sorted(res.timings.items())}
        if i == args.warmup - 1:
            # A schedule grow on this very request leaves the rebuild (and
            # its one retrace) pending; absorb it with an untimed repeat of
            # an already-admitted pair before the gate arms.
            jax.block_until_ready(engine.execute(A, B).C.val)
            warm_traces = total_traces()   # retrace gate arms here
        if args.check:
            ref = np.asarray(spgemm_reference(A, B))
            np.testing.assert_allclose(np.asarray(res.C.to_dense()), ref,
                                       rtol=1e-4, atol=1e-4)

    cold = times[0]
    tail = times[len(times) // 2:]
    steady = sum(tail) / len(tail)
    steady_min = min(tail)     # noise-robust statistic (overhead gates)
    speedup = cold / steady
    hit_rate = engine.cache.hit_rate
    retraces = total_traces() - warm_traces

    print("request,call_ms")
    for i, t in enumerate(times):
        print(f"{i},{t * 1e3:.2f}")
    print()
    print(f"method:        {args.method:>9s}")
    print(f"cold call:     {cold * 1e3:9.1f} ms  (trace + compile)")
    print(f"steady state:  {steady * 1e3:9.2f} ms  "
          f"(mean of last {len(tail)} calls)")
    print(f"speedup:       {speedup:9.1f} x   (target >= 5x)")
    print(f"hit rate:      {hit_rate * 100:9.1f} %   (target >= 90%)")
    print(f"hot traces:    {total_traces():9d}  "
          f"({retraces} after {args.warmup}-request warmup, target 0)")

    # ---- sharded parity: merged C must match the unsharded path ----------
    parity = True
    if args.shards > 1:
        A0, B0 = stream[0]
        base = SpgemmEngine(SpgemmConfig(method=args.method)).execute(A0, B0)
        parity = result_parity(base, engine.execute(A0, B0),
                               bitwise_val=False)
        print(f"shard parity:  {'OK' if parity else 'MISMATCH':>9s}  "
              f"({args.shards} shards vs unsharded: nnz/rpt/col/val)")

    # ---- fused gates: bitwise parity with two-pass + access reduction -----
    # The fused kernels are exercised only under --fused, so the plain
    # --method hash gate keeps isolating two-pass regressions.
    access = None
    access_ok = True
    if args.method == "hash":
        A0, B0 = stream[0]
        acc_s, acc_n, acc_f = measure_hash_accesses(
            A0, B0, config, with_fused=args.fused)
        access = {"symbolic": acc_s, "numeric": acc_n, "fused": acc_f}
        if args.fused:
            reduction = (acc_s + acc_n) / max(acc_f, 1)
            access["reduction"] = round(reduction, 3)
            access_ok = reduction >= 1.5
            print(f"table access:  {acc_s + acc_n:9d} two-pass (sym {acc_s} "
                  f"+ num {acc_n}) vs {acc_f} fused = "
                  f"{reduction:.2f}x reduction")
            base = SpgemmEngine(SpgemmConfig(
                method="hash", fuse_numeric=False)).execute(A0, B0)
            fused_parity = result_parity(base, engine.execute(A0, B0),
                                         bitwise_val=True)
            print(f"fused parity:  {'OK' if fused_parity else 'MISMATCH':>9s}"
                  f"  (fused vs two-pass oracle: nnz/rpt/col/val bitwise)")
            parity = parity and fused_parity   # keep any shard MISMATCH
        else:
            print(f"table access:  {acc_s + acc_n:9d} two-pass "
                  f"(sym {acc_s} + num {acc_n})")

    # ---- adaptive gates: no static knobs, parity, headroom latency --------
    headroom_ok = True
    policy_ok = True
    if args.adaptive:
        # Every request went through the policy (shard count and headroom
        # came from telemetry, not knobs); a gate, not an assert — it must
        # survive python -O and reach the FAIL reporting path.
        policy_ok = engine.stats.auto_requests >= args.requests
        decisions = sorted({e.plan.policy.shard_decision
                            for _, e in engine.cache.items()
                            if e.plan.policy is not None
                            and e.plan.policy.shard_decision is not None})
        headrooms = sorted({round(e.plan.policy.headroom, 3)
                            for _, e in engine.cache.items()
                            if e.plan.policy is not None
                            and e.plan.hash_schedule is not None})
        print(f"policy:        shards->{decisions} headroom={headrooms} "
              f"({engine.stats.schedule_trims} schedule trims, "
              f"{engine.stats.policy_revisions} shard revisions)")
        # ... the fused default stays faithful to the two-pass oracle
        # (bitwise when unsharded; a sharded merge keeps structure bitwise
        # but may reorder FP sums) ...
        A0, B0 = stream[0]
        base = SpgemmEngine(
            SpgemmConfig(method="hash", fuse_numeric=False)).execute(A0, B0)
        adaptive_parity = result_parity(
            base, engine.execute(A0, B0),
            bitwise_val=engine.stats.sharded_requests == 0)
        print(f"adapt parity:  "
              f"{'OK' if adaptive_parity else 'MISMATCH':>9s}  "
              f"(fused-default vs two-pass oracle)")
        parity = parity and adaptive_parity
        # ... and the tracked headroom is no worse than the fixed-2x
        # baseline this file's plain --method hash run recorded (2x wall-
        # clock tolerance: interpret-mode timings are noisy).
        fixed_key = f"hash@{args.m}x{args.k}x{args.n}r{args.requests}"
        try:
            fixed = json.loads(BENCH_JSON.read_text()).get(fixed_key)
        except (ValueError, OSError):
            fixed = None
        if fixed is not None:
            headroom_ok = steady * 1e3 <= 2.0 * fixed["steady_ms"]
            print(f"vs fixed 2x:   {steady * 1e3:9.2f} ms adaptive vs "
                  f"{fixed['steady_ms']:.2f} ms fixed "
                  f"({'OK' if headroom_ok else 'WORSE'})")
        else:
            print(f"vs fixed 2x:   no '{fixed_key}' baseline in "
                  f"{BENCH_JSON.name}; run --method hash first to arm "
                  f"the latency gate")

    # ---- phase 2: batched submit/drain (double-buffered overlap) ----------
    uids = [engine.submit(A, B) for A, B in stream]
    t0 = time.perf_counter()
    results = engine.drain()
    jax.block_until_ready([results[u].C.val for u in uids])
    drain_s = time.perf_counter() - t0
    print(f"drain:         {drain_s * 1e3:9.1f} ms for {len(uids)} requests "
          f"({drain_s / len(uids) * 1e3:.2f} ms/req, "
          f"{engine.stats.overlapped} overlapped, "
          f"{engine.stats.reordered} reordered)")
    print()
    print(engine.report())

    # ---- trajectory key (shared by the trace gate below) ------------------
    # The workload shape is part of the key so a --smoke run never
    # overwrites a full-size baseline recorded for the same config.
    key = args.method + ("_fused" if args.fused else "")
    if args.adaptive:
        key += "_adaptive"
    if args.shards > 1:
        key += f"_shards{args.shards}"
    key += f"@{args.m}x{args.k}x{args.n}r{args.requests}"

    # ---- trace export + telemetry gates -----------------------------------
    # Untraced runs report the cold request's own per-step timings; traced
    # runs override with the aggregated span durations below.
    phases_ms = cold_phases
    trace_tax = None
    trace_ok = True
    overhead_ok = True
    if args.trace:
        jsonl_path = Path(args.trace)
        n_jsonl = telemetry.export_jsonl(jsonl_path)
        spans = telemetry.finished_spans()
        names = {s["name"] for s in spans}
        # The acceptance trace must show the full nested pipeline.
        required = {"request", "plan_lookup", "dispatch", "cold_steps",
                    "symbolic", "numeric", "verify_sync", "finalize",
                    "drain"}
        if args.shards > 1:
            required |= {"shard", "partition", "shard_merge"}
        missing = sorted(required - names)
        trace_ok = not missing
        agg = {}
        for s in spans:
            agg[s["name"]] = agg.get(s["name"], 0.0) + s["dur"]
        phases_ms = {n: round(t * 1e3, 3) for n, t in sorted(agg.items())}
        print(f"trace:         {n_jsonl} JSONL rows -> {jsonl_path}, "
              f"{telemetry.events.dropped} ring overflows"
              + ("" if trace_ok else f"; MISSING spans {missing}"))
        # Overhead gate: tracing must add <5% to steady-state latency.
        # Ambient machine load routinely swings a ~2 ms CPU workload by
        # more than 5% between two separate processes, so the GATE is a
        # same-process A/B: re-run the steady tail on this same engine
        # (same plans, same executables) with tracing on, then off,
        # twice each in alternation, and compare min-of-tail — adjacent
        # loops see the same ambient load, so the ratio isolates the
        # tracing cost.  The cross-run number vs the untraced baseline
        # in BENCH_engine.json is still printed for the trajectory.
        def steady_pass():
            ts = []
            for A, B in stream[len(stream) // 2:]:
                t0 = time.perf_counter()
                res = engine.execute(A, B)
                jax.block_until_ready(res.C.val)
                ts.append(time.perf_counter() - t0)
            return min(ts)

        traced_min, control_min = float("inf"), float("inf")
        for _ in range(2):
            engine.telemetry.enabled = True
            traced_min = min(traced_min, steady_pass())
            engine.telemetry.enabled = False
            control_min = min(control_min, steady_pass())
        engine.telemetry.enabled = True
        overhead_ok = traced_min <= 1.05 * control_min
        trace_tax = {"traced_min_ms": round(traced_min * 1e3, 4),
                     "control_min_ms": round(control_min * 1e3, 4)}
        print(f"trace tax:     {traced_min * 1e3:9.2f} ms traced vs "
              f"{control_min * 1e3:.2f} ms tracing-off steady-min "
              f"(same-process A/B, "
              f"{'OK' if overhead_ok else '>5% REGRESSION'})")
        try:
            base = json.loads(BENCH_JSON.read_text()).get(key)
        except (ValueError, OSError):
            base = None
        base_min = (base or {}).get("steady_min_ms")
        if base_min:
            print(f"               cross-run: {steady_min * 1e3:.2f} ms "
                  f"this run vs {base_min:.2f} ms untraced '{key}' "
                  f"baseline (informational — separate-process runs "
                  f"carry ambient-load noise)")
        key += "_traced"   # never clobber the tracing-disabled baseline

    # ---- perf-trajectory artifact (baseline for future PRs) ---------------
    record_trajectory(key, {
        "requests": args.requests,
        "shape": [args.m, args.k, args.n],
        "cold_ms": round(cold * 1e3, 3),
        "steady_ms": round(steady * 1e3, 4),
        "steady_min_ms": round(steady_min * 1e3, 4),
        "speedup": round(speedup, 2),
        "hit_rate": round(hit_rate, 4),
        "retraces_after_warmup": retraces,
        "drain_ms_per_request": round(drain_s / len(uids) * 1e3, 4),
        "peak_workspace_bytes": engine.arena.peak_bytes,
        "arena_hit_rate": round(engine.arena.hit_rate, 4),
        "table_accesses": access,
        "phases_ms": phases_ms,
        "trace_tax": trace_tax,
        "traced": bool(args.trace),
        "git_rev": git_rev(BENCH_JSON.parent),
        "recorded_at": utc_now_iso(),
    })
    print(f"trajectory:    {BENCH_JSON.name} <- {key}")

    ok = (speedup >= 5.0 and hit_rate >= 0.90 and retraces == 0
          and parity and access_ok and headroom_ok and policy_ok
          and trace_ok and overhead_ok)
    print()
    print("PASS" if ok else "FAIL",
          f"(speedup {speedup:.1f}x, hit rate {hit_rate * 100:.1f}%, "
          f"{retraces} steady-state retraces"
          + ("" if parity else ", parity MISMATCH")
          + ("" if access_ok else ", access reduction < 1.5x")
          + ("" if headroom_ok else ", adaptive steady > 2x fixed-2x")
          + ("" if policy_ok else ", requests bypassed the AUTO policy")
          + ("" if trace_ok else ", trace missing required spans")
          + ("" if overhead_ok else ", tracing overhead > 5%")
          + ")")
    return 0 if ok else 1


if __name__ == "__main__":
    sys.exit(main())
