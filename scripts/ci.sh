#!/usr/bin/env bash
# Tier-1 gate + engine perf wiring, run on every PR.
#   ./scripts/ci.sh
set -euo pipefail
cd "$(dirname "$0")/.."
export PYTHONPATH=src${PYTHONPATH:+:$PYTHONPATH}

echo "== tier-1 tests =="
python -m pytest -x -q

echo
echo "== opslint gate (static analysis: fail on NEW findings vs baseline) =="
# AST-only — no JAX execution, so it runs ahead of the bench gates.
# Rules: trace-safety (TRC), donation discipline (DON), lock order /
# guarded-by races (LCK), host-int width (INT), kernel budgets (KRN).
# `--fail-on-new` diffs against the checked-in opslint_baseline.json;
# refresh it with `scripts/opslint --write-baseline opslint_baseline.json`
# only after triaging (fix true positives, suppress documented FPs).
python -m repro.analysis_static src/repro --fail-on-new \
    --baseline opslint_baseline.json --format json

echo
echo "== engine smoke benchmark (plan-cache effectiveness) =="
python benchmarks/bench_engine.py --smoke

echo
echo "== engine smoke benchmark (hash method: zero-retrace steady state) =="
python benchmarks/bench_engine.py --smoke --method hash

echo
echo "== engine smoke benchmark (adaptive policy: auto shards + tracked headroom) =="
python benchmarks/bench_engine.py --smoke --method hash --adaptive

echo
echo "== engine smoke benchmark (fused hash: one-build tables + row packing) =="
python benchmarks/bench_engine.py --smoke --method hash --fused

echo
echo "== engine smoke benchmark (sharded: partition parity + plan reuse) =="
python benchmarks/bench_engine.py --smoke --shards 2

echo
echo "== arena gate (K shape buckets under a governor cap: peak bytes, parity) =="
# 8 distinct shape-bucket plans share one workspace arena with the
# governor capped at 0.6x the per-plan-buffer baseline; gates peak
# workspace bytes <= cap (and strictly below the baseline), zero
# retraces after warmup, and bitwise parity vs an uncapped engine.
python benchmarks/bench_engine.py --smoke --arena

echo
echo "== estimate gate (sampled cold planning: >=3x sizing, bitwise parity) =="
# plan_mode="estimate" stream first (cold — its sizing is a host-side
# sampled estimate, no kernel compiles), exact-planning baseline second
# on a fresh engine in the same process (ordering biases AGAINST the
# gate).  Gates: the estimator beats the exact symbolic sizing pass
# >=3x, the full first call is no slower, zero post-warmup retraces,
# steady state no worse, and bitwise result parity on every request.
python benchmarks/bench_engine.py --smoke --estimate --method hash

echo
echo "== telemetry gate (traced smoke: full span pipeline, <5% overhead) =="
# The traced run's spans must cover the full nested pipeline including
# the sharded fan-out; the event log is exported as JSON Lines.  The <5%
# overhead gate is a same-process A/B (steady tail re-run with tracing on
# vs off on the same engine) so ambient machine load between separate CI
# steps can't flake it; the untraced --shards 2 smoke above still records
# the cross-run steady_min_ms baseline printed for the trajectory.
python benchmarks/bench_engine.py --smoke --shards 2 \
    --trace /tmp/opsparse_smoke_trace.jsonl

echo
echo "== chaos gate (serving front-end: seeded faults, zero failures, parity) =="
# A mixed-tenant stream runs fault-free, then again under a seeded
# FaultPlan (lease denials + verify overflows, plus a deterministic
# double denial that forces the service retry ladder).  Gates: zero
# failed well-formed requests, every chaos result bitwise identical to
# its fault-free twin, bounded p99 inflation, a poisoned request errors
# WITHOUT retrying, a stalled request under deadline returns a
# structured timeout, and the per-tenant counters appear on a live
# /metrics scrape.
python benchmarks/bench_engine.py --smoke --serve
