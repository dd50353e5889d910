#!/usr/bin/env python3
"""Smoke run of the SpGEMM engine on a TPU: a smoke run, not a benchmark.

Drives the main path (``SpgemmService.call`` -> ``SpgemmEngine`` -> the
ESC expand-sort-compress steps or the Pallas hash kernels) at the full
size of two Table-3 matrices, the synthetic analogs of cage12 and
webbase-1M from ``benchmarks/matrices.py`` made from a fixed seed.  It
computes C = A @ A and checks every C against ``scipy.sparse`` on the
host: exact nnz and structure, values within 1e-5 of the float64 product,
relative to the sum of the absolute products.

    python chip_smoke.py              # one chip: {cage12, webbase-1M} x {esc, hash}
    python chip_smoke.py --chips 4    # four chips: cage12 in 4 row-block
                                      # shards over a mesh vs one device

Each phase prints one line.  The last line of stdout is one JSON object,
``{"ok": true, "device": {"platform": "tpu", "kind": ..., "count": N}}``.
The script exits non-zero without printing it when JAX finds no TPU or
when any phase fails.  The compile cache lives where
``JAX_COMPILATION_CACHE_DIR`` says, else in ``<repo>/.jax_cache``.
"""
from __future__ import annotations

import argparse
import dataclasses
import json
import sys
import time
from pathlib import Path
from typing import Optional

import jax
import numpy as np

ROOT = Path(__file__).resolve().parent
MATRICES = ("cage12", "webbase-1M")
METHODS = ("esc", "hash")
REPEATS = 3          # requests after the cold one (the first builds the
                     # steady-state executable)
RTOL = 1e-5


class SmokeFailure(Exception):
    """A phase produced a wrong or unexpected result."""


def check(cond, msg: str) -> None:
    if not cond:
        raise SmokeFailure(msg)


def device_info() -> dict:
    devs = jax.devices()
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs)}


def require_tpu(info: dict) -> None:
    if info["platform"] != "tpu":
        raise SystemExit(f"chip_smoke: needs a TPU, JAX found "
                         f"{info['platform']!r}; nothing was run")


def _import_repo() -> None:
    for p in (ROOT, ROOT / "src"):
        if str(p) not in sys.path:
            sys.path.insert(0, str(p))


# ---------------------------------------------------------------------------
# Phases.
# ---------------------------------------------------------------------------

def phase_generate(name: str, scale: int):
    """Build the Table-3 analog ``name`` on the device; returns (A, s)."""
    from benchmarks.matrices import TABLE3, generate
    spec = next(s for s in TABLE3 if s.name == name)
    t0 = time.perf_counter()
    A = generate(spec, scale=scale)
    jax.block_until_ready(A.val)
    return A, time.perf_counter() - t0


def reference(A):
    """Host scipy reference for A @ A: (float64 product, |A| @ |A|).

    ``|A| @ |A|`` has no cancellation, so its pattern is the exact
    structure of the product and its values bound the rounding error.
    """
    import scipy.sparse as sp
    nnz = int(A.rpt[-1])
    rpt = np.asarray(A.rpt)
    col = np.asarray(A.col)[:nnz]
    val = np.asarray(A.val)[:nnz].astype(np.float64)
    S = sp.csr_matrix((val, col, rpt), shape=A.shape)
    S_abs = sp.csr_matrix((np.abs(val), col, rpt), shape=A.shape)
    C, C_abs = S @ S, S_abs @ S_abs
    C.sort_indices()
    C_abs.sort_indices()
    check(C.nnz == C_abs.nnz, "reference product cancelled to exact zeros")
    return C, C_abs


def check_product(C, ref) -> int:
    """Exact nnz and structure, values to RTOL; returns nnz."""
    C_ref, C_abs = ref
    rpt = np.asarray(C.rpt)
    nnz = int(rpt[-1])
    check(nnz == C_abs.nnz, f"nnz {nnz} != reference {C_abs.nnz}")
    check(np.array_equal(rpt, C_abs.indptr), "row pointers differ")
    check(np.array_equal(np.asarray(C.col)[:nnz], C_abs.indices),
          "column structure differs")
    err = np.abs(np.asarray(C.val)[:nnz].astype(np.float64) - C_ref.data)
    worst = float(np.max(err / C_abs.data)) if nnz else 0.0
    check(worst <= RTOL, f"values off by {worst:.3g} > {RTOL}")
    return nnz


def _timed_call(svc, A):
    t0 = time.perf_counter()
    r = svc.call(A, A)
    if r.status == "ok":
        jax.block_until_ready(r.value.C)
    dt = time.perf_counter() - t0
    check(r.status == "ok", f"service status {r.status!r}: {r.error}")
    return r.value, dt


def lowered_text(entry, A) -> str:
    """StableHLO of the plan's steady-state executable for operand A."""
    plan = entry.plan
    args = [A.with_capacity(plan.a_sig.cap_bucket),
            A.with_capacity(plan.b_sig.cap_bucket)]
    spec = plan.workspace_spec()
    if spec is not None:          # the arena lease rides as two buffers
        args += [jax.ShapeDtypeStruct((spec.i32_cells,), np.int32),
                 jax.ShapeDtypeStruct((spec.val_cells,), spec.val_dtype)]
    return entry.executable.lower(*args).as_text()


def chip_checks(method: str, entry, A) -> None:
    """The kernels ran compiled: no interpret mode, and the hash plan's
    steady-state program holds Pallas TPU kernels."""
    from repro.kernels import resolve_interpret
    check(resolve_interpret(None) is False, "Pallas would run interpreted")
    check(entry.plan.config.interpret is None,
          "config forces an interpret mode")
    if method == "hash":
        check("tpu_custom_call" in lowered_text(entry, A),
              "hash executable holds no tpu_custom_call")


def peak_bytes() -> int:
    stats = jax.devices()[0].memory_stats() or {}
    return int(stats.get("peak_bytes_in_use", -1))


class CompileClock:
    """Backend compile seconds and persistent-cache hits in this process,
    read from JAX's monitoring events (a cache hit costs no compile)."""

    def __init__(self):
        self.compile_s = 0.0
        self.cache_hits = 0
        jax.monitoring.register_event_duration_secs_listener(self._duration)
        jax.monitoring.register_event_listener(self._event)

    def _duration(self, event: str, secs: float, **_) -> None:
        if event == "/jax/core/compile/backend_compile_duration":
            self.compile_s += secs

    def _event(self, event: str, **_) -> None:
        if event == "/jax/compilation_cache/cache_hits":
            self.cache_hits += 1

    def read(self) -> tuple:
        return self.compile_s, self.cache_hits


def phase_service(name: str, A, ref, method: str, *,
                  repeats: int = REPEATS,
                  clock: Optional[CompileClock] = None) -> dict:
    """One cold request, then ``repeats`` requests of the same product
    through ``SpgemmService.call``; every result checked against ``ref``.
    The first repeat builds the steady-state executable (one trace); no
    later repeat may retrace, and none may leave the hot path."""
    from repro.core import SpgemmConfig
    from repro.engine import total_traces
    from repro.serve import SpgemmService

    compile0 = clock.read() if clock else (0.0, 0)
    svc = SpgemmService(SpgemmConfig(method=method))
    eng = svc.engine()
    res, cold_s = _timed_call(svc, A)
    nnz = check_product(res.C, ref)
    (_, entry), = eng.cache.items()

    def counters():
        return (total_traces(), entry.stats.steps_calls,
                entry.stats.hot_calls, eng.stats.capacity_grows,
                eng.stats.bin_overflows, eng.stats.arena_spills)

    seen = [counters()]
    latency = []
    for _ in range(repeats):
        res, dt = _timed_call(svc, A)
        check_product(res.C, ref)
        latency.append(dt)
        seen.append(counters())
    builds = seen[1][0] - seen[0][0]
    retraces = seen[-1][0] - seen[1][0]
    delta = [a - b for a, b in zip(seen[-1], seen[0])]
    check(builds == 1, f"first repeat traced {builds} times, expected 1")
    check(retraces == 0, f"{retraces} retraces on the later repeats")
    check(delta[1] == 0, f"{delta[1]} repeats rerouted to the steps path")
    check(delta[2] == repeats, f"hot calls {delta[2]} != {repeats}")
    check(delta[3:] == [0, 0, 0],
          f"grows/overflows/spills on the repeats: {delta[3:]}")
    chip_checks(method, entry, A)
    svc.close()
    compile1 = clock.read() if clock else (0.0, 0)
    return {"matrix": name, "method": method, "cold_s": cold_s,
            "first_hot_s": latency[0], "steady_s": latency[1:],
            "n_prod": res.total_nprod, "nnz": nnz,
            "steps_calls": entry.stats.steps_calls,
            "hot_calls": entry.stats.hot_calls, "retraces": retraces,
            "compile_s": compile1[0] - compile0[0],
            "cache_hits": compile1[1] - compile0[1],
            "peak_bytes": peak_bytes()}


def phase_sharded(A, ref, shards: int) -> dict:
    """C = A @ A in ``shards`` row-block shards placed over the host mesh,
    compared bitwise in nnz and structure with the one-device result.

    The fan-out and merge do not depend on the method or the planning
    mode.  ``hash`` planned by estimate compiles least: each shard device
    compiles its own copy of every program it runs, and estimate planning
    skips the many small programs of the exact cold path.
    """
    from repro.core import SpgemmConfig
    from repro.engine import SpgemmEngine
    from repro.launch.mesh import make_host_mesh

    t0 = time.perf_counter()
    cfg = SpgemmConfig(method="hash", plan_mode="estimate")
    one = SpgemmEngine(cfg).execute(A, A)
    jax.block_until_ready(one.C)
    one_s = time.perf_counter() - t0
    nnz = check_product(one.C, ref)
    eng = SpgemmEngine(dataclasses.replace(cfg, shards=shards),
                       mesh=make_host_mesh(), telemetry=True)
    t0 = time.perf_counter()
    res = eng.execute(A, A)
    jax.block_until_ready(res.C)
    sharded_s = time.perf_counter() - t0
    check(int(res.C.rpt[-1]) == nnz, "sharded nnz differs from one device")
    check(np.array_equal(np.asarray(res.C.rpt), np.asarray(one.C.rpt)),
          "sharded row pointers differ from one device")
    check(np.array_equal(np.asarray(res.C.col)[:nnz],
                         np.asarray(one.C.col)[:nnz]),
          "sharded structure differs from one device")
    check_product(res.C, ref)
    merges = [s for s in eng.telemetry.finished_spans()
              if s["name"] == "shard_merge"]
    check(len(merges) == 1, f"{len(merges)} shard merges, expected 1")
    devices = list(merges[0]["attrs"]["devices"])
    want = min(shards, len(jax.devices()))
    check(len(set(devices)) == want,
          f"shards landed on devices {devices}, expected {want} distinct")
    return {"matrix": "cage12", "method": cfg.method,
            "plan_mode": cfg.plan_mode, "shards": shards,
            "shard_devices": devices,
            "one_device_s": one_s, "sharded_s": sharded_s, "nnz": nnz,
            "peak_bytes": peak_bytes()}


# ---------------------------------------------------------------------------
# Runs.
# ---------------------------------------------------------------------------

def _say(kind: str, fields: dict) -> None:
    print(f"smoke {kind}: " + json.dumps(fields), flush=True)


def run_single(*, scale: int = 1, matrices=MATRICES, methods=METHODS,
               repeats: int = REPEATS,
               clock: Optional[CompileClock] = None) -> None:
    for name in matrices:
        A, gen_s = phase_generate(name, scale)
        t0 = time.perf_counter()
        ref = reference(A)
        _say("generate", {"matrix": name, "rows": A.nrows,
                          "nnz": int(A.rpt[-1]), "gen_s": gen_s,
                          "reference_s": time.perf_counter() - t0})
        for method in methods:
            _say("phase", phase_service(name, A, ref, method,
                                        repeats=repeats, clock=clock))


def run_sharded(*, scale: int = 1, shards: int = 4) -> None:
    A, gen_s = phase_generate("cage12", scale)
    ref = reference(A)
    _say("generate", {"matrix": "cage12", "rows": A.nrows,
                      "nnz": int(A.rpt[-1]), "gen_s": gen_s})
    _say("sharded", phase_sharded(A, ref, shards))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: run only the sharded mesh phase on four chips")
    args = ap.parse_args(argv)
    info = device_info()
    require_tpu(info)
    if info["count"] < args.chips:
        raise SystemExit(f"chip_smoke: --chips {args.chips} needs "
                         f"{args.chips} devices, found {info['count']}")
    _import_repo()
    from repro.kernels import use_compile_cache
    _say("setup", {"compile_cache": use_compile_cache(), **info})
    clock = CompileClock()
    t0 = time.perf_counter()
    try:
        if args.chips == 4:
            run_sharded()
        else:
            run_single(clock=clock)
    except SmokeFailure as e:
        print(f"chip_smoke: FAILED: {e}", file=sys.stderr)
        return 1
    _say("done", {"total_s": time.perf_counter() - t0,
                  "compile_s": clock.compile_s,
                  "cache_hits": clock.cache_hits})
    print(json.dumps({"ok": True, "device": info}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
