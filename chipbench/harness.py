"""One run of one benchmark cell on the chip.

    python3 chipbench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

A cell of ``BENCHMARK.json`` names a configuration (a file under
``chipbench/configs/``, whose ``generator`` is a module under
``chipbench/generators/``) and a traffic mix (``chipbench/traffic/<name>
.json``, whose ``driver`` is a module under ``chipbench/drivers/``).
Each per-layer metric is the module ``chipbench/metrics/<name>.py``.  So
a new configuration, mix or metric is a new file; nothing here changes.

A run: the matrix's structure, checked against its source; warm-up
(set-up ends there); the window, traced with ``--trace 1``; the device's
peak memory; the program released; then the comparison of the kept
answers with the plain reference of their own values.  Earlier lines
of standard output describe the matrix, the set-up and the window; the
last lines of standard error give each number compared beside its
limit; the last line of standard output is the result, one JSON object.

The run refuses (non-zero exit, no result) when JAX finds no TPU or
fewer chips than the cell asks for, or when the program is missing.
"""
from __future__ import annotations

import argparse
import gc
import importlib
import json
import shutil
import sys
import tempfile
import time
import types
from pathlib import Path
from typing import List, Optional

import numpy as np

from chipbench import reference, tracing, work

ROOT = Path(__file__).resolve().parents[1]
PKG = "chipbench"


# ---------------------------------------------------------------------------
# Cells, found by name in data files.
# ---------------------------------------------------------------------------

class Cell(types.SimpleNamespace):
    """name, chips, config (dict), traffic (dict), end_to_end and
    per_layer: the cell's metrics as BENCHMARK.json lists them."""


def _applies(metric: dict, cell: str) -> bool:
    return "workloads" not in metric or cell in metric["workloads"]


def load_cell(root: Path, name: str) -> Cell:
    bench = json.loads((root / "BENCHMARK.json").read_text())
    cells = {w["name"]: w for w in bench["workloads"]}
    if name not in cells:
        raise SystemExit(f"chipbench: no workload {name!r}; "
                         f"known: {sorted(cells)}")
    w = cells[name]
    entry = next(c for c in bench["configs"] if c["name"] == w["config"])
    config = json.loads((root / entry["file"]).read_text())
    traffic = json.loads(
        (root / PKG / "traffic" / f"{w['traffic']}.json").read_text())
    return Cell(name=name, chips=int(w["chips"]), config=config,
                traffic=traffic,
                end_to_end=[m for m in bench["end_to_end"]
                            if _applies(m, name)],
                per_layer=[m for m in bench["per_layer"]
                           if _applies(m, name)])


def plugin(kind: str, name: str):
    """``chipbench/<kind>/<name>.py`` as a module."""
    return importlib.import_module(f"{PKG}.{kind}.{name}")


# ---------------------------------------------------------------------------
# The device.
# ---------------------------------------------------------------------------

def device_info() -> dict:
    import jax
    devs = jax.devices()
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs)}


def require_accelerator(info: dict, chips: int) -> None:
    if info["platform"] != "tpu":
        raise SystemExit(f"chipbench: needs a TPU, JAX found "
                         f"{info['platform']!r}; nothing was run")
    if info["count"] < chips:
        raise SystemExit(f"chipbench: the cell needs {chips} chips, JAX "
                         f"found {info['count']}")


def device_peaks(kind: str) -> dict:
    table = json.loads((ROOT / PKG / "peaks.json").read_text())["devices"]
    if kind not in table:
        raise SystemExit(f"chipbench: no peaks for device kind {kind!r} "
                         f"in peaks.json")
    return table[kind]


def memory_peak_bytes() -> int:
    import jax
    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use", 0)
             for d in jax.local_devices()]
    return int(max(peaks))


def use_compile_cache(root: Path) -> str:
    """JAX's persistent cache at a fixed path inside the checkout, so that
    only a checkout's first run of a cell compiles, for programs of any
    size (the cold path is many small programs)."""
    import jax
    path = str(root / ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    return path


class CompileClock:
    """Backend compiles, their seconds and persistent-cache hits in this
    process, from JAX's monitoring events."""

    def __init__(self):
        import jax
        self.compiles = 0
        self.compile_s = 0.0
        self.cache_hits = 0
        jax.monitoring.register_event_duration_secs_listener(self._duration)
        jax.monitoring.register_event_listener(self._event)

    def _duration(self, event: str, secs: float, **_) -> None:
        if event == "/jax/core/compile/backend_compile_duration":
            self.compiles += 1
            self.compile_s += secs

    def _event(self, event: str, **_) -> None:
        if event == "/jax/compilation_cache/cache_hits":
            self.cache_hits += 1


def import_program(root: Path) -> types.SimpleNamespace:
    """The system under test: its entry, its CSR and its trace counter."""
    src = str(root / "src")
    if src not in sys.path:
        sys.path.insert(0, src)
    try:
        from repro.core import CSR, SpgemmConfig
        from repro.engine import total_traces
        from repro.serve import SpgemmService
    except ImportError as e:
        raise SystemExit(f"chipbench: the program is missing ({e})")
    return types.SimpleNamespace(CSR=CSR, SpgemmConfig=SpgemmConfig,
                                 SpgemmService=SpgemmService,
                                 total_traces=total_traces)


# ---------------------------------------------------------------------------
# One run.
# ---------------------------------------------------------------------------

def say(kind: str, fields: dict) -> None:
    print(f"chipbench {kind}: " + json.dumps(fields), flush=True)


def parse_args(argv: Optional[List[str]]) -> argparse.Namespace:
    ap = argparse.ArgumentParser(description="One run of one benchmark "
                                             "cell (see BENCHMARK.json).")
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    if args.seed < 0:
        ap.error("--seed must be a whole number >= 0")
    return args


def layer_metrics(cell: Cell, summary, products: int,
                  least_s: float) -> dict:
    run = types.SimpleNamespace(summary=summary, products=products,
                                least_s=least_s)
    out = {}
    for m in cell.per_layer:
        value = plugin("metrics", m["name"]).read(run)
        if value is not None:
            out[m["name"]] = {"value": value, "unit": m["unit"]}
    return out


def main(argv: Optional[List[str]] = None, *, t_start: Optional[float] = None,
         root: Path = ROOT) -> int:
    t_start = time.perf_counter() if t_start is None else t_start
    args = parse_args(argv)
    cell = load_cell(root, args.workload)
    cache_dir = use_compile_cache(root)
    info = device_info()
    require_accelerator(info, cell.chips)
    peaks = device_peaks(info["kind"])
    repro = import_program(root)
    clock = CompileClock()

    config, traffic = cell.config, cell.traffic
    gen = plugin("generators", config["generator"])
    rpt, col = gen.structure(config)
    shape = (int(config["rows"]), int(config["cols"]))
    nprod = work.n_prod(rpt, col, rpt)
    say("matrix", {"config": config["name"], "seed": args.seed,
                   **gen.check(config, rpt, nprod)})

    driver = plugin("drivers", traffic["driver"])
    session = driver.start(repro, rpt, col, shape, traffic, args.seed)
    setup_s = time.perf_counter() - t_start
    say("setup", {"setup_s": setup_s, "warmup_s": session.warmup_s,
                  "compiles": clock.compiles, "compile_s": clock.compile_s,
                  "cache_hits": clock.cache_hits, "cache_dir": cache_dir})

    rng = np.random.default_rng([args.seed, 2])
    compiles0 = clock.compiles
    trace_dir = tempfile.mkdtemp(prefix="chipbench-trace-") \
        if args.trace else None
    gc.collect()
    gc.disable()        # no collector pause inside the window
    try:
        if trace_dir:
            with tracing.record(trace_dir), tracing.annotate("window"):
                win = driver.window(repro, session, args.seconds, rng)
            events = tracing.read(trace_dir)
        else:
            with tracing.annotate("window"):
                win = driver.window(repro, session, args.seconds, rng)
    finally:
        gc.enable()
        if trace_dir:
            shutil.rmtree(trace_dir, ignore_errors=True)
    say("window", {**driver.describe(win),
                   "compiles": clock.compiles - compiles0})
    peak = memory_peak_bytes()

    # Release the program's state, then check against the reference of
    # each kept answer's own values.
    answers = driver.answers(win)
    driver.close(session, win)
    del session
    gc.collect()
    P = reference.pattern(rpt, col, shape)
    say("reference", gen.check(config, rpt, nprod, P.nnz))
    pairs = [(reference.reference(rpt, col, val, shape, P), ans)
             for val, ans in answers]
    checks = reference.compare(pairs, win.reported, win.failed, nprod,
                               P.nnz, shape[1], config["limits"])
    products = len(win.latencies) - win.failed

    device = {"platform": info["platform"], "kind": info["kind"],
              "count": info["count"], "memory_peak_bytes": peak}
    result = {"correct": reference.passed(checks),
              "attempted": len(win.latencies), "failed": win.failed}
    if args.trace:
        summary = tracing.summarize(events)
        least_s = work.least_seconds(
            peaks, flop=work.flops(nprod),
            bytes_=work.gustavson_bytes(shape[0], int(rpt[-1]), nprod,
                                        P.nnz))
        result["metrics"] = layer_metrics(cell, summary, products, least_s)
        device.update(busy_s=summary.busy_s, window_s=summary.window_s)
        result["device"] = device
        result["breakdown"] = tracing.breakdown(summary)
    else:
        e2e = driver.metrics(win, work.flops(nprod))
        e2e.update(setup_s=setup_s, peak_hbm_gb=peak / 1e9)
        missing = [m["name"] for m in cell.end_to_end
                   if m["name"] not in e2e]
        if missing:
            raise SystemExit(f"chipbench: no reading of {missing}")
        result["metrics"] = {m["name"]: {"value": e2e[m["name"]],
                                         "unit": m["unit"]}
                             for m in cell.end_to_end}
        result["device"] = device
    result["checks"] = checks
    for name, c in checks.items():
        print(f"check {name}: {c['value']} (limit {c['limit']})",
              file=sys.stderr, flush=True)
    print(json.dumps(result), flush=True)
    return 0
