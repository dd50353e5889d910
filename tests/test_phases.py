"""The engine's spans in a profiler trace, and the epilogue's counters.

A CPU profiler trace of ``SpgemmService.call`` holds the engine's spans
as ``opsparse.<name>`` annotations, nested as the engine opened them,
and a disabled ``Telemetry`` adds none.  The hot hash finalize counts
the epilogue's table slots, those of its gathering rungs, and the
entries it writes.  (The device scopes of the steady executables are
checked where they compile for the chip, ``tests/test_chip_compile.py``.)
"""
import glob
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.profiler import ProfileData, TraceAnnotation

from repro import phases
from repro.core import SpgemmConfig, random_csr
from repro.core.binning import bin_rows
from repro.core.binning_ranges import make_ladder
from repro.engine import SpgemmEngine, prometheus_text
from repro.engine.executor import _fallback_nnz
from repro.kernels import spgemm_hash
from repro.serve import SpgemmService

CALL = "chipbench.call"


def _pair(seed, m=32, k=28, n=36, avg=3.0):
    A = random_csr(jax.random.PRNGKey(seed), m, k, avg_nnz_per_row=avg)
    B = random_csr(jax.random.PRNGKey(seed + 1), k, n, avg_nnz_per_row=avg)
    return A, B


def _host_spans(log_dir):
    """(name, start_ns, end_ns) of every host annotation of the trace
    named ``opsparse.*`` or ``chipbench.call``."""
    path, = glob.glob(os.path.join(log_dir, "**", "*.xplane.pb"),
                      recursive=True)
    out = []
    for plane in ProfileData.from_file(path).planes:
        if plane.name.startswith("/device:"):
            continue
        for line in plane.lines:
            for ev in line.events:
                if ev.name.startswith(phases.PREFIX) or ev.name == CALL:
                    out.append((ev.name, ev.start_ns, ev.end_ns))
    return out


def _traced_calls(tmp_path, telemetry):
    """Trace a cold and a hot ``SpgemmService.call`` of one product, each
    inside a ``chipbench.call`` annotation."""
    svc = SpgemmService(telemetry=telemetry)
    A, B = _pair(3)
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    with jax.profiler.trace(str(tmp_path), profiler_options=opts):
        for _ in range(2):
            with TraceAnnotation(CALL):
                r = svc.call(A, B, config=SpgemmConfig(method="esc"))
                assert r.ok, r.error
                r.value.C.block_until_ready()
    svc.close()
    return _host_spans(str(tmp_path))


def _within(inner, outer):
    return outer[1] <= inner[1] and inner[2] <= outer[2]


def test_profiler_trace_holds_the_engine_spans(tmp_path):
    spans = _traced_calls(tmp_path, telemetry=True)
    calls = [s for s in spans if s[0] == CALL]
    assert len(calls) == 2
    named = lambda n: [s for s in spans if s[0] == phases.PREFIX + n]
    requests = named("request")
    assert len(requests) == 2
    for call, request in zip(sorted(calls, key=lambda s: s[1]),
                             sorted(requests, key=lambda s: s[1])):
        assert _within(request, call)
    # Each dispatch and finalize lies in a request; each verify sync (the
    # hot call's) in a finalize.
    for child, parent in (("dispatch", "request"), ("finalize", "request"),
                          ("verify_sync", "finalize"),
                          ("cold_steps", "request")):
        assert named(child), child
        for c in named(child):
            assert any(_within(c, p) for p in named(parent)), (child, c)
    # The cold call's steps carry the phase names of the device scopes.
    for step in (phases.NPROD, phases.BIN, phases.ROWPTR):
        assert any(_within(s, c) for s in named(step)
                   for c in named("cold_steps")), step


def test_disabled_telemetry_adds_no_annotation(tmp_path):
    spans = _traced_calls(tmp_path, telemetry=False)
    assert [s[0] for s in spans] == [CALL, CALL]


def test_epilogue_counters_count_slots_and_entries():
    """Each admitted hot hash product adds its schedule's table slots and
    its nnz (no fallback rung here) to the two counters, which Prometheus
    exposes under their own names."""
    A, B = _pair(5, m=48, k=40, n=40, avg=4.0)
    engine = SpgemmEngine(SpgemmConfig(method="hash"))
    results = [engine.execute(A, B) for _ in range(3)]
    plan = next(e for _, e in engine.cache.items()).plan
    hot = next(e for _, e in engine.cache.items()).stats.hot_calls
    assert hot == 2 and plan.hash_schedule.sym_row_buckets[-1] == 0
    slots = spgemm_hash.epilogue_slots(
        plan.sym_ladder, plan.hash_schedule.sym_row_buckets,
        row_packing=plan.config.row_packing)
    assert slots > 0
    reg = engine.telemetry.registry
    entries = reg.get("opsparse_epilogue_entries_total").value
    assert reg.get("opsparse_epilogue_slots_total").value == hot * slots
    assert entries == sum(r.total_nnz for r in results[1:])
    assert 0 < entries < hot * slots
    text = prometheus_text(engine)
    assert f"opsparse_epilogue_slots_total {hot * slots}" in text
    assert "opsparse_epilogue_entries_total " in text


@pytest.mark.parametrize("nnz_bucket", [None, 1 << 16])
def test_epilogue_gathered_slots_counter(nnz_bucket):
    """Each admitted hot hash product adds the slots of its rungs whose
    epilogue gathers (their tables outnumber C's positions); with C's
    storage prewarmed past every rung's table, none gathers and the
    counter reads 0.  Prometheus exposes it under its own name."""
    A, B = _pair(5, m=48, k=40, n=40, avg=4.0)
    engine = SpgemmEngine(SpgemmConfig(method="hash"))
    if nnz_bucket:
        engine.prewarm(A, B, prod_bucket=1 << 14, nnz_bucket=nnz_bucket)
    for _ in range(3):
        engine.execute(A, B)
    entry = next(e for _, e in engine.cache.items())
    plan, hot = entry.plan, entry.stats.hot_calls
    assert hot == 2
    gathered = spgemm_hash.epilogue_gathered_slots(
        plan.sym_ladder, plan.hash_schedule.sym_row_buckets,
        nnz_capacity=plan.nnz_bucket)
    slots = spgemm_hash.epilogue_slots(
        plan.sym_ladder, plan.hash_schedule.sym_row_buckets)
    assert (0 < gathered <= slots) if nnz_bucket is None else gathered == 0
    reg = engine.telemetry.registry
    assert reg.get("opsparse_epilogue_gathered_slots_total").value \
        == hot * gathered
    assert reg.get("opsparse_epilogue_slots_total").value == hot * slots
    assert (f"opsparse_epilogue_gathered_slots_total {hot * gathered}"
            in prometheus_text(engine))


def test_epilogue_slots_are_the_dumped_tables():
    """The slot count is the size of the tables the fused kernels dump
    for the epilogue, rung by rung (a packed rung shares its tiles)."""
    A, B = _pair(7, m=64, k=80, n=80, avg=6.0)
    ladder = make_ladder((32, 64, 128), 1.2, (32, 64, 128))
    row_buckets = (16, 32, 8, 0)
    for packed in (False, True):
        dumped = 0
        for b, rows_cap in enumerate(row_buckets[:-1]):
            pack = min(ladder.rows_per_block[b] if packed else 1, rows_cap)
            _, col_tabs, _, _ = spgemm_hash.fused_bin_call(
                jnp.zeros(rows_cap, jnp.int32), jnp.zeros(1, jnp.int32),
                A.rpt, A.col, A.val, B.rpt, B.col, B.val,
                t_size=ladder.table_sizes[b], rows_cap=rows_cap, pack=pack)
            dumped += col_tabs.size
        assert spgemm_hash.epilogue_slots(
            ladder, row_buckets, row_packing=packed) == dumped


@pytest.mark.parametrize("fallback_rows", [0, 8])
def test_fallback_nnz_counts_only_fallback_rows(fallback_rows):
    ladder = make_ladder((32, 64), 1.0, (32, 64))
    sizes = np.array([3, 40, 70, 200, 5, 90, 31, 64], np.int32)
    nnz = jnp.asarray(np.arange(1, 9, dtype=np.int32))
    binning = bin_rows(jnp.asarray(sizes), upper=ladder.upper,
                       num_bins=ladder.num_bins)
    got = int(_fallback_nnz(binning, nnz, ladder, (8, 8, fallback_rows)))
    # Rows above the last bound (64) land on the fallback rung: 2, 3, 5.
    assert got == (3 + 4 + 6 if fallback_rows else 0)
