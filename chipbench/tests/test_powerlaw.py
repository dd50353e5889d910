"""The webbase-1M analog: calibrated to Table 3 at full size, fixed by its
name, workable at small sizes; the benchmark's tiny power-law cell run
end to end on the CPU, whole and with its product broken; and
``upper_rung_kernel_s``, which reads only the kernels of the upper hash
rungs."""
import json
import types

import numpy as np
import pytest

from chipbench import reference, tracing, work
from chipbench.generators import powerlaw, table3
from chipbench.metrics import upper_rung_kernel_s

from ..conftest import TINY_SIZES
from .conftest import ROOT, last_json
from .test_faults import (_alter_column, _alter_value, _drop_half_the_rows,
                          _stale_answer)

TINY = dict(TINY_SIZES["webbase-1M"], name="webbase-1M-tiny", cols=2048)


def _config(**changes):
    cfg = json.loads((ROOT / "chipbench" / "configs" / "webbase-1M.json")
                     .read_text())
    cfg.update(changes)
    return cfg


def test_webbase_matches_its_source_at_full_size():
    cfg = _config()
    rpt, col = powerlaw.structure(cfg)
    shape = (cfg["rows"], cfg["cols"])
    P = reference.pattern(rpt, col, shape)
    s = table3.check(cfg, rpt, work.n_prod(rpt, col, rpt), P.nnz)
    assert s["rows"] == 1000005 and s["max_nnz_per_row"] == 4700
    assert s["mean_nnz_per_row"] == pytest.approx(3.1, rel=0.01)
    assert s["nprod"] == pytest.approx(69.5e6, rel=0.01)
    assert s["nnz_c"] == pytest.approx(51.1e6, rel=0.01)
    assert s["nprod"] / s["nnz_c"] == pytest.approx(1.36, rel=0.02)


def test_a_small_structure_is_fixed_by_the_name():
    cfg = _config(**TINY)
    a, b = powerlaw.structure(cfg), powerlaw.structure(dict(cfg))
    for x, y in zip(a, b):
        np.testing.assert_array_equal(x, y)
    rpt, col = a
    assert rpt.dtype == np.int32 and col.dtype == np.int32
    sizes = np.diff(rpt)
    assert sizes.size == 2048 and sizes.max() == 300 and sizes.min() >= 1
    assert int(rpt[-1]) == round(3.1 * 2048)
    rows = np.repeat(np.arange(sizes.size), sizes)
    key = rows.astype(np.int64) * cfg["cols"] + col
    assert (np.diff(key) > 0).all()              # sorted, no duplicate
    assert ((col >= 0) & (col < cfg["cols"])).all()
    other = powerlaw.structure(dict(cfg, name="other"))
    assert not np.array_equal(other[1], col)


def test_in_degree_follows_out_degree():
    """Pages with more links are linked to more: the products per link
    run well above the mean row, as in a web crawl."""
    rpt, col = powerlaw.structure(_config(**TINY))
    sizes = np.diff(rpt)
    indeg = np.bincount(col, minlength=sizes.size)
    assert np.corrcoef(indeg, sizes)[0, 1] > 0.3
    assert work.n_prod(rpt, col, rpt) / col.size > 2 * sizes.mean()


def test_a_tiny_powerlaw_run_is_correct(tiny_root, on_cpu, capsys):
    assert on_cpu.main(["--workload", "webbase-1M-tiny.hash-repeat",
                        "--seed", str(2**31 + 9), "--seconds", "1"],
                       root=tiny_root) == 0
    captured = capsys.readouterr()
    line = last_json(captured.out)
    assert line["correct"] is True and line["failed"] == 0
    assert line["attempted"] >= 1
    assert set(line["metrics"]) == {"gflops", "latency_p50_s",
                                    "peak_hbm_gb", "setup_s"}
    window, = [json.loads(x.split(": ", 1)[1]) for x in
               captured.out.splitlines() if x.startswith("chipbench window")]
    assert window["retraces"] == 0 and window["compiles"] == 0
    assert window["steps_calls"] == 0 and window["capacity_grows"] == 0
    assert len(window["sym_bin_rows"]) == 9
    assert sum(window["sym_bin_rows"]) == 2048


@pytest.mark.parametrize("make_fault,caught_by", [
    (lambda: _alter_value, "value_err"),
    (lambda: _alter_column, "col_off"),
    (lambda: _drop_half_the_rows, "rpt_off"),
    (_stale_answer, "value_err"),
])
def test_a_broken_powerlaw_product_is_not_correct(
        tiny_root, on_cpu, capsys, monkeypatch, make_fault, caught_by):
    """The faults of ``test_faults`` make the tiny power-law cell's run
    read ``correct: false`` too, on the hash path through every rung."""
    fault = make_fault()
    on_cpu.import_program(tiny_root)
    from repro.engine.executor import SpgemmEngine
    finalize = SpgemmEngine._finalize_record

    def broken(self, rec):
        result = finalize(self, rec)
        result.C = fault(result.C)
        return result

    monkeypatch.setattr(SpgemmEngine, "_finalize_record", broken)
    assert on_cpu.main(["--workload", "webbase-1M-tiny.hash-repeat",
                        "--seed", str(2**31 + 9), "--seconds", "1"],
                       root=tiny_root) == 0
    line = last_json(capsys.readouterr().out)
    assert line["correct"] is False
    c = line["checks"][caught_by]
    assert c["value"] is None or c["value"] > c["limit"]


def _run(op_s, products):
    summary = tracing.Summary(window_s=10.0, busy_s=9.0, chips=1,
                              op_s=op_s, gaps=[])
    return types.SimpleNamespace(summary=summary, products=products,
                                 least_s=1e-3)


def test_upper_rung_kernel_s_reads_only_the_upper_rungs():
    """Kernels named for tables of 4,096 slots or more count; smaller
    rungs, kernels named otherwise and other ops with such a name do
    not."""
    op_s = {("%opsparse_hash_t24576.3", "tpu_custom_call"): 2.0,
            ("%opsparse_hash_t4096.1", "tpu_custom_call"): 1.0,
            ("%opsparse_hash_t2048.2", "tpu_custom_call"): 5.0,
            ("%opsparse_hash_t32", "tpu_custom_call"): 4.0,
            ("%fused_bin_call.6", "tpu_custom_call"): 7.0,
            ("%opsparse_hash_t8192.fusion", "fusion"): 3.0}
    assert upper_rung_kernel_s.read(_run(op_s, 2)) == pytest.approx(1.5)
    lower = {k: v for k, v in op_s.items()
             if (upper_rung_kernel_s.table_size(k[0]) or 0) < 4096}
    assert upper_rung_kernel_s.read(_run(lower, 2)) is None
    assert upper_rung_kernel_s.read(_run(op_s, 0)) is None
    assert upper_rung_kernel_s.table_size("%opsparse_hash_t12288.7") == 12288
    assert upper_rung_kernel_s.table_size("%fused_bin_call.6") is None
