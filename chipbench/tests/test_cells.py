"""A cell found by name in data files alone, run end to end on the CPU
at a tiny size, prints the contract's lines."""
import json

import pytest

from .conftest import ROOT, last_json


def test_load_cell_from_data_files(tiny_root):
    from chipbench import harness
    cell = harness.load_cell(tiny_root, "cage12-tiny.hash-repeat")
    assert cell.chips == 1 and cell.traffic["method"] == "hash"
    assert cell.config["rows"] == 512
    assert [m["name"] for m in cell.end_to_end] == [
        "gflops", "latency_p50_s", "peak_hbm_gb", "setup_s"]
    assert "hash_kernel_s" in [m["name"] for m in cell.per_layer]
    esc = harness.load_cell(tiny_root, "cage12-tiny.esc-repeat")
    assert not {"hash_kernel_s", "sort_s"} & {m["name"]
                                              for m in esc.per_layer}
    with pytest.raises(SystemExit):
        harness.load_cell(tiny_root, "no-such.cell")


def test_every_cell_of_the_benchmark_loads():
    from chipbench import harness
    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    for w in bench["workloads"]:
        cell = harness.load_cell(ROOT, w["name"])
        harness.plugin("generators", cell.config["generator"])
        harness.plugin("drivers", cell.traffic["driver"])
        for m in cell.per_layer:
            assert callable(harness.plugin("metrics", m["name"]).read)
        assert cell.config["limits"]["value_err"] > 0


@pytest.mark.parametrize("cell", ["cage12-tiny.hash-repeat",
                                  "cage12-tiny.esc-repeat"])
def test_a_tiny_run_is_correct(tiny_root, on_cpu, capsys, cell):
    assert on_cpu.main(["--workload", cell, "--seed", str(2**31 + 5),
                        "--seconds", "1"], root=tiny_root) == 0
    captured = capsys.readouterr()
    line = last_json(captured.out)
    assert list(line) == ["correct", "attempted", "failed", "metrics",
                          "device", "checks"]
    assert line["correct"] is True and line["failed"] == 0
    assert line["attempted"] >= 1
    assert set(line["metrics"]) == {"gflops", "latency_p50_s",
                                    "peak_hbm_gb", "setup_s"}
    assert all(m["value"] > 0 for k, m in line["metrics"].items()
               if k != "peak_hbm_gb")            # the CPU reports no peak
    assert line["device"]["platform"] == "cpu"
    window = [json.loads(x.split(": ", 1)[1]) for x in
              captured.out.splitlines() if x.startswith("chipbench window")]
    assert window[0]["retraces"] == 0 and window[0]["compiles"] == 0
    assert window[0]["steps_calls"] == 0
    ref = [json.loads(x.split(": ", 1)[1]) for x in
           captured.out.splitlines() if x.startswith("chipbench reference")]
    assert ref[0]["nnz_c"] == ref[0]["paper_nnz_c"]
    err = captured.err.strip().splitlines()
    assert err[-len(line["checks"]):] == [
        f"check {k}: {c['value']} (limit {c['limit']})"
        for k, c in line["checks"].items()]
