"""The benchmark's own tests, on the CPU at tiny sizes.

    python -m pytest chipbench/tests

``tiny_root`` lays out a checkout whose BENCHMARK.json names tiny copies
of the configurations (data files only) and points at the program's
``src``; ``on_cpu`` lets a run skip the harness's look for a chip.
"""
import json
import os
import sys
from pathlib import Path

os.environ.setdefault("JAX_PLATFORMS", "cpu")

import numpy as np  # noqa: E402
import pytest  # noqa: E402

ROOT = Path(__file__).resolve().parents[2]
if str(ROOT) not in sys.path:
    sys.path.insert(0, str(ROOT))

TINY_ROWS = {"cage12": 512}


def tiny_config(name: str) -> dict:
    """The configuration at a tiny size, its paper numbers replaced by
    the tiny matrix's own, so that its check still holds."""
    from chipbench import reference, work
    from chipbench.generators import table3
    cfg = json.loads((ROOT / "chipbench" / "configs" / f"{name}.json")
                     .read_text())
    rows = TINY_ROWS[name]
    cfg.update(name=f"{name}-tiny", rows=rows, cols=rows)
    rpt, col = table3.structure(cfg)
    P = reference.pattern(rpt, col, (rows, rows))
    cfg.update(avg_nnz_per_row=float(np.diff(rpt).mean()),
               max_nnz_per_row=int(np.diff(rpt).max()),
               paper_nprod=work.n_prod(rpt, col, rpt), paper_nnz_c=P.nnz)
    return cfg


@pytest.fixture
def tiny_root(tmp_path):
    """A checkout with tiny cells "<config>-tiny.<traffic>" of every
    cell of the real BENCHMARK.json, made of data files alone."""
    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    (tmp_path / "chipbench" / "configs").mkdir(parents=True)
    (tmp_path / "chipbench" / "traffic").mkdir()
    for c in bench["configs"]:
        cfg = tiny_config(c["name"])
        path = f"chipbench/configs/{cfg['name']}.json"
        (tmp_path / path).write_text(json.dumps(cfg))
        c.update(name=cfg["name"], file=path)
    for w in bench["workloads"]:
        traffic = ROOT / "chipbench" / "traffic" / f"{w['traffic']}.json"
        (tmp_path / "chipbench" / "traffic" / traffic.name).write_text(
            traffic.read_text())
        w["config"] += "-tiny"
        w["name"] = f"{w['config']}.{w['traffic']}"
    for m in bench["end_to_end"] + bench["per_layer"]:
        if "workloads" in m:
            m["workloads"] = [f"{w.split('.')[0]}-tiny.{w.split('.', 1)[1]}"
                              for w in m["workloads"]]
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(bench))
    (tmp_path / "src").symlink_to(ROOT / "src")
    return tmp_path


@pytest.fixture
def on_cpu(monkeypatch):
    """Skip the look for a TPU and give the CPU a peak table."""
    from chipbench import harness
    monkeypatch.setattr(harness, "require_accelerator",
                        lambda info, chips: None)
    monkeypatch.setattr(harness, "device_peaks",
                        lambda kind: {"peak_flops": 1e12,
                                      "hbm_bytes_per_s": 1e11,
                                      "hbm_bytes": 1e10})
    return harness


def last_json(out: str) -> dict:
    return json.loads(out.strip().splitlines()[-1])
