"""No TPU, or no program: a non-zero exit and no result."""
import os
import shutil
import subprocess
import sys

import pytest

from .conftest import ROOT


def test_refuses_without_a_tpu(capsys):
    from chipbench import harness
    with pytest.raises(SystemExit) as exc:
        harness.main(["--workload", "cage12.hash-repeat", "--seed", "1",
                      "--seconds", "1"])
    assert exc.value.code not in (0, None)
    assert capsys.readouterr().out == ""


def test_refuses_too_few_chips():
    from chipbench import harness
    with pytest.raises(SystemExit):
        harness.require_accelerator({"platform": "tpu", "count": 1}, 4)
    harness.require_accelerator({"platform": "tpu", "count": 4}, 4)


def test_unknown_device_has_no_peaks():
    from chipbench import harness
    assert harness.device_peaks("TPU v5 lite")["hbm_bytes_per_s"] == 819e9
    with pytest.raises(SystemExit):
        harness.device_peaks("cpu")


def test_benchmark_alone_refuses(tmp_path):
    """A directory with BENCHMARK.json and chipbench/ only: no program."""
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path)
    shutil.copytree(ROOT / "chipbench", tmp_path / "chipbench",
                    ignore=shutil.ignore_patterns("__pycache__", "tests"))
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run([sys.executable, "chipbench/run.py", "--workload",
                        "cage12.hash-repeat", "--seed", "1", "--seconds",
                        "1", "--trace", "0"], cwd=tmp_path, env=env,
                       capture_output=True, text=True, timeout=300)
    assert p.returncode != 0 and p.stdout == ""


def test_missing_program_refuses(tmp_path):
    from chipbench import harness
    with pytest.raises(SystemExit, match="program is missing"):
        saved = list(sys.path)
        mods = {k: v for k, v in sys.modules.items()
                if k == "repro" or k.startswith("repro.")}
        try:
            sys.path[:] = [p for p in sys.path if not p.endswith("/src")]
            for k in mods:
                del sys.modules[k]
            harness.import_program(tmp_path)
        finally:
            sys.path[:] = saved
            sys.modules.update(mods)
