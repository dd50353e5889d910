"""``chip_smoke.py`` phases at a tiny size on the CPU.

The chip-only checks (compiled kernels, ``tpu_custom_call``) are stubbed
here, inside the test; everything else — generation, the service path,
the scipy reference, the retrace and step-path counters, the sharded
mesh phase — runs as it does on the chip.  This catches wrong paths and
arguments before a chip run is spent on them.
"""
import importlib.util
import json
import zlib
from pathlib import Path

import numpy as np
import pytest

ROOT = Path(__file__).resolve().parent.parent
TINY = 4096      # benchmarks.matrices scale: 256-row analogs
# crc32 of the TINY cage12 analog's rpt, col and val bytes.
PINNED_CAGE12_CRC = 3692247490


@pytest.fixture(scope="module")
def smoke():
    spec = importlib.util.spec_from_file_location("chip_smoke",
                                                  ROOT / "chip_smoke.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    mod._import_repo()
    return mod


def _phase_lines(out: str, kind: str):
    prefix = f"smoke {kind}: "
    return [json.loads(line[len(prefix):]) for line in out.splitlines()
            if line.startswith(prefix)]


def test_smoke_single_chip_phases_tiny(smoke, monkeypatch, capsys):
    seen = []
    monkeypatch.setattr(
        smoke, "chip_checks",
        lambda method, entry, A: seen.append(
            (method, smoke.lowered_text(entry, A))))
    smoke.run_single(scale=TINY, repeats=2)
    phases = _phase_lines(capsys.readouterr().out, "phase")
    assert [(p["matrix"], p["method"]) for p in phases] == [
        (m, meth) for m in smoke.MATRICES for meth in smoke.METHODS]
    for p in phases:
        assert p["retraces"] == 0 and p["steps_calls"] == 1
        assert p["hot_calls"] == 2 and len(p["steady_s"]) == 1
        assert p["nnz"] > 0 and p["n_prod"] >= p["nnz"]
    assert [m for m, _ in seen] == list(smoke.METHODS) * 2
    assert all(text for _, text in seen)


def test_smoke_sharded_phase_tiny(smoke, capsys):
    smoke.run_sharded(scale=TINY, shards=4)
    (line,) = _phase_lines(capsys.readouterr().out, "sharded")
    assert line["shards"] == 4 and len(line["shard_devices"]) == 4


def test_smoke_check_product_rejects_wrong_values(smoke):
    from benchmarks.matrices import TABLE3, generate
    from repro.core import spgemm
    A = generate(next(s for s in TABLE3 if s.name == "cage12"), scale=TINY)
    ref = smoke.reference(A)
    C = spgemm(A, A).C
    assert smoke.check_product(C, ref) == ref[1].nnz
    bad = C.__class__(rpt=C.rpt, col=C.col, val=C.val * (1 + 1e-4),
                      shape=C.shape)
    with pytest.raises(smoke.SmokeFailure, match="values"):
        smoke.check_product(bad, ref)


def test_smoke_refuses_without_tpu(smoke, capsys):
    with pytest.raises(SystemExit) as exc:
        smoke.main([])
    assert exc.value.code not in (0, None)
    assert capsys.readouterr().out == ""      # no work, no result line


def test_table3_matrix_is_the_same_in_every_process(smoke):
    """The generator's seed is a stable hash of the matrix name, so every
    process builds the same A (pinned checksum)."""
    from benchmarks.matrices import TABLE3, generate
    A = generate(next(s for s in TABLE3 if s.name == "cage12"), scale=TINY)
    nnz = int(A.rpt[-1])
    digest = zlib.crc32(np.asarray(A.rpt).tobytes())
    digest = zlib.crc32(np.asarray(A.col)[:nnz].tobytes(), digest)
    digest = zlib.crc32(np.asarray(A.val)[:nnz].tobytes(), digest)
    assert (A.nrows, nnz) == (256, 3802)
    assert digest == PINNED_CAGE12_CRC

