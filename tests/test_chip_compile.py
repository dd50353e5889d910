"""Compile the engine's main-path programs for a described TPU v5e chip.

Nothing runs: the TPU compiler installed with JAX compiles for a chip
that is described, not attached, and refuses what the chip would refuse
(block shapes off the tiling, scalar loads from HBM, SMEM overflow).
Interpret mode accepts all of these, so the CPU parity tests cannot see
them.  The compiled text also shows the device scopes (``repro.phases``)
each op of a steady executable carries.  The topology is described inside a fixture, never at import: only
one process may load the TPU library at a time.
"""
import os
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import SingleDeviceSharding

from repro import phases
from repro.core import CSR, SpgemmConfig
from repro.core.binning_ranges import numeric_ladder, symbolic_ladder
from repro.engine.executor import (_build_fused_hash_executable,
                                   _build_hash_executable,
                                   _build_hot_executable)
from repro.engine.plan import HashSchedule, MatrixSig, plan as make_plan
from repro.kernels import spgemm_hash

# cage12 at the paper's full scale (benchmarks/matrices.py, scale=1).
ROWS = 130228
NNZ_CAP = 1 << 21       # 1.96M nonzeros
PROD_CAP = 1 << 26      # 29.6M intermediate products, with headroom
C_NNZ_CAP = 1 << 24
M_CAP = 1 << 17         # pow-2 row-count bucket of the largest rung


@pytest.fixture(scope="module")
def one_chip():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — any failure means "can't"
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    return SingleDeviceSharding(topo.devices[0])


def _arr(sharding, n, dtype=jnp.int32):
    return jax.ShapeDtypeStruct((n,), dtype, sharding=sharding)


def _csr(sharding):
    return CSR(rpt=_arr(sharding, ROWS + 1), col=_arr(sharding, NNZ_CAP),
               val=_arr(sharding, NNZ_CAP, jnp.float32), shape=(ROWS, ROWS))


_SYM, _NUM = symbolic_ladder(1.2), numeric_ladder(2.0)

# Row buckets of rungs 4-7 in webbase-1M's fused schedule at the engine's
# 2x headroom (3,662 / 1,880 / 555 / 328 rows;
# chipbench/configs/webbase-1M.json).
WEB_UPPER_BUCKETS = {4: 8192, 5: 4096, 6: 2048, 7: 1024}


def _custom_call_names(hlo):
    """The instruction names of the compiled Pallas kernels, without the
    ``.<n>`` XLA appends."""
    return [re.sub(r"\.\d+$", "", name) for name in re.findall(
        r"%([\w.-]+) = [^\n]*custom_call_target=\"tpu_custom_call\"", hlo)]


@pytest.mark.parametrize("kernel,t_size,pack,rows_cap", [
    # smallest (packed) rung at the full row bucket, and the top rung
    ("fused", _SYM.table_sizes[0], _SYM.rows_per_block[0], M_CAP),
    ("fused", _SYM.table_sizes[-1], 1, 8),
    ("symbolic", _SYM.table_sizes[0], _SYM.rows_per_block[0], M_CAP),
    ("symbolic", _SYM.table_sizes[-1], 1, 8),
    ("numeric", _NUM.table_sizes[0], 1, M_CAP),
    ("numeric", _NUM.table_sizes[-1], 1, 8),
] + [
    # rungs 4-7 (one row per grid step) at webbase-1M's row buckets
    ("fused", _SYM.table_sizes[b], 1, rows_cap)
    for b, rows_cap in WEB_UPPER_BUCKETS.items()
])
def test_hash_kernel_compiles_for_v5e(one_chip, kernel, t_size, pack,
                                      rows_cap):
    A = _csr(one_chip)
    rows, count = _arr(one_chip, rows_cap), _arr(one_chip, 1)
    kw = dict(t_size=t_size, rows_cap=rows_cap, interpret=False)

    def call(r, c, rpt, col, val):           # C = A @ A over one bin
        if kernel == "symbolic":
            return spgemm_hash.symbolic_bin_call(r, c, rpt, col, rpt, col,
                                                 pack=pack, **kw)
        if kernel == "fused":
            return spgemm_hash.fused_bin_call(r, c, rpt, col, val, rpt, col,
                                              val, pack=pack, **kw)
        return spgemm_hash.numeric_bin_call(r, c, rpt, col, val, rpt, col,
                                            val, single_access=True, **kw)

    compiled = jax.jit(call).lower(rows, count, A.rpt, A.col,
                                   A.val).compile()
    assert _custom_call_names(compiled.as_text()) == [
        spgemm_hash.kernel_name(t_size)]


def test_fused_executable_of_every_rung_compiles_for_v5e(one_chip):
    """The steady fused hash executable with all eight table rungs and
    the ESC fallback rung populated, as webbase-1M's schedule has them,
    compiles for the chip; each rung's custom call carries its table
    size in its instruction name, which a device trace shows as the op's
    name (what ``upper_rung_kernel_s`` reads)."""
    plan = _tiny_plan("hash")
    plan = plan.with_hash_schedule(HashSchedule(
        (32, 32) + (8,) * 6 + (16,), plan.hash_schedule.num_row_buckets,
        1 << 12))
    sig = plan.a_sig
    spec = plan.workspace_spec()
    A = CSR(rpt=_arr(one_chip, sig.nrows + 1),
            col=_arr(one_chip, sig.cap_bucket),
            val=_arr(one_chip, sig.cap_bucket, jnp.float32),
            shape=(sig.nrows, sig.ncols))
    hlo = _build_fused_hash_executable(plan).lower(
        A, A, _arr(one_chip, spec.i32_cells),
        _arr(one_chip, spec.val_cells, jnp.float32)).compile().as_text()
    assert sorted(_custom_call_names(hlo)) == sorted(
        spgemm_hash.kernel_name(t) for t in _SYM.table_sizes)
    assert "opsparse.fallback" in hlo


def test_esc_hot_executable_compiles_for_v5e(one_chip):
    sig = MatrixSig(nrows=ROWS, ncols=ROWS, cap_bucket=NNZ_CAP,
                    dtype="float32")
    plan = make_plan(sig, sig, SpgemmConfig()).with_capacities(
        PROD_CAP, C_NNZ_CAP)
    spec = plan.workspace_spec()
    A = _csr(one_chip)
    compiled = _build_hot_executable(plan).lower(
        A, A, _arr(one_chip, spec.i32_cells),
        _arr(one_chip, spec.val_cells, jnp.float32)).compile()
    mem = compiled.memory_analysis()
    assert mem.temp_size_in_bytes < 16 << 30       # fits one v5e's HBM


def test_off_path_kernels_compile_for_v5e(one_chip):
    """The binning histogram and BCSR SpMM kernels (tests only, off the
    engine path) compile too."""
    from repro.kernels.binning_pallas import binning_histogram
    from repro.kernels.bsr_spmm import bsr_spmm
    hist = jax.jit(lambda s: binning_histogram(
        s, upper=_SYM.upper, num_bins=_SYM.num_bins, interpret=False))
    assert "tpu_custom_call" in hist.lower(
        _arr(one_chip, 1000005)).compile().as_text()
    nnzb, bm, bk, n = 64, 128, 128, 256
    spmm = jax.jit(lambda r, c, b, d: bsr_spmm(r, c, b, d, n_block_rows=16,
                                               interpret=False))
    blocks = jax.ShapeDtypeStruct((nnzb, bm, bk), jnp.float32,
                                  sharding=one_chip)
    dense = jax.ShapeDtypeStruct((4 * bk, n), jnp.float32, sharding=one_chip)
    assert "tpu_custom_call" in spmm.lower(
        _arr(one_chip, nnzb), _arr(one_chip, nnzb), blocks,
        dense).compile().as_text()


def _tiny_plan(method, fuse_numeric=True):
    """A specialized plan at 256 rows whose schedule populates two table
    rungs and the ESC fallback rung."""
    sig = MatrixSig(nrows=256, ncols=256, cap_bucket=2048, dtype="float32")
    plan = make_plan(sig, sig, SpgemmConfig(
        method=method, fuse_numeric=fuse_numeric,
        interpret=False)).with_capacities(1 << 14, 1 << 13)
    if method != "hash":
        return plan
    sym = (32, 32) + (0,) * (plan.sym_ladder.num_bins - 3) + (16,)
    num = (32, 32) + (0,) * (plan.num_ladder.num_bins - 3) + (16,)
    return plan.with_hash_schedule(HashSchedule(sym, num, 1 << 12))


@pytest.mark.parametrize("builder,method,fused,must", [
    (_build_fused_hash_executable, "hash", True,
     {"hash.r0", "hash.r1", "fallback", "epilogue.r0", "epilogue.r1",
      "epilogue.fallback", "bin", "nprod"}),
    (_build_hash_executable, "hash", False,
     {"hash.r0", "hash.r1", "fallback", "epilogue.r0", "epilogue.r1",
      "epilogue.fallback", "bin", "nprod"}),
    (_build_hot_executable, "esc", True,
     {"esc.sort", "esc.compress", "bin", "nprod"}),
])
def test_steady_executables_scope_their_device_ops(one_chip, builder,
                                                   method, fused, must):
    """Compiled for the chip, every sort, scatter and Pallas kernel of a
    steady executable carries an ``opsparse.`` phase in its op_name, and
    each phase that holds such ops shows."""
    plan = _tiny_plan(method, fused)
    spec = plan.workspace_spec()
    sig = plan.a_sig
    A = CSR(rpt=_arr(one_chip, sig.nrows + 1),
            col=_arr(one_chip, sig.cap_bucket),
            val=_arr(one_chip, sig.cap_bucket, jnp.float32),
            shape=(sig.nrows, sig.ncols))
    args = (A, A) + ((_arr(one_chip, spec.i32_cells),
                      _arr(one_chip, spec.val_cells, jnp.float32))
                     if spec is not None else ())
    hlo = builder(plan).lower(*args).compile().as_text()
    seen, bare = set(), []
    for line in hlo.splitlines():
        op = re.search(r" (sort|scatter|custom-call)\(", line)
        if op is None or (op.group(1) == "custom-call"
                          and "tpu_custom_call" not in line):
            continue
        name = re.search(r'op_name="([^"]*)"', line)
        scopes = [p[len(phases.PREFIX):]
                  for p in (name.group(1) if name else "").split("/")
                  if p.startswith(phases.PREFIX)]
        if scopes:
            seen.add(scopes[-1])
        else:
            bare.append(line.strip()[:120])
    assert not bare, bare
    assert must <= seen, must - seen


def _scatter_updates(hlo, scope):
    """Update counts of the scatters whose innermost ``opsparse.`` scope
    is ``scope``, read off the shapes of their last operand."""
    shapes = dict(re.findall(r"(%[\w.-]+) = \w+\[([\d,]*)\]", hlo))
    counts = []
    for line in hlo.splitlines():
        op = re.search(r" scatter\(([^)]*)\)", line)
        name = re.search(r'op_name="([^"]*)"', line)
        if op is None or name is None:
            continue
        inner = [p for p in name.group(1).split("/")
                 if p.startswith(phases.PREFIX)]
        if inner and inner[-1] == phases.PREFIX + scope:
            dims = shapes[op.group(1).split(",")[-1].strip()]
            counts.append(int(np.prod([int(d) for d in dims.split(",")
                                       if d])))
    return counts


def test_fused_hash_gather_rung_compiles_for_v5e(one_chip):
    """The steady fused hash executable compiles for the chip at a
    schedule where rung 1's tables (32 rows x 512 slots) outnumber C's
    8192 positions, so its epilogue gathers: no scatter of its scope
    moves a slot per table entry, while rung 0 (32 x 128) still scatters
    every slot."""
    plan = _tiny_plan("hash")
    sig = plan.a_sig
    spec = plan.workspace_spec()
    A = CSR(rpt=_arr(one_chip, sig.nrows + 1),
            col=_arr(one_chip, sig.cap_bucket),
            val=_arr(one_chip, sig.cap_bucket, jnp.float32),
            shape=(sig.nrows, sig.ncols))
    args = (A, A, _arr(one_chip, spec.i32_cells),
            _arr(one_chip, spec.val_cells, jnp.float32))
    hlo = _build_fused_hash_executable(plan).lower(*args).compile().as_text()
    assert spgemm_hash.epilogue_gathers(32, 512, plan.nnz_bucket)
    assert not spgemm_hash.epilogue_gathers(32, 128, plan.nnz_bucket)
    gathering = _scatter_updates(hlo, "epilogue.r1")
    assert gathering and max(gathering) <= sig.nrows   # per row, not slot
    assert _scatter_updates(hlo, "epilogue.r0").count(32 * 128) == 2
    assert re.search(r" gather\(.*opsparse\.epilogue\.r1/", hlo)
