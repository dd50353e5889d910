"""Compile the engine's main-path programs for a described TPU v5e chip.

Nothing runs: the TPU compiler installed with JAX compiles for a chip
that is described, not attached, and refuses what the chip would refuse
(block shapes off the tiling, scalar loads from HBM, SMEM overflow).
Interpret mode accepts all of these, so the CPU parity tests cannot see
them.  The topology is described inside a fixture, never at import: only
one process may load the TPU library at a time.
"""
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.core import CSR, SpgemmConfig
from repro.core.binning_ranges import numeric_ladder, symbolic_ladder
from repro.engine.executor import _build_hot_executable
from repro.engine.plan import MatrixSig, plan as make_plan
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


@pytest.mark.parametrize("kernel,t_size,pack,rows_cap", [
    # smallest (packed) rung at the full row bucket, and the top rung
    ("fused", _SYM.table_sizes[0], _SYM.rows_per_block[0], M_CAP),
    ("fused", _SYM.table_sizes[-1], 1, 8),
    ("symbolic", _SYM.table_sizes[0], _SYM.rows_per_block[0], M_CAP),
    ("symbolic", _SYM.table_sizes[-1], 1, 8),
    ("numeric", _NUM.table_sizes[0], 1, M_CAP),
    ("numeric", _NUM.table_sizes[-1], 1, 8),
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
    assert "tpu_custom_call" in compiled.as_text()


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
