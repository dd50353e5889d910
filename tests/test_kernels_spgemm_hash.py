"""Per-kernel sweeps: Pallas hash kernels vs the pure-jnp oracle."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import (bin_rows_for_ladder, next_bucket, nprod_into_rpt,
                        random_csr, esc)
from repro.core.analysis import exclusive_sum_in_place
from repro.core.binning_ranges import make_ladder, numeric_ladder, symbolic_ladder
from repro.kernels import ref as kref
from repro.kernels import spgemm_hash


def _pair(seed, m, k, n, da, db, dist="uniform", dtype=jnp.float32):
    A = random_csr(jax.random.PRNGKey(seed), m, k, avg_nnz_per_row=da,
                   distribution=dist, dtype=dtype)
    B = random_csr(jax.random.PRNGKey(seed + 100), k, n, avg_nnz_per_row=db,
                   distribution=dist, dtype=dtype)
    return A, B


@pytest.mark.parametrize("shape", [(16, 16, 16, 2.0, 2.0),
                                   (48, 32, 64, 4.0, 3.0),
                                   (9, 130, 7, 8.0, 1.5),
                                   (64, 64, 64, 6.0, 6.0)])
@pytest.mark.parametrize("single_access", [True, False])
def test_symbolic_kernel_sweep(shape, single_access):
    m, k, n, da, db = shape
    A, B = _pair(int(m + n), m, k, n, da, db)
    nprod = nprod_into_rpt(A, B)[:m]
    lad = symbolic_ladder(1.2)
    bn = bin_rows_for_ladder(nprod, lad)
    nnz = spgemm_hash.symbolic_binned(A, B, bn, lad, prod_capacity=1,
                                      single_access=single_access)
    expect = kref.row_nnz_from_support(A, B)
    np.testing.assert_array_equal(np.asarray(nnz[:m]), expect)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.float64])
@pytest.mark.parametrize("single_access", [True, False])
def test_numeric_kernel_sweep(dtype, single_access):
    if dtype == jnp.float64 and not jax.config.jax_enable_x64:
        dtype = jnp.float32  # x64 disabled: exercise the f32 path twice
    m, k, n = 40, 48, 36
    A, B = _pair(5, m, k, n, 5.0, 4.0, dtype=dtype)
    ref = np.asarray(A.to_dense()) @ np.asarray(B.to_dense())
    nnz_buf = esc.symbolic(A, B, prod_capacity=next_bucket(4096))
    rpt = exclusive_sum_in_place(nnz_buf)
    cap = next_bucket(int(rpt[-1]))
    lad = numeric_ladder(2.0)
    bn = bin_rows_for_ladder(nnz_buf[:m], lad)
    C = spgemm_hash.numeric_binned(A, B, rpt, bn, lad, prod_capacity=1,
                                   nnz_capacity=cap,
                                   single_access=single_access)
    np.testing.assert_allclose(np.asarray(C.to_dense()), ref, rtol=1e-5,
                               atol=1e-5)


def test_tiny_ladder_forces_every_rung():
    """Tiny tables force multi-rung + fallback coverage in one matrix."""
    m = 96
    A, B = _pair(9, m, 200, 150, 10.0, 8.0, dist="powerlaw")
    nprod = nprod_into_rpt(A, B)[:m]
    lad = make_ladder((32, 64, 128), 1.2, (32, 64, 128))
    bn = bin_rows_for_ladder(nprod, lad)
    sizes = np.asarray(bn.bin_size)
    assert (sizes > 0).sum() >= 2, sizes  # at least two rungs exercised
    nnz = spgemm_hash.symbolic_binned(A, B, bn, lad, prod_capacity=1)
    np.testing.assert_array_equal(np.asarray(nnz[:m]),
                                  kref.row_nnz_from_support(A, B))


def test_single_access_reduces_transactions():
    """Fig. 9's mechanism: single-access must strictly reduce table
    transactions whenever any insert happens."""
    m = 64
    A, B = _pair(21, m, 80, 90, 6.0, 5.0)
    nprod = nprod_into_rpt(A, B)[:m]
    lad = symbolic_ladder(1.2)
    bn = bin_rows_for_ladder(nprod, lad)
    _, acc_single = spgemm_hash.symbolic_binned(
        A, B, bn, lad, prod_capacity=1, single_access=True,
        collect_accesses=True)
    _, acc_multi = spgemm_hash.symbolic_binned(
        A, B, bn, lad, prod_capacity=1, single_access=False,
        collect_accesses=True)
    assert int(acc_single) < int(acc_multi)


def test_pow2_and_mod_hash_paths():
    """Symbolic rungs are pow2 (AND-mask), numeric rungs are non-pow2
    (mod) — both must agree with the oracle (paper §5.2 last paragraph)."""
    from repro.kernels.spgemm_hash import _hash_init, _hash_next, _is_pow2
    assert _is_pow2(512) and not _is_pow2(511)
    for t in (512, 511):
        h = _hash_init(jnp.int32(12345), t)
        assert 0 <= int(h) < t
        h2 = _hash_next(jnp.int32(t - 1), t)
        assert int(h2) == 0


def test_scheduled_symbolic_matches_oracle_under_jit():
    """Tentpole regression: the schedule-driven symbolic phase must trace
    cleanly (zero host syncs) and agree with the oracle on a mixed bin
    ladder that populates several rungs AND the ESC fallback rung."""
    m = 96
    A, B = _pair(9, m, 200, 150, 10.0, 8.0, dist="powerlaw")
    nprod = nprod_into_rpt(A, B)[:m]
    lad = make_ladder((32, 64, 128), 1.2, (32, 64, 128))
    bn = bin_rows_for_ladder(nprod, lad)
    row_buckets, fall_cap = spgemm_hash.host_schedule(A, B, bn, lad)
    assert row_buckets[-1] > 0 and fall_cap > 0   # fallback rung exercised

    @jax.jit
    def sym(A, B, bn):
        return spgemm_hash.symbolic_scheduled(
            A, B, bn, lad, row_buckets=row_buckets,
            fallback_prod_capacity=fall_cap)

    nnz_buf, sub_prod, _ = sym(A, B, bn)
    np.testing.assert_array_equal(np.asarray(nnz_buf[:m]),
                                  kref.row_nnz_from_support(A, B))
    assert 0 < int(sub_prod) <= fall_cap


def test_scheduled_pipeline_hash_vs_esc_parity_under_jit():
    """hash-vs-ESC oracle parity with BOTH phases jitted end-to-end on
    tiny mixed ladders (multi-rung + fallback in each phase)."""
    m, k, n = 80, 160, 120
    A, B = _pair(17, m, k, n, 9.0, 7.0, dist="powerlaw")
    sym_lad = make_ladder((32, 64, 128), 1.2, (32, 64, 128))
    num_lad = make_ladder((32, 64, 128), 2.0, (31, 63, 127))

    nprod = nprod_into_rpt(A, B)[:m]
    sym_bn = bin_rows_for_ladder(nprod, sym_lad)
    sym_buckets, sym_fall = spgemm_hash.host_schedule(A, B, sym_bn, sym_lad)
    # Derive the numeric schedule from the (oracle) symbolic result so the
    # jitted pipeline below is schedule-static, like the engine's hot path.
    nnz_oracle = esc.symbolic(A, B, prod_capacity=next_bucket(8192))
    num_bn = bin_rows_for_ladder(nnz_oracle[:m], num_lad)
    num_buckets, num_fall = spgemm_hash.host_schedule(A, B, num_bn, num_lad)
    nnz_cap = next_bucket(int(nnz_oracle.sum()))

    @jax.jit
    def pipeline(A, B):
        nnz_buf, _, _ = spgemm_hash.symbolic_scheduled(
            A, B, bin_rows_for_ladder(nprod_into_rpt(A, B)[:m], sym_lad,
                                      allow_fast_path=False),
            sym_lad, row_buckets=sym_buckets,
            fallback_prod_capacity=sym_fall)
        rpt = exclusive_sum_in_place(nnz_buf)
        num_bn = bin_rows_for_ladder(nnz_buf[:m], num_lad,
                                     allow_fast_path=False)
        C, _, _ = spgemm_hash.numeric_scheduled(
            A, B, rpt, num_bn, num_lad, row_buckets=num_buckets,
            nnz_capacity=nnz_cap, fallback_prod_capacity=num_fall)
        return C

    C = pipeline(A, B)
    esc_C = esc.spgemm_fused(A, B, prod_capacity=next_bucket(8192),
                             nnz_capacity=nnz_cap)
    ref = np.asarray(A.to_dense()) @ np.asarray(B.to_dense())
    np.testing.assert_allclose(np.asarray(C.to_dense()), ref,
                               rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(np.asarray(C.to_dense()),
                               np.asarray(esc_C.to_dense()),
                               rtol=1e-5, atol=1e-5)


def test_host_schedule_headroom_and_caps():
    """Learned buckets honor headroom, the pow-2 floor, and the row cap."""
    m = 64
    A, B = _pair(3, m, 64, 64, 4.0, 4.0)
    nprod = nprod_into_rpt(A, B)[:m]
    lad = symbolic_ladder(1.2)
    bn = bin_rows_for_ladder(nprod, lad)
    exact, _ = spgemm_hash.host_schedule(A, B, bn, lad)
    padded, _ = spgemm_hash.host_schedule(A, B, bn, lad, headroom=2.0)
    sizes = np.asarray(bn.bin_size)
    m_cap = next_bucket(m, minimum=8)
    for s, e, p in zip(sizes, exact, padded):
        if not s:
            assert e == 0 and p == 0
            continue
        assert e >= int(s) and e & (e - 1) == 0      # pow-2, admits count
        assert p >= min(m_cap, 2 * int(s)) and p <= m_cap


@pytest.mark.parametrize("dist", ["uniform", "powerlaw"])
def test_packed_symbolic_matches_unpacked(dist):
    """Row packing on the STANDALONE symbolic kernel (paper opt. 3): the
    packed launch — several pow-2 sub-tables per VMEM tile — must agree
    bitwise with the unpacked kernel and the oracle, on a tiny ladder
    whose small rungs actually pack (rows_per_block > 1)."""
    m = 96
    A, B = _pair(29, m, 160, 120, 8.0, 6.0, dist=dist)
    nprod = nprod_into_rpt(A, B)[:m]
    lad = make_ladder((32, 64, 128), 1.2, (32, 64, 128))
    assert max(lad.rows_per_block) > 1      # packing actually engages
    bn = bin_rows_for_ladder(nprod, lad)
    packed = spgemm_hash.symbolic_binned(A, B, bn, lad, prod_capacity=1,
                                         row_packing=True)
    unpacked = spgemm_hash.symbolic_binned(A, B, bn, lad, prod_capacity=1,
                                           row_packing=False)
    np.testing.assert_array_equal(np.asarray(packed), np.asarray(unpacked))
    np.testing.assert_array_equal(np.asarray(packed[:m]),
                                  kref.row_nnz_from_support(A, B))


def test_packed_scheduled_symbolic_under_jit():
    """Schedule-driven packed symbolic (the engine's two-pass hot path
    form) traces cleanly and matches the oracle; buckets are floored to
    whole packs so every rung divides into grid steps."""
    m = 96
    A, B = _pair(9, m, 200, 150, 10.0, 8.0, dist="powerlaw")
    nprod = nprod_into_rpt(A, B)[:m]
    lad = make_ladder((32, 64, 128), 1.2, (32, 64, 128))
    bn = bin_rows_for_ladder(nprod, lad)
    row_buckets, fall_cap = spgemm_hash.host_schedule(
        A, B, bn, lad, packs=lad.rows_per_block)
    for b, cap in enumerate(row_buckets):
        if cap and b < len(lad.rows_per_block):
            assert cap % lad.rows_per_block[b] == 0

    @jax.jit
    def sym(A, B, bn):
        return spgemm_hash.symbolic_scheduled(
            A, B, bn, lad, row_buckets=row_buckets,
            fallback_prod_capacity=fall_cap, row_packing=True)

    nnz_buf, _, _ = sym(A, B, bn)
    np.testing.assert_array_equal(np.asarray(nnz_buf[:m]),
                                  kref.row_nnz_from_support(A, B))


def test_numeric_epilogue_sorted_and_complete():
    m, k, n = 32, 32, 32
    A, B = _pair(33, m, k, n, 4.0, 4.0)
    nnz_buf = esc.symbolic(A, B, prod_capacity=2048)
    rpt = exclusive_sum_in_place(nnz_buf)
    cap = next_bucket(int(rpt[-1]))
    lad = numeric_ladder(2.0)
    bn = bin_rows_for_ladder(nnz_buf[:m], lad)
    C = spgemm_hash.numeric_binned(A, B, rpt, bn, lad, prod_capacity=1,
                                   nnz_capacity=cap)
    rptn, coln = np.asarray(C.rpt), np.asarray(C.col)
    for i in range(m):
        seg = coln[rptn[i]:rptn[i + 1]]
        assert (np.diff(seg) > 0).all()


def test_nprod_of_rows_matches_the_row_sums():
    """n_prod of chosen rows (repeats and the last row among them; an
    id past A's last row reads that row) equals the sum of B's row sizes
    over each row's entries, on padded storage."""
    A, B = _pair(11, 40, 30, 30, 3.0, 4.0, dist="powerlaw")
    A = A.with_capacity(next_bucket(A.capacity + 100))
    rpt, col = np.asarray(A.rpt), np.asarray(A.col)
    b_sizes = np.diff(np.asarray(B.rpt))
    want = np.array([b_sizes[col[rpt[r]:rpt[r + 1]]].sum()
                     for r in range(40)])
    rows = jnp.asarray([0, 39, 7, 7, 40, 3] + list(range(40)), jnp.int32)
    got = np.asarray(jax.jit(spgemm_hash.nprod_of_rows)(A, B, rows))
    np.testing.assert_array_equal(
        got, want[np.minimum(np.asarray(rows), 39)])
