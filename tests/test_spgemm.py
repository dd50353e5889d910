import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import (CSR, SpgemmConfig, compression_ratio, random_csr,
                        spgemm)


def _pair(seed, m=48, k=40, n=56, da=4.0, db=3.0, dist="uniform"):
    A = random_csr(jax.random.PRNGKey(seed), m, k, avg_nnz_per_row=da,
                   distribution=dist)
    B = random_csr(jax.random.PRNGKey(seed + 1), k, n, avg_nnz_per_row=db,
                   distribution=dist)
    return A, B


@pytest.mark.parametrize("method", ["esc", "hash"])
@pytest.mark.parametrize("dist", ["uniform", "powerlaw", "banded"])
def test_spgemm_matches_dense(method, dist):
    A, B = _pair(7, dist=dist)
    res = spgemm(A, B, SpgemmConfig(method=method))
    ref = np.asarray(A.to_dense()) @ np.asarray(B.to_dense())
    np.testing.assert_allclose(np.asarray(res.C.to_dense()), ref,
                               rtol=1e-5, atol=1e-5)


def test_two_phase_nnz_exact():
    A, B = _pair(11)
    res = spgemm(A, B)
    dense = np.asarray(A.to_dense()) @ np.asarray(B.to_dense())
    a = np.asarray(A.to_dense()) != 0
    b = np.asarray(B.to_dense()) != 0
    support = (a.astype(np.int64) @ b.astype(np.int64)) > 0
    assert res.total_nnz == support.sum()
    rpt = np.asarray(res.C.rpt)
    np.testing.assert_array_equal(rpt[1:] - rpt[:-1], support.sum(axis=1))


def test_output_rows_sorted_by_column():
    A, B = _pair(13, dist="powerlaw")
    res = spgemm(A, B)
    rpt, col = np.asarray(res.C.rpt), np.asarray(res.C.col)
    for i in range(A.nrows):
        seg = col[rpt[i]:rpt[i + 1]]
        assert (np.diff(seg) > 0).all()


def test_fused_esc_equals_two_phase():
    A, B = _pair(17)
    r1 = spgemm(A, B, SpgemmConfig(method="esc"))
    r2 = spgemm(A, B, SpgemmConfig(method="esc", fuse_esc=True))
    np.testing.assert_allclose(np.asarray(r1.C.to_dense()),
                               np.asarray(r2.C.to_dense()), rtol=1e-6)
    np.testing.assert_array_equal(np.asarray(r1.C.rpt), np.asarray(r2.C.rpt))


def test_hash_equals_esc():
    A, B = _pair(19, dist="powerlaw")
    r1 = spgemm(A, B, SpgemmConfig(method="esc"))
    r2 = spgemm(A, B, SpgemmConfig(method="hash"))
    np.testing.assert_array_equal(np.asarray(r1.C.rpt), np.asarray(r2.C.rpt))
    np.testing.assert_allclose(np.asarray(r1.C.to_dense()),
                               np.asarray(r2.C.to_dense()), rtol=1e-5,
                               atol=1e-6)


def test_matrix_square():
    """The paper's benchmark is A^2 — exercise the square path."""
    A = random_csr(jax.random.PRNGKey(3), 60, 60, avg_nnz_per_row=4.0)
    res = spgemm(A, A)
    ref = np.asarray(A.to_dense())
    np.testing.assert_allclose(np.asarray(res.C.to_dense()), ref @ ref,
                               rtol=1e-5, atol=1e-5)
    cr = compression_ratio(A, A, res.C)
    assert cr >= 1.0


def test_empty_result():
    # A's columns only hit empty rows of B.
    a = np.zeros((4, 4), np.float32)
    a[0, 3] = 1.0
    b = np.zeros((4, 4), np.float32)
    b[0, 0] = 1.0  # row 3 of B is empty
    A, B = CSR.from_dense(a), CSR.from_dense(b)
    res = spgemm(A, B)
    assert res.total_nnz == 0
    np.testing.assert_allclose(np.asarray(res.C.to_dense()), a @ b)


def test_duplicate_accumulation_correctness():
    """Rows of A with repeated columns hitting the same B row must sum."""
    a = np.array([[2.0, 3.0], [1.0, 0.0]], np.float32)
    b = np.array([[1.0, 4.0], [1.0, 4.0]], np.float32)
    A, B = CSR.from_dense(a), CSR.from_dense(b)
    res = spgemm(A, B)
    np.testing.assert_allclose(np.asarray(res.C.to_dense()), a @ b)
    assert res.total_nprod == 3 * 2  # 3 A entries x 2-entry B rows
    assert res.total_nnz == 4
    assert res.compression_ratio == pytest.approx(1.5)


def test_timing_instrumentation():
    A, B = _pair(23)
    res = spgemm(A, B, SpgemmConfig(timing=True))
    for step in ("nprod", "bin", "symbolic", "rowptr", "numeric"):
        assert step in res.timings


def test_rectangular_shapes():
    A, B = _pair(29, m=10, k=64, n=7, da=6.0, db=2.0)
    res = spgemm(A, B)
    ref = np.asarray(A.to_dense()) @ np.asarray(B.to_dense())
    np.testing.assert_allclose(np.asarray(res.C.to_dense()), ref, rtol=1e-5,
                               atol=1e-5)
