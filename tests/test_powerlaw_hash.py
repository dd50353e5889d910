"""A power-law web graph through ``SpgemmService`` on the CPU: every hash
rung and the ESC fallback rung do work, and the answers match a dense
product.

The matrix is the benchmark's webbase-1M family
(``chipbench/generators/powerlaw.py``) at 4,096 rows.  A row reaches the
fallback rung only past 20,480 products, so A needs more nonzeros than
that: the case raises the mean row to 10 and the largest to 2,000, and
draws fewer bundles of links by out-degree (0.3) to keep the products
near 1.3M.  Its name fixes a structure that fills all nine rungs.

Small hand-made matrices check the product counters on both hash paths,
and that a fused plan sizes its fallback expansion by the symbolic
ladder alone (webbase-1M's numeric fallback rung expands 16x more).
"""
import json
import sys
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import CSR, SpgemmConfig
from repro.core.binning_ranges import symbolic_ladder
from repro.engine import SpgemmEngine
from repro.serve import SpgemmService

ROOT = Path(__file__).resolve().parents[1]
if str(ROOT) not in sys.path:
    sys.path.insert(0, str(ROOT))

from chipbench.generators import powerlaw  # noqa: E402

U = 2.0 ** -24           # float32's unit roundoff


def _config():
    cfg = json.loads((ROOT / "chipbench" / "configs" / "webbase-1M.json")
                     .read_text())
    cfg.update(name="webbase-tiny-8", rows=4096, cols=4096,
               avg_nnz_per_row=10, max_nnz_per_row=2000)
    cfg["powerlaw"] = dict(cfg["powerlaw"], by_degree=0.3)
    return cfg


@pytest.fixture(scope="module")
def web():
    """A, its rows' products and rung, and the dense references: A·A,
    |A|·|A| (no cancellation: its nonzeros are C's structure) and the
    number of products summed into each entry."""
    rpt, col = powerlaw.structure(_config())
    m = rpt.size - 1
    val = np.random.default_rng(8).standard_normal(col.size).astype(
        np.float32)
    A = CSR(rpt=jnp.asarray(rpt), col=jnp.asarray(col),
            val=jnp.asarray(val), shape=(m, m))
    sizes = np.diff(rpt).astype(np.int64)
    row_prod = np.add.reduceat(sizes[col], rpt[:-1])
    rung = np.searchsorted(symbolic_ladder().upper, row_prod, side="left")
    with jax.default_matmul_precision("highest"):
        dense = A.to_dense()
        ref = np.asarray(dense @ dense)
        bound = np.asarray(jnp.abs(dense) @ jnp.abs(dense))
        ones = (dense != 0).astype(jnp.float32)
        terms = np.asarray(ones @ ones)
    return dict(A=A, row_prod=row_prod, rung=rung, ref=ref, bound=bound,
                terms=terms)


def _check(C: CSR, web):
    """C against the dense product.  Structure: exactly the nonzeros of
    |A|·|A|.  Values: the engine and the dense product each sum an
    entry's k products in float32, in different orders, so each side is
    within (k + 1)·u of |A|·|A| at that entry (u = 2^-24, one rounding per
    product and per addition); the two may differ by twice that."""
    rpt = np.asarray(C.rpt)
    nnz = int(rpt[-1])
    col = np.asarray(C.col)[:nnz]
    rows = np.repeat(np.arange(rpt.size - 1), np.diff(rpt))
    want_rows, want_cols = np.nonzero(web["bound"])
    assert np.array_equal(rows, want_rows) and np.array_equal(col, want_cols)
    got = np.asarray(C.val)[:nnz].astype(np.float64)
    want = web["ref"][rows, col].astype(np.float64)
    k = web["terms"][rows, col].astype(np.float64)
    tol = 2 * (k + 1) * U * web["bound"][rows, col]
    assert (np.abs(got - want) <= tol).all()


def test_the_matrix_fills_every_rung(web):
    """Rungs 0-7 (tables of 32 to 24,576 slots) and the fallback rung
    each hold rows of this matrix."""
    counts = np.bincount(web["rung"], minlength=symbolic_ladder().num_bins)
    assert counts.size == 9 and (counts > 0).all(), counts


@pytest.mark.parametrize("method", ["hash", "esc"])
def test_powerlaw_product_matches_dense(web, method):
    """A cold and a hot product through the service, each equal to the
    dense product; under ``hash`` the hot product's binning puts rows on
    every rung, and the two product counters count it: all its products,
    and those of the fallback rung's rows.  ESC counts none."""
    svc = SpgemmService()
    A = web["A"]
    results = [svc.call(A, A, config=SpgemmConfig(method=method))
               for _ in range(2)]
    for r in results:
        assert r.ok, r.error
        _check(r.value.C, web)
    engine = svc.engine()
    entry = next(e for _, e in engine.cache.items())
    assert entry.stats.hot_calls == 1
    reg = engine.telemetry.registry
    hash_prod = reg.get("opsparse_hash_products_total").value
    fall_prod = reg.get("opsparse_fallback_products_total").value
    if method == "esc":
        assert hash_prod == fall_prod == 0
        return
    bins = np.asarray(results[1].value.sym_binning.bin_size)
    np.testing.assert_array_equal(
        bins, np.bincount(web["rung"], minlength=bins.size))
    assert hash_prod == int(web["row_prod"].sum())
    assert fall_prod == int(web["row_prod"][web["rung"] == 8].sum()) > 0


@pytest.mark.parametrize("fuse_numeric", [True, False])
def test_product_counters_on_both_hash_paths(fuse_numeric):
    """An admitted hot product adds its products, and those of rows on
    the fallback rung, on the fused path and on the two-pass one.  Row 0
    links to rows 1-21 of 1,000 entries each: 21,000 products, past the
    top rung's 20,480.  Rows 22-99 link to row 1 (1,000 products each);
    rows 1-21 link only to empty rows."""
    m, wide = 1100, np.arange(100, 1100)
    rows = ([np.arange(1, 22)] + [wide] * 21 + [np.array([1])] * 78
            + [np.array([], int)] * 1000)
    rpt = np.concatenate([[0], np.cumsum([r.size for r in rows])])
    col = np.concatenate(rows)
    A = CSR(rpt=jnp.asarray(rpt, jnp.int32), col=jnp.asarray(col, jnp.int32),
            val=jnp.ones(col.size, jnp.float32), shape=(m, m))
    engine = SpgemmEngine(SpgemmConfig(method="hash",
                                       fuse_numeric=fuse_numeric))
    for _ in range(2):                   # cold, then one hot product
        engine.execute(A, A)
    reg = engine.telemetry.registry
    assert reg.get("opsparse_hash_products_total").value == \
        21 * 1000 + 78 * 1000
    assert reg.get("opsparse_fallback_products_total").value == 21 * 1000


@pytest.mark.parametrize("fuse_numeric", [True, False])
def test_a_fused_plan_learns_only_the_symbolic_fallback(fuse_numeric):
    """Row 0 links to rows 1-5, of 1,000 distinct columns each: 5,000
    products (symbolic rung 5) and 5,000 nonzeros of C, past the numeric
    ladder's top rung (4,096).  Only the two-pass plan expands the
    numeric fallback in its steady executable, so only it learns a
    fallback capacity; both give C."""
    m = 5100
    rows = ([np.arange(1, 6)] + [np.arange(100 + 1000 * i, 1100 + 1000 * i)
                                 for i in range(5)]
            + [np.array([], int)] * (m - 6))
    rpt = np.concatenate([[0], np.cumsum([r.size for r in rows])])
    col = np.concatenate(rows)
    A = CSR(rpt=jnp.asarray(rpt, jnp.int32), col=jnp.asarray(col, jnp.int32),
            val=jnp.ones(col.size, jnp.float32), shape=(m, m))
    engine = SpgemmEngine(SpgemmConfig(method="hash",
                                       fuse_numeric=fuse_numeric))
    for _ in range(2):
        C = engine.execute(A, A).C
        assert int(C.rpt[1]) == 5000 and int(C.rpt[-1]) == 5000
        np.testing.assert_array_equal(np.asarray(C.col)[:5000],
                                      np.arange(100, 5100))
    sched = next(e for _, e in engine.cache.items()).plan.hash_schedule
    assert sched.sym_row_buckets[5] > 0 and sched.sym_row_buckets[-1] == 0
    assert sched.num_row_buckets[-1] > 0
    assert (sched.fall_prod_bucket == 0) == fuse_numeric
