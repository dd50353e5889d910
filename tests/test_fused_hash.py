"""Fused symbolic->numeric hash kernels + multi-row VMEM packing (ISSUE 4).

Covers the tentpole guarantees: the one-build fused pipeline is bitwise-
identical to the two-pass oracle (nnz / structure / values, both probe
disciplines), row packing is a pure layout change (bitwise parity across
rung boundaries), fusion strictly reduces per-row table transactions
(fused <= symbolic + numeric, measured not asserted), and the engine's
fused steady state serves repeat shapes with zero retraces.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import (SpgemmConfig, bin_rows_for_ladder, next_bucket,
                        nprod_into_rpt, random_csr, esc)
from repro.core.analysis import exclusive_sum_in_place
from repro.core.binning_ranges import (make_ladder, numeric_ladder,
                                       rows_per_block_of, symbolic_ladder)
from repro.engine import SpgemmEngine, total_traces
from repro.kernels import default_interpret, resolve_interpret, spgemm_hash


def _pair(seed, m, k, n, da, db, dist="uniform"):
    A = random_csr(jax.random.PRNGKey(seed), m, k, avg_nnz_per_row=da,
                   distribution=dist)
    B = random_csr(jax.random.PRNGKey(seed + 100), k, n, avg_nnz_per_row=db,
                   distribution=dist)
    return A, B


def _two_pass(A, B, sym_lad, num_lad, single_access=True):
    """The two-pass oracle: symbolic -> rpt -> numeric."""
    m = A.nrows
    nprod = nprod_into_rpt(A, B)[:m]
    sym_bn = bin_rows_for_ladder(nprod, sym_lad)
    nnz_buf = spgemm_hash.symbolic_binned(A, B, sym_bn, sym_lad,
                                          single_access=single_access)
    num_bn = bin_rows_for_ladder(nnz_buf[:m], num_lad)
    cap = next_bucket(max(int(nnz_buf[:m].sum()), 1))
    rpt = exclusive_sum_in_place(nnz_buf)
    C = spgemm_hash.numeric_binned(A, B, rpt, num_bn, num_lad,
                                   nnz_capacity=cap,
                                   single_access=single_access)
    return C, cap, sym_bn


def _fused(A, B, sym_lad, cap, sym_bn, *, single_access=True, packed=False):
    return spgemm_hash.fused_binned(A, B, sym_bn, sym_lad, nnz_capacity=cap,
                                    single_access=single_access,
                                    row_packing=packed)


@pytest.mark.parametrize("single_access", [True, False])
def test_fused_vs_two_pass_bitwise_parity(single_access):
    """One table build must reproduce the double build EXACTLY: same nnz,
    same sorted structure, bitwise-equal values (the per-column accumulation
    order — A-entry major, B-entry minor — is identical in both kernels)."""
    A, B = _pair(7, 72, 96, 80, 5.0, 4.0)
    sym_lad, num_lad = symbolic_ladder(1.2), numeric_ladder(2.0)
    C2, cap, sym_bn = _two_pass(A, B, sym_lad, num_lad, single_access)
    C1 = _fused(A, B, sym_lad, cap, sym_bn, single_access=single_access)
    nnz = int(C2.rpt[-1])
    assert nnz > 0
    np.testing.assert_array_equal(np.asarray(C1.rpt), np.asarray(C2.rpt))
    np.testing.assert_array_equal(np.asarray(C1.col)[:nnz],
                                  np.asarray(C2.col)[:nnz])
    np.testing.assert_array_equal(np.asarray(C1.val)[:nnz],
                                  np.asarray(C2.val)[:nnz])


def test_fused_multi_rung_with_fallback_matches_oracle():
    """Tiny ladders force several rungs AND the ESC fallback rung through
    the fused path; nnz/structure stay exact against the dense oracle
    (values allclose: ESC fallback rows may sum in a different order)."""
    m = 96
    A, B = _pair(9, m, 200, 150, 10.0, 8.0, dist="powerlaw")
    sym_lad = make_ladder((32, 64, 128), 1.2, (32, 64, 128))
    nprod = nprod_into_rpt(A, B)[:m]
    sym_bn = bin_rows_for_ladder(nprod, sym_lad)
    sizes = np.asarray(sym_bn.bin_size)
    assert (sizes[:-1] > 0).sum() >= 2 and sizes[-1] > 0  # rungs + fallback
    nnz_buf = esc.symbolic(A, B, prod_capacity=next_bucket(8192))
    cap = next_bucket(int(nnz_buf.sum()))
    C = spgemm_hash.fused_binned(A, B, sym_bn, sym_lad, nnz_capacity=cap)
    ref = np.asarray(A.to_dense()) @ np.asarray(B.to_dense())
    np.testing.assert_array_equal(
        np.asarray(C.rpt[1:]) - np.asarray(C.rpt[:-1]),
        (ref != 0).sum(axis=1))
    np.testing.assert_allclose(np.asarray(C.to_dense()), ref,
                               rtol=1e-5, atol=1e-5)
    rptn, coln = np.asarray(C.rpt), np.asarray(C.col)
    for i in range(m):
        seg = coln[rptn[i]:rptn[i + 1]]
        assert (np.diff(seg) > 0).all()    # rows sorted by column


def test_packed_vs_unpacked_bitwise_parity_across_rungs():
    """Row packing is a pure occupancy/layout change: sub-tables keep the
    per-row table size, so probe sequences — and therefore nnz, structure,
    values, and transaction counts — are bitwise-identical."""
    m = 96
    A, B = _pair(11, m, 160, 120, 8.0, 6.0, dist="powerlaw")
    sym_lad = make_ladder((32, 64, 128, 256), 1.2, (32, 64, 128, 256))
    assert sym_lad.rows_per_block[0] > 1     # packing actually engages
    nprod = nprod_into_rpt(A, B)[:m]
    sym_bn = bin_rows_for_ladder(nprod, sym_lad)
    assert (np.asarray(sym_bn.bin_size)[:-1] > 0).sum() >= 2
    cap = next_bucket(int(esc.symbolic(A, B,
                                       prod_capacity=next_bucket(8192)).sum()))
    Cu, acc_u = spgemm_hash.fused_binned(A, B, sym_bn, sym_lad,
                                         nnz_capacity=cap, row_packing=False,
                                         collect_accesses=True)
    Cp, acc_p = spgemm_hash.fused_binned(A, B, sym_bn, sym_lad,
                                         nnz_capacity=cap, row_packing=True,
                                         collect_accesses=True)
    np.testing.assert_array_equal(np.asarray(Cu.rpt), np.asarray(Cp.rpt))
    np.testing.assert_array_equal(np.asarray(Cu.col), np.asarray(Cp.col))
    np.testing.assert_array_equal(np.asarray(Cu.val), np.asarray(Cp.val))
    assert int(acc_u) == int(acc_p)


def test_packed_geometry_and_ladder_rows_per_block():
    """Pack counts are pow-2, tile-bounded, and 1 once a table fills the
    minimum (8, 128) int32 tile."""
    assert rows_per_block_of(32) == 32
    assert rows_per_block_of(512) == 2
    assert rows_per_block_of(1024) == 1
    assert rows_per_block_of(24576) == 1
    lad = symbolic_ladder(1.2)
    assert lad.rows_per_block == tuple(
        rows_per_block_of(t) for t in lad.table_sizes)
    for t, p in zip(lad.table_sizes, lad.rows_per_block):
        t_rows, stride = spgemm_hash._packed_geom(t, p)
        assert stride >= t and t_rows * 128 == p * stride


def test_fused_accesses_leq_two_pass_per_row():
    """Access-count regression (the Fig.-9 counters, per row): building the
    table once must cost no more transactions than building it twice —
    fused <= symbolic + numeric for EVERY row."""
    m = 80
    A, B = _pair(13, m, 100, 90, 6.0, 5.0)
    sym_lad, num_lad = symbolic_ladder(1.2), numeric_ladder(2.0)
    nprod = nprod_into_rpt(A, B)[:m]
    sym_bn = bin_rows_for_ladder(nprod, sym_lad)
    nnz_buf = spgemm_hash.symbolic_binned(A, B, sym_bn, sym_lad)
    num_bn = bin_rows_for_ladder(nnz_buf[:m], num_lad)

    def per_row_accesses(binning, ladder, call):
        out = {}
        sizes = np.asarray(binning.bin_size)
        for b, t_size in enumerate(ladder.table_sizes):
            if not sizes[b]:
                continue
            rows_cap = next_bucket(int(sizes[b]), minimum=8)
            rows, count = binning.rows_of_bin(b, rows_cap)
            acc = call(rows, count.reshape(1), t_size, rows_cap)
            rr, aa = np.asarray(rows), np.asarray(acc)
            for i in range(int(sizes[b])):
                out[int(rr[i])] = int(aa[i])
        return out

    sym_acc = per_row_accesses(
        sym_bn, sym_lad,
        lambda rows, cnt, t, cap: spgemm_hash.symbolic_bin_call(
            rows, cnt, A.rpt, A.col, B.rpt, B.col,
            t_size=t, rows_cap=cap, single_access=True)[1])
    num_acc = per_row_accesses(
        num_bn, num_lad,
        lambda rows, cnt, t, cap: spgemm_hash.numeric_bin_call(
            rows, cnt, A.rpt, A.col, A.val, B.rpt, B.col, B.val,
            t_size=t, rows_cap=cap, single_access=True)[2])
    fused_acc = per_row_accesses(
        sym_bn, sym_lad,
        lambda rows, cnt, t, cap: spgemm_hash.fused_bin_call(
            rows, cnt, A.rpt, A.col, A.val, B.rpt, B.col, B.val,
            t_size=t, rows_cap=cap, single_access=True)[3])

    assert set(fused_acc) == set(sym_acc)
    checked = 0
    for r, f in fused_acc.items():
        if r in num_acc:               # row served by kernels in both phases
            assert f <= sym_acc[r] + num_acc[r], r
            checked += 1
    assert checked >= m // 2
    total_two = sum(sym_acc.values()) + sum(num_acc.values())
    total_fused = sum(fused_acc.values())
    assert total_fused * 3 <= total_two * 2    # >= 1.5x reduction overall


def test_host_schedule_pack_alignment():
    """``host_schedule(packs=...)`` floors populated rungs at their pack
    so packed kernels always get whole grid steps."""
    m = 96
    A, B = _pair(17, m, 160, 120, 8.0, 6.0, dist="powerlaw")
    lad = make_ladder((32, 64, 128), 1.2, (32, 64, 128))
    bn = bin_rows_for_ladder(nprod_into_rpt(A, B)[:m], lad)
    buckets, _ = spgemm_hash.host_schedule(A, B, bn, lad,
                                           packs=lad.rows_per_block)
    sizes = np.asarray(bn.bin_size)
    for b, (s, cap) in enumerate(zip(sizes[:-1], buckets[:-1])):
        if not s:
            assert cap == 0
            continue
        pack = lad.rows_per_block[b]
        assert cap >= max(int(s), pack) and cap % pack == 0


@pytest.mark.parametrize("row_packing", [False, True])
def test_engine_fused_steady_state_zero_retraces(row_packing):
    """The fused executable serves repeat shapes with zero retraces and
    stays bitwise-identical to the two-pass engine path."""
    cfg = SpgemmConfig(method="hash", fuse_numeric=True,
                       row_packing=row_packing)
    engine = SpgemmEngine(cfg)
    # Explicit two-pass oracle: fuse_numeric became the hash DEFAULT, so
    # a bare hash config would compare the fused executable with itself.
    oracle = SpgemmEngine(SpgemmConfig(method="hash", fuse_numeric=False))
    pairs = [_pair(31 + s, 48, 64, 56, 4.0, 3.0) for s in range(5)]
    cap_a = next_bucket(max(A.capacity for A, _ in pairs))
    cap_b = next_bucket(max(B.capacity for _, B in pairs))
    pairs = [(A.with_capacity(cap_a), B.with_capacity(cap_b))
             for A, B in pairs]

    baseline = None
    for i, (A, B) in enumerate(pairs):
        res = engine.execute(A, B)
        ref = oracle.execute(A, B)
        nnz = ref.total_nnz
        assert res.total_nnz == nnz
        # Steady-state fused results keep the cold-call telemetry shape.
        assert res.sym_binning is not None and res.num_binning is not None
        np.testing.assert_array_equal(np.asarray(res.C.rpt),
                                      np.asarray(ref.C.rpt))
        np.testing.assert_array_equal(np.asarray(res.C.col)[:nnz],
                                      np.asarray(ref.C.col)[:nnz])
        np.testing.assert_array_equal(np.asarray(res.C.val)[:nnz],
                                      np.asarray(ref.C.val)[:nnz])
        if i == 1:
            baseline = total_traces()   # cold + first fused/oracle traces
    assert total_traces() == baseline   # zero retraces on the tail
    entry = next(e for _, e in engine.cache.items())
    assert entry.stats.hot_calls >= 3
    assert entry.plan.config.fuse_numeric


def test_engine_fused_overflow_grows_and_recovers():
    """A same-signature request outgrowing the fused plan's schedule must
    fall back to the steps oracle, grow the plan, and stay correct."""
    cfg = SpgemmConfig(method="hash", fuse_numeric=True, row_packing=True)
    engine = SpgemmEngine(cfg)
    small = _pair(41, 64, 96, 72, 2.0, 2.0)
    big = _pair(43, 64, 96, 72, 12.0, 9.0, dist="powerlaw")
    cap_a = next_bucket(max(small[0].capacity, big[0].capacity))
    cap_b = next_bucket(max(small[1].capacity, big[1].capacity))
    for A, B in (small, big, small):
        A, B = A.with_capacity(cap_a), B.with_capacity(cap_b)
        res = engine.execute(A, B)
        ref = np.asarray(A.to_dense()) @ np.asarray(B.to_dense())
        np.testing.assert_allclose(np.asarray(res.C.to_dense()), ref,
                                   rtol=1e-4, atol=1e-4)


def _bitwise_same(C1, C2, nnz):
    np.testing.assert_array_equal(np.asarray(C1.rpt), np.asarray(C2.rpt))
    np.testing.assert_array_equal(np.asarray(C1.col)[:nnz],
                                  np.asarray(C2.col)[:nnz])
    np.testing.assert_array_equal(np.asarray(C1.val)[:nnz],
                                  np.asarray(C2.val)[:nnz])


@pytest.mark.parametrize("row_packing", [False, True])
def test_fused_degenerate_all_zero_rows(row_packing):
    """All-zero rows under the fused/packed path: empty rows become empty
    sub-tables (nnz 0, no scatter), bitwise-mirroring the two-pass
    oracle.  Regression for the packed sub-table offsets of empty rows."""
    from repro.core import CSR
    m = 48
    d = np.zeros((m, 40), np.float32)
    rng = np.random.RandomState(0)
    occupied = rng.choice(m, size=m // 3, replace=False)
    d[occupied, :5] = rng.rand(len(occupied), 5).astype(np.float32) + 0.5
    A = CSR.from_dense(d)
    B = random_csr(jax.random.PRNGKey(3), 40, 36, avg_nnz_per_row=4.0)
    sym_lad, num_lad = symbolic_ladder(1.2), numeric_ladder(2.0)
    C2, cap, sym_bn = _two_pass(A, B, sym_lad, num_lad)
    C1 = spgemm_hash.fused_binned(A, B, sym_bn, sym_lad, nnz_capacity=cap,
                                  row_packing=row_packing)
    nnz = int(C2.rpt[-1])
    assert nnz > 0
    _bitwise_same(C1, C2, nnz)
    # Zero rows really are zero in the result.
    rpt = np.asarray(C1.rpt)
    empty = np.setdiff1d(np.arange(m), occupied)
    assert (rpt[empty + 1] == rpt[empty]).all()


@pytest.mark.parametrize("zero_side", ["A", "B", "both"])
def test_fused_degenerate_nnz_zero_matrices(zero_side):
    """nnz=0 operands through the fused/packed pipeline: the result is the
    empty CSR, bitwise-mirroring the two-pass oracle (empty rows' packed
    sub-table offsets must not scatter anything)."""
    from repro.core import CSR
    m, k, n = 32, 28, 24
    A = (CSR.from_dense(np.zeros((m, k), np.float32)) if zero_side != "B"
         else random_csr(jax.random.PRNGKey(5), m, k, avg_nnz_per_row=3.0))
    B = (CSR.from_dense(np.zeros((k, n), np.float32)) if zero_side != "A"
         else random_csr(jax.random.PRNGKey(6), k, n, avg_nnz_per_row=3.0))
    sym_lad, num_lad = symbolic_ladder(1.2), numeric_ladder(2.0)
    C2, cap, sym_bn = _two_pass(A, B, sym_lad, num_lad)
    C1 = spgemm_hash.fused_binned(A, B, sym_bn, sym_lad, nnz_capacity=cap,
                                  row_packing=True)
    assert int(C1.rpt[-1]) == 0
    _bitwise_same(C1, C2, 0)
    assert not np.asarray(C1.to_dense()).any()


def test_engine_fused_packed_degenerate_stream():
    """The engine's fused+packed steady state on degenerate inputs: an
    all-zero A and a zero-row A share the signature bucket with a dense
    one; every result mirrors the two-pass engine bitwise."""
    from repro.core import CSR
    m, k, n = 32, 28, 24
    cfg = SpgemmConfig(method="hash", fuse_numeric=True, row_packing=True)
    engine = SpgemmEngine(cfg)
    oracle = SpgemmEngine(SpgemmConfig(method="hash", fuse_numeric=False))
    dense, B = _pair(51, m, k, n, 3.0, 3.0)
    d_half = np.asarray(dense.to_dense()).copy()
    d_half[m // 2:] = 0.0                # bottom half all-zero rows
    cap_a = next_bucket(dense.capacity)
    variants = [dense.with_capacity(cap_a),
                CSR.from_dense(d_half).with_capacity(cap_a),
                CSR.from_dense(np.zeros((m, k), np.float32))
                .with_capacity(cap_a)]
    for A in variants * 2:               # cold + hot coverage per variant
        res = engine.execute(A, B)
        ref = oracle.execute(A, B)
        assert res.total_nnz == ref.total_nnz
        _bitwise_same(res.C, ref.C, ref.total_nnz)


def test_interpret_auto_detect():
    """interpret=None resolves per-backend (interpreted off-TPU), and the
    config default no longer hardwires interpret mode."""
    assert SpgemmConfig().interpret is None
    assert resolve_interpret(None) == default_interpret()
    assert default_interpret() == (jax.default_backend() != "tpu")
    assert resolve_interpret(True) is True
    assert resolve_interpret(False) is False


# ---------------------------------------------------------------------------
# The epilogue's two ways into C: gather from the sorted tables, or scatter
# every table slot.  Same arithmetic, so the same C bit for bit.
# ---------------------------------------------------------------------------

def _epilogue_operands(seed=13, m=96, k=64, n=64):
    """A product whose A has empty rows (every 7th), with its structural
    row sizes and C's row pointers."""
    from repro.core import CSR
    A, B = _pair(seed, m, k, n, 2.0, 3.0)
    dense = np.asarray(A.to_dense()).copy()
    dense[::7] = 0.0
    A = CSR.from_dense(dense)
    a, b = (dense != 0).astype(np.int64), (np.asarray(B.to_dense()) != 0)
    support = (a @ b.astype(np.int64)) > 0
    nprod = a @ b.sum(axis=1)
    rpt = np.concatenate([[0], np.cumsum(support.sum(axis=1))])
    return A, B, support, nprod, rpt.astype(np.int32)


@pytest.mark.parametrize(
    "kind,t_size,pack,rows_cap,gathers,fallback,dtype", [
        ("fused", 32, 1, 64, True, False, "float32"),    # symbolic, unpacked
        ("fused", 32, 1, 64, False, False, "float32"),   # rule's boundary
        ("fused", 32, 32, 64, True, False, "float32"),   # packed sub-tables
        ("fused", 32, 1, 64, True, True, "float32"),     # a fallback rung
        ("numeric", 31, 1, 64, True, False, "float32"),  # mod probing
        ("numeric", 255, 1, 16, False, False, "float32"),
        ("fused", 32, 1, 64, True, False, "bfloat16"),   # values not 32-bit
    ])
def test_epilogue_gather_matches_scatter_bitwise(kind, t_size, pack,
                                                 rows_cap, gathers,
                                                 fallback, dtype):
    """Gathering C's entries from a rung's sorted tables writes exactly
    what scattering every slot writes, and nothing else: positions of
    rows off the rung (a fallback rung's among them) and past nnz(C)
    keep what they held.  The rung holds empty rows, C's last row, fewer
    valid rows than its bucket, and a padding row id of a row off the
    rung."""
    from repro.core.csr import gather_rows
    A, B, support, nprod, rpt = _epilogue_operands()
    m = A.nrows
    fits = nprod <= t_size * 0.8 if kind == "fused" else \
        support.sum(axis=1) <= t_size // 2
    on = [r for r in range(m) if fits[r] and (r % 2 or r % 7 == 0)]
    count = min(len(on), rows_cap - 3)
    on = on[-count:]                 # C's last row among them
    off = [r for r in range(m) if r not in on and support[r].any()]
    assert any(support[r].sum() == 0 for r in on) and count < rows_cap
    assert on[-1] == m - 1 and support[m - 1].any()
    rows = np.full(rows_cap, off[0], np.int32)        # padding: a real row
    rows[:count] = on
    rows, cnt = jnp.asarray(rows), jnp.asarray([count], jnp.int32)
    if kind == "fused":
        _, col_tabs, val_tabs, _ = spgemm_hash.fused_bin_call(
            rows, cnt, A.rpt, A.col, A.val, B.rpt, B.col, B.val,
            t_size=t_size, rows_cap=rows_cap, pack=pack)
    else:
        col_tabs, val_tabs, _ = spgemm_hash.numeric_bin_call(
            rows, cnt, A.rpt, A.col, A.val, B.rpt, B.col, B.val,
            t_size=t_size, rows_cap=rows_cap, single_access=True)
    val_tabs = val_tabs.astype(dtype)
    cap = next_bucket(int(rpt[-1])) if gathers else col_tabs.size
    assert spgemm_hash.epilogue_gathers(*col_tabs.shape, cap) == gathers
    assert rpt[-1] <= cap
    rpt_d = jnp.asarray(rpt)
    c_col = -2 - jnp.arange(cap, dtype=jnp.int32)      # "held before"
    c_val = (1000.0 + jnp.arange(cap, dtype=jnp.float32)).astype(dtype)
    if fallback:                                       # a real ESC rung
        f_rows = jnp.asarray(off[:3] + [m] * 5, jnp.int32)
        valid = jnp.arange(8) < 3
        subC = esc.spgemm_fused(gather_rows(A, f_rows, valid), B,
                                prod_capacity=1024, nnz_capacity=1024)
        c_col, c_val = spgemm_hash.scatter_sub_rows(
            subC, f_rows, valid, rpt_d, c_col, c_val, nnz_capacity=cap)
    before = np.asarray(c_col), np.asarray(c_val)
    g_col, g_val = spgemm_hash.epilogue_gather(     # C's storage donated
        col_tabs, val_tabs, rows, cnt[0], rpt_d, jnp.array(before[0]),
        jnp.array(before[1]))
    s_col, s_val = spgemm_hash.epilogue_scatter(
        col_tabs, val_tabs, rows, cnt[0], rpt_d, jnp.array(before[0]),
        jnp.array(before[1]))
    np.testing.assert_array_equal(np.asarray(g_col), np.asarray(s_col))
    np.testing.assert_array_equal(
        np.asarray(g_val.view(jnp.int16 if dtype == "bfloat16" else jnp.int32)),
        np.asarray(s_val.view(jnp.int16 if dtype == "bfloat16" else jnp.int32)))
    mine = np.zeros(cap, bool)
    for r in on:
        mine[rpt[r]:rpt[r + 1]] = True
        np.testing.assert_array_equal(np.asarray(g_col)[rpt[r]:rpt[r + 1]],
                                      np.flatnonzero(support[r]))
    np.testing.assert_array_equal(np.asarray(g_col)[~mine], before[0][~mine])
    np.testing.assert_array_equal(np.asarray(g_val)[~mine], before[1][~mine])
    ref = np.asarray(A.to_dense()) @ np.asarray(B.to_dense())
    got = np.asarray(g_val.astype(jnp.float32))
    tol = 1e-2 if dtype == "bfloat16" else 1e-5
    for r in on:
        np.testing.assert_allclose(got[rpt[r]:rpt[r + 1]],
                                   ref[r, support[r]], rtol=tol, atol=tol)


def test_per_position_carries_each_rows_value():
    """Each position of C's storage reads its row's value; empty rows
    own no position, and positions past nnz(C) read the last row that
    starts at or before them."""
    rpt = jnp.asarray([0, 0, 3, 3, 3, 5, 6, 6], jnp.int32)   # 7 rows
    per_row = jnp.asarray([-9, 40, 7, -2, 11, 5, 3], jnp.int32)
    np.testing.assert_array_equal(
        np.asarray(spgemm_hash._per_position(rpt, per_row, 8)),
        [40, 40, 40, 11, 11, 5, 3, 3])


@pytest.mark.parametrize("m,k,n,da,db,short_rows", [
    (48, 40, 40, 4.0, 4.0, 0),      # both populated rungs gather
    (16, 64, 600, 40.0, 30.0, 3),   # long rows gather, short rows scatter
])
def test_engine_hash_gather_epilogue_matches_esc(m, k, n, da, db,
                                                 short_rows):
    """Through the engine, a hash product whose epilogue gathers equals
    the ESC product exactly: integer values keep every sum exact, so the
    summation order cannot tell the two methods apart."""
    from repro.core import CSR
    A, B = _pair(21, m, k, n, da, db)
    rng = np.random.RandomState(4)
    dA, dB = (np.where(d != 0, rng.randint(1, 5, d.shape), 0)
              .astype(np.float32)
              for d in (np.asarray(A.to_dense()), np.asarray(B.to_dense())))
    dA[:short_rows, 1:] = 0.0       # rows of a small rung, which scatters
    A, B = CSR.from_dense(dA), CSR.from_dense(dB)
    hash_engine = SpgemmEngine(SpgemmConfig(method="hash"))
    got = [hash_engine.execute(A, B) for _ in range(2)][-1]
    plan = next(e for _, e in hash_engine.cache.items()).plan
    buckets = plan.hash_schedule.sym_row_buckets
    gathered = spgemm_hash.epilogue_gathered_slots(
        plan.sym_ladder, buckets, nnz_capacity=plan.nnz_bucket)
    slots = spgemm_hash.epilogue_slots(plan.sym_ladder, buckets)
    assert 0 < gathered and (gathered < slots) == bool(short_rows)
    want = SpgemmEngine(SpgemmConfig(method="esc")).execute(A, B)
    nnz = want.total_nnz
    assert got.total_nnz == nnz > 0
    np.testing.assert_array_equal(np.asarray(got.C.rpt),
                                  np.asarray(want.C.rpt))
    np.testing.assert_array_equal(np.asarray(got.C.col)[:nnz],
                                  np.asarray(want.C.col)[:nnz])
    np.testing.assert_array_equal(np.asarray(got.C.val)[:nnz],
                                  np.asarray(want.C.val)[:nnz])
