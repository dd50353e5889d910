"""Row-wise operation and byte counts on a hand-worked 4x4 product."""
import numpy as np

from chipbench import reference, work

#     [1 0 2 0]        A*A = [ 9  0  2 10]
# A = [0 3 0 0]              [ 0  9  0  0]
#     [4 0 0 5]              [ 4  0 38  0]
#     [0 0 6 0]              [24  0  0 30]
RPT = np.array([0, 2, 3, 5, 6])
COL = np.array([0, 2, 1, 0, 3, 2])
VAL = np.array([1, 2, 3, 4, 5, 6], np.float32)


def test_counts_by_hand():
    # n_prod: row 0 reads B rows 0 and 2 (2 + 2), row 1 row 1 (1),
    # row 2 rows 0 and 3 (2 + 1), row 3 row 2 (2): 10 products.
    nprod = work.n_prod(RPT, COL, RPT)
    assert nprod == 10
    assert work.flops(nprod) == 20
    # read A: 5 pointers + 6 entries; read B: 2 pointers per A entry and
    # one entry per product; write C: 5 pointers + 8 entries.
    assert work.gustavson_bytes(4, 6, nprod, 8) == \
        (5 * 4 + 6 * 8) + (6 * 2 * 4 + 10 * 8) + (5 * 4 + 8 * 8) == 280


def test_least_seconds_takes_the_larger_bound():
    peaks = {"peak_flops": 10.0, "hbm_bytes_per_s": 100.0}
    assert work.least_seconds(peaks, flop=20, bytes_=280) == 2.8
    assert work.least_seconds(peaks, flop=50, bytes_=280) == 5.0


def test_reference_by_hand():
    ref = reference.reference(RPT, COL, VAL, (4, 4))
    dense = np.zeros((4, 4))
    for i in range(4):
        dense[i, ref.C.col[ref.C.rpt[i]:ref.C.rpt[i + 1]]] = \
            ref.C.val[ref.C.rpt[i]:ref.C.rpt[i + 1]]
    A = np.array([[1, 0, 2, 0], [0, 3, 0, 0], [4, 0, 0, 5], [0, 0, 6, 0]])
    np.testing.assert_array_equal(dense, A @ A)
    assert ref.nprod == 10 and ref.nnz == 8


def test_a_stored_zero_keeps_its_entries():
    """A[1, 1] = 0: C[1, 1] = 0 * 0 is still an entry of the structural
    product (scipy alone would drop it), and an answer that holds it as 0
    passes."""
    val = VAL.copy()
    val[2] = 0.0
    ref = reference.reference(RPT, COL, val, (4, 4))
    assert ref.nnz == 8
    row1 = slice(ref.C.rpt[1], ref.C.rpt[2])
    assert list(ref.C.col[row1]) == [1] and ref.C.val[row1][0] == 0
    ans = reference.Answer(ref.C.rpt, ref.C.col, ref.C.val.astype(np.float32))
    checks = reference.compare([(ref, ans)], [(10, 8)], 0, 10, 8, 4,
                               {"value_err": 1e-5})
    assert reference.passed(checks)
    ans.val[row1] = 1e-30                    # not exactly 0: caught
    assert not reference.passed(reference.compare(
        [(ref, ans)], [(10, 8)], 0, 10, 8, 4, {"value_err": 1e-5}))


def test_each_answer_is_held_to_its_own_values():
    """Two requests with other values: an answer is right against the
    reference of its own values and wrong against the other's."""
    refs = [reference.reference(RPT, COL, VAL * k, (4, 4)) for k in (1, 2)]
    answers = [reference.Answer(r.C.rpt, r.C.col, r.C.val.astype(np.float32))
               for r in refs]
    limits = {"value_err": 1e-5}
    assert reference.passed(reference.compare(
        list(zip(refs, answers)), [(10, 8)] * 2, 0, 10, 8, 4, limits))
    assert not reference.passed(reference.compare(
        list(zip(refs, answers[::-1])), [(10, 8)] * 2, 0, 10, 8, 4, limits))
