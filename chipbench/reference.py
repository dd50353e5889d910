"""The plain reference for C = A * A, its lower-precision control, and
the comparison that decides whether a run is correct.

The reference is ``scipy.sparse`` on the host, in float64, and takes
nothing from the program but the answers it compares.  The structure of
C is the structural product (every (i, j) reached by some product
A[i, k] * A[k, j], whatever the values: scipy drops sums that come to
exactly 0, so it is taken with all values 1).  |A| * |A| has no
cancellation; its values bound the rounding error of each entry.  An
answer is held to:

- every product of the window: status ``ok``, and the n_prod and nnz the
  program reports equal to the structure's (exact);
- every answer kept for checking: row pointers and columns equal to the
  reference's (exact), and each value within ``value_err`` of the
  float64 product of its own request's values, relative to the entry of
  |A| * |A|.

The control is the reference computed one precision step below the
configuration's float32: values stored in bfloat16 (the inputs and C),
products accumulated in float32.  It must fail ``value_err``.
"""
from __future__ import annotations

import dataclasses
from typing import Dict, List, Optional, Sequence, Tuple

import ml_dtypes
import numpy as np
import scipy.sparse as sp

TINY = float(np.finfo(np.float32).tiny)     # the bound of an all-0 entry


@dataclasses.dataclass
class Answer:
    """One C on the host: row pointers, and columns and values cut to
    the nnz that the row pointers give."""
    rpt: np.ndarray
    col: np.ndarray
    val: np.ndarray


@dataclasses.dataclass
class Reference:
    C: Answer              # float64 values
    abs_val: np.ndarray    # |A| * |A| on C's pattern
    nprod: int

    @property
    def nnz(self) -> int:
        return int(self.C.rpt[-1])


def _csr(rpt, col, val, shape) -> sp.csr_matrix:
    return sp.csr_matrix((val, col, rpt), shape=shape)


def _answer(M: sp.csr_matrix) -> Answer:
    return Answer(M.indptr, M.indices, M.data)


def _entry_keys(ans: Answer, ncols: int) -> Optional[np.ndarray]:
    """row * ncols + col of each entry; None where the row pointers do
    not describe the columns (not rising, or not ending at their count)."""
    steps = np.diff(ans.rpt)
    if ans.rpt.size == 0 or ans.rpt[0] != 0 or (steps < 0).any() \
            or int(ans.rpt[-1]) != ans.col.size:
        return None
    rows = np.repeat(np.arange(steps.size, dtype=np.int64), steps)
    return rows * ncols + ans.col.astype(np.int64)


def _on_pattern(X: sp.csr_matrix, P: sp.csr_matrix) -> np.ndarray:
    """X's values at P's entries (0 where X has none).  scipy drops
    entries that cancel to exactly 0; P keeps them."""
    if X.nnz == P.nnz and np.array_equal(X.indptr, P.indptr) \
            and np.array_equal(X.indices, P.indices):
        return X.data
    ncols = P.shape[1]
    kx, kp = _entry_keys(_answer(X), ncols), _entry_keys(_answer(P), ncols)
    idx = np.minimum(np.searchsorted(kx, kp), max(kx.size - 1, 0))
    found = kx[idx] == kp if kx.size else np.zeros(kp.size, bool)
    return np.where(found, X.data[idx] if kx.size else 0, 0)


def _square(M: sp.csr_matrix) -> sp.csr_matrix:
    C = M @ M
    C.sort_indices()
    return C


def pattern(rpt, col, shape) -> sp.csr_matrix:
    """The structural product: every (i, j) reached by some product,
    whatever the values (values 1)."""
    return _square(_csr(rpt, col, np.ones(col.size), shape))


def reference(rpt, col, val, shape,
              P: Optional[sp.csr_matrix] = None) -> Reference:
    """The float64 product A * A on the structural pattern ``P`` (made
    here when not given), and its error bound |A| * |A|."""
    val = np.asarray(val, np.float64)
    P = pattern(rpt, col, shape) if P is None else P
    C = _square(_csr(rpt, col, val, shape))
    bound = _square(_csr(rpt, col, np.abs(val), shape))
    nprod = int(np.diff(rpt)[col].astype(np.int64).sum())
    return Reference(C=Answer(P.indptr.astype(np.int64),
                              P.indices.astype(np.int64),
                              _on_pattern(C, P)),
                     abs_val=_on_pattern(bound, P), nprod=nprod)


def control(rpt, col, val, shape, ref: Reference) -> Answer:
    """The reference in bfloat16 storage: inputs and C rounded to
    bfloat16, products summed in float32 (scipy accumulates in the
    operands' dtype)."""
    v16 = np.asarray(val, np.float32).astype(ml_dtypes.bfloat16)
    S = _csr(rpt, col, v16.astype(np.float32), shape)
    C = _square(S)
    P = _csr(ref.C.rpt, ref.C.col, np.ones(ref.C.col.size), shape)
    out = _on_pattern(C, P).astype(ml_dtypes.bfloat16).astype(np.float32)
    return Answer(ref.C.rpt.copy(), ref.C.col.copy(), out)


def _structure_off(ans: Answer, ref: Answer, ncols: int) -> Tuple[int, int]:
    """(rows whose pointer differs, entries in one structure and not the
    other)."""
    if ans.rpt.shape != ref.rpt.shape:
        return ref.rpt.size - 1, int(ref.rpt[-1]) + ans.col.size
    rpt_off = int(np.count_nonzero(ans.rpt != ref.rpt))
    if rpt_off == 0 and np.array_equal(ans.col, ref.col):
        return 0, 0
    a = _entry_keys(ans, ncols)
    if a is None:
        return rpt_off, int(ref.rpt[-1]) + ans.col.size
    return rpt_off, int(np.setxor1d(a, _entry_keys(ref, ncols)).size)


def value_err(ans: Answer, ref: Reference, ncols: int) -> Optional[float]:
    """Worst |c - c_ref| / (|A| * |A|) over the entries the answer and the
    reference share; None where they share none.  Where every product of
    an entry is exactly 0 (A may store a 0), the bound is 0 and the entry
    has to be exactly 0."""
    if np.array_equal(ans.rpt, ref.C.rpt) and np.array_equal(ans.col,
                                                            ref.C.col):
        got, want, bound = ans.val, ref.C.val, ref.abs_val
    else:
        a = _entry_keys(ans, ncols)
        if a is None:
            return None
        _, ia, ir = np.intersect1d(a, _entry_keys(ref.C, ncols),
                                   return_indices=True)
        got, want, bound = ans.val[ia], ref.C.val[ir], ref.abs_val[ir]
    if got.size == 0:
        return None
    err = np.abs(got.astype(np.float64) - want) / np.maximum(bound, TINY)
    return float(err.max())


def compare(pairs: Sequence[Tuple[Reference, Answer]],
            reported: Sequence[Tuple[int, int]], failed: int,
            nprod: int, nnz: int, ncols: int,
            limits: Dict[str, float]) -> Dict[str, dict]:
    """Each number compared, beside its limit.  ``reported`` holds the
    (n_prod, nnz) the program returned for every product of the window,
    held to the structure's ``nprod`` and ``nnz``; ``pairs`` each answer
    kept for checking with the reference of its own values."""
    rpt_off = col_off = 0
    errs: List[float] = []
    for ref, ans in pairs:
        r, c = _structure_off(ans, ref.C, ncols)
        rpt_off += r
        col_off += c
        e = value_err(ans, ref, ncols)
        if e is not None:
            errs.append(e)
    worst = max(errs) if len(errs) == len(pairs) and errs else None
    numbers = {
        "failed": failed,
        "nprod_off": sum(p != nprod for p, _ in reported),
        "nnz_off": sum(z != nnz for _, z in reported),
        "rpt_off": rpt_off,
        "col_off": col_off,
        "value_err": worst,
    }
    return {k: {"value": v, "limit": limits.get(k, 0)}
            for k, v in numbers.items()}


def passed(checks: Dict[str, dict]) -> bool:
    return all(c["value"] is not None and c["value"] <= c["limit"]
               for c in checks.values())
