"""Operations and bytes of one sparse product C = A * B, counted row-wise.

Row-wise (Gustavson) SpGEMM is the least traffic any implementation
needs: read A once, and for each nonzero A[i, k] read B's two row
pointers and the entries of B's row k; then write C once.  An entry is a
32-bit column index and a 32-bit value (8 B); a row pointer is 4 B.  The
count depends only on the sizes (rows, nnz(A), the products n_prod and
nnz(C)), never on how the program does the work, so every implementation
is held to the same least time.

Each product is a multiply and an add: 2 * n_prod operations.  At about
0.16 operation per byte for the Table-3 matrices the product is bound by
memory bandwidth, not by arithmetic.
"""
from __future__ import annotations

import numpy as np

INDEX_BYTES = 4
ENTRY_BYTES = 8          # 32-bit column + 32-bit value


def n_prod(a_rpt: np.ndarray, a_col: np.ndarray, b_rpt: np.ndarray) -> int:
    """Intermediate products of A * B: sum over A's nonzeros of |B row|."""
    b_len = np.diff(b_rpt).astype(np.int64)
    return int(b_len[a_col[:int(a_rpt[-1])]].sum())


def flops(nprod: int) -> int:
    return 2 * int(nprod)


def gustavson_bytes(rows: int, nnz_a: int, nprod: int, nnz_c: int) -> int:
    """Least HBM traffic of one row-wise product with B = A's shape."""
    read_a = (rows + 1) * INDEX_BYTES + nnz_a * ENTRY_BYTES
    read_b = nnz_a * 2 * INDEX_BYTES + nprod * ENTRY_BYTES
    write_c = (rows + 1) * INDEX_BYTES + nnz_c * ENTRY_BYTES
    return int(read_a + read_b + write_c)


def least_seconds(peaks: dict, *, flop: int, bytes_: int) -> float:
    """Roofline time: the larger of operations over peak compute and
    bytes over peak bandwidth."""
    return max(flop / peaks["peak_flops"], bytes_ / peaks["hbm_bytes_per_s"])
