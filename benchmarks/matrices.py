"""Synthetic SuiteSparse-analog suite (paper Table 3).

The container is offline, so the 26 benchmark matrices are SYNTHESIZED to
match Table 3's row counts, mean/max nnz-per-row and structural family
(banded FEM-like, power-law web/circuit-like, uniform).  Sizes default to
1/SCALE of the originals so CPU wall-times stay in seconds; ``--full``
generates the original row counts.  Every generated matrix's achieved
stats are reported next to the paper's, so the fidelity of the analog is
visible in the output.
"""
from __future__ import annotations

import dataclasses
import zlib
from typing import Dict, List

import jax
import numpy as np

from repro.core import CSR, random_csr


@dataclasses.dataclass(frozen=True)
class MatrixSpec:
    name: str
    rows: int
    avg_nnz: float          # paper's Nnz/row
    max_nnz: int            # paper's Max nnz/row
    dist: str               # banded | powerlaw | uniform
    large: bool = False     # paper's "large" group (cuSPARSE OOM group)
    paper_cr: float = 0.0   # paper's compression ratio of A^2


# Paper Table 3, 19 "normal" + 7 "large" matrices.
TABLE3: List[MatrixSpec] = [
    MatrixSpec("m133-b3", 200200, 4.0, 4, "uniform", paper_cr=1.01),
    MatrixSpec("mac_econ_fwd500", 206500, 6.2, 44, "uniform", paper_cr=1.13),
    MatrixSpec("patents_main", 240547, 2.3, 206, "powerlaw", paper_cr=1.14),
    MatrixSpec("webbase-1M", 1000005, 3.1, 4700, "powerlaw", paper_cr=1.36),
    MatrixSpec("mc2depi", 525825, 4.0, 4, "uniform", paper_cr=1.60),
    MatrixSpec("scircuit", 170998, 5.6, 353, "powerlaw", paper_cr=1.66),
    MatrixSpec("mario002", 389874, 5.4, 7, "uniform", paper_cr=1.99),
    MatrixSpec("cage12", 130228, 15.6, 33, "banded", paper_cr=2.27),
    MatrixSpec("majorbasis", 160000, 10.9, 11, "banded", paper_cr=2.33),
    MatrixSpec("offshore", 259789, 16.3, 31, "banded", paper_cr=3.05),
    MatrixSpec("2cubes_sphere", 101492, 16.2, 31, "banded", paper_cr=3.06),
    MatrixSpec("poisson3Da", 13514, 26.1, 110, "banded", paper_cr=3.98),
    MatrixSpec("filter3D", 106437, 25.4, 112, "banded", paper_cr=4.26),
    MatrixSpec("mono_500Hz", 169410, 29.7, 719, "powerlaw", paper_cr=4.93),
    MatrixSpec("conf5_4-8x8-05", 49152, 39.0, 39, "banded", paper_cr=6.85),
    MatrixSpec("cant", 62451, 64.2, 78, "banded", paper_cr=15.45),
    MatrixSpec("consph", 83334, 72.1, 81, "banded", paper_cr=17.48),
    MatrixSpec("shipsec1", 140874, 55.5, 102, "banded", paper_cr=18.71),
    MatrixSpec("rma10", 46835, 50.7, 145, "banded", paper_cr=19.81),
    MatrixSpec("delaunay_n24", 16777216, 6.0, 26, "banded", True, 1.83),
    MatrixSpec("cage15", 5154859, 19.2, 47, "banded", True, 2.24),
    MatrixSpec("wb-edu", 9845725, 5.8, 3841, "powerlaw", True, 2.48),
    MatrixSpec("cop20k_A", 121192, 21.7, 81, "banded", True, 4.27),
    MatrixSpec("hood", 220542, 48.8, 77, "banded", True, 16.41),
    MatrixSpec("pwtk", 217918, 53.4, 180, "banded", True, 19.10),
    MatrixSpec("pdb1HYS", 36417, 119.3, 204, "banded", True, 28.34),
]

NORMAL = [m for m in TABLE3 if not m.large]
LARGE = [m for m in TABLE3 if m.large]

DEFAULT_SCALE = 32
LARGE_SCALE = 512


def generate(spec: MatrixSpec, *, scale: int | None = None,
             seed: int = 0) -> CSR:
    """Square synthetic analog of one Table-3 matrix (A for the A^2 bench)."""
    s = scale if scale is not None else (
        LARGE_SCALE if spec.large else DEFAULT_SCALE)
    n = max(spec.rows // s, 256)
    return random_csr(
        # crc32, not hash(): str hashes are salted per process.
        jax.random.PRNGKey(zlib.crc32(spec.name.encode()) % (2 ** 31) + seed),
        n, n,
        avg_nnz_per_row=spec.avg_nnz,
        max_nnz_per_row=min(spec.max_nnz, n),
        distribution=spec.dist)


def stats(A: CSR) -> Dict[str, float]:
    per_row = np.asarray(A.nnz_per_row())
    return {
        "rows": A.nrows,
        "nnz": int(A.nnz()),
        "avg_nnz": float(per_row.mean()),
        "max_nnz": int(per_row.max()),
    }
