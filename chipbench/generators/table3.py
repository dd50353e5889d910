"""Synthetic analogs of the OpSparse Table-3 matrices (arXiv:2206.07244),
calibrated to the published sizes.

The SuiteSparse originals cannot be fetched here, so a configuration is a
synthetic matrix that keeps what the paper publishes of its original:
the rows, the mean and the largest nnz per row, the intermediate
products of C = A * A (``paper_nprod``) and the nonzeros of C
(``paper_nnz_c``).  ``check`` refuses a matrix that departs from any of
them by more than the configuration's ``tolerance``; the largest row is
held exactly.

One family is built, ``banded`` (FEM- and Markov-chain-like): row i
holds distinct columns drawn uniformly from a window of ``window`` x its
size around the diagonal.  Row sizes are a field that varies smoothly
along the diagonal, as regions of a mesh do: a Normal level per block of
``block_rows`` rows, plus a Normal jitter per row, rounded and clipped to
[1, max].  Neighbouring rows then have alike sizes, so the rows that are
long are also the columns that are read often, which sets the products;
the window sets how much the rows of B overlap, which sets nnz(C).  The
parameters under ``banded`` were fitted once to the paper's numbers.

The structure is fixed by the configuration's name, as a real matrix is
fixed: every seed does the same work.  The values are drawn per request
by the traffic's driver.
"""
from __future__ import annotations

import zlib

import numpy as np

FAMILIES = ("banded",)


def _row_sizes(rng, rows: int, max_r: int, p: dict) -> np.ndarray:
    block = int(p["block_rows"])
    level = np.repeat(rng.standard_normal(-(-rows // block)), block)[:rows]
    sizes = (p["size_mean"] + p["size_sd_block"] * level
             + p["size_sd_row"] * rng.standard_normal(rows))
    return np.clip(np.rint(sizes), 1, max_r).astype(np.int64)


def _banded_cols(rng, sizes: np.ndarray, n: int, window: float):
    """Per row i, ``sizes[i]`` distinct columns from the window of
    ``window * sizes[i]`` slots centred on ``i * n / m``, sorted within
    the row; returns (row sizes, columns)."""
    m = sizes.size
    center = (np.arange(m, dtype=np.int64) * n) // max(m, 1)
    half = np.ceil(window * sizes / 2).astype(np.int64)
    lo = np.maximum(0, center - half)
    width = np.minimum(n, center + half + 1) - lo
    take = np.minimum(sizes, width)
    # Draw a random key per (row, window slot); a row keeps the slots with
    # its ``take`` smallest keys: a uniform choice without replacement.
    owner = np.repeat(np.arange(m), width)
    first = np.repeat(np.cumsum(width) - width, width)
    slot = np.arange(owner.size) - first
    # owner + u sorts by row, then by the key u in [0, 1), in one argsort.
    order = np.argsort(owner + rng.random(owner.size))
    rank = np.empty(owner.size, np.int64)
    rank[order] = slot
    keep = rank < np.repeat(take, width)      # slots stay row-sorted
    return take, (lo[owner] + slot)[keep]


def structure(config: dict):
    """(rpt, col) of the configuration's fixed structure, as int32."""
    family = config["family"]
    if family not in FAMILIES:
        raise ValueError(f"unknown family {family!r}; known: {FAMILIES}")
    m, n = int(config["rows"]), int(config["cols"])
    p = config[family]
    rng = np.random.default_rng(zlib.crc32(config["name"].encode()))
    sizes = _row_sizes(rng, m, min(int(config["max_nnz_per_row"]), n), p)
    sizes, col = _banded_cols(rng, sizes, n, float(p["window"]))
    rpt = np.concatenate([[0], np.cumsum(sizes)])
    return rpt.astype(np.int32), col.astype(np.int32)


def stats(config: dict, rpt: np.ndarray, nprod: int,
          nnz_c: int | None = None) -> dict:
    """The generated matrix beside the paper's numbers."""
    sizes = np.diff(rpt)
    out = {"rows": int(sizes.size), "paper_rows": int(config["rows"]),
           "nnz": int(rpt[-1]),
           "mean_nnz_per_row": float(sizes.mean()),
           "paper_mean_nnz_per_row": float(config["avg_nnz_per_row"]),
           "max_nnz_per_row": int(sizes.max()),
           "paper_max_nnz_per_row": int(config["max_nnz_per_row"]),
           "nprod": int(nprod), "paper_nprod": int(config["paper_nprod"])}
    if nnz_c is not None:
        out.update(nnz_c=int(nnz_c), paper_nnz_c=int(config["paper_nnz_c"]))
    return out


def check(config: dict, rpt: np.ndarray, nprod: int,
          nnz_c: int | None = None) -> dict:
    """``stats``, or SystemExit where the matrix departs from the paper:
    a relative gap above ``tolerance`` in the mean row, the products or
    nnz(C), or another largest row or row count."""
    s = stats(config, rpt, nprod, nnz_c)
    tol = float(config["tolerance"])
    off = [k for k in ("rows", "max_nnz_per_row") if s[k] != s[f"paper_{k}"]]
    off += [k for k in ("mean_nnz_per_row", "nprod", "nnz_c")
            if k in s and abs(s[k] / s[f"paper_{k}"] - 1) > tol]
    if off:
        raise SystemExit(f"chipbench: {config['name']} departs from its "
                         f"source in {off}: {s}")
    return s
