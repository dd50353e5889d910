"""Synthetic analogs of the power-law Table-3 matrices of OpSparse
(arXiv:2206.07244): web crawls, whose rows are pages and whose columns
are the pages they link to.

As for ``table3``, the originals cannot be fetched here, so the matrix
keeps what the paper publishes of its original (rows, mean and largest
nnz per row, the products of C = A * A and nnz(C)) and ``check``
(``table3.check``, unchanged) refuses one that departs from them.

The family is built from three properties of crawled web graphs:

- *Heavy-tailed out-degrees.*  Rows come in hosts (runs of neighbouring
  rows, as a crawl ordered by URL keeps a site's pages together; sizes
  geometric with mean ``host_rows``).  A host has a Pareto level of tail
  ``exponent``; a page's size is its host's level times a log-normal
  jitter of spread ``page_sd``, clipped to [1, max].  The scale is set so
  that the mean is the paper's, then single rows are nudged by one until
  nnz is exact, and the largest row is held at exactly ``max``.
- *In-degree follows out-degree* ("hubs are linked to").  Links go to
  hosts in bundles of ``bundle`` links, each bundle to one host and each
  link to a page of it drawn uniformly.  A share ``by_degree`` of the
  bundles picks its host by the host's total out-degree, the rest
  uniformly by page.  This sets the products, sum over k of in(k) out(k).
- *Host locality*: neighbouring pages share out-links.  Each host keeps
  one list of links (as long as its largest page), bundled as above; a
  page's first ``shared`` share of links is that list's head, the rest
  its own.  Two pages of a host then overlap in the shorter head, so a
  row that links into a host collects the same columns more than once:
  this sets nnz(C).

``exponent``, ``host_rows``, ``page_sd`` and ``bundle`` are assumed (the
configuration's ``assumed`` says why); ``by_degree`` and ``shared`` were
fitted once to the paper's products and nnz(C).  The structure is fixed
by the configuration's name, as in ``table3.structure``.
"""
from __future__ import annotations

import zlib

import numpy as np

from chipbench.generators.table3 import check, stats  # noqa: F401

FAMILY = "powerlaw"
_DEDUPE_ROUNDS = 1000   # redraws of colliding links before giving up
_BUNDLED_ROUNDS = 4     # rounds that redraw a collision inside its bundle
_TARGETED_ROUNDS = 100  # rounds before a collision is redrawn uniformly


def _hosts(rng, m: int, host_rows: float) -> np.ndarray:
    """Sizes of the runs of rows that make up the hosts (sum ``m``)."""
    sizes = rng.geometric(1.0 / host_rows, size=m)
    n = int(np.searchsorted(np.cumsum(sizes), m)) + 1
    sizes = sizes[:n].astype(np.int64)
    sizes[-1] -= int(sizes.sum()) - m
    return sizes


def _row_sizes(rng, host_of: np.ndarray, n_hosts: int, mean: float,
               max_r: int, p: dict) -> np.ndarray:
    m = host_of.size
    level = rng.random(n_hosts) ** (-1.0 / (float(p["exponent"]) - 1.0))
    sd = float(p["page_sd"])
    raw = level[host_of] * np.exp(sd * rng.standard_normal(m) - sd * sd / 2)

    def sized(scale):
        return np.clip(np.floor(scale * raw), 1, max_r).astype(np.int64)

    lo, hi = 0.0, 2.0 * mean * max_r
    for _ in range(100):                  # the scale that gives the mean
        mid = (lo + hi) / 2
        lo, hi = (mid, hi) if sized(mid).mean() < mean else (lo, mid)
    sizes = sized(hi)
    largest = int(np.argmax(sizes))
    sizes[largest] = max_r
    target = int(round(mean * m))
    while (gap := target - int(sizes.sum())) != 0:
        room = sizes < max_r if gap > 0 else sizes > 1
        room[largest] = False
        pick = np.flatnonzero(room)
        pick = rng.choice(pick, size=min(abs(gap), pick.size), replace=False)
        sizes[pick] += 1 if gap > 0 else -1
    return sizes


class _Targets:
    """Draws of linked pages: a bundle picks a host, by out-degree with
    chance ``by_degree`` and else uniformly by page; each link of the
    bundle is a page of that host drawn uniformly."""

    def __init__(self, rng, sizes, host_of, host_start, host_rows,
                 by_degree: float):
        self.rng, self.host_of = rng, host_of
        self.host_start, self.host_rows = host_start, host_rows
        self.cum = np.cumsum(sizes.astype(np.float64))
        self.by_degree = by_degree

    def pages(self, n: int) -> np.ndarray:
        """``n`` pages, each the page a fresh bundle's host is drawn by."""
        rng = self.rng
        page = np.searchsorted(self.cum, rng.random(n) * self.cum[-1],
                               side="right")
        uniform = rng.random(n) >= self.by_degree
        page[uniform] = rng.integers(0, self.host_of.size,
                                     int(uniform.sum()))
        return page

    def bundled(self, bundle_id: np.ndarray) -> np.ndarray:
        """One link per entry; entries of equal ``bundle_id`` (an int64
        key per entry) land in one host."""
        ids, inverse = np.unique(bundle_id, return_inverse=True)
        host = self.host_of[self.pages(ids.size)][inverse]
        return self.host_start[host] + self.rng.integers(
            0, self.host_rows[host])


def _distinct(owner, col, free, bundle_id, targets: _Targets, m: int):
    """Redraw links until no owner holds a column twice: of two equal
    links the one that is ``free`` to move (the later where both are) is
    drawn anew, first inside its bundle, then as a bundle of its own,
    last uniformly (a row near ``m`` links long)."""
    for r in range(_DEDUPE_ROUNDS):
        key = owner * m + col
        order = np.argsort(key, kind="stable")
        same = key[order[1:]] == key[order[:-1]]
        if not same.any():
            return col
        later, earlier = order[1:][same], order[:-1][same]
        move = np.unique(np.where(free[later], later, earlier))
        if r < _BUNDLED_ROUNDS:
            col[move] = targets.bundled(bundle_id[move])
        elif r < _TARGETED_ROUNDS:
            col[move] = targets.pages(move.size)
        else:
            col[move] = targets.rng.integers(0, m, move.size)
    raise RuntimeError("powerlaw: links still collide after "
                       f"{_DEDUPE_ROUNDS} redraws")


def structure(config: dict):
    """(rpt, col) of the configuration's fixed structure, as int32."""
    m, n = int(config["rows"]), int(config["cols"])
    if m != n:
        raise ValueError("powerlaw: a web graph is square (rows == cols)")
    p = config[FAMILY]
    bundle = int(p["bundle"])
    rng = np.random.default_rng(zlib.crc32(config["name"].encode()))
    host_rows = _hosts(rng, m, float(p["host_rows"]))
    host_start = np.concatenate([[0], np.cumsum(host_rows)[:-1]])
    host_of = np.repeat(np.arange(host_rows.size), host_rows)
    sizes = _row_sizes(rng, host_of, host_rows.size,
                       float(config["avg_nnz_per_row"]),
                       min(int(config["max_nnz_per_row"]), n), p)
    targets = _Targets(rng, sizes, host_of, host_start, host_rows,
                       float(p["by_degree"]))

    # Each host's shared list of links, as long as its largest page.
    list_len = np.zeros(host_rows.size, np.int64)
    np.maximum.at(list_len, host_of, sizes)
    list_start = np.concatenate([[0], np.cumsum(list_len)[:-1]])
    list_host = np.repeat(np.arange(host_rows.size), list_len)
    list_bundle = (np.arange(list_host.size) - list_start[list_host]) \
        // bundle + list_start[list_host]
    shared = targets.bundled(list_bundle)
    shared = _distinct(list_host, shared, np.ones(shared.size, bool),
                       list_bundle, targets, m)

    # Each page: the head of its host's list, then links of its own.
    nnz = int(sizes.sum())
    owner = np.repeat(np.arange(m), sizes)
    row_start = np.concatenate([[0], np.cumsum(sizes)[:-1]])
    pos = np.arange(nnz) - row_start[owner]
    head = np.floor(float(p["shared"]) * sizes).astype(np.int64)
    own = pos >= head[owner]
    col = np.empty(nnz, np.int64)
    col[~own] = shared[list_start[host_of[owner[~own]]] + pos[~own]]
    own_bundle = (pos - head[owner]) // bundle + row_start[owner]
    col[own] = targets.bundled(own_bundle[own])
    col = _distinct(owner, col, own, own_bundle, targets, m)

    col = col[np.argsort(owner * m + col, kind="stable")]
    rpt = np.concatenate([[0], np.cumsum(sizes)])
    return rpt.astype(np.int32), col.astype(np.int32)
