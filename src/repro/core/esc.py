"""ESC (expand–sort–compress) accumulator — the TPU-idiomatic / fallback path.

The paper's accumulator is a per-row shared-memory hash table; rows too big
for the largest table spill to a *global-memory* hash table (symbolic
kernel8 / numeric kernel7).  On TPU, scalar hash probing underuses the VPU,
and the natural HBM-resident accumulator is a **sorted reduction**: expand
all intermediate products, sort by (row, col), and segment-reduce
duplicates.  This module implements that path fully vectorized in jnp — it
serves as

  * the production accumulator on flat/vector hardware,
  * the fallback ("global memory") rung of the hash ladder, and
  * the oracle the Pallas hash kernels are validated against.

Shapes are static: the expansion size is a host-chosen bucket
``prod_capacity >= total_nprod`` (pow-2 bucketing, see ``spgemm.py``);
padding products carry row id M / col id N and sort to the end.

Device scopes (``repro.phases``): the three products run under
``opsparse.esc.compress``, with the expansion and the sort inside it
under ``opsparse.esc.expand`` and ``opsparse.esc.sort``.
"""
from __future__ import annotations

from functools import partial
from typing import Tuple

import jax
import jax.numpy as jnp

from repro import phases

from .csr import CSR
from .analysis import nprod_per_entry


@partial(jax.jit, static_argnames=("prod_capacity", "with_values"))
@phases.scope(phases.ESC_EXPAND)
def expand_products(A: CSR, B: CSR, *, prod_capacity: int,
                    with_values: bool = True):
    """Enumerate all intermediate products of C = A·B, row-major.

    Returns (rows, cols, vals, valid):
      rows/cols: (prod_capacity,) int32; padding = (M, N).
      vals:      (prod_capacity,) or None when ``with_values=False`` —
                 the symbolic phase avoids the multiply, like the paper.
      valid:     (prod_capacity,) bool.

    Construction: per-A-entry product counts -> exclusive offsets; each
    product slot t finds its A entry by searchsorted, its B entry by
    ``t - offset[e]``.  Everything is a gather; no data-dependent shapes.
    """
    m, n = A.nrows, B.ncols
    per_entry = nprod_per_entry(A, B)                       # (capA,)
    offsets = jnp.concatenate(
        [jnp.zeros(1, jnp.int32),
         jnp.cumsum(per_entry)[:-1].astype(jnp.int32)])     # (capA,)
    total = jnp.sum(per_entry)

    t = jnp.arange(prod_capacity, dtype=jnp.int32)
    valid = t < total
    # A entry owning product slot t: last e with offsets[e] <= t.
    e = jnp.searchsorted(offsets, t, side="right").astype(jnp.int32) - 1
    e = jnp.clip(e, 0, max(A.capacity - 1, 0))
    j = t - offsets[e]

    a_col = jnp.minimum(A.col[e], B.nrows - 1)
    b_idx = jnp.minimum(B.rpt[a_col] + j, max(B.capacity - 1, 0))

    a_rows = A.row_ids()                                    # (capA,)
    rows = jnp.where(valid, a_rows[e], m).astype(jnp.int32)
    cols = jnp.where(valid, B.col[b_idx], n).astype(jnp.int32)
    vals = None
    if with_values:
        vals = jnp.where(valid, A.val[e] * B.val[b_idx], 0)
    return rows, cols, vals, valid


@phases.scope(phases.ESC_SORT)
def _sort_products(rows, cols, vals):
    """Stable (row, col) sort.  Two-key lexsort avoids 64-bit keys (the
    fused key row*N+col overflows int32 for the paper's large matrices)."""
    order = jnp.lexsort((cols, rows))
    rows, cols = rows[order], cols[order]
    vals = None if vals is None else vals[order]
    return rows, cols, vals


@partial(jax.jit, static_argnames=("prod_capacity",))
@phases.scope(phases.ESC_COMPRESS)
def symbolic(A: CSR, B: CSR, *, prod_capacity: int) -> jax.Array:
    """Symbolic phase: (M+1,) buffer with n_nz per row in [0:M] (rpt reuse).

    No value multiply — mirrors the paper's symbolic step.
    """
    rows, cols, _, valid = expand_products(
        A, B, prod_capacity=prod_capacity, with_values=False)
    rows, cols, _ = _sort_products(rows, cols, None)
    prev_rows = jnp.concatenate([jnp.full((1,), -1, jnp.int32), rows[:-1]])
    prev_cols = jnp.concatenate([jnp.full((1,), -1, jnp.int32), cols[:-1]])
    is_new = (rows != prev_rows) | (cols != prev_cols)
    is_real = rows < A.nrows
    buf = jnp.zeros(A.nrows + 1, dtype=jnp.int32)
    return buf.at[rows].add((is_new & is_real).astype(jnp.int32), mode="drop")


@partial(jax.jit, static_argnames=("prod_capacity", "nnz_capacity"))
@phases.scope(phases.ESC_COMPRESS)
def numeric(A: CSR, B: CSR, rpt: jax.Array, *, prod_capacity: int,
            nnz_capacity: int) -> CSR:
    """Numeric phase: fill C.col / C.val given the symbolic-phase ``rpt``.

    Output rows are sorted by column id (the paper's numeric kernels sort
    after condensing; the global (row, col) sort gives this for free).
    """
    m, n = A.nrows, B.ncols
    rows, cols, vals, valid = expand_products(
        A, B, prod_capacity=prod_capacity, with_values=True)
    rows, cols, vals = _sort_products(rows, cols, vals)
    prev_rows = jnp.concatenate([jnp.full((1,), -1, jnp.int32), rows[:-1]])
    prev_cols = jnp.concatenate([jnp.full((1,), -1, jnp.int32), cols[:-1]])
    is_real = rows < m
    is_new = ((rows != prev_rows) | (cols != prev_cols)) & is_real
    # Output slot of each product = (#unique keys before it) - 1; products
    # of the same (row, col) share the slot and accumulate.
    out_idx = jnp.cumsum(is_new.astype(jnp.int32)) - 1
    out_idx = jnp.where(is_real, out_idx, nnz_capacity)  # drop padding
    col_out = jnp.zeros(nnz_capacity, jnp.int32).at[out_idx].max(
        jnp.where(is_real, cols, 0), mode="drop")
    val_out = jnp.zeros(nnz_capacity, vals.dtype).at[out_idx].add(
        jnp.where(is_real, vals, 0), mode="drop")
    return CSR(rpt=rpt, col=col_out, val=val_out, shape=(m, n))


@partial(jax.jit, static_argnames=("prod_capacity", "nnz_capacity"))
@phases.scope(phases.ESC_COMPRESS)
def spgemm_fused(A: CSR, B: CSR, *, prod_capacity: int,
                 nnz_capacity: int) -> CSR:
    """One-pass ESC SpGEMM (expand once, derive rpt AND values).

    Beyond-paper optimization for the sorted accumulator: the symbolic and
    numeric phases share one expansion+sort when the nnz bucket is already
    known (steady-state shapes), halving HBM traffic.  Falls back to the
    faithful two-phase flow in ``spgemm.py`` when capacities are unknown.
    """
    m, n = A.nrows, B.ncols
    rows, cols, vals, _ = expand_products(
        A, B, prod_capacity=prod_capacity, with_values=True)
    rows, cols, vals = _sort_products(rows, cols, vals)
    prev_rows = jnp.concatenate([jnp.full((1,), -1, jnp.int32), rows[:-1]])
    prev_cols = jnp.concatenate([jnp.full((1,), -1, jnp.int32), cols[:-1]])
    is_real = rows < m
    is_new = ((rows != prev_rows) | (cols != prev_cols)) & is_real
    nnz_buf = jnp.zeros(m + 1, jnp.int32).at[rows].add(
        is_new.astype(jnp.int32), mode="drop")
    rpt = jnp.concatenate(
        [jnp.zeros(1, jnp.int32), jnp.cumsum(nnz_buf[:-1]).astype(jnp.int32)])
    out_idx = jnp.cumsum(is_new.astype(jnp.int32)) - 1
    out_idx = jnp.where(is_real, out_idx, nnz_capacity)
    col_out = jnp.zeros(nnz_capacity, jnp.int32).at[out_idx].max(
        jnp.where(is_real, cols, 0), mode="drop")
    val_out = jnp.zeros(nnz_capacity, vals.dtype).at[out_idx].add(
        jnp.where(is_real, vals, 0), mode="drop")
    return CSR(rpt=rpt, col=col_out, val=val_out, shape=(m, n))
