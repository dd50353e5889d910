"""Pallas TPU kernels: per-bin hash-table SpGEMM phases (OpSparse §5.2, §5.6).

Each grid step builds the hash tables of a few output rows (kernel1..7 of
the paper).  The probe loop is scalar code, and the TPU scalar unit can
address only SMEM, so the tables live in SMEM (the analog of the V100's
96 KB shared memory; 1 MiB on a v5e core, which bounds the table rungs).
Bin row ids and the CSR arrays stay in HBM (``pl.ANY``) and reach SMEM
through block DMAs of aligned 1024-entry tiles, each kept resident while
its index stream (rows, a_rpt, A entries, b_rpt, B entries) stays inside
it.  A dumped table leaves the kernel as a blocked SMEM output covering a
whole 1-D HBM tile per grid step.

Probe disciplines (paper §5.2, Fig. 9):
  * ``single_access=True``  — Algorithms 4/5: ONE table transaction per
    probe iteration.  On GPU this is the swapped-`atomicCAS` trick; a Pallas
    grid step owns its rows' tables, so the same discipline is a single
    read-modify-write per iteration, no CAS needed.
  * ``single_access=False`` — the nsparse/spECK baseline: check-then-CAS,
    i.e. a second table transaction whenever an empty slot is claimed (and
    for the numeric phase an extra transaction on the value slot).

Both variants report per-row TABLE ACCESS COUNTS so the Fig. 9 reproduction
can compare transaction counts exactly rather than relying on interpret-mode
wall time.

Overflow routing: the orchestrator bins rows so that row size <= table_size
/ multiplier; rows larger than the top rung go straight to the ESC (HBM)
accumulator (`core/esc.py`) — the analog of the paper's global-memory hash
kernels (symbolic kernel8 / numeric kernel7).  Unlike the paper we never
try-and-recompute: for the symbolic phase n_prod >= n_nz bounds the table
occupancy a priori, so the direct route can never overflow (the paper's
0.8-threshold recompute exists because it bins by n_prod but sizes kernel7's
table optimistically).  A probe-count guard (2*t_size) still protects
against misuse.

Sorting/condensing (paper's numeric "condense + sort" phases): done as a
*vectorized epilogue* outside the kernel — a per-row sort of the dumped
tables with the values as payload, then C's entries either gathered out
of the sorted tables (a rung whose tables outnumber C's positions) or
the tables' slots scattered into C (a smaller rung).  On TPU, sorts
vectorize on the VPU, whereas in-kernel scalar condense loops would
serialize; this is the hardware adaptation recorded in DESIGN.md.

Fusion (paper opt. 2, taken one step further): the two-pass flow builds
every row's hash table TWICE — the symbolic phase counts it, the numeric
phase rebuilds it from scratch to accumulate values.  ``fused_bin_call``
builds the (col, val) table ONCE per row and emits nnz, the raw table, and
the per-row transaction count in one ``pallas_call``; the numeric result
reuses the symbolic build instead of re-probing, roughly halving per-row
table transactions (measured by the Fig.-9 access counters, not asserted).
The two-pass kernels stay as the parity/access-count oracle.  All three
phases are one kernel factory (``_make_kernel``) with different outputs.

Row packing (paper opt. 3 trade-off, TPU form): a rung whose table is
smaller than a (8, 128) int32 tile is laid out ``ladder.rows_per_block[b]``
rows per tile as independent sub-tables (per-sub-row offsets), so the
dumped table stride shrinks with the rung instead of padding every row to
the tile.  The two-pass NUMERIC kernels stay unpacked: they dump their raw
tables, so packing would change the dumped stride for no occupancy win.
"""
from __future__ import annotations

import functools
import math
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro import phases
from repro.core import esc
from repro.core.analysis import exclusive_sum_in_place, nprod_per_entry
from repro.core.binning import Binning
from repro.core.binning_ranges import BinLadder
from repro.core.csr import CSR, gather_rows
from repro.core.workspace import next_bucket
from repro.kernels import resolve_interpret

HASH_SCALE = 107  # nsparse's multiplicative constant, kept (§5.2 "same way")
_PROBE_GUARD_FACTOR = 2  # safety: bail after 2*t_size probes (misuse guard)
_ROW_BUCKET_MIN = 8      # smallest per-rung row-count bucket
_BLK = 1024              # 1-D HBM tile: the granule of every operand DMA

INT32_MAX = np.iinfo(np.int32).max


def _is_pow2(n: int) -> bool:
    return n & (n - 1) == 0


def _hash_init(key, t_size: int):
    if _is_pow2(t_size):
        return (key * HASH_SCALE) & (t_size - 1)
    return (key * HASH_SCALE) % t_size


def _hash_next(h, t_size: int):
    if _is_pow2(t_size):          # §5.2: logic-AND when pow2 (symbolic)
        return (h + 1) & (t_size - 1)
    return jnp.where(h + 1 < t_size, h + 1, 0)  # mod path (numeric)


def _packed_geom(t_size: int, pack: int) -> Tuple[int, int]:
    """Packed table geometry.

    ``pack`` sub-tables of ``t_size`` entries live at stride ``stride``
    inside one lane-aligned (t_rows, 128) tile; returns (t_rows, stride).
    ``pack`` must be a power of two <= 128 so the tile splits evenly.
    """
    assert pack >= 1 and pack & (pack - 1) == 0 and pack <= 128, pack
    t_rows = max(1, -(-(pack * t_size) // 128))
    flat = t_rows * 128
    assert flat % pack == 0, (t_size, pack)
    return t_rows, flat // pack


def _step_geom(t_size: int, pack: int, rows_cap: int):
    """Grid geometry shared by the three kernels.

    One grid step owns ``tiles`` consecutive (t_rows, 128) tiles — enough
    that a step's flat table span is a whole 1-D HBM tile (_BLK entries),
    which is what a blocked SMEM output may cover.  Returns ``(stride,
    rps, steps)``: the per-row table stride, rows per grid step and the
    number of grid steps (``steps * rps >= rows_cap``; surplus rows are
    masked like any padding row).
    """
    assert rows_cap % pack == 0, (rows_cap, pack)
    t_rows, stride = _packed_geom(t_size, pack)
    tiles = 8 // math.gcd(t_rows, 8)
    rps = tiles * pack
    return stride, rps, -(-rows_cap // rps)


def _pad_blocks(x):
    """Pad a 1-D operand to whole _BLK blocks so every block DMA is in
    bounds on every backend."""
    pad = -x.shape[0] % _BLK
    return jnp.pad(x, (0, pad)) if pad else x


# ---------------------------------------------------------------------------
# The hash kernel.  One factory serves the three phases:
#   symbolic  (values=False, emit_nnz=True):  per-row nnz + accesses;
#   numeric   (values=True,  emit_nnz=False): dumped (col, val) tables;
#   fused     (values=True,  emit_nnz=True):  both from ONE build.
# ---------------------------------------------------------------------------

def _make_kernel(t_size: int, stride: int, rps: int, rows_cap: int, *,
                 values: bool, emit_nnz: bool, single_access: bool,
                 val_dtype):
    guard = _PROBE_GUARD_FACTOR * t_size
    span = rps * stride                       # table entries per grid step
    # HBM operands, grouped by the index stream that reads them: rows,
    # a_rpt, A entries (col[, val]), b_rpt, B entries (col[, val]).  A
    # group shares one block tag, and a miss fetches its members at once.
    w = 2 if values else 1
    groups = ((0,), (1,), tuple(range(2, 2 + w)), (2 + w,),
              tuple(range(3 + w, 3 + 2 * w)))
    ROWS, ARPT, AENT, BRPT, BENT = range(5)
    n_src = 3 + 2 * w

    def kernel(count_smem, *refs):
        zero = jnp.zeros((), val_dtype)
        src, refs = refs[:n_src], refs[n_src:]
        n_out = (2 if values else 0) + (1 if emit_nnz else 0) + 1
        outs, scratch = refs[:n_out], refs[n_out:]
        if values:                  # the tables are the dumped outputs
            col_tab, val_tab = outs[0], outs[1]
            outs = outs[2:]
        else:
            col_tab, scratch = scratch[0], scratch[1:]
            val_tab = None
        nnz_out = outs[0] if emit_nnz else None
        acc_out = outs[-1]
        bufs, (tags, sems) = scratch[:n_src], scratch[n_src:]
        step = pl.program_id(0)

        @pl.when(step == 0)
        def _():
            for g in range(len(groups)):
                tags[g] = -1

        def read(g: int, idx):
            """``src[s][idx]`` for every member ``s`` of group ``g``,
            through a one-block SMEM cache: scalar loads cannot address
            HBM, so the aligned _BLK block holding ``idx`` is DMA'd in
            first unless it is already resident."""
            blk = idx // _BLK

            @pl.when(tags[g] != blk)
            def _():
                start = pl.multiple_of(blk * _BLK, _BLK)
                cps = [pltpu.make_async_copy(src[s].at[pl.ds(start, _BLK)],
                                             bufs[s], sems.at[i])
                       for i, s in enumerate(groups[g])]
                for cp in cps:
                    cp.start()
                for cp in cps:
                    cp.wait()
                tags[g] = blk

            vals = tuple(bufs[s][idx % _BLK] for s in groups[g])
            return vals if len(vals) > 1 else vals[0]

        # One fresh table span per grid step (the paper re-initializes per
        # thread block); row j of the step owns [j*stride, j*stride+t_size).
        def clear(t, carry):
            col_tab[t] = jnp.int32(-1)
            if values:
                val_tab[t] = zero
            return carry

        jax.lax.fori_loop(0, span, clear, 0)

        def row(j, carry):
            idx = step * rps + j
            active = idx < count_smem[0]
            r = jnp.where(active, read(ROWS, jnp.minimum(idx, rows_cap - 1)),
                          0)
            base = j * stride
            a_lo = jnp.where(active, read(ARPT, r), 0)
            a_hi = jnp.where(active, read(ARPT, r + 1), 0)

            def insert(key, prod, carry):
                nnz, acc = carry
                h0 = _hash_init(key, t_size)

                def cond(st):
                    h, done, ins, probes = st
                    return (~done) & (probes < guard)

                def add_val(slot, hit):
                    if values:
                        val_tab[slot] = val_tab[slot] + jnp.where(
                            hit, prod, zero)

                if single_access:
                    # Alg 4/5 discipline: ONE col-table transaction per
                    # probe iteration; value touched on the terminal one.
                    def body(st):
                        h, done, ins, probes = st
                        slot = base + h
                        cur = col_tab[slot]                   # 1 transaction
                        empty = cur == -1
                        hit = empty | (cur == key)
                        col_tab[slot] = jnp.where(empty, key, cur)
                        add_val(slot, hit)
                        return (_hash_next(h, t_size), hit, ins | empty,
                                probes + 1)
                else:
                    # nsparse-style check-then-CAS baseline: a separate
                    # transaction claims the empty slot (read-again-and-write).
                    def body(st):
                        h, done, ins, probes = st
                        slot = base + h
                        cur = col_tab[slot]                   # transaction 1
                        empty = cur == -1
                        cur2 = jnp.where(empty, col_tab[slot], cur)  # 2
                        col_tab[slot] = jnp.where(empty, key, cur2)
                        hit = empty | (cur == key)
                        add_val(slot, hit)
                        return (_hash_next(h, t_size), hit, ins | empty,
                                probes +
                                jnp.where(empty, 2, 1).astype(jnp.int32))

                h, done, ins, probes = jax.lax.while_loop(
                    cond, body, (h0, jnp.asarray(False), jnp.asarray(False),
                                 jnp.int32(0)))
                return nnz + ins.astype(jnp.int32), acc + probes

            def outer(e, carry):
                k, av = read(AENT, a_lo + e) if values else (
                    read(AENT, a_lo + e), None)
                b_lo = read(BRPT, k)
                b_hi = read(BRPT, k + 1)

                def inner(jj, carry):
                    if values:
                        c, bv = read(BENT, b_lo + jj)
                        return insert(c, av * bv, carry)
                    return insert(read(BENT, b_lo + jj), None, carry)

                return jax.lax.fori_loop(0, b_hi - b_lo, inner, carry)

            nnz, acc = jax.lax.fori_loop(0, a_hi - a_lo, outer,
                                         (jnp.int32(0), jnp.int32(0)))
            if emit_nnz:
                nnz_out[0, j] = jnp.where(active, nnz, 0)
            acc_out[0, j] = jnp.where(active, acc, 0)
            return carry

        jax.lax.fori_loop(0, rps, row, 0)

    return kernel


def kernel_name(t_size: int) -> str:
    """The name of a hash kernel with ``t_size``-slot tables, as its
    compiled instruction and a device trace's op carry it."""
    return f"opsparse_hash_t{t_size}"


def _hash_call(rows, count, operands, *, t_size: int, rows_cap: int,
               pack: int, emit_nnz: bool, single_access: bool,
               interpret: Optional[bool]):
    """Build and run one hash ``pallas_call`` over one bin.

    Tables, block caches and the per-row counters live in SMEM: the
    probe loop is scalar code, and the TPU scalar unit addresses SMEM
    only.  The CSR operands stay in HBM and reach the kernel through
    aligned block DMAs.  Returns the flat outputs trimmed to
    ``rows_cap`` rows: ``[col_tabs, val_tabs]`` (rows_cap, stride) when
    the operands carry values, ``[nnz]`` when ``emit_nnz``, then
    ``accesses``.
    """
    interpret = resolve_interpret(interpret)
    stride, rps, steps = _step_geom(t_size, pack, rows_cap)
    values = len(operands) == 6             # (a_rpt, a_col, [a_val], ...)
    val_dtype = operands[2].dtype if values else jnp.int32
    srcs = [_pad_blocks(x) for x in (rows, *operands)]
    span = rps * stride
    smem_blk = lambda n: pl.BlockSpec((n,), lambda i, cnt: (i,),
                                      memory_space=pltpu.SMEM)
    row_blk = pl.BlockSpec((None, 1, rps), lambda i, cnt: (i, 0, 0),
                           memory_space=pltpu.SMEM)
    out_specs, out_shape = [], []
    if values:
        out_specs += [smem_blk(span), smem_blk(span)]
        out_shape += [jax.ShapeDtypeStruct((steps * span,), jnp.int32),
                      jax.ShapeDtypeStruct((steps * span,), val_dtype)]
    n_row_outs = 2 if emit_nnz else 1           # [nnz,] accesses
    out_specs += [row_blk] * n_row_outs
    out_shape += [jax.ShapeDtypeStruct((steps, 1, rps), jnp.int32)
                  ] * n_row_outs
    scratch = [] if values else [pltpu.SMEM((span,), jnp.int32)]
    scratch += [pltpu.SMEM((_BLK,), x.dtype) for x in srcs]
    scratch += [pltpu.SMEM((5,), jnp.int32),        # block tag per group
                pltpu.SemaphoreType.DMA((2,))]
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(steps,),
        in_specs=[pl.BlockSpec(memory_space=pl.ANY)] * len(srcs),
        out_specs=out_specs,
        scratch_shapes=scratch,
    )
    kernel = _make_kernel(t_size, stride, rps, rows_cap, values=values,
                          emit_nnz=emit_nnz, single_access=single_access,
                          val_dtype=val_dtype)
    # The name becomes the compiled custom call's, so a device trace
    # tells the rungs apart by their table size.
    outs = pl.pallas_call(kernel, grid_spec=grid_spec, out_shape=out_shape,
                          interpret=interpret,
                          name=kernel_name(t_size))(count, *srcs)
    tables = [t.reshape(steps * rps, stride)[:rows_cap]
              for t in outs[:2 if values else 0]]
    per_row = [c.reshape(-1)[:rows_cap] for c in outs[2 if values else 0:]]
    return tables + per_row


@functools.partial(
    jax.jit,
    static_argnames=("t_size", "rows_cap", "pack", "single_access",
                     "interpret"))
def symbolic_bin_call(rows, count, a_rpt, a_col, b_rpt, b_col, *,
                      t_size: int, rows_cap: int, pack: int = 1,
                      single_access: bool = True,
                      interpret: Optional[bool] = None):
    """Run the symbolic hash kernel over one bin.

    rows:  (rows_cap,) int32 row ids (padded); count: (1,) int32 valid rows.
    ``pack`` rows share one (t_rows, 128) tile as sub-tables (``pack=1``
    reproduces the one-table-per-row layout).
    Returns (nnz, accesses): both (rows_cap,) int32.
    """
    nnz, acc = _hash_call(
        rows, count, (a_rpt, a_col, b_rpt, b_col), t_size=t_size,
        rows_cap=rows_cap, pack=pack, emit_nnz=True,
        single_access=single_access, interpret=interpret)
    return nnz, acc


@functools.partial(
    jax.jit,
    static_argnames=("t_size", "rows_cap", "single_access", "interpret"))
def numeric_bin_call(rows, count, a_rpt, a_col, a_val, b_rpt, b_col, b_val,
                     *, t_size: int, rows_cap: int, single_access: bool,
                     interpret: Optional[bool] = None):
    """Run the numeric hash kernel over one bin (one table per row).

    Returns (col_tabs, val_tabs, accesses):
      col_tabs (rows_cap, t_pad) int32 — raw hash tables (-1 = empty);
      val_tabs (rows_cap, t_pad);  accesses (rows_cap,) int32.
    """
    col, val, acc = _hash_call(
        rows, count, (a_rpt, a_col, a_val, b_rpt, b_col, b_val),
        t_size=t_size, rows_cap=rows_cap, pack=1, emit_nnz=False,
        single_access=single_access, interpret=interpret)
    return col, val, acc


@functools.partial(
    jax.jit,
    static_argnames=("t_size", "rows_cap", "pack", "single_access",
                     "interpret"))
def fused_bin_call(rows, count, a_rpt, a_col, a_val, b_rpt, b_col, b_val,
                   *, t_size: int, rows_cap: int, pack: int = 1,
                   single_access: bool = True, interpret: Optional[bool] = None):
    """Run the fused symbolic->numeric hash kernel over one bin.

    ``pack`` rows' tables share one (t_rows, 128) tile as sub-tables
    (``pack=1`` reproduces the one-table-per-row layout).  Returns
    ``(nnz, col_tabs, val_tabs, accesses)``:
      nnz      (rows_cap,) int32 — distinct columns per row;
      col_tabs (rows_cap, stride) int32 — raw per-row tables (-1 empty);
      val_tabs (rows_cap, stride) — accumulated values;
      accesses (rows_cap,) int32 — per-row table transactions.
    """
    col, val, nnz, acc = _hash_call(
        rows, count, (a_rpt, a_col, a_val, b_rpt, b_col, b_val),
        t_size=t_size, rows_cap=rows_cap, pack=pack, emit_nnz=True,
        single_access=single_access, interpret=interpret)
    return nnz, col, val, acc


# ---------------------------------------------------------------------------
# Vectorized epilogue: condense + sort the dumped tables into CSR storage.
#
# Each rung's tables are sorted row by row (empties keyed to INT32_MAX sort
# last, the values ride along as the sort's payload).  The sorted entries
# then reach C one of two ways, picked per rung from static shapes
# (:func:`epilogue_gathers`): a rung whose table holds more slots than C
# has positions PULLS C's entries out of the sorted table (a gather over
# C's positions, each finding its slot through a running sum over C's
# row pointers); a smaller rung PUSHES its slots into C (a masked
# scatter over the table).  No arithmetic differs, so both give the same
# C bit for bit.
# ---------------------------------------------------------------------------

def epilogue_gathers(rows_cap: int, stride: int, nnz_capacity: int) -> bool:
    """Whether a rung's epilogue gathers C's entries from its sorted table
    (its ``rows_cap x stride`` table outnumbers C's ``nnz_capacity``
    positions) rather than scattering every table slot into C."""
    return rows_cap * stride > nnz_capacity


def _sort_tables(col_tabs, val_tabs):
    """Each row's table sorted by column, empties (keyed to INT32_MAX)
    last.  Returns ``(key, val)``: a row's first ``nnz`` keys are its
    columns, ascending."""
    key = jnp.where(col_tabs < 0, INT32_MAX, col_tabs)
    return jax.lax.sort((key, val_tabs), dimension=1, num_keys=1)


def _per_position(rpt, per_row, capacity: int):
    """``per_row``'s value of the row of each position of C's storage,
    ``(capacity,)`` int32.

    Each row's value lands at its ``rpt`` as the difference from the
    row before (empty rows' differences add up into the next row that
    starts at the same position), and a running sum carries it over the
    row's positions.  Positions past nnz(C) read the value of the last
    row that starts at or before them: callers mask them off."""
    steps = jnp.zeros(capacity, jnp.int32).at[rpt[:-1]].add(
        jnp.diff(per_row, prepend=0), mode="drop")
    return jnp.cumsum(steps)


@functools.partial(jax.jit, donate_argnames=("c_col", "c_val"))
def epilogue_scatter(col_tabs, val_tabs, bin_rows, count, rpt, c_col, c_val):
    """Sort each row's table and scatter every slot into C storage: slot
    ``j`` of row ``r`` to ``rpt[r] + j``, slots past the row's nnz (and
    padding rows) out of bounds, where they drop.  C's storage is
    donated: called outside a jit, C is updated in place."""
    rows_cap, stride = col_tabs.shape
    key, val = _sort_tables(col_tabs, val_tabs)
    nnz_row = jnp.sum((key < INT32_MAX).astype(jnp.int32), axis=1)
    valid_row = jnp.arange(rows_cap, dtype=jnp.int32) < count
    lane = jnp.arange(stride, dtype=jnp.int32)[None, :]
    mask = (lane < nnz_row[:, None]) & valid_row[:, None]
    start = rpt[jnp.where(valid_row, bin_rows, 0)][:, None]
    target = jnp.where(mask, start + lane, c_col.shape[0]).reshape(-1)
    c_col = c_col.at[target].set(key.reshape(-1), mode="drop")
    c_val = c_val.at[target].set(val.reshape(-1), mode="drop")
    return c_col, c_val


@functools.partial(jax.jit, donate_argnames=("c_col", "c_val"))
def epilogue_gather(col_tabs, val_tabs, bin_rows, count, rpt, c_col, c_val):
    """Sort each row's table and gather C's entries of this rung's rows
    out of it: position ``k`` of row ``r`` takes slot ``k - rpt[r]`` of
    ``r``'s table.  Positions of other rows and past nnz(C) keep what
    they hold.  ``bin_rows`` lists the valid rows in increasing order, as
    ``Binning.rows_of_bin`` does.  C's storage is donated, as in
    :func:`epilogue_scatter`."""
    rows_cap, stride = col_tabs.shape
    cap, m = c_col.shape[0], rpt.shape[0] - 1
    key, val = _sort_tables(col_tabs, val_tabs)
    valid_row = jnp.arange(rows_cap, dtype=jnp.int32) < count
    slot = jnp.full(m, -1, jnp.int32).at[
        jnp.where(valid_row, bin_rows, m)].set(
            jnp.arange(rows_cap, dtype=jnp.int32), mode="drop")
    # A row's flat table index of its entry k, less k; below -k off
    # the rung.
    base = jnp.where(slot >= 0, slot * stride - rpt[:m], -cap)
    k = jnp.arange(cap, dtype=jnp.int32)
    src = k + _per_position(rpt, base, cap)
    take = (src >= 0) & (k < rpt[m])
    # ``bin_rows`` lists a rung's rows in row order, so their slots, and
    # the sources of C's positions, rise with k; the running max carries
    # them over the other positions, which makes the gather's indices
    # sorted.  Keys and values ride one gather, a pair per index.
    src = jax.lax.cummax(jnp.where(take, src, 0))
    key, val = key.reshape(-1), val.reshape(-1)
    if val.dtype.itemsize == key.dtype.itemsize:
        pair = jnp.stack([key, jax.lax.bitcast_convert_type(val, key.dtype)])
        got = pair.at[:, src].get(indices_are_sorted=True,
                                  mode="promise_in_bounds")
        key_k, val_k = got[0], jax.lax.bitcast_convert_type(got[1], val.dtype)
    else:
        key_k, val_k = key[src], val[src]
    return jnp.where(take, key_k, c_col), jnp.where(take, val_k, c_val)


def numeric_epilogue(rung: int, col_tabs, val_tabs, bin_rows, count, rpt,
                     c_col, c_val):
    """Rung ``rung``'s dumped tables into C under the rung's epilogue
    scope, by gather or by scatter as :func:`epilogue_gathers` rules."""
    gathers = epilogue_gathers(*col_tabs.shape, c_col.shape[0])
    with phases.scope(phases.epilogue_rung(rung)):
        return (epilogue_gather if gathers else epilogue_scatter)(
            col_tabs, val_tabs, bin_rows, count, rpt, c_col, c_val)


# ---------------------------------------------------------------------------
# Schedule-driven drivers (called by the engine and by the binned wrappers).
#
# The launch schedule — which rungs run, with how many (padded) rows each —
# used to be a per-call host decision (``np.asarray(binning.bin_size)``).
# It is now a STATIC argument: ``row_buckets`` gives a pow-2 row-count
# capacity per rung (last entry = the ESC fallback rung), 0 meaning the
# rung is statically absent.  With the schedule static the whole phase is
# one traceable function with zero host syncs; callers verify afterwards
# that the actual bin sizes fit the buckets (the engine folds that check
# into its single finalize sync and grows the plan on overflow).
# ---------------------------------------------------------------------------

def _fallback_rows(binning: Binning, ladder: BinLadder, cap: int, m: int):
    """Fallback-rung row ids padded to static ``cap`` (+ validity mask)."""
    fallback_bin = len(ladder.table_sizes)
    rows, count = binning.rows_of_bin(fallback_bin, cap)
    valid = jnp.arange(cap, dtype=jnp.int32) < count
    return jnp.where(valid, rows, m), valid


def _check_schedule(row_buckets, ladder: BinLadder, fallback_prod_capacity):
    assert len(row_buckets) == ladder.num_bins, (row_buckets, ladder)
    assert not row_buckets[-1] or fallback_prod_capacity > 0, \
        "active fallback rung needs a sub-product capacity"


def symbolic_scheduled(A: CSR, B: CSR, binning: Binning, ladder: BinLadder,
                       *, row_buckets, fallback_prod_capacity: int = 0,
                       single_access: bool = True, interpret: Optional[bool] = None,
                       row_packing: bool = False,
                       collect_accesses: bool = False):
    """Symbolic phase over a static bucketed schedule — fully traceable.

    Rungs are dispatched LARGEST first (the §5.5 launch-order rule: the
    long pole starts earliest), beginning with the ESC fallback rung.
    Returns ``(nnz_buf, sub_prod, accesses)`` where ``sub_prod`` is the
    fallback rung's intermediate-product total (a device scalar the
    caller verifies against ``fallback_prod_capacity``; an overflowed
    fallback truncates its expansion, so results are only trustworthy
    when the check passes).

    ``row_packing`` batches ``ladder.rows_per_block[b]`` rows per grid
    step on rungs whose tables underfill a VMEM tile (``row_buckets``
    must then be multiples of the pack — ``host_schedule(packs=...)``
    guarantees it), exactly as in :func:`fused_scheduled`.
    """
    _check_schedule(row_buckets, ladder, fallback_prod_capacity)
    m = A.nrows
    with phases.scope(phases.ROWPTR):
        nnz_buf = jnp.zeros(m + 1, dtype=jnp.int32)
    accesses = jnp.int32(0)
    sub_prod = jnp.int32(0)

    if row_buckets[-1]:
        # Global-memory-analog rung: ESC on the gathered sub-matrix.
        with phases.scope(phases.FALLBACK):
            rows, valid = _fallback_rows(binning, ladder, row_buckets[-1], m)
            sub = gather_rows(A, rows, valid)
            sub_prod = jnp.sum(jnp.where(
                valid, nprod_of_rows(A, B, rows), 0)).astype(jnp.int32)
            sub_nnz = esc.symbolic(sub, B,
                                   prod_capacity=fallback_prod_capacity)
            tgt = jnp.where(valid, rows, m + 1)
            nnz_buf = nnz_buf.at[tgt].set(sub_nnz[:rows.shape[0]],
                                          mode="drop")

    for b in range(len(ladder.table_sizes) - 1, -1, -1):
        rows_cap = row_buckets[b]
        if not rows_cap:
            continue
        pack = ladder.rows_per_block[b] if row_packing else 1
        pack = min(pack, rows_cap)         # both pow-2: stays divisible
        with phases.scope(phases.hash_rung(b)):
            rows, count = binning.rows_of_bin(b, rows_cap)
            nnz_bin, acc_bin = symbolic_bin_call(
                rows, count.reshape(1), A.rpt, A.col, B.rpt, B.col,
                t_size=ladder.table_sizes[b], rows_cap=rows_cap, pack=pack,
                single_access=single_access, interpret=interpret)
            valid = jnp.arange(rows_cap, dtype=jnp.int32) < count
            tgt = jnp.where(valid, rows, m + 1)
            nnz_buf = nnz_buf.at[tgt].set(nnz_bin, mode="drop")
            if collect_accesses:
                accesses = accesses + jnp.sum(jnp.where(valid, acc_bin, 0))

    return nnz_buf, sub_prod, accesses


def schedule_bucket(count: int, *, m_cap: int, headroom: float,
                    pack: int = 1) -> int:
    """Pow-2 bin-count bucket for one rung's observed row count.

    The ONE shared copy of the schedule bucket math: ``host_schedule``
    (cold derivation) and ``engine/autotune`` (trim re-derivation from
    observed maxima) must agree bit-for-bit or a trimmed schedule would
    drift from what a later cold floor re-derives.  ``count`` is coerced
    to a Python int, so near-2^31 counts widen instead of wrapping.

    With headroom the bucket must strictly EXCEED the headroom target: an
    observed count already on a pow-2 would otherwise learn a bucket with
    zero margin, and any jitter overflows it (the boundary-straddle
    failure the headroom exists to prevent).  headroom=1.0 (the faithful
    per-call path) keeps exact buckets.  ``pack`` floors the bucket at a
    rung's pow-2 rows-per-block so packed kernels get whole grid steps.
    """
    count = int(count)
    if not count:
        return 0
    lo = max(_ROW_BUCKET_MIN, int(pack))
    strict = 1 if headroom > 1.0 else 0
    return min(max(m_cap, lo),
               next_bucket(int(np.ceil(count * headroom)) + strict,
                           minimum=lo))


def fallback_capacity_bucket(sub_prod: int, *, headroom: float) -> int:
    """Pow-2 capacity bucket for the fallback rung's ESC expansion (same
    strict-exceed rule as :func:`schedule_bucket`; host int math)."""
    strict = 1 if headroom > 1.0 else 0
    return next_bucket(int(np.ceil(max(int(sub_prod), 1) * headroom))
                       + strict, minimum=_ROW_BUCKET_MIN)


def host_schedule(A: CSR, B: CSR, binning: Binning, ladder: BinLadder, *,
                  headroom: float = 1.0, packs: Tuple[int, ...] = None):
    """Host-side schedule derivation (the cold path's ONE metadata sync).

    Reads the device bin sizes, buckets each rung's row count to a pow-2
    capacity (0 = empty rung, statically skipped), and — when the
    fallback rung is populated — syncs its sub-product total to size the
    ESC expansion.  ``headroom`` over-provisions the buckets (the engine
    learns schedules with headroom so steady-state bin-count jitter stays
    inside the learned buckets instead of forcing retraces: padding rows
    are masked grid steps, far cheaper than a recompile).

    ``packs`` (per table rung, e.g. ``ladder.rows_per_block``) floors each
    populated rung's bucket at its pow-2 rows-per-block so packed kernels
    always get a whole number of grid steps; padding rows beyond the bin
    count are masked sub-tables.
    """
    sizes = np.asarray(binning.bin_size)       # host sync: launch schedule
    m_cap = next_bucket(binning.bins.shape[0], minimum=_ROW_BUCKET_MIN)

    row_buckets = tuple(
        schedule_bucket(
            s, m_cap=m_cap, headroom=headroom,
            pack=(packs[b] if packs is not None and b < len(packs) else 1))
        for b, s in enumerate(sizes))
    fallback_prod_capacity = 0
    if row_buckets[-1]:
        rows, valid = _fallback_rows(binning, ladder, row_buckets[-1],
                                     A.nrows)
        sub_prod = int(jnp.sum(                # host sync: fallback alloc
            jnp.where(valid, nprod_of_rows(A, B, rows), 0)))
        fallback_prod_capacity = fallback_capacity_bucket(
            sub_prod, headroom=headroom)
    return row_buckets, fallback_prod_capacity


def symbolic_binned(A: CSR, B: CSR, binning: Binning, ladder: BinLadder, *,
                    prod_capacity: int = 0, single_access: bool = True,
                    interpret: Optional[bool] = None,
                    row_packing: bool = False,
                    collect_accesses: bool = False):
    """Host-orchestrated symbolic phase (cold / standalone path).

    Syncs the bin sizes once to derive an exact bucketed schedule, then
    runs the traceable ``symbolic_scheduled`` form.  Returns the (M+1,)
    n_nz buffer (optionally also the total table-access count).
    ``prod_capacity`` is unused (kept for signature compatibility: the
    hash rungs size their tables from the ladder, not the expansion).
    """
    del prod_capacity
    packs = ladder.rows_per_block if row_packing else None
    row_buckets, fall_cap = host_schedule(A, B, binning, ladder, packs=packs)
    nnz_buf, _, accesses = symbolic_scheduled(
        A, B, binning, ladder, row_buckets=row_buckets,
        fallback_prod_capacity=fall_cap, single_access=single_access,
        interpret=interpret, row_packing=row_packing,
        collect_accesses=collect_accesses)
    if collect_accesses:
        return nnz_buf, accesses
    return nnz_buf


def nprod_of_rows(A: CSR, B: CSR, rows: jax.Array) -> jax.Array:
    """n_prod of each of ``rows`` (ids past A's last row read its last
    row; callers mask them): a running sum of A's products per entry,
    differenced at the rows' pointers.  It holds one value per stored
    entry of A whatever the number of rows; a mask of A's storage per
    row held rows x storage (webbase-1M's numeric fallback rung: 4,096
    rows x 4.19M slots, 16 GB, in the eager cold path).  The int32 sum
    may wrap; a row's difference is exact while the row has under 2^31
    products."""
    csum = jnp.cumsum(nprod_per_entry(A, B))
    csum = jnp.concatenate([jnp.zeros(1, csum.dtype), csum])
    safe_rows = jnp.minimum(rows, A.nrows - 1)
    return csum[A.rpt[safe_rows + 1]] - csum[A.rpt[safe_rows]]


def numeric_scheduled(A: CSR, B: CSR, rpt: jax.Array, binning: Binning,
                      ladder: BinLadder, *, row_buckets,
                      nnz_capacity: int, fallback_prod_capacity: int = 0,
                      single_access: bool = True, interpret: Optional[bool] = None,
                      collect_accesses: bool = False):
    """Numeric phase over a static bucketed schedule — fully traceable.

    Mirrors ``symbolic_scheduled``: per-rung fixed-capacity kernels,
    largest rung (the ESC fallback) first, no host syncs.  Returns
    ``(C, sub_prod, accesses)``; the caller verifies ``sub_prod`` against
    ``fallback_prod_capacity`` (overflow truncates the fallback rows).
    """
    _check_schedule(row_buckets, ladder, fallback_prod_capacity)
    m, n = A.nrows, B.ncols
    with phases.scope(phases.ROWPTR):          # C's storage
        c_col = jnp.zeros(nnz_capacity, jnp.int32)
        c_val = jnp.zeros(nnz_capacity, A.val.dtype)
    accesses = jnp.int32(0)
    sub_prod = jnp.int32(0)

    if row_buckets[-1]:
        with phases.scope(phases.FALLBACK):
            rows, valid = _fallback_rows(binning, ladder, row_buckets[-1], m)
            sub = gather_rows(A, rows, valid)
            sub_prod = jnp.sum(jnp.where(
                valid, nprod_of_rows(A, B, rows), 0)).astype(jnp.int32)
            subC = esc.spgemm_fused(sub, B,
                                    prod_capacity=fallback_prod_capacity,
                                    nnz_capacity=fallback_prod_capacity)
        with phases.scope(phases.EPILOGUE_FALLBACK):
            c_col, c_val = scatter_sub_rows(
                subC, rows, valid, rpt, c_col, c_val,
                nnz_capacity=nnz_capacity)

    for b in range(len(ladder.table_sizes) - 1, -1, -1):
        rows_cap = row_buckets[b]
        if not rows_cap:
            continue
        with phases.scope(phases.hash_rung(b)):
            rows, count = binning.rows_of_bin(b, rows_cap)
            col_tabs, val_tabs, acc_bin = numeric_bin_call(
                rows, count.reshape(1), A.rpt, A.col, A.val, B.rpt, B.col,
                B.val, t_size=ladder.table_sizes[b], rows_cap=rows_cap,
                single_access=single_access, interpret=interpret)
            if collect_accesses:
                valid = jnp.arange(rows_cap, dtype=jnp.int32) < count
                accesses = accesses + jnp.sum(jnp.where(valid, acc_bin, 0))
        c_col, c_val = numeric_epilogue(b, col_tabs, val_tabs, rows, count,
                                        rpt, c_col, c_val)

    C = CSR(rpt=rpt, col=c_col, val=c_val, shape=(m, n))
    return C, sub_prod, accesses


def numeric_binned(A: CSR, B: CSR, rpt: jax.Array, binning: Binning,
                   ladder: BinLadder, *, prod_capacity: int = 0,
                   nnz_capacity: int, single_access: bool = True,
                   interpret: Optional[bool] = None,
                   collect_accesses: bool = False):
    """Host-orchestrated numeric phase (cold / standalone path) -> CSR.

    Schedule derivation as in ``symbolic_binned``; ``prod_capacity`` is
    unused (signature compatibility).
    """
    del prod_capacity
    row_buckets, fall_cap = host_schedule(A, B, binning, ladder)
    C, _, accesses = numeric_scheduled(
        A, B, rpt, binning, ladder, row_buckets=row_buckets,
        nnz_capacity=nnz_capacity, fallback_prod_capacity=fall_cap,
        single_access=single_access, interpret=interpret,
        collect_accesses=collect_accesses)
    if collect_accesses:
        return C, accesses
    return C


def fused_scheduled(A: CSR, B: CSR, binning: Binning, ladder: BinLadder, *,
                    row_buckets, nnz_capacity: int,
                    fallback_prod_capacity: int = 0,
                    single_access: bool = True, interpret: Optional[bool] = None,
                    row_packing: bool = False,
                    collect_accesses: bool = False):
    """Fused symbolic->numeric phase over a static schedule — traceable.

    ONE binning (by n_prod, the symbolic ladder — the only pre-data row
    size), ONE table build per row: each populated rung's
    :func:`fused_bin_call` emits per-row nnz AND the accumulated (col,
    val) tables, the fallback rung runs the single-expansion ESC
    (``esc.spgemm_fused``, its n_nz read off the sub-result's rpt), and
    once every row's nnz is known the row pointers are an exclusive sum
    and the dumped tables condense/sort into C — no second probe
    pass anywhere.  The symbolic-ladder tables are sized by n_prod
    (>= n_nz), so the numeric accumulation can never overflow them; the
    larger tables trade VMEM footprint for a LOWER collision rate than
    the two-pass numeric rungs (§5.6's trade-off, resolved towards fewer
    transactions).

    ``row_packing`` batches ``ladder.rows_per_block[b]`` rows per grid
    step on rungs whose tables underfill a VMEM tile (``row_buckets``
    must then be multiples of the pack — ``host_schedule(packs=...)``
    guarantees it).

    Returns ``(C, nnz, sub_prod, accesses)``: the assembled CSR, the (M,)
    per-row nnz (the caller's total_nnz source), the fallback rung's
    sub-product total to verify against ``fallback_prod_capacity``, and
    the summed table-transaction count (0 unless ``collect_accesses``).
    """
    _check_schedule(row_buckets, ladder, fallback_prod_capacity)
    m, n = A.nrows, B.ncols
    with phases.scope(phases.ROWPTR):
        nnz_buf = jnp.zeros(m + 1, dtype=jnp.int32)
    accesses = jnp.int32(0)
    sub_prod = jnp.int32(0)
    fallback = None
    kept = []

    if row_buckets[-1]:
        # Global-memory-analog rung, fused form: one ESC expansion yields
        # both the sub-result values AND (via its rpt) the per-row nnz.
        with phases.scope(phases.FALLBACK):
            rows, valid = _fallback_rows(binning, ladder, row_buckets[-1], m)
            sub = gather_rows(A, rows, valid)
            sub_prod = jnp.sum(jnp.where(
                valid, nprod_of_rows(A, B, rows), 0)).astype(jnp.int32)
            subC = esc.spgemm_fused(sub, B,
                                    prod_capacity=fallback_prod_capacity,
                                    nnz_capacity=fallback_prod_capacity)
            cap = rows.shape[0]
            sub_nnz = (subC.rpt[1:cap + 1] - subC.rpt[:cap]).astype(jnp.int32)
            tgt = jnp.where(valid, rows, m + 1)
            nnz_buf = nnz_buf.at[tgt].set(sub_nnz, mode="drop")
        fallback = (subC, rows, valid)

    for b in range(len(ladder.table_sizes) - 1, -1, -1):
        rows_cap = row_buckets[b]
        if not rows_cap:
            continue
        pack = ladder.rows_per_block[b] if row_packing else 1
        pack = min(pack, rows_cap)         # both pow-2: stays divisible
        with phases.scope(phases.hash_rung(b)):
            rows, count = binning.rows_of_bin(b, rows_cap)
            nnz_bin, col_tabs, val_tabs, acc_bin = fused_bin_call(
                rows, count.reshape(1), A.rpt, A.col, A.val, B.rpt, B.col,
                B.val, t_size=ladder.table_sizes[b], rows_cap=rows_cap,
                pack=pack, single_access=single_access, interpret=interpret)
            valid = jnp.arange(rows_cap, dtype=jnp.int32) < count
            tgt = jnp.where(valid, rows, m + 1)
            nnz_buf = nnz_buf.at[tgt].set(nnz_bin, mode="drop")
            if collect_accesses:
                accesses = accesses + jnp.sum(jnp.where(valid, acc_bin, 0))
        kept.append((b, rows, count, col_tabs, val_tabs))

    with phases.scope(phases.ROWPTR):
        nnz = nnz_buf[:m]
        rpt = exclusive_sum_in_place(nnz_buf)
        c_col = jnp.zeros(nnz_capacity, jnp.int32)
        c_val = jnp.zeros(nnz_capacity, A.val.dtype)
    if fallback is not None:
        subC, rows, valid = fallback
        with phases.scope(phases.EPILOGUE_FALLBACK):
            c_col, c_val = scatter_sub_rows(
                subC, rows, valid, rpt, c_col, c_val,
                nnz_capacity=nnz_capacity)
    for b, rows, count, col_tabs, val_tabs in kept:
        c_col, c_val = numeric_epilogue(b, col_tabs, val_tabs, rows, count,
                                        rpt, c_col, c_val)

    C = CSR(rpt=rpt, col=c_col, val=c_val, shape=(m, n))
    return C, nnz, sub_prod, accesses


def _dumped_tables(ladder: BinLadder, row_buckets, row_packing: bool):
    """``(rows_cap, stride)`` of each populated table rung's dumped
    tables under a schedule (``row_packing`` as in
    :func:`fused_scheduled`; the two-pass numeric tables are unpacked)."""
    for b, t_size in enumerate(ladder.table_sizes):
        rows_cap = row_buckets[b]
        if rows_cap:
            pack = min(ladder.rows_per_block[b] if row_packing else 1,
                       rows_cap)
            yield rows_cap, _step_geom(t_size, pack, rows_cap)[0]


def epilogue_slots(ladder: BinLadder, row_buckets, *,
                   row_packing: bool = False) -> int:
    """Table slots the epilogue sorts for one product under a schedule:
    over the populated table rungs, the rung's row bucket times its
    dumped table stride (padding rows and empty slots included, as
    :func:`numeric_epilogue` sorts whole tables)."""
    return sum(r * s for r, s in _dumped_tables(ladder, row_buckets,
                                                row_packing))


def epilogue_gathered_slots(ladder: BinLadder, row_buckets, *,
                            nnz_capacity: int,
                            row_packing: bool = False) -> int:
    """The part of :func:`epilogue_slots` in rungs whose epilogue gathers
    C's entries (:func:`epilogue_gathers`) instead of scattering every
    slot."""
    return sum(r * s for r, s in _dumped_tables(ladder, row_buckets,
                                                row_packing)
               if epilogue_gathers(r, s, nnz_capacity))


def fused_binned(A: CSR, B: CSR, binning: Binning, ladder: BinLadder, *,
                 nnz_capacity: int, single_access: bool = True,
                 interpret: Optional[bool] = None, row_packing: bool = False,
                 collect_accesses: bool = False):
    """Host-orchestrated fused pipeline (cold / standalone path) -> CSR.

    ``binning`` must be the n_prod binning on the SYMBOLIC ladder (the
    fused kernel sizes each row's one table by n_prod).  Schedule
    derivation as in ``symbolic_binned``, with pack-aligned buckets when
    ``row_packing``.
    """
    packs = ladder.rows_per_block if row_packing else None
    row_buckets, fall_cap = host_schedule(A, B, binning, ladder, packs=packs)
    C, nnz, _, accesses = fused_scheduled(
        A, B, binning, ladder, row_buckets=row_buckets,
        nnz_capacity=nnz_capacity, fallback_prod_capacity=fall_cap,
        single_access=single_access, interpret=interpret,
        row_packing=row_packing, collect_accesses=collect_accesses)
    if collect_accesses:
        return C, accesses
    return C


@functools.partial(jax.jit, static_argnames=("nnz_capacity",))
def scatter_sub_rows(subC: CSR, orig_rows, valid, rpt, c_col, c_val, *,
                     nnz_capacity: int):
    """Copy rows of a sub-CSR result into the final C storage."""
    sub_rows = subC.row_ids()                     # sub-row of each entry
    entry_ok = subC.entry_mask() & (sub_rows < subC.nrows)
    safe_sub = jnp.minimum(sub_rows, subC.nrows - 1)
    row_ok = entry_ok & valid[safe_sub]
    orig = orig_rows[safe_sub]
    offs = jnp.arange(subC.capacity, dtype=jnp.int32) - subC.rpt[safe_sub]
    target = jnp.where(row_ok, rpt[jnp.minimum(orig, rpt.shape[0] - 2)] + offs,
                       nnz_capacity)
    c_col = c_col.at[target].set(subC.col, mode="drop")
    c_val = c_val.at[target].set(subC.val, mode="drop")
    return c_col, c_val
