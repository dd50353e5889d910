"""Pallas TPU kernel: binning pass-1 histogram with VMEM accumulation.

The direct analog of the paper's Alg. 1: each grid step (thread-block
analog) owns a block of rows, classifies them against the rung bounds in
registers/VMEM, accumulates a LOCAL histogram, and adds one line into the
global bin_size output — one HBM transaction per block instead of one
atomic per row (the paper's s_bin_size -> d_bin_size staging).  Also
tracks the running max row size (Alg. 1 line 6/19) for the Alg. 3
fast-path decision.

Grid steps on TPU run sequentially per core, so the accumulation into the
shared output block is race-free by construction (the same property the
paper gets from atomics).
"""
from __future__ import annotations

import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels import resolve_interpret


_LANES = 128


def _make_kernel(upper: Tuple[int, ...], num_bins: int, rows: int,
                 m: int):
    def kernel(sizes_ref, hist_ref, max_ref):
        i = pl.program_id(0)
        vals = sizes_ref[...]                          # (rows, 128)
        r = jax.lax.broadcasted_iota(jnp.int32, vals.shape, 0)
        lane = jax.lax.broadcasted_iota(jnp.int32, vals.shape, 1)
        valid = (i * rows + r) * _LANES + lane < m
        # classify: first rung admitting the size == count of exceeded
        # bounds (vectorized Alg-1 range scan; bounds are static ints)
        bin_ids = jnp.zeros(vals.shape, jnp.int32)
        for bound in upper:
            bin_ids += (vals > bound).astype(jnp.int32)

        @pl.when(i == 0)
        def _init():
            hist_ref[...] = jnp.zeros_like(hist_ref)
            max_ref[...] = jnp.zeros_like(max_ref)

        # local histogram (one vector line) -> one accumulate into the
        # output line
        out_lane = jax.lax.broadcasted_iota(jnp.int32, hist_ref.shape, 1)
        local = jnp.zeros(hist_ref.shape, jnp.int32)
        for b in range(num_bins):
            count = jnp.sum(((bin_ids == b) & valid).astype(jnp.int32),
                            keepdims=True)
            local = jnp.where(out_lane == b, count, local)
        hist_ref[...] += local
        max_ref[...] = jnp.maximum(
            max_ref[...], jnp.max(jnp.where(valid, vals, 0), keepdims=True))

    return kernel


@functools.partial(jax.jit,
                   static_argnames=("upper", "num_bins", "block",
                                    "interpret"))
def binning_histogram(sizes, *, upper: Tuple[int, ...], num_bins: int,
                      block: int = 1024, interpret: Optional[bool] = None):
    """Pass-1 of the binning method as a Pallas kernel.

    A grid step classifies ``block`` row sizes, rounded up to whole
    (8, 128) int32 tiles.  ``interpret=None`` auto-detects (compiled on
    TPU, interpreted elsewhere).  Returns (bin_size (num_bins,) int32,
    max_size () int32)."""
    interpret = resolve_interpret(interpret)
    assert num_bins <= _LANES, num_bins
    m = sizes.shape[0]
    rows = -(-max(block, 8 * _LANES) // (8 * _LANES)) * 8
    step = rows * _LANES
    m_pad = -(-m // step) * step
    sizes = jnp.pad(sizes.astype(jnp.int32), (0, m_pad - m))
    kernel = _make_kernel(upper, num_bins, rows, m)
    hist, mx = pl.pallas_call(
        kernel,
        grid=(m_pad // step,),
        in_specs=[pl.BlockSpec((rows, _LANES), lambda i: (i, 0))],
        out_specs=[pl.BlockSpec((1, _LANES), lambda i: (0, 0)),
                   pl.BlockSpec((1, 1), lambda i: (0, 0))],
        out_shape=[jax.ShapeDtypeStruct((1, _LANES), jnp.int32),
                   jax.ShapeDtypeStruct((1, 1), jnp.int32)],
        interpret=interpret,
    )(sizes.reshape(-1, _LANES))
    return hist[0, :num_bins], mx[0, 0]
