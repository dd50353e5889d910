"""Fig. 7/8 reproduction: binning cost — fused two-pass vs naive multi-pass.

The paper's claim: nsparse/spECK spend ~10% of total SpGEMM time binning
(global-memory atomics, one pass per bin); OpSparse's shared-memory binning
is ~1.5%.  Our analogs:
  * fused    — core.binning.bin_rows (histogram + cumsum + one stable sort,
               all device-side, one dispatch) = the shared-memory method.
  * naive    — one PASS PER BIN with a host sync each (boolean mask ->
               nonzero -> separate allocation), the global-memory
               many-kernel pattern of the baselines.

Reported: absolute binning time and binning as % of total spgemm() time.
"""
from __future__ import annotations

from typing import List

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import (SpgemmConfig, bin_rows_for_ladder, nprod_into_rpt,
                        spgemm, symbolic_ladder)

from .common import timeit
from .matrices import NORMAL, generate


def naive_binning(sizes, ladder):
    """One masked pass per bin + host syncs (baseline pattern)."""
    out = []
    prev = -1
    bounds = list(ladder.upper) + [np.inf]
    sizes_np = np.asarray(sizes)          # host roundtrip (global memory)
    for ub in bounds:
        members = np.nonzero((sizes_np > prev) & (sizes_np <= ub))[0]
        out.append(jnp.asarray(members))  # separate allocation per bin
        prev = ub
    return out


def run() -> List[str]:
    rows = []
    lad = symbolic_ladder(1.2)
    for spec in NORMAL[:12]:
        A = generate(spec)
        nprod = nprod_into_rpt(A, A)[:A.nrows]

        t_fused = timeit(lambda: bin_rows_for_ladder(nprod, lad).bins)
        t_naive = timeit(lambda: naive_binning(nprod, lad)[0])

        res = spgemm(A, A, SpgemmConfig(timing=True))
        total = sum(res.timings.values())
        bin_t = res.timings.get("bin", 0)      # both binnings
        rows.append(
            f"bench_binning/{spec.name},{t_fused*1e6:.0f},"
            f"naive_us={t_naive*1e6:.0f};speedup={t_naive/t_fused:.1f}x;"
            f"binning_pct_of_total={100*bin_t/max(total,1e-9):.1f}%")
        print(rows[-1], flush=True)
    return rows


if __name__ == "__main__":
    run()
