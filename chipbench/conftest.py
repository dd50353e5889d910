"""Tiny copies of configurations whose generator is not ``table3``.

``tests/conftest.py``'s ``tiny_root`` builds a tiny copy of every
configuration of BENCHMARK.json through ``tiny_config``, which knows
only ``table3``'s sizes (``TINY_ROWS``).  This conftest, loaded before
that one, gives ``tiny_config`` the other families: each configuration
is built with its own generator at the size given here, its paper
numbers replaced by the tiny matrix's own, so that its check still
holds.  ``table3`` configurations are left to the original.
"""
import json

import numpy as np

from chipbench.tests import conftest as _tests

# Tiny sizes by configuration name: a few thousand rows, a largest row
# that still lands on the upper hash rungs.
TINY_SIZES = {"webbase-1M": dict(rows=2048, max_nnz_per_row=300)}


def tiny_config(name: str, _table3=_tests.tiny_config) -> dict:
    if name in _tests.TINY_ROWS:
        return _table3(name)
    from chipbench import harness, reference, work
    cfg = json.loads((_tests.ROOT / "chipbench" / "configs" / f"{name}.json")
                     .read_text())
    size = TINY_SIZES[name]
    cfg.update(size, name=f"{name}-tiny", cols=size["rows"])
    rpt, col = harness.plugin("generators", cfg["generator"]).structure(cfg)
    P = reference.pattern(rpt, col, (cfg["rows"], cfg["cols"]))
    cfg.update(avg_nnz_per_row=float(np.diff(rpt).mean()),
               max_nnz_per_row=int(np.diff(rpt).max()),
               paper_nprod=work.n_prod(rpt, col, rpt), paper_nnz_c=P.nnz)
    return cfg


_tests.tiny_config = tiny_config
