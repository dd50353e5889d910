"""The control, the reference in bfloat16 storage, fails the value
limit that float32 answers pass (tiny sizes; the readings at the cells'
own sizes are in PERF.md)."""
import numpy as np
import pytest
import scipy.sparse as sp

from chipbench import reference
from chipbench.drivers import closed_loop
from chipbench.generators import table3

from .conftest import tiny_config


def _float32_answer(rpt, col, val, shape, ref):
    S = sp.csr_matrix((val.astype(np.float32), col, rpt), shape=shape)
    C = S @ S
    C.sort_indices()
    P = sp.csr_matrix((ref.abs_val, ref.C.col, ref.C.rpt), shape=shape)
    return reference.Answer(ref.C.rpt, ref.C.col,
                            reference._on_pattern(C, P).astype(np.float32))


@pytest.mark.parametrize("seed", [1, 2, 3, 2**31 + 7])
def test_control_fails_where_float32_passes(seed):
    cfg = tiny_config("cage12")
    shape = (cfg["rows"], cfg["cols"])
    rpt, col = table3.structure(cfg)
    val = closed_loop.values(seed, 0, col.size)
    ref = reference.reference(rpt, col, val, shape)
    reported = [(ref.nprod, ref.nnz)]
    limits = cfg["limits"]

    def compare(ans):
        return reference.compare([(ref, ans)], reported, 0, ref.nprod,
                                 ref.nnz, shape[1], limits)

    ok = compare(_float32_answer(rpt, col, val, shape, ref))
    assert reference.passed(ok), ok
    assert ok["value_err"]["value"] < limits["value_err"] / 10

    ctl = compare(reference.control(rpt, col, val, shape, ref))
    assert not reference.passed(ctl)
    assert ctl["value_err"]["value"] > 10 * limits["value_err"]
    assert all(ctl[k]["value"] == 0 for k in ("rpt_off", "col_off"))
