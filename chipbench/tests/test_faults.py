"""A whole run on the CPU with the timed path broken underneath: each
fault a cell of this benchmark can have makes ``correct`` false.

(A step that returns its state unchanged and a lost exchange between
chips cannot happen here: no cell trains, and every cell has one chip.)
"""
import jax.numpy as jnp
import pytest

from .conftest import last_json


def _alter_value(C):
    return C.__class__(rpt=C.rpt, col=C.col, val=C.val.at[0].add(1.0),
                       shape=C.shape)


def _alter_column(C):
    n = C.shape[1]
    return C.__class__(rpt=C.rpt, col=C.col.at[1].set((C.col[1] + 1) % n),
                       shape=C.shape, val=C.val)


def _drop_half_the_rows(C):
    m = C.shape[0]
    rpt = jnp.where(jnp.arange(m + 1) > m // 2, C.rpt[m // 2], C.rpt)
    return C.__class__(rpt=rpt, col=C.col, val=C.val, shape=C.shape)


def _stale_answer():
    """Every request gets the first answer the program computed, as a
    cache of results would give it: right structure, old values."""
    first = []

    def fault(C):
        first[:] = first or [C]
        return first[0]
    return fault


@pytest.mark.parametrize("make_fault,caught_by", [
    (lambda: _alter_value, "value_err"),
    (lambda: _alter_column, "col_off"),
    (lambda: _drop_half_the_rows, "rpt_off"),
    (_stale_answer, "value_err"),
])
def test_a_broken_product_is_not_correct(tiny_root, on_cpu, capsys,
                                         monkeypatch, make_fault, caught_by):
    fault = make_fault()
    on_cpu.import_program(tiny_root)
    from repro.engine.executor import SpgemmEngine
    finalize = SpgemmEngine._finalize_record

    def broken(self, rec):
        result = finalize(self, rec)
        result.C = fault(result.C)
        return result

    monkeypatch.setattr(SpgemmEngine, "_finalize_record", broken)
    assert on_cpu.main(["--workload", "cage12-tiny.esc-repeat", "--seed",
                        "9", "--seconds", "1"], root=tiny_root) == 0
    line = last_json(capsys.readouterr().out)
    assert line["correct"] is False
    c = line["checks"][caught_by]
    assert c["value"] is None or c["value"] > c["limit"]
