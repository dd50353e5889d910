"""The Table-3 analog: calibrated to the paper's numbers at full size,
fixed by the configuration's name, refused where it departs; and the
per-request values drawn from the seed."""
import json

import numpy as np
import pytest

from chipbench import reference, work
from chipbench.drivers import closed_loop
from chipbench.generators import table3

from .conftest import ROOT, tiny_config


def _config(name):
    return json.loads((ROOT / "chipbench" / "configs" / f"{name}.json")
                      .read_text())


def test_cage12_matches_its_source_at_full_size():
    cfg = _config("cage12")
    rpt, col = table3.structure(cfg)
    shape = (cfg["rows"], cfg["cols"])
    P = reference.pattern(rpt, col, shape)
    s = table3.check(cfg, rpt, work.n_prod(rpt, col, rpt), P.nnz)
    assert s["rows"] == 130228 and s["max_nnz_per_row"] == 33
    assert s["mean_nnz_per_row"] == pytest.approx(15.6, rel=0.01)
    assert s["nprod"] == pytest.approx(34.6e6, rel=0.01)
    assert s["nnz_c"] == pytest.approx(34.6e6 / 2.27, rel=0.01)


@pytest.mark.parametrize("field,factor", [
    ("paper_nprod", 1.05), ("paper_nnz_c", 0.95),
    ("avg_nnz_per_row", 1.05), ("max_nnz_per_row", 2)])
def test_a_departure_from_the_source_is_refused(field, factor):
    cfg = tiny_config("cage12")
    rpt, col = table3.structure(cfg)
    nprod = work.n_prod(rpt, col, rpt)
    nnz_c = reference.pattern(rpt, col, (cfg["rows"], cfg["cols"])).nnz
    table3.check(cfg, rpt, nprod, nnz_c)
    bad = dict(cfg, **{field: type(cfg[field])(cfg[field] * factor)})
    with pytest.raises(SystemExit, match="departs from its source"):
        table3.check(bad, rpt, nprod, nnz_c)


def test_the_structure_is_fixed_by_the_name():
    cfg = tiny_config("cage12")
    a, b = table3.structure(cfg), table3.structure(dict(cfg))
    for x, y in zip(a, b):
        np.testing.assert_array_equal(x, y)
    rpt, col = a
    assert rpt.dtype == np.int32 and col.dtype == np.int32
    rows = np.repeat(np.arange(rpt.size - 1), np.diff(rpt))
    key = rows.astype(np.int64) * cfg["cols"] + col
    assert (np.diff(key) > 0).all()              # sorted, no duplicate
    other = table3.structure(dict(cfg, name="other"))
    assert not np.array_equal(other[1], col)


def test_banded_rows_stay_near_the_diagonal():
    cfg = tiny_config("cage12")
    rpt, col = table3.structure(cfg)
    sizes = np.diff(rpt)
    rows = np.repeat(np.arange(sizes.size), sizes)
    half = np.ceil(cfg["banded"]["window"] * sizes / 2)
    assert (np.abs(col - rows) <= np.repeat(half, sizes)).all()


def test_unknown_family_is_refused():
    with pytest.raises(ValueError):
        table3.structure(dict(tiny_config("cage12"), family="dense"))


def test_values_are_drawn_from_the_seed_and_the_request():
    big = 2**31 + 11
    v = closed_loop.values(big, 3, 1000)
    assert v.dtype == np.float32 and v.shape == (1000,)
    np.testing.assert_array_equal(v, closed_loop.values(big, 3, 1000))
    assert not np.array_equal(v, closed_loop.values(big, 4, 1000))
    assert not np.array_equal(v, closed_loop.values(big + 1, 3, 1000))
    assert abs(float(v.mean())) < 0.2 and 0.8 < float(v.std()) < 1.2
