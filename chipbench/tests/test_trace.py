"""The reduction from trace events to busy time, op time and idle gaps,
by hand and on a trace recorded on a TPU v5e."""
import json
from pathlib import Path

import pytest

from chipbench import tracing
from chipbench.metrics import (device_idle_share, hash_kernel_s,
                               product_roofline, sort_s)

DATA = Path(__file__).with_name("data")


def _run(summary, products=1, least_s=1e-3):
    import types
    return types.SimpleNamespace(summary=summary, products=products,
                                 least_s=least_s)


def test_summary_by_hand():
    ms = 1_000_000
    events = {
        "spans": [["chipbench.window", 0, 100 * ms],
                  ["chipbench.call", 0, 60 * ms],
                  ["chipbench.wait", 60 * ms, 30 * ms],
                  ["chipbench.between", 90 * ms, 10 * ms]],
        "ops": [[0, "%sort.1", "sort", 10 * ms, 20 * ms],
                [0, "%fusion.2", "fusion", 20 * ms, 20 * ms],   # overlaps
                [0, "%k.3", "tpu_custom_call", 70 * ms, 10 * ms],
                [0, "%fusion.4", "fusion", 95 * ms, 20 * ms],   # cut at 100
                [0, "%fusion.5", "fusion", 150 * ms, 5 * ms]],  # outside
    }
    s = tracing.summarize(events)
    assert s.window_s == pytest.approx(0.1)
    # busy: [10, 40) + [70, 80) + [95, 100) = 45 ms
    assert s.busy_s == pytest.approx(0.045)
    assert s.seconds(tracing.is_sort) == pytest.approx(0.02)
    assert s.seconds(tracing.is_pallas) == pytest.approx(0.01)
    # gaps: [0,10) call, [40,70) call/wait edge at 55 -> call,
    # [80,95) -> wait (mid 87.5)
    assert s.gaps == [("call", pytest.approx(0.03)),
                      ("wait", pytest.approx(0.015)),
                      ("call", pytest.approx(0.01))]
    assert device_idle_share.read(_run(s)) == pytest.approx(55.0)
    assert product_roofline.read(_run(s, products=3, least_s=0.003)) == \
        pytest.approx(100 * 0.003 / 0.015)
    assert sort_s.read(_run(s, products=2)) == pytest.approx(0.01)
    b = tracing.breakdown(s)
    assert b["device_ops"][0] == ["%sort.1 = sort", pytest.approx(0.02)]
    assert len(b["idle_gaps"]) == 3


def test_chips_are_averaged():
    events = {"spans": [["chipbench.window", 0, 100]],
              "ops": [[0, "%a", "fusion", 0, 100],
                      [1, "%a", "fusion", 0, 50]]}
    s = tracing.summarize(events)
    assert s.chips == 2 and s.busy_s == pytest.approx(75e-9)


def test_a_trace_without_device_ops_is_refused():
    with pytest.raises(ValueError):
        tracing.summarize({"spans": [["chipbench.window", 0, 1]],
                           "ops": []})


def test_metrics_find_nothing_to_read():
    s = tracing.summarize({"spans": [["chipbench.window", 0, 100]],
                           "ops": [[0, "%f.1", "fusion", 0, 10]]})
    assert hash_kernel_s.read(_run(s)) is None
    assert sort_s.read(_run(s)) is None
    assert product_roofline.read(_run(s, products=0)) is None


def test_op_name_and_kind_from_hlo_text():
    assert tracing.op_name_kind(
        "%fusion.5 = f32[67108864]{0:T(1024)} fusion(f32[131072,512]"
        "{1,0:T(8,128)} %reshape.8), kind=kCustom") == ("%fusion.5", "fusion")
    assert tracing.op_name_kind(
        "%sort.10 = (s32[130228]{0:T(1024)}, s32[130228]{0:T(1024)S(1)}) "
        "sort(s32[130228]{0:T(1024)S(1)} %a), dimensions={0}") == \
        ("%sort.10", "sort")
    assert tracing.op_name_kind(
        '%k.1 = (s32[4]{0}, f32[4]{0}) custom-call(s32[1]{0} %a), '
        'custom_call_target="tpu_custom_call"') == ("%k.1",
                                                    "tpu_custom_call")
    assert tracing.op_name_kind("plain") == ("plain", "")


def test_recorded_tpu_trace():
    """Two cage12 products under method hash, traced on a TPU v5e."""
    events = json.loads((DATA / "trace_cage12_hash.json").read_text())
    s = tracing.summarize(events)
    assert s.chips == 1
    assert s.window_s == pytest.approx(12.017505926)
    assert s.busy_s == pytest.approx(11.991239172)
    assert device_idle_share.read(_run(s)) == pytest.approx(0.2186, abs=1e-3)
    assert hash_kernel_s.read(_run(s, products=2)) == \
        pytest.approx(3.992742712 / 2)
    assert sort_s.read(_run(s, products=2)) == \
        pytest.approx(0.678523949 / 2)
    b = tracing.breakdown(s)
    assert b["device_ops"][0] == ["%fused_bin_call.1 = tpu_custom_call",
                                  pytest.approx(3.992742712)]
    assert len(b["device_ops"]) == len(b["idle_gaps"]) == tracing.TOP
    assert {name for name, _ in b["idle_gaps"]} <= {"call", "wait",
                                                    "between", "none"}
