"""Device time by program phase (``chipbench/phases.py``): the reduction
by hand, the scopes read from an ``.xplane.pb``, and the harness's own
reduction left as it was."""
import json
from pathlib import Path

import pytest

from chipbench import phases, tracing

MS = 1_000_000
DATA = Path(__file__).with_name("data")


def _events():
    return {
        "spans": [["chipbench.window", 0, 100 * MS],
                  ["chipbench.call", 0, 90 * MS],
                  ["chipbench.wait", 90 * MS, 5 * MS],
                  ["chipbench.between", 95 * MS, 5 * MS]],
        "program_spans": [["opsparse.request", 1 * MS, 88 * MS],
                          ["opsparse.dispatch", 1 * MS, 2 * MS],
                          ["opsparse.finalize", 80 * MS, 9 * MS],
                          ["opsparse.verify_sync", 80 * MS, 8 * MS]],
        "ops": [[0, "%while.1", "while", 5 * MS, 40 * MS],
                [0, "%fusion.2", "fusion", 6 * MS, 38 * MS],  # its body
                [0, "%fusion.3", "fusion", 45 * MS, 10 * MS],
                [0, "%fusion.4", "fusion", 55 * MS, 10 * MS],
                [0, "%fusion.5", "fusion", 60 * MS, 10 * MS],
                [0, "%copy.6", "copy", 70 * MS, 5 * MS],
                [0, "%fusion.7", "fusion", 76 * MS, 2 * MS],
                [0, "%fusion.8", "fusion", 90 * MS, 20 * MS]],  # cut at 100
        "scopes": ["esc.compress/esc.sort", "esc.compress/esc.sort",
                   "esc.compress", "epilogue.r0", "epilogue.r1", "", "", ""],
    }


def test_phases_by_hand():
    ph = phases.summarize(_events())
    assert ph.window_s == pytest.approx(0.1)
    # busy: [5, 75) + [76, 78) + [90, 100) = 82 ms
    assert ph.busy_s == pytest.approx(0.082)
    # The sort loop and its body count once: 40 ms, not 78.
    assert ph.phase_s == {"esc.sort": pytest.approx(0.040),
                          "esc.compress": pytest.approx(0.010),
                          "epilogue.r0": pytest.approx(0.010),
                          "epilogue.r1": pytest.approx(0.010),
                          "": pytest.approx(0.017)}
    assert ph.unscoped_s == pytest.approx(0.017)
    # Gaps, longest first, named by the open spans, outermost first.
    assert ph.gaps == [
        ("call/request/finalize/verify_sync", pytest.approx(0.012)),
        ("call/request/dispatch", pytest.approx(0.005)),
        ("call/request", pytest.approx(0.001))]
    # Two products: the epilogue's two rungs overlap for 5 ms.
    assert phases.hash_epilogue_s(ph, 2) == pytest.approx(0.0075)
    assert phases.esc_sort_s(ph, 2) == pytest.approx(0.020)
    assert phases.unscoped_share(ph) == pytest.approx(100 * 17 / 82)


def test_metrics_find_nothing_to_read():
    events = _events()
    events["scopes"] = [""] * len(events["ops"])
    ph = phases.summarize(events)
    assert phases.hash_epilogue_s(ph, 2) is None
    assert phases.esc_sort_s(ph, 2) is None
    assert phases.unscoped_share(ph) == pytest.approx(100.0)
    assert phases.hash_epilogue_s(phases.summarize(_events()), 0) is None


def test_harness_reduction_reads_the_same_events():
    """The added lists change nothing the harness's reduction gives."""
    events = _events()
    plain = {k: events[k] for k in ("spans", "ops")}
    assert tracing.breakdown(tracing.summarize(events)) == \
        tracing.breakdown(tracing.summarize(plain))
    # The harness names a gap by its own innermost span alone.
    assert {name for name, _ in tracing.summarize(events).gaps} == {"call"}


@pytest.mark.parametrize("texts,scope", [
    (["%fusion.1 = f32[4] fusion(...)",
      "jit(run)/opsparse.fallback/jit(spgemm_fused)/opsparse.esc.compress"
      "/opsparse.esc.sort/sort"], "fallback/esc.compress/esc.sort"),
    (['%s.2 = s32[4] scatter(...), metadata={op_name="jit(run)/'
      'opsparse.epilogue.r1/scatter" source_file="x.py"}'], "epilogue.r1"),
    (["%copy.3 = s32[4] copy(...)", "copy"], ""),
])
def test_scope_of_op_texts(texts, scope):
    assert phases.scope_of(texts) == scope


# A hand-made XSpace: the protobuf wire format of the fields read.

def _varint(n):
    out = bytearray()
    while True:
        out.append((n & 0x7F) | (0x80 if n > 0x7F else 0))
        n >>= 7
        if not n:
            return bytes(out)


def _msg(*fields):
    out = b""
    for number, value in fields:
        if isinstance(value, float):                 # a fixed64 field
            out += _varint(number << 3 | 1) + bytes(8)
        elif isinstance(value, int):
            out += _varint(number << 3) + _varint(value)
        else:
            value = value.encode() if isinstance(value, str) else value
            out += _varint(number << 3 | 2) + _varint(len(value)) + value
    return out


def _xspace():
    stat_md = lambda i, name: (5, _msg((1, i), (2, _msg((1, i), (2, name)))))
    event_md = lambda i, *fields: (4, _msg((1, i), (2, _msg((1, i),
                                                            *fields))))
    tf_op = lambda v: (5, _msg((1, 9), (2, 1.0), (5, v)))
    device = _msg(
        (1, 5), (2, "/device:TPU:0"),
        stat_md(9, "tf_op"), stat_md(10, "jit(run)/opsparse.rowptr/cumsum"),
        event_md(3, (2, "%fusion.1 = s32[4] fusion(...)"),
                 tf_op("jit(run)/opsparse.hash.r2/scatter")),
        event_md(4, (2, "%copy.1 = s32[4] copy(...)")),
        event_md(6, (2, "%f.3"), (5, _msg((1, 9), (7, 10)))),
        (3, _msg((2, "XLA Modules"), (4, _msg((1, 4))))),
        (3, _msg((2, "XLA Ops"), *[(4, _msg((1, i), (2, 1000), (3, 5000)))
                                  for i in (3, 4, 6, 3)])))
    host = _msg((2, "/host:CPU"), event_md(1, (2, "opsparse.request")),
                (3, _msg((2, "python"), (4, _msg((1, 1))))))
    return _msg((1, host), (1, device))


def test_op_scopes_from_an_xplane(tmp_path):
    path = tmp_path / "t.xplane.pb"
    path.write_bytes(_xspace())
    assert phases.op_scopes(str(path)) == ["hash.r2", "", "rowptr",
                                           "hash.r2"]


def _recorded(name):
    return json.loads((DATA / name).read_text())


def test_recorded_hash_trace_by_phase():
    """Three cage12 products under method hash, traced on a TPU v5e with
    the program's scopes."""
    events = _recorded("trace_cage12_hash_phases.json")
    ph = phases.summarize(events)
    assert ph.window_s == pytest.approx(32.447092808)
    assert ph.busy_s == pytest.approx(32.405533645)
    per_product = {p: v / 3 for p, v in ph.phase_s.items()}
    assert per_product["epilogue.r2"] == pytest.approx(3.8885, abs=1e-4)
    assert per_product["hash.r1"] == pytest.approx(1.6474, abs=1e-4)
    assert phases.hash_epilogue_s(ph, 3) == pytest.approx(7.742574445)
    assert phases.esc_sort_s(ph, 3) is None
    assert phases.unscoped_share(ph) == pytest.approx(0.1514, abs=1e-4)
    # The harness's own reduction reads the same window and busy time.
    assert tracing.summarize(events).busy_s == pytest.approx(ph.busy_s)
    assert [g for g, _ in ph.gaps[:4]] == [
        "call/request", "call/request", "call/request",
        "call/request/finalize/verify_sync"]


def test_recorded_esc_trace_by_phase():
    """Two cage12 products under method esc: the expansion's binary
    search loop is ``esc.expand``, not the sort."""
    events = _recorded("trace_cage12_esc_phases.json")
    ph = phases.summarize(events)
    assert ph.window_s == pytest.approx(52.447633045)
    assert ph.busy_s == pytest.approx(52.422241983)
    assert ph.phase_s["esc.expand"] / 2 == pytest.approx(19.3805, abs=1e-4)
    assert phases.esc_sort_s(ph, 2) == pytest.approx(4.6987254445)
    assert phases.hash_epilogue_s(ph, 2) is None
    assert phases.unscoped_share(ph) == pytest.approx(0.0544, abs=1e-4)
