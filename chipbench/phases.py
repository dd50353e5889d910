"""Device time by program phase, from a profiler trace of the window.

The program names its phases (``src/repro/phases.py``): every device op
of its steady executables carries ``opsparse.<phase>`` scopes in its HLO
``op_name`` metadata, and the engine's telemetry spans are host
annotations ``opsparse.<span>`` on the same clock.  ``tracing.read``
keeps neither; ``read`` here returns its events plus two more lists:

- ``scopes``: per op (aligned with ``ops``), the op's ``opsparse.``
  scopes outermost first, joined by "/" without the prefix (for example
  ``fallback/esc.compress/esc.sort``), or "" when it has none.  Taken
  from the op's event metadata in the ``.xplane.pb``: the first of its
  name and string stats that mentions a scope;
- ``program_spans``: one ``[name, start_ns, dur_ns]`` per host
  annotation of the program (names starting ``opsparse.``).

``summarize`` reduces those to device seconds per phase (an op's phase
is its innermost scope) as the union of the phase's op intervals inside
the window, so a ``while`` and the fusions of its body count once; the
busy time no scoped op covers; and the idle gaps, each named by the
spans open across it, harness and program, outermost first
(``call/request/finalize/verify_sync``).

    python3 -m chipbench.phases --workload <cell> --seed <n> --seconds <s>

traces one window of a cell as the harness does and prints the phase
breakdown per product (``--events PATH`` also writes the events), from
the root of a checkout.  It needs a TPU, like ``run.py``.
"""
from __future__ import annotations

import argparse
import dataclasses
import glob
import json
import os
import sys
import tempfile
from pathlib import Path
from typing import Dict, List, Optional, Tuple

from chipbench import tracing

PREFIX = "opsparse."
UNSCOPED = ""
EPILOGUE = "epilogue."
ESC_SORT = "esc.sort"


# ---------------------------------------------------------------------------
# The op metadata of an .xplane.pb (the XSpace protobuf, decoded by hand:
# only the fields below are read).
# ---------------------------------------------------------------------------

def _varint(buf, i: int) -> Tuple[int, int]:
    shift = value = 0
    while True:
        b = buf[i]
        i += 1
        value |= (b & 0x7F) << shift
        if b < 0x80:
            return value, i
        shift += 7


def _fields(buf):
    """(number, value) of each field of one message: an int for a varint,
    bytes for a length-delimited field (fixed-width fields are skipped)."""
    i, n = 0, len(buf)
    while i < n:
        key, i = _varint(buf, i)
        number, wire = key >> 3, key & 7
        if wire == 0:
            value, i = _varint(buf, i)
        elif wire == 2:
            size, i = _varint(buf, i)
            value, i = buf[i:i + size], i + size
        elif wire in (1, 5):
            i += 8 if wire == 1 else 4
            continue
        else:
            raise ValueError(f"unsupported protobuf wire type {wire}")
        yield number, value


def _text(b) -> str:
    return bytes(b).decode("utf-8", "replace")


def _op_texts(plane) -> List[List[str]]:
    """For each event of a device plane's ``XLA Ops`` line in order, the
    texts of its metadata: name, display name, string stats (a stat that
    refers to a stat name gives that name).  None for other planes."""
    fields = list(_fields(plane))
    name = next((_text(v) for f, v in fields if f == 2), "")   # XPlane.name
    if tracing._chip_of(name) is None:
        return []
    stat_names = {}
    for f, v in fields:
        if f == 5:                               # stat_metadata map entry
            entry = dict(_fields(v))
            stat_names[entry.get(1, 0)] = _text(
                dict(_fields(entry.get(2, b""))).get(2, b""))
    meta: Dict[int, List[str]] = {}
    for f, v in fields:
        if f != 4:                               # event_metadata map entry
            continue
        entry = dict(_fields(v))
        texts = []
        for g, w in _fields(entry.get(2, b"")):
            if g in (2, 4):                      # name, display_name
                texts.append(_text(w))
            elif g == 5:                         # stats
                stat = dict(_fields(w))
                if 5 in stat:                    # str_value
                    texts.append(_text(stat[5]))
                elif 7 in stat:                  # ref_value
                    texts.append(stat_names.get(stat[7], ""))
        meta[entry.get(1, 0)] = texts
    ops: List[List[str]] = []
    for f, line in fields:
        if f != 3:                               # XPlane.lines
            continue
        line_fields = list(_fields(line))
        if next((_text(v) for g, v in line_fields if g == 2), "") \
                != tracing.OPS_LINE:
            continue
        for g, event in line_fields:
            if g == 4:                           # XLine.events
                ops.append(meta.get(dict(_fields(event)).get(1, 0), []))
    return ops


def scope_of(texts: List[str]) -> str:
    """The ``opsparse.`` scopes of the first text that names one (an
    ``op_name`` path, or HLO text with ``op_name="..."``), joined by "/"
    without the prefix."""
    for text in texts:
        if PREFIX not in text:
            continue
        if 'op_name="' in text:
            text = text.split('op_name="', 1)[1].split('"', 1)[0]
        return "/".join(p[len(PREFIX):] for p in text.split("/")
                        if p.startswith(PREFIX))
    return UNSCOPED


def op_scopes(path: str) -> List[str]:
    """The scope of every device op of an ``.xplane.pb``, in the order
    ``tracing.read`` lists the ops."""
    buf = memoryview(Path(path).read_bytes())
    out: List[str] = []
    for number, plane in _fields(buf):
        if number == 1:                          # XSpace.planes
            out.extend(scope_of(texts) for texts in _op_texts(plane))
    return out


def read(log_dir: str) -> dict:
    """``tracing.read``'s events of the newest trace under ``log_dir``,
    with each op's scopes and the program's host spans."""
    from jax.profiler import ProfileData
    events = tracing.read(log_dir)
    path = sorted(glob.glob(os.path.join(log_dir, "**", "*.xplane.pb"),
                            recursive=True), key=os.path.getmtime)[-1]
    scopes = op_scopes(path)
    if len(scopes) != len(events["ops"]):
        raise ValueError(f"{len(scopes)} op scopes for "
                         f"{len(events['ops'])} ops")
    spans = []
    for plane in ProfileData.from_file(path).planes:
        if tracing._chip_of(plane.name) is None:
            for line in plane.lines:
                for ev in line.events:
                    if ev.name.startswith(PREFIX):
                        spans.append([ev.name, int(ev.start_ns),
                                      int(ev.duration_ns)])
    return {**events, "scopes": scopes, "program_spans": spans}


# ---------------------------------------------------------------------------
# The reduction.
# ---------------------------------------------------------------------------

@dataclasses.dataclass
class Phases:
    window_s: float
    chips: int
    intervals: Dict[str, List[Tuple[int, int, int]]]  # phase -> (chip, s, e)
    gaps: List[Tuple[str, float]]  # the longest idle gaps, named by spans

    def seconds(self, pred) -> float:
        """Seconds (mean per chip) in which an op whose phase satisfies
        ``pred`` ran: the union of their intervals."""
        ivs = [iv for p, ivs in self.intervals.items() if pred(p)
               for iv in ivs]
        return sum(_length([(s, e) for c, s, e in ivs if c == chip])
                   for chip in {c for c, _, _ in ivs}) / self.chips / 1e9

    @property
    def busy_s(self) -> float:
        return self.seconds(lambda p: True)

    @property
    def unscoped_s(self) -> float:
        """Busy time that no scoped op covers."""
        return self.busy_s - self.seconds(lambda p: p != UNSCOPED)

    @property
    def phase_s(self) -> Dict[str, float]:
        """Seconds (mean per chip) of each phase ("" for unscoped ops)."""
        return {p: self.seconds(lambda q, p=p: q == p)
                for p in self.intervals}


def phase_of(scope: str) -> str:
    """An op's phase: its innermost scope."""
    return scope.rsplit("/", 1)[-1]


def _length(intervals) -> int:
    return sum(e - s for s, e in tracing._union(intervals))


def _open_spans(spans, t: int) -> str:
    """The spans open at ``t``, harness and program, outermost first,
    without their prefixes ("none" when none is)."""
    open_ = sorted(((d, name) for name, s, d in spans
                    if name != tracing.WINDOW_SPAN and s <= t < s + d),
                   key=lambda dn: -dn[0])
    names = [name.split(".", 1)[1] for _, name in open_]
    return "/".join(names) or "none"


def summarize(events: dict) -> Phases:
    """The ops' intervals by phase and the idle gaps (of the first chip)
    inside the window span."""
    windows = [(s, s + d) for name, s, d in events["spans"]
               if name == tracing.WINDOW_SPAN]
    if len(windows) != 1:
        raise ValueError(f"expected one {tracing.WINDOW_SPAN} span, "
                         f"found {len(windows)}")
    w0, w1 = windows[0]
    chips = sorted({op[0] for op in events["ops"]})
    if not chips:
        raise ValueError("the trace holds no device operation")
    intervals: Dict[str, List[Tuple[int, int, int]]] = {}
    for (c, _, _, s, d), scope in zip(events["ops"], events["scopes"]):
        s, e = max(s, w0), min(s + d, w1)
        if e > s:
            intervals.setdefault(phase_of(scope), []).append((c, s, e))
    merged = tracing._union([(s, e) for ivs in intervals.values()
                             for c, s, e in ivs if c == chips[0]])
    edges = [w0] + [x for se in merged for x in se] + [w1]
    gaps = [(s, e) for s, e in zip(edges[0::2], edges[1::2]) if e > s]
    spans = events["spans"] + events.get("program_spans", [])
    longest = sorted(gaps, key=lambda g: g[0] - g[1])[:tracing.TOP]
    return Phases(window_s=(w1 - w0) / 1e9, chips=len(chips),
                  intervals=intervals,
                  gaps=[(_open_spans(spans, (s + e) // 2), (e - s) / 1e9)
                        for s, e in longest])


def hash_epilogue_s(ph: Phases, products: int) -> Optional[float]:
    """Device seconds per product in the hash epilogue
    (``opsparse.epilogue.*``)."""
    s = ph.seconds(lambda p: p.startswith(EPILOGUE))
    return s / products if products and s > 0 else None


def esc_sort_s(ph: Phases, products: int) -> Optional[float]:
    """Device seconds per product in ESC's (row, col) sort
    (``opsparse.esc.sort``), its loop counted once."""
    s = ph.seconds(lambda p: p == ESC_SORT)
    return s / products if products and s > 0 else None


def unscoped_share(ph: Phases) -> Optional[float]:
    """Share (%) of the busy time in which no scoped op ran."""
    return 100.0 * ph.unscoped_s / ph.busy_s if ph.busy_s > 0 else None


# ---------------------------------------------------------------------------
# One traced window of a cell.
# ---------------------------------------------------------------------------

def _epilogue_counts(session) -> Tuple[int, int]:
    """The engine's epilogue slot and entry counters (0 where the program
    has none)."""
    reg = session.service.engine().telemetry.registry
    return tuple(getattr(reg.get(f"opsparse_epilogue_{n}_total"), "value", 0)
                 for n in ("slots", "entries"))


def main(argv: Optional[List[str]] = None, *,
         root: Optional[Path] = None) -> int:
    import numpy as np
    from chipbench import harness
    ap = argparse.ArgumentParser(description="Device time by program "
                                             "phase in one traced window.")
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--events", help="write the window's events here")
    args = ap.parse_args(argv)
    root = harness.ROOT if root is None else root
    cell = harness.load_cell(root, args.workload)
    harness.use_compile_cache(root)
    info = harness.device_info()
    harness.require_accelerator(info, cell.chips)
    repro = harness.import_program(root)
    config, traffic = cell.config, cell.traffic
    rpt, col = harness.plugin("generators", config["generator"]).structure(
        config)
    shape = (int(config["rows"]), int(config["cols"]))
    driver = harness.plugin("drivers", traffic["driver"])
    session = driver.start(repro, rpt, col, shape, traffic, args.seed)
    rng = np.random.default_rng([args.seed, 2])
    slots0, entries0 = _epilogue_counts(session)
    with tempfile.TemporaryDirectory(prefix="chipbench-phases-") as d:
        with tracing.record(d), tracing.annotate("window"):
            win = driver.window(repro, session, args.seconds, rng)
        events = read(d)
    slots1, entries1 = _epilogue_counts(session)
    products = len(win.latencies) - win.failed
    ph = summarize(events)
    per_product = {p or "(unscoped)": v / max(products, 1)
                   for p, v in sorted(ph.phase_s.items(),
                                      key=lambda kv: -kv[1])}
    harness.say("phases", {"workload": cell.name, "products": products,
                           "latencies_s": win.latencies,
                           "window_s": ph.window_s, "busy_s": ph.busy_s,
                           "s_per_product": per_product})
    harness.say("phase metrics", {
        "hash_epilogue_s": hash_epilogue_s(ph, products),
        "esc_sort_s": esc_sort_s(ph, products),
        "unscoped_share": unscoped_share(ph),
        "epilogue_slot_yield": (100.0 * (entries1 - entries0)
                                / (slots1 - slots0)
                                if slots1 > slots0 else None),
        "epilogue_slots": slots1 - slots0,
        "epilogue_entries": entries1 - entries0,
        "idle_gaps": ph.gaps})
    if args.events:
        Path(args.events).write_text(json.dumps(events))
    driver.close(session, win)
    return 0


if __name__ == "__main__":
    sys.exit(main())
