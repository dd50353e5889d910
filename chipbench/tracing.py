"""From a profiler trace of the measured window to device time.

``record(dir)`` traces the window with JAX's profiler.  ``read(dir)``
reduces the ``.xplane.pb`` it writes to plain events, which is all the
rest of this module and the per-layer metrics see:

- ``ops``: one ``[chip, name, kind, start_ns, dur_ns]`` per operation
  that ran on a device (planes named ``/device:<kind>:<n>``, line
  ``XLA Ops``): the HLO instruction's name and opcode (``op_name_kind``);
- ``spans``: one ``[name, start_ns, dur_ns]`` per host annotation of the
  harness (names starting ``chipbench.``), on the same clock.

``summarize`` turns those events into the window's length, the time in
which the devices were busy, the time per operation, and the longest
idle gaps with the host span that was open in each.
"""
from __future__ import annotations

import contextlib
import dataclasses
import glob
import os
from typing import Dict, List, Optional, Tuple

SPAN_PREFIX = "chipbench."
WINDOW_SPAN = "chipbench.window"
OPS_LINE = "XLA Ops"
TOP = 10                 # entries kept per list of the breakdown


@contextlib.contextmanager
def record(log_dir: str):
    """Trace the enclosed block: device activity and host annotations,
    without the Python tracer (it would slow the host path measured)."""
    import jax
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.enable_hlo_proto = False
    with jax.profiler.trace(log_dir, profiler_options=opts):
        yield


def annotate(name: str):
    """A host span that lands in the trace (a no-op when not tracing)."""
    import jax
    return jax.profiler.TraceAnnotation(SPAN_PREFIX + name)


def _chip_of(plane_name: str) -> Optional[int]:
    # "/device:TPU:0" -> 0; host planes ("/host:CPU") are not devices.
    if not plane_name.startswith("/device:"):
        return None
    tail = plane_name.rsplit(":", 1)[-1]
    return int(tail) if tail.isdigit() else None


def op_name_kind(text: str) -> Tuple[str, str]:
    """("%fusion.5", "fusion") from the HLO text a TPU trace gives as an
    op's name: the instruction's name and its opcode, with Pallas
    kernels (custom calls to ``tpu_custom_call``) as ``tpu_custom_call``."""
    name, sep, rest = text.partition(" = ")
    if not sep:
        return text, ""
    if rest.startswith("("):           # a tuple shape: skip to its ")"
        depth = 0
        for i, ch in enumerate(rest):
            depth += (ch == "(") - (ch == ")")
            if depth == 0:
                rest = rest[i + 1:]
                break
    else:
        rest = rest.partition(" ")[2]
    kind = rest.strip().partition("(")[0]
    if kind == "custom-call" and 'custom_call_target="tpu_custom_call"' \
            in text:
        kind = "tpu_custom_call"
    return name, kind


def read(log_dir: str) -> dict:
    """The newest trace under ``log_dir``, reduced to plain events."""
    from jax.profiler import ProfileData
    paths = sorted(glob.glob(os.path.join(log_dir, "**", "*.xplane.pb"),
                             recursive=True), key=os.path.getmtime)
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {log_dir}")
    data = ProfileData.from_file(paths[-1])
    ops: List[list] = []
    spans: List[list] = []
    for plane in data.planes:
        chip = _chip_of(plane.name)
        for line in plane.lines:
            if chip is not None and line.name == OPS_LINE:
                for ev in line.events:
                    ops.append([chip, *op_name_kind(ev.name),
                                int(ev.start_ns), int(ev.duration_ns)])
            elif chip is None:
                for ev in line.events:
                    if ev.name.startswith(SPAN_PREFIX):
                        spans.append([ev.name, int(ev.start_ns),
                                      int(ev.duration_ns)])
    return {"ops": ops, "spans": spans}


def _union(intervals: List[Tuple[int, int]]) -> List[Tuple[int, int]]:
    merged: List[Tuple[int, int]] = []
    for s, e in sorted(intervals):
        if merged and s <= merged[-1][1]:
            merged[-1] = (merged[-1][0], max(merged[-1][1], e))
        else:
            merged.append((s, e))
    return merged


@dataclasses.dataclass
class Summary:
    window_s: float
    busy_s: float                      # mean over the chips in the trace
    chips: int
    op_s: Dict[Tuple[str, str], float]  # per (op name, kind), s per chip
    gaps: List[Tuple[str, float]]      # the TOP longest idle gaps

    def seconds(self, pred) -> float:
        """Seconds (mean per chip) of the ops whose kind satisfies
        ``pred``."""
        return sum(s for (_, kind), s in self.op_s.items() if pred(kind))


def is_sort(kind: str) -> bool:
    return kind == "sort"


def is_pallas(kind: str) -> bool:
    return kind == "tpu_custom_call"


def _open_span(spans, t: int) -> str:
    """The innermost harness span open at ``t`` (the shortest that
    covers it), or "none"."""
    best = None
    for name, s, d in spans:
        if name != WINDOW_SPAN and s <= t < s + d \
                and (best is None or d < best[1]):
            best = (name, d)
    return best[0][len(SPAN_PREFIX):] if best else "none"


def summarize(events: dict) -> Summary:
    """Busy time, time per op and the idle gaps inside the window span."""
    windows = [(s, s + d) for name, s, d in events["spans"]
               if name == WINDOW_SPAN]
    if len(windows) != 1:
        raise ValueError(f"expected one {WINDOW_SPAN} span, found "
                         f"{len(windows)}")
    w0, w1 = windows[0]
    chips = sorted({op[0] for op in events["ops"]})
    if not chips:
        raise ValueError("the trace holds no device operation")
    busy = 0
    op_ns: Dict[Tuple[str, str], int] = {}
    gaps: List[Tuple[int, int]] = []
    for chip in chips:
        iv = []
        for c, name, kind, s, d in events["ops"]:
            s, e = max(s, w0), min(s + d, w1)
            if c != chip or e <= s:
                continue
            iv.append((s, e))
            op_ns[name, kind] = op_ns.get((name, kind), 0) + (e - s)
        merged = _union(iv)
        busy += sum(e - s for s, e in merged)
        if chip == chips[0]:
            edges = [w0] + [x for se in merged for x in se] + [w1]
            gaps = [(s, e) for s, e in zip(edges[0::2], edges[1::2])
                    if e > s]
    n = len(chips)
    longest = sorted(gaps, key=lambda g: g[0] - g[1])[:TOP]
    return Summary(window_s=(w1 - w0) / 1e9, busy_s=busy / n / 1e9,
                   chips=n,
                   op_s={k: v / n / 1e9 for k, v in op_ns.items()},
                   gaps=[(_open_span(events["spans"], (s + e) // 2),
                          (e - s) / 1e9) for s, e in longest])


def breakdown(summary: Summary) -> dict:
    """The device ops that took most time and the longest idle gaps."""
    ops = sorted(summary.op_s.items(), key=lambda kv: -kv[1])[:TOP]
    return {"device_ops": [[f"{name} = {kind}", v]
                           for (name, kind), v in ops],
            "idle_gaps": [[k, v] for k, v in summary.gaps]}
