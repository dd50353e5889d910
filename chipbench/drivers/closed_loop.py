"""Closed loop, one client: C = A * A back to back through
``SpgemmService.call``, on a fixed structure with new values each time.

This is the repeated-structure product of iterative solvers and graph
kernels: the pattern of A stays, its values change between calls, so
the plan is reused while every answer has to be computed anew.  Request
i takes values drawn on the device from the seed and i (standard
normal, float32), in one jitted call.  The traffic file gives the
per-call ``SpgemmConfig.method``; every other field keeps its default.

Warm-up sends one cold request (it plans) and one hot request (it builds
the steady-state executable).  The window then starts products while
less than ``seconds`` have passed since it opened, and each product is
timed from the draw of its values to ``block_until_ready`` of its C.  Up
to ``KEEP`` answers, drawn from the seed (reservoir sampling), stay on
the device with their values for the comparison after the window; the
rest are dropped as they come, so the memory held does not grow with the
number of products.
"""
from __future__ import annotations

import dataclasses
import functools
import statistics
import time
from typing import Dict, List, Optional, Tuple

import numpy as np

from chipbench import tracing
from chipbench.reference import Answer

KEEP = 2


@dataclasses.dataclass
class Session:
    service: object
    config: object
    CSR: object
    rpt: object                          # A's structure on the device
    col: object
    shape: Tuple[int, int]
    key: object                          # the values' key, from the seed
    draw: object                         # (key, i) -> values of request i
    requests: int = 0
    warmup_s: Dict[str, float] = dataclasses.field(default_factory=dict)


@dataclasses.dataclass
class Window:
    latencies: List[float]
    reported: List[Tuple[int, int]]      # (n_prod, nnz) the program gave
    failed: int
    seconds: float                       # open to the last product's end
    kept: List[Tuple[int, object, object]]   # (index, values, C) on device
    counters: Dict[str, float]
    last_bins: Optional[object] = None   # the last product's n_prod bins


def value_key(seed: int):
    """The key of a seed's values: any whole seed, folded to 31 bits."""
    import jax
    return jax.random.key(int(np.random.default_rng([seed, 1])
                              .integers(0, 2**31 - 1)))


@functools.lru_cache(maxsize=None)
def _draw_fn(nnz: int):
    import jax
    import jax.numpy as jnp

    @jax.jit
    def draw(key, i):
        return jax.random.normal(jax.random.fold_in(key, i), (nnz,),
                                 jnp.float32)
    return draw


def values(seed: int, i: int, nnz: int) -> np.ndarray:
    """Request i's values under ``seed``, on the host."""
    return np.asarray(_draw_fn(nnz)(value_key(seed), i))


def reseed(session: Session, seed: int) -> None:
    session.key = value_key(seed)
    session.requests = 0


def _call(session: Session):
    """Request ``session.requests``: its values, the product, the wait."""
    svc = session.service
    with tracing.annotate("call"):
        val = session.draw(session.key, session.requests)
        A = session.CSR(rpt=session.rpt, col=session.col, val=val,
                        shape=session.shape)
        r = svc.call(A, A, config=session.config)
    with tracing.annotate("wait"):
        if r.ok:
            r.value.C.block_until_ready()
    session.requests += 1
    return r, val


def start(repro, rpt, col, shape, traffic: dict, seed: int) -> Session:
    """The service and its warm-up: one cold and one hot request."""
    import jax.numpy as jnp
    cfg = repro.SpgemmConfig(method=traffic["method"])
    rpt_d, col_d = jnp.asarray(rpt), jnp.asarray(col)
    session = Session(service=repro.SpgemmService(), config=cfg,
                      CSR=repro.CSR, rpt=rpt_d, col=col_d,
                      shape=tuple(shape), key=value_key(seed),
                      draw=_draw_fn(int(col.size)))
    for phase in ("cold", "hot"):
        t0 = time.perf_counter()
        r, _ = _call(session)
        session.warmup_s[phase] = time.perf_counter() - t0
        if not r.ok:
            raise RuntimeError(f"{phase} warm-up request: {r.status}: "
                               f"{r.error}")
    return session


def _counters(repro, session: Session) -> Dict[str, int]:
    eng = session.service.engine()
    steps = hot = 0
    for _, entry in eng.cache.items():
        steps += entry.stats.steps_calls
        hot += entry.stats.hot_calls
    return {"retraces": repro.total_traces(), "steps_calls": steps,
            "hot_calls": hot, "capacity_grows": eng.stats.capacity_grows,
            "bin_overflows": eng.stats.bin_overflows,
            "arena_spills": eng.stats.arena_spills}


def window(repro, session: Session, seconds: float,
           rng: np.random.Generator) -> Window:
    before = _counters(repro, session)
    latencies: List[float] = []
    reported: List[Tuple[int, int]] = []
    kept: List[Tuple[int, object, object]] = []
    failed = 0
    last_bins = None
    t_open = t_end = time.perf_counter()
    while t_end - t_open < seconds:
        t0 = time.perf_counter()
        i = session.requests
        r, val = _call(session)
        t_end = time.perf_counter()
        with tracing.annotate("between"):
            latencies.append(t_end - t0)
            if not r.ok:
                failed += 1
                continue
            if r.value.sym_binning is not None:
                last_bins = r.value.sym_binning.bin_size
            reported.append((int(r.value.total_nprod),
                             int(r.value.total_nnz)))
            if len(kept) < KEEP:
                kept.append((i, val, r.value.C))
            else:           # keeps each of the n answers with chance KEEP/n
                j = int(rng.integers(0, len(reported)))
                if j < KEEP:
                    kept[j] = (i, val, r.value.C)
            # Hold no other C: the memory held is that of the kept ones.
            del r, val
            t_end = time.perf_counter()
    after = _counters(repro, session)
    return Window(latencies=latencies, reported=reported, failed=failed,
                  seconds=t_end - t_open, kept=kept,
                  counters={k: after[k] - before[k] for k in after},
                  last_bins=last_bins)


def metrics(win: Window, flops_per_product: int) -> Dict[str, float]:
    """gflops: the work of the completed products over all the window's
    time.  latency_p50_s: the median over all its products, failed ones
    included; with one to a few products a window holds no tail."""
    done = len(win.latencies) - win.failed
    out = {"gflops": flops_per_product * done / win.seconds / 1e9}
    if win.latencies:
        out["latency_p50_s"] = statistics.median(win.latencies)
    return out


def answers(win: Window) -> List[Tuple[np.ndarray, Answer]]:
    """(A's values, C) of each kept request on the host, C cut to its
    nnz (after the window)."""
    out = []
    for _, val, C in sorted(win.kept, key=lambda ivc: ivc[0]):
        rpt = np.asarray(C.rpt)
        nnz = int(rpt[-1])
        out.append((np.asarray(val), Answer(rpt, np.asarray(C.col)[:nnz],
                                            np.asarray(C.val)[:nnz])))
    return out


def describe(win: Window) -> dict:
    """What an earlier line says of the window: the products, the
    engine's counters (any retrace, grow or step-path call is a window
    that left the steady state) and the last product's bins."""
    info = {"products": len(win.latencies), "failed": win.failed,
            "window_s": win.seconds, "latencies_s": win.latencies,
            "kept": sorted(i for i, _, _ in win.kept), **win.counters}
    if win.last_bins is not None:
        info["sym_bin_rows"] = np.asarray(win.last_bins).tolist()
    return info


def close(session: Session, win: Window) -> None:
    session.service.close()
    win.kept.clear()
    win.last_bins = None
    session.rpt = session.col = None
