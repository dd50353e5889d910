"""The window's arithmetic: products start while the window is open,
every metric covers every product, and the kept answers are a seeded
sample of fixed size, each with its own request's values."""
import types

import numpy as np
import pytest

from chipbench.drivers import closed_loop


class FakeClock:
    def __init__(self):
        self.t = 100.0

    def __call__(self):
        return self.t


class FakeService:
    """Each call takes ``dt`` seconds of the fake clock."""

    def __init__(self, clock, dts, ok=True):
        self.clock, self.dts, self.ok, self.calls = clock, list(dts), ok, 0

    def call(self, A, B, config=None):
        self.clock.t += self.dts[min(self.calls, len(self.dts) - 1)]
        self.calls += 1
        C = types.SimpleNamespace(block_until_ready=lambda: None,
                                  tag=self.calls)
        value = types.SimpleNamespace(C=C, total_nprod=10, total_nnz=8,
                                      sym_binning=None)
        return types.SimpleNamespace(ok=self.ok, status="ok" if self.ok
                                     else "error", value=value, error=None)

    def engine(self):
        stats = types.SimpleNamespace(capacity_grows=0, bin_overflows=0,
                                      arena_spills=0)
        return types.SimpleNamespace(cache=types.SimpleNamespace(
            items=lambda: []), stats=stats)


REPRO = types.SimpleNamespace(total_traces=lambda: 0)


def _window(monkeypatch, dts, seconds, seed=0, ok=True):
    clock = FakeClock()
    monkeypatch.setattr(closed_loop.time, "perf_counter", clock)
    svc = FakeService(clock, dts, ok=ok)
    session = closed_loop.Session(service=svc, config=None,
                                  CSR=lambda **kw: kw, rpt=None, col=None,
                                  shape=(1, 1), key=seed,
                                  draw=lambda key, i: (key, i))
    return closed_loop.window(REPRO, session, seconds,
                              np.random.default_rng(seed))


def test_products_start_while_the_window_is_open(monkeypatch):
    # Starts at 0, 2, 4 (< 5); the third ends at 6, which closes it.
    win = _window(monkeypatch, [2.0], seconds=5)
    assert len(win.latencies) == 3 and win.seconds == 6.0
    m = closed_loop.metrics(win, flops_per_product=3_000_000_000)
    assert m["gflops"] == pytest.approx(3 * 3.0 / 6.0)
    assert m["latency_p50_s"] == 2.0


def test_a_product_that_starts_just_before_the_close_counts(monkeypatch):
    win = _window(monkeypatch, [4.999, 10.0], seconds=5)
    assert win.latencies == pytest.approx([4.999, 10.0])
    assert win.seconds == pytest.approx(14.999)


def test_median_latency():
    win = closed_loop.Window(latencies=[6.0, 9.0, 6.2, 6.1, 6.0],
                             reported=[], failed=0, seconds=1.0, kept=[],
                             counters={})
    assert closed_loop.metrics(win, 1)["latency_p50_s"] == 6.1
    win.latencies = [26.0, 25.0]        # two products: their mean
    assert closed_loop.metrics(win, 1)["latency_p50_s"] == 25.5


def test_failed_products_count_in_latency_not_in_work(monkeypatch):
    win = _window(monkeypatch, [1.0], seconds=3, ok=False)
    assert win.failed == 3 and win.reported == []
    m = closed_loop.metrics(win, 10**9)
    assert m["gflops"] == 0.0 and m["latency_p50_s"] == 1.0


def test_kept_answers_are_a_seeded_sample_of_fixed_size(monkeypatch):
    picks = []
    for seed in (1, 1, 2, 3, 4):
        win = _window(monkeypatch, [1.0], seconds=20, seed=seed)
        assert len(win.latencies) == 20
        assert len(win.kept) == closed_loop.KEEP
        # Each kept answer with the values of its own request.
        assert all(C.tag == i + 1 and val == (seed, i)
                   for i, val, C in win.kept)
        picks.append(sorted(i for i, _, _ in win.kept))
    assert picks[0] == picks[1]                  # same seed, same sample
    assert len({tuple(p) for p in picks}) > 1    # seeds draw others
