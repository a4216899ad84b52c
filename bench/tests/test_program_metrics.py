"""The per-layer metrics that read the program's own tracer
(``repro.core.tracing``), each on a hand-built tracer and ``ctx`` with a
known answer."""
import contextlib
import importlib
import sys
from types import SimpleNamespace

import pytest

from bench.trace import Reduced
from repro.core import tracing

READERS = ("engine_self_ms", "switch_wait_ms", "queue_wait_ms",
           "first_token_ms", "idle_unattributed_share")


def reader(name):
    return importlib.import_module(f"bench.metrics.{name}").read


@pytest.fixture
def tr(monkeypatch):
    t = tracing.Tracer()
    monkeypatch.setattr(tracing, "_TRACER", t)
    return t


@contextlib.contextmanager
def at(tr, name, t0, t1, rid=None):
    """A span that nests like the code around it, with set times."""
    with tr.span(name, rid, engine=0) as sp:
        yield sp
    sp.t0, sp.t1 = t0, t1


def mark(tr, name, t, rid):
    tr._ring.append(tracing.Mark(name, t, rid, 0, 0, {}))


def ctx(window=(10.0, 20.0), **kw):
    return SimpleNamespace(window=window, **kw)


def test_engine_self_time_takes_out_overlapping_children_once(tr):
    # step 10.0-10.1: swap.out 10.02-10.05 holding a dispatch, a build
    # 10.04-10.06 overlapping it (on the step), the decode launch
    # 10.07-10.08 inside the alloc phase, which itself stays engine work
    with at(tr, "step", 10.0, 10.1):
        with at(tr, "swap.out", 10.02, 10.05, rid=1):
            with at(tr, "swap.dispatch", 10.03, 10.05, rid=1):
                pass
        with at(tr, "build:jit(cond)", 10.04, 10.06):
            pass
        with at(tr, "step.alloc", 10.065, 10.09):
            with at(tr, "decode.launch", 10.07, 10.08):
                pass
    # a second step with no children; one outside the window
    with at(tr, "step", 11.0, 11.01):
        pass
    with at(tr, "step", 25.0, 26.0):
        pass
    # (100 - 40 - 10) ms and 10 ms
    assert reader("engine_self_ms")(ctx()) == pytest.approx((50 + 10) / 2)


def test_switch_wait_is_the_engine_threads_swap_union_per_dispatch(tr):
    with at(tr, "step", 10.0, 10.5):
        with at(tr, "swap.out", 10.1, 10.2, rid=1):
            with at(tr, "swap.dispatch", 10.15, 10.2, rid=1):
                with at(tr, "pool_lock.wait", 10.16, 10.17):
                    pass
        with at(tr, "swap.in", 10.3, 10.32, rid=2):
            with at(tr, "swap.dispatch", 10.3, 10.31, rid=2):
                pass
        with at(tr, "swap.conflict", 10.4, 10.43):
            with at(tr, "swap.sync", 10.4, 10.43):
                pass
    # a sync on another thread is not the engine's wait
    with at(tr, "swap.sync", 10.0, 10.5) as other:
        pass
    other.thread = -1
    assert reader("switch_wait_ms")(ctx()) == pytest.approx(
        (100 + 20 + 30) / 2)


def test_queue_wait_is_the_p90_from_arrival_to_first_start(tr):
    # request 1 waits 0.1 s; 2 follows up and is restored after 0.3 s;
    # 3 waits 0.5 s; a later preempt/resume of 1 is no queue wait; 4
    # arrived before the window, so it is not counted though admitted in
    # it; 5 arrives at 19.4 s and is admitted only after the window, so
    # it counts with the 0.6 s it waited by the window's end
    mark(tr, "arrive", 9.0, 4)
    mark(tr, "arrive", 10.0, 1)
    mark(tr, "admit", 10.1, 1)
    mark(tr, "continue", 10.2, 2)
    mark(tr, "swap_in", 10.5, 2)
    mark(tr, "admit", 10.6, 4)
    mark(tr, "arrive", 11.0, 3)
    mark(tr, "preempt", 11.2, 1)
    mark(tr, "resume", 11.4, 1)
    mark(tr, "admit", 11.5, 3)
    mark(tr, "arrive", 19.4, 5)
    mark(tr, "admit", 20.5, 5)
    # p90 of (100, 300, 500, 600) ms, numpy's linear rule: 500 + 0.7 * 100
    assert reader("queue_wait_ms")(ctx()) == pytest.approx(570.0)


def test_first_token_is_the_mean_draw(tr):
    for t0, t1 in ((10.0, 10.2), (12.0, 12.1), (30.0, 31.0)):
        with at(tr, "prefill.first_token", t0, t1, rid=1):
            pass
    assert reader("first_token_ms")(ctx()) == pytest.approx(150.0)


def test_an_idle_gap_half_covered_by_a_span_is_half_unattributed(tr):
    # trace window 0-1000 ns on the trace clock, starting at host 10.0 s;
    # the device runs 0-400 and 600-1000: one idle gap 400-600, of
    # which a prefill span covers host 10.0000004-10.0000005 (400-500)
    red = Reduced(ops={0: [("a", 0.0, 400.0), ("b", 600.0, 1000.0)]})
    with at(tr, "step", 10.0, 10.000001):
        with at(tr, "prefill.first_token", 10.0000004, 10.0000005, rid=1):
            pass
    c = ctx(trace=red, lo=0.0, hi=1000.0, host_window=(10.0, 10.000001))
    assert reader("idle_unattributed_share")(c) == pytest.approx(50.0)


@pytest.mark.parametrize("name", READERS)
def test_nothing_to_read_is_none(tr, name):
    c = ctx(trace=Reduced(ops={0: [("a", 0.0, 1000.0)]}), lo=0.0,
            hi=1000.0, host_window=(10.0, 10.000001))
    assert reader(name)(c) is None


@pytest.mark.parametrize("name", READERS)
def test_a_program_without_the_tracer_reads_none(monkeypatch, name):
    import repro.core
    monkeypatch.delattr(repro.core, "tracing")
    monkeypatch.setitem(sys.modules, "repro.core.tracing", None)
    c = ctx(trace=Reduced(ops={0: [("a", 0.0, 400.0)]}), lo=0.0,
            hi=1000.0, host_window=(10.0, 10.000001))
    assert reader(name)(c) is None
