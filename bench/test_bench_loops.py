"""The traced slice of a closed loop (``loops.closed_loop_cut``), on the
CPU with a system whose queries sleep and start/stop callbacks that only
note the time: no profiler, no program."""
from __future__ import annotations

import time

import pytest

from bench import loops


class Sleeper:
    """Queries that sleep ``took`` seconds and answer with their number;
    ``running`` is true while one is in flight."""

    def __init__(self, took: float, fail_at: int | None = None):
        self.took, self.fail_at = took, fail_at
        self.calls = 0
        self.running = False

    def run(self, name: str):
        self.running = True
        try:
            self.calls += 1
            if self.calls == self.fail_at:
                raise ValueError(f"query {self.calls} failed")
            time.sleep(self.took)
            return (name, self.calls)
        finally:
            self.running = False


class Callbacks:
    """``start``/``stop`` as the harness passes them, noting when each
    ran and whether a query was in flight at the stop."""

    def __init__(self, system):
        self.system = system
        self.started = self.stopped = None
        self.in_flight_at_stop = None

    def start(self):
        self.started = loops.clock()

    def stop(self):
        self.stopped = loops.clock()
        self.in_flight_at_stop = self.system.running


def cut(system, lead_s, cut_s):
    cb = Callbacks(system)
    out = loops.closed_loop_cut(system, ["a", "b"], lead_s=lead_s,
                                cut_s=cut_s, start=cb.start, stop=cb.stop,
                                span=loops.no_span)
    return out, cb


def test_short_queries_fill_the_slice_whole():
    system = Sleeper(0.02)
    out, cb = cut(system, lead_s=0.05, cut_s=0.2)
    assert out["cut_s"] == 0.2 and out["kind"] == "closed"
    qs = out["queries"]
    whole = [q for q in qs if cb.started <= q[1] and q[2] <= cb.stopped]
    assert len(whole) >= 5
    # every query the thread ran is answered, in turn, with its own answer
    assert len(out["answers"]) == len(qs) == system.calls
    assert out["answers"] == [(n, (n, i + 1)) for i, (n, _, _)
                              in enumerate(qs)]
    assert [n for n, _, _ in qs] == ["a", "b"] * (len(qs) // 2) \
        + ["a"] * (len(qs) % 2)
    # queries ran from before the slice until it had ended, and none began
    # once the trace was stopped but the one in flight then
    assert qs[0][1] < cb.started and qs[-1][2] >= cb.stopped
    assert all(q[1] < cb.stopped for q in qs[:-1])


def test_a_query_longer_than_the_slice_is_the_only_one():
    system = Sleeper(0.4)
    out, cb = cut(system, lead_s=0.05, cut_s=0.1)
    assert system.calls == 1 and len(out["answers"]) == 1
    (name, a, b), = out["queries"]
    assert a < cb.started and b > cb.stopped
    assert out["cut_s"] == 0.1


@pytest.mark.filterwarnings(
    "ignore::pytest.PytestUnhandledThreadExceptionWarning")
def test_an_error_on_the_query_thread_is_raised_here():
    system = Sleeper(0.01, fail_at=3)
    cb = Callbacks(system)
    with pytest.raises(ValueError, match="query 3 failed"):
        loops.closed_loop_cut(system, ["a"], lead_s=0.05, cut_s=0.05,
                              start=cb.start, stop=cb.stop,
                              span=loops.no_span)
    assert cb.stopped is not None and system.calls == 3


@pytest.mark.parametrize("took,lead_s", [(0.3, 0.35), (0.4, 0.05)],
                         ids=["second_query", "only_query"])
def test_the_trace_stops_before_the_last_query_is_joined(took, lead_s):
    system = Sleeper(took)
    out, cb = cut(system, lead_s=lead_s, cut_s=0.1)
    assert cb.in_flight_at_stop is True
    assert out["queries"][-1][2] > cb.stopped
    assert len(out["queries"]) == (2 if lead_s > took else 1)
