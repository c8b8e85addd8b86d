"""The span readers (``spans.py``), the harness's span metrics and the
stream's fold layer metric, on three traces recorded on a TPU v5e by the
harness after the program named its stages and stream programs
(``spans.py record``: ``tpch_sf1.q3_q6`` at 0.5% of its rows for
0.025 s, one Q3; ``tpch_sf1.stream_topn_distinct`` for one micro-batch
of ``STREAM_BATCH`` rows; ``tpch_sf01.q1`` at 0.03% of its rows,
``--set trace_lead_s=0.3 --set trace_cut_s=0.03``, a slice holding two
whole Q1s run by the worker thread), on the older q3_q6 trace, whose
program opened no spans, and on intervals whose answer is known."""
from __future__ import annotations

import os

import numpy as np
import pytest

from bench import harness, reduce_trace, spans

DATA = os.path.join(harness.BENCH_DIR, "testdata")
OLD = os.path.join(DATA, "q3_q6_small.xplane.pb")
Q3 = os.path.join(DATA, "q3_q6_spans_small.xplane.pb")
STREAM = os.path.join(DATA, "stream_small.xplane.pb")
STREAM_BATCH = 2048       # the micro-batch it was recorded with
Q1 = os.path.join(DATA, "q1_small.xplane.pb")
SPEC = harness.load_spec()


def _reduced(path, layers=None):
    return reduce_trace.reduce_file(
        path, harness.load_layers() if layers is None else layers,
        window="bench.window")


def _ctx(path, cell, **sets):
    """What the harness hands a metric for a traced run of ``cell``
    recorded in ``path`` (with the traffic keys ``sets`` overridden);
    the window's records are rebuilt from the harness's own spans."""
    traffic = dict(harness.cell_parts(SPEC, cell)[3], **sets)
    t = spans.read(path)
    if traffic["loop"] == "closed":
        window = {"kind": "closed",
                  "queries": [(n, s, e) for n, s, e in t["spans"]
                              if n.startswith("query.")]}
        if "trace_cut_s" in traffic:
            window["cut_s"] = traffic["trace_cut_s"]
    else:
        folds = sum(n == "stream.fold" for n, _, _ in t["spans"])
        window = {"kind": "open", "offered": folds * traffic["micro_batch"]}
    return harness.Context(window, 0.0, _reduced(path), None, traffic, {})


def _metric(name, ctx):
    return harness.load_metric(name).read(ctx)


def test_interval_helpers_match_a_grid():
    rng = np.random.default_rng(4)
    a = [(float(s), float(s + d)) for s, d in
         zip(rng.integers(0, 900, 60), rng.integers(1, 50, 60))]
    b = [(float(s), float(s + d)) for s, d in
         zip(rng.integers(0, 900, 60), rng.integers(1, 50, 60))]
    ga, gb = np.zeros(1000, bool), np.zeros(1000, bool)
    for s, e in a:
        ga[int(s):int(e)] = True
    for s, e in b:
        gb[int(s):int(e)] = True
    ma, mb = spans._merged(a), spans._merged(b)
    assert sum(e - s for s, e in ma) == ga.sum()
    assert spans._overlap_s(ma, mb) == (ga & gb).sum()


def test_inside_matches_a_scan_of_the_intervals():
    rng = np.random.default_rng(5)
    starts = rng.integers(0, 900, 40).astype(float)
    ends = starts + rng.integers(0, 60, 40)
    t = np.arange(-5.0, 1000.0, 0.5)
    want = [any(s <= x <= e for s, e in zip(starts, ends)) for x in t]
    assert reduce_trace._inside(t, starts, ends).tolist() == want
    assert not reduce_trace._inside(t, np.zeros(0), np.zeros(0)).any()


def _line(*spans_):
    return ([n for n, _, _ in spans_], np.array([s for _, s, _ in spans_]),
            np.array([e for _, _, e in spans_]))


def test_spans_come_from_the_thread_that_ran_the_queries():
    window = _line(("bench.window", 10.0, 20.0))
    worker = _line(("query.q1_pricing", 5.0, 12.0), ("suite.q1", 5.0, 12.0),
                   ("query.q1_pricing", 12.0, 15.0), ("suite.q1", 12.0, 15.0))
    other = _line(("PjitFunction(x)", 11.0, 11.5))
    pick = reduce_trace._query_thread
    assert pick([other, window, worker], "bench.window", 10.0, 20.0) is worker
    # the window's own thread, where it ran them itself
    both = _line(("bench.window", 10.0, 20.0), ("query.q3", 11.0, 12.0))
    assert pick([worker, both], "bench.window", 10.0, 20.0) is both
    # no thread ran one inside the window: the window's own
    assert pick([other, window, worker], "bench.window", 30.0, 40.0) \
        is window


def test_host_bound_leaves_out_gaps_inside_a_running_program():
    t = {"window_s": 1.0, "spans": [("suite.q1", 0.0, 0.8),
                                    ("query.q1_pricing", 0.0, 1.0)],
         "gaps": [[(0.1, 0.2), (0.5, 0.6), (0.85, 0.9)]],
         "gap_in_program": [[True, False, False]]}
    assert spans.host_bound_idle_share(t) == pytest.approx(10.0)
    t["gap_in_program"] = [[True, True, False]]
    assert spans.host_bound_idle_share(t) == 0.0
    t["spans"] = [("query.q1_pricing", 0.0, 1.0)]   # no program span
    assert spans.host_bound_idle_share(t) is None


def test_old_keys_read_as_before_with_the_new_layers():
    pass1 = {"engine pass 1": harness.load_layers()["engine pass 1"]}
    before, after = _reduced(OLD, pass1), _reduced(OLD)
    for key in ("window_s", "busy_s", "devices", "breakdown"):
        assert after[key] == before[key]
    assert after["layer_s"]["engine pass 1"] \
        == before["layer_s"]["engine pass 1"]
    assert after["layer_s"]["stream fold"] == 0.0


@pytest.mark.parametrize("path", [OLD, Q3, STREAM],
                         ids=["old", "q3_q6", "stream"])
def test_gaps_sum_to_window_minus_busy(path):
    t, r = spans.read(path), _reduced(path, {})
    assert t["window_s"] == pytest.approx(r["window_s"], rel=1e-12)
    assert len(t["gaps"]) == r["devices"] == 1
    idle = sum(b - a for a, b in t["gaps"][0])
    assert idle == pytest.approx(r["window_s"] - r["busy_s"], rel=1e-9)
    assert all(0 <= a < b <= t["window_s"] for a, b in t["gaps"][0])


def test_new_readers_return_none_on_a_trace_without_them():
    t = spans.read(OLD)
    assert spans.host_bound_idle_share(t) is None
    assert spans.stage_ms_per_query(t, "master") is None
    assert spans.stage_ms_per_query(t, "to_host") is None
    q3 = _ctx(OLD, "tpch_sf1.q3_q6")
    assert _metric("stream_fold_ms_per_batch", q3) is None
    # an open window over a trace with no fold program reads nothing too
    traffic = harness.cell_parts(SPEC, "tpch_sf1.stream_topn_distinct")[3]
    stream = harness.Context({"kind": "open", "offered": 16384}, 0.0,
                             q3.trace, None, traffic, {})
    assert _metric("stream_fold_ms_per_batch", stream) is None


def test_q3_q6_readers_positive():
    t = spans.read(Q3)
    share = spans.host_bound_idle_share(t)
    r = _reduced(Q3)
    assert 0 < share <= 100 * (1 - r["busy_s"] / r["window_s"])
    assert spans.stage_ms_per_query(t, "master") > 0
    assert spans.stage_ms_per_query(t, "to_host") > 0
    names = {n for n, _, _ in t["spans"] if spans.program(n)}
    for stage in ("to_host", "master", "join", "to_device"):
        assert f"suite.q3.{stage}" in names
    assert {"engine_prune.pass1", "engine_prune.to_host"} <= names


def test_q3_q6_longest_idle_gaps_fall_in_program_spans():
    # every one of the ten longest device-idle gaps, outside the harness's
    # own loop, lies inside a stage span the program opened
    t = spans.read(Q3)
    program = [(s, e) for n, s, e in t["spans"] if spans.program(n)]
    longest = sorted(t["gaps"][0], key=lambda g: g[0] - g[1])[:10]
    for a, b in longest:
        mid = (a + b) / 2
        if any(s <= mid <= e for n, s, e in t["spans"]
               if n.startswith("query.")):
            assert any(s <= mid <= e for s, e in program), (a, b)
    names = [n for n, _ in _reduced(Q3)["breakdown"]["idle_gaps"]]
    assert not [n for n in names if n.startswith("query.") and " > " not in n]


def test_stream_readers_positive_and_programs_named():
    t = spans.read(STREAM)
    r = _reduced(STREAM)
    assert 0 < spans.host_bound_idle_share(t) \
        <= 100 * (1 - r["busy_s"] / r["window_s"])
    assert _metric("stream_fold_ms_per_batch",
                   _ctx(STREAM, "tpch_sf1.stream_topn_distinct",
                        micro_batch=STREAM_BATCH)) > 0
    names = [n for n, _ in r["breakdown"]["device_ops"]]
    assert not any(n.startswith("jit_worker") for n in names)
    for algo in ("distinct", "topn_det"):
        assert any(n.startswith(f"jit_{algo}_stream_fold") for n in names)


@pytest.mark.parametrize("path,cell,metric,key", [
    (Q3, "tpch_sf1.q3_q6", "host_bound_idle_share.query",
     "host_bound_idle_share"),
    (Q3, "tpch_sf1.q3_q6", "master_ms_per_query", "master_ms_per_query"),
    (Q3, "tpch_sf1.q3_q6", "to_host_ms_per_query", "to_host_ms_per_query"),
    (STREAM, "tpch_sf1.stream_topn_distinct", "host_bound_idle_share.stream",
     "host_bound_idle_share"),
], ids=["q3_q6-host_bound", "q3_q6-master", "q3_q6-to_host",
        "stream-host_bound"])
def test_harness_span_metrics_equal_spans_readings(path, cell, metric, key):
    entry = next(m for m in SPEC["per_layer"] if m["name"] == metric)
    assert cell in entry["workloads"]
    want = spans.readings(spans.read(path))[key]
    assert want is not None
    assert _metric(metric, _ctx(path, cell)) == want


def test_q1_slice_holds_whole_queries_of_the_worker_thread():
    t = spans.read(Q1)
    whole = [(s, e) for n, s, e in t["spans"]
             if n == "query.q1_pricing" and 0 < s and e < t["window_s"]]
    assert len(whole) >= 2
    # their stages come from the worker thread, inside the slice
    names = {n for n, _, _ in t["spans"]}
    assert {"suite.q1", "suite.q1.master", "suite.q1.to_host"} <= names
    # the scan's gaps between steps run inside its program and are not
    # host-bound; the reader counts only the rest
    gaps, inside = t["gaps"][0], t["gap_in_program"][0]
    assert any(inside) and not all(inside)
    outside = sum(b - a for (a, b), i in zip(gaps, inside) if not i)
    share = spans.host_bound_idle_share(t)
    assert 0 < share <= 100 * outside / t["window_s"] * (1 + 1e-9)
    ctx = _ctx(Q1, "tpch_sf01.q1")
    assert _metric("host_bound_idle_share.query", ctx) == share
    assert _metric("pass1_share.query", ctx) > 0
    assert 0 < _metric("device_idle_share.query", ctx) < 100
    for stage in ("master_ms_per_query", "to_host_ms_per_query",
                  "pass1_ms_per_query"):    # a slice's queries are not whole
        assert _metric(stage, ctx) is None
