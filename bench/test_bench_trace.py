"""The trace reduction, on a small trace recorded on a TPU v5e by the
harness itself (``tpch_sf1.q3_q6`` at 0.5% of its rows, ``--trace 1``)
and on intervals whose union is known."""
from __future__ import annotations

import os

import numpy as np
import pytest

from bench import harness, reduce_trace

TRACE = os.path.join(harness.BENCH_DIR, "testdata", "q3_q6_small.xplane.pb")


def test_union_matches_a_grid():
    rng = np.random.default_rng(3)
    starts = rng.integers(0, 1000, 200).astype(float)
    ends = starts + rng.integers(1, 40, 200)
    us, ue = reduce_trace._union(starts, ends)
    grid = np.zeros(1100, bool)
    for s, e in zip(starts.astype(int), ends.astype(int)):
        grid[s:e] = True
    assert (ue - us).sum() == grid.sum()
    assert np.all(us[1:] > ue[:-1])


@pytest.fixture(scope="module")
def reduced():
    return reduce_trace.reduce_file(TRACE, harness.load_layers(),
                                    window="bench.window")


def test_busy_inside_window(reduced):
    assert reduced["devices"] == 1
    assert 0 < reduced["busy_s"] <= reduced["window_s"]


def test_pass1_layer_found(reduced):
    # Q3's two-pass TOP-N runs jit_topn_det_prune
    assert 0 < reduced["layer_s"]["engine pass 1"] <= reduced["busy_s"]


def test_breakdown_shape(reduced):
    b = reduced["breakdown"]
    for key in ("device_ops", "idle_gaps"):
        assert 0 < len(b[key]) <= 10
        assert all(isinstance(n, str) and t > 0 for n, t in b[key])
    times = [t for _, t in b["device_ops"]]
    assert times == sorted(times, reverse=True)
    idle = reduced["window_s"] - reduced["busy_s"]
    assert sum(t for _, t in b["idle_gaps"]) <= idle * (1 + 1e-9)
    assert any(n.startswith("query.") for n, _ in b["idle_gaps"])


def test_unknown_window_is_an_error():
    with pytest.raises(ValueError):
        reduce_trace.reduce_file(TRACE, {}, window="no.such.span")
