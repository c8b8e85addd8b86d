"""TPC-H-subset suite oracles: every query differentially tested
against its plain-Python reference (exact equality — the generators are
built so f32 addition order can't matter), under analytic AND tuned
plans, plus generator determinism."""
import numpy as np
import pytest

from repro.core import engine, planner
from repro.query import workloads

SCALE = 1500


@pytest.fixture(scope="module")
def tables():
    return workloads.tpch_tables(scale=SCALE, seed=0)


@pytest.mark.parametrize("query", workloads.SUITE,
                         ids=[q.name for q in workloads.SUITE])
def test_suite_query_matches_reference(query, tables):
    assert query.run(tables) == query.reference(tables)


@pytest.mark.parametrize("query", workloads.SUITE,
                         ids=[q.name for q in workloads.SUITE])
def test_suite_query_tuned_matches_reference(query, tables, monkeypatch):
    monkeypatch.setattr(planner, "MEASURE_HOOK", lambda p, t: 10.0)
    assert query.run(tables, tune="race") == query.reference(tables)
    # and the persisted winner replays to the same answer
    assert query.run(tables, tune="cached") == query.reference(tables)


@pytest.mark.parametrize("seed", [0, 1, 2])
def test_suite_reference_stable_across_seeds(seed):
    """References stay exact (no float ambiguity) for other seeds too."""
    tables = workloads.tpch_tables(scale=700, seed=seed)
    for q in workloads.SUITE:
        assert q.run(tables) == q.reference(tables), q.name


def test_generators_deterministic():
    a = workloads.make_lineitem(1000, seed=3)
    b = workloads.make_lineitem(1000, seed=3)
    for col in a.cols:
        assert np.array_equal(np.asarray(a.cols[col]),
                              np.asarray(b.cols[col])), col
    c = workloads.make_lineitem(1000, seed=4)
    assert not np.array_equal(np.asarray(a.cols["orderkey"]),
                              np.asarray(c.cols["orderkey"]))


def test_extprice_unique_and_revenue_integer_valued():
    li = workloads.make_lineitem(5000, seed=0).cols
    ext = np.asarray(li["extprice"])
    assert len(np.unique(ext)) == ext.shape[0]  # TOP-N unambiguous
    rev = np.asarray(li["revenue"])
    assert rev.dtype == np.int32                # exact integer sums
    assert rev.min() >= 1 and rev.max() <= 50


def test_tpch_tables_shapes():
    t = workloads.tpch_tables(scale=900, seed=0)
    assert t["lineitem"].num_rows == 900
    assert t["orders"].num_rows == 300
    assert set(t["lineitem"].cols) >= {"orderkey", "shipdate", "revenue",
                                       "extprice", "flag", "discount",
                                       "quantity"}


def test_engine_streams_cover_all_algorithms(tables):
    for algo in engine.ALGORITHMS:
        streams, params = workloads.engine_streams(algo, tables)
        assert streams and all(
            int(s.shape[0]) == SCALE for s in streams), algo
        r = engine.execute_plan(
            algo, *streams,
            plan=planner.analytic_plan(algo, streams, params), **params)
        assert r.keep.shape == (SCALE,)
    with pytest.raises(KeyError):
        workloads.engine_streams("sort", tables)


def test_get_by_name():
    assert workloads.get("q1_pricing").algo == "groupby"
    with pytest.raises(KeyError):
        workloads.get("q99")
