"""The TPC-H generator against the rules of the spec's clause 4.2.3,
and each configuration's stated sizes against what it generates."""
from __future__ import annotations

import numpy as np
import pytest

from bench import harness
from bench.schemas import tpch

SEED = 2**33 + 5
SMALL = {"scale_factor": 0.01, "template_seed": 0}


@pytest.fixture(scope="module")
def tables():
    return tpch.make_tables(SMALL, SEED)


def test_orders_keys_sparse_and_lines_clustered(tables):
    li, o = tables["lineitem"], tables["orders"]
    assert o["o_orderkey"].size == 15_000
    k = o["o_orderkey"].astype(np.int64)
    assert ((k - 1) % 32 < 8).all() and (np.diff(k) > 0).all()
    # every line has its order, the lines of an order are adjacent,
    # 1 to 7 of them, numbered from 1
    _, first, per = np.unique(li["l_orderkey"], return_index=True,
                              return_counts=True)
    assert np.array_equal(np.unique(li["l_orderkey"]), o["o_orderkey"])
    assert (np.diff(li["l_orderkey"].astype(np.int64)) >= 0).all()
    assert per.min() == 1 and per.max() == 7
    assert (li["l_linenumber"][first] == 1).all()


def test_dates_flags_and_prices_follow_the_rules(tables):
    li, o = tables["lineitem"], tables["orders"]
    od = dict(zip(o["o_orderkey"].tolist(), o["o_orderdate"].tolist()))
    odate = np.array([od[k] for k in li["l_orderkey"].tolist()])
    assert o["o_orderdate"].min() >= tpch.STARTDATE
    assert o["o_orderdate"].max() <= tpch.ENDDATE - 151
    ship = li["l_shipdate"] - odate
    assert ship.min() >= 1 and ship.max() <= 121
    commit = li["l_commitdate"] - odate
    assert commit.min() >= 30 and commit.max() <= 90
    receipt = li["l_receiptdate"] - li["l_shipdate"]
    assert receipt.min() >= 1 and receipt.max() <= 30
    rf = li["l_returnflag_linestatus"] >> 8
    ls = li["l_returnflag_linestatus"] & 0xFF
    late = li["l_receiptdate"] > tpch.CURRENTDATE
    assert (rf[late] == ord("N")).all()
    assert set(rf[~late].tolist()) == {ord("R"), ord("A")}
    assert np.array_equal(ls == ord("O"), li["l_shipdate"] > tpch.CURRENTDATE)
    p = li["l_partkey"].astype(np.int64)
    retail = 90000 + (p // 10) % 20001 + 100 * (p % 1000)
    assert np.array_equal(li["l_extendedprice"],
                          (li["l_quantity"] * retail).astype(np.float32))
    assert li["l_discount"].max() == 10 and li["l_tax"].max() == 8
    assert (o["o_custkey"] % 3 != 0).all()


def test_day_numbers():
    assert tpch.day(1998, 9, 2) == 2200       # the suite's Q1 cut
    assert tpch.EPOCH.isoformat() == "1992-08-24"


def test_stream_continues_the_lineitem(tables):
    n = tables["lineitem"]["l_orderkey"].size
    cols = ["l_orderkey", "l_extendedprice"]
    s = tpch.make_stream(SMALL, SEED, "lineitem", n + 5000, cols)
    for c in cols:
        assert np.array_equal(s[c][:n], tables["lineitem"][c])
    assert s["l_orderkey"][n] == tpch.order_keys(np.array([15_000]))[0]


@pytest.mark.parametrize("name", ["tpch_sf01", "tpch_sf1"])
def test_config_sizes_are_what_it_generates(name):
    cfg = next(c for c in harness.load_spec()["configs"] if c["name"] == name)
    cfg = harness._json(harness.os.path.join(harness.ROOT, cfg["file"]))
    per = tpch._lines_per_order(cfg, tpch.n_orders(cfg))
    assert per.size == cfg["orders_rows"]
    assert int(per.sum()) == cfg["lineitem_rows"]
