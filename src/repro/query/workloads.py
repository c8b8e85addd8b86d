"""TPC-H-subset workload suite: the tuning race bed + end-to-end bench.

Grown out of ``examples/tpch_q3.py``: three TPC-H-flavoured queries over
a deterministic seeded lineitem/orders pair, each with BOTH a pruned
execution path through the engine (`SuiteQuery.run`, honoring the
``tune=`` knob) and a plain-Python reference implementation
(`SuiteQuery.reference` — no numpy, no pandas, just dict/loop SQL
semantics) so every suite run is a differential correctness check, not
just a timing row:

``q1_pricing``  (Q1: filter + GROUP BY)
    SELECT flag, SUM(revenue) WHERE shipdate <= CUT GROUP BY flag —
    the groupby pruner forwards evicted partials + final switch state,
    master folds them into the exact per-flag sums.
``q3_shipping`` (Q3: join + TOP-N)
    date-filtered orders Bloom-joined against lineitem (superset-safe
    switch filter, master re-verifies exactly), then ORDER BY extprice
    LIMIT N via the deterministic TOP-N pruner.
``q6_forecast`` (Q6: selective aggregate)
    SUM(revenue * discount) under a 5-predicate conjunctive WHERE —
    predicate decomposition prunes at the switch, master applies the
    full formula and sums survivors.

Exactness is by construction, not tolerance: ``revenue`` is an int32
column (1..50), held the way engines hold TPC-H DECIMAL, so GROUP BY
sums it in an integer state, exact in any order up to 2^31 per group
(a Q1 group at SF1 sums to about 23.4M, past f32's exact 2^24);
``extprice`` is a permutation (all values distinct, exact in f32 up to
2^24 rows), so TOP-N has a unique answer; Q6 sums in int64.

The generators also back the six per-algorithm tuning beds
(``engine_streams``): every ``core.ALGORITHMS`` entry gets a stream
drawn from the suite tables, which is what the mask-invariance property
tests and ``benchmarks/bench_tpch.py`` race plans on.
"""
from __future__ import annotations

import dataclasses
from typing import Callable

import jax.numpy as jnp
import numpy as np

from repro import core
from repro.obs import span
from .engine import QuerySpec, run_query
from .tables import Table

# date axis spans [0, DATE_MAX); cuts chosen for TPC-H-like selectivity
DATE_MAX = 2400
Q1_SHIP_CUT = 2200        # Q1 keeps ~92% (the classic near-full scan)
Q3_ORDER_CUT = 1200       # Q3 keeps ~half the orders
Q3_LIMIT = 10
Q6_SHIP_LO, Q6_SHIP_HI = 1000, 1400   # one "year"
Q6_DISC_LO, Q6_DISC_HI = 2, 4
Q6_QTY_LT = 24


# ------------------------------------------------------------ generators
def make_lineitem(scale: int, seed: int = 0) -> Table:
    """Deterministic lineitem-like table with `scale` rows.

    revenue: int32 in [1, 50] (exact integer GROUP BY sums);
    extprice: a permutation of 1..scale (unique — TOP-N is unambiguous);
    flag: returnflag/linestatus-style 6-value group key;
    discount/quantity: small ints for Q6's conjunctive predicate.
    """
    rng = np.random.default_rng(seed)
    return Table("lineitem", {
        "orderkey": jnp.asarray(
            rng.integers(0, 2 * scale, scale).astype(np.uint32)),
        "shipdate": jnp.asarray(
            rng.integers(0, DATE_MAX, scale).astype(np.int32)),
        "revenue": jnp.asarray(
            rng.integers(1, 51, scale).astype(np.int32)),
        "extprice": jnp.asarray(
            (rng.permutation(scale) + 1).astype(np.float32)),
        "flag": jnp.asarray(rng.integers(0, 6, scale).astype(np.uint32)),
        "discount": jnp.asarray(
            rng.integers(0, 11, scale).astype(np.int32)),
        "quantity": jnp.asarray(
            rng.integers(1, 51, scale).astype(np.int32)),
    })


def make_orders(scale: int, seed: int = 1) -> Table:
    """Orders-like table with `scale` rows; orderkey = arange, so about
    half of lineitem's [0, 2·scale) orderkeys find a real order."""
    rng = np.random.default_rng(seed)
    return Table("orders", {
        "orderkey": jnp.asarray(np.arange(scale, dtype=np.uint32)),
        "custkey": jnp.asarray(
            rng.integers(0, max(scale // 3, 1), scale).astype(np.uint32)),
        "orderdate": jnp.asarray(
            rng.integers(0, DATE_MAX, scale).astype(np.int32)),
    })


def tpch_tables(scale: int = 30_000, seed: int = 0) -> dict:
    """The suite's table set: lineitem at `scale` rows, orders at
    scale/3 (TPC-H's ~1:3 orders:lineitem ratio, truncated)."""
    return {"lineitem": make_lineitem(scale, seed),
            "orders": make_orders(max(scale // 3, 8), seed + 1)}


# ------------------------------------------------------------- Q1 bodies
# Each body opens ``suite.<q>`` and a span per stage inside it:
# ``to_host`` (a device->host copy), ``master`` (host completion),
# ``to_device`` (a host->device upload), and the query's switch stage.
def _q1_run(tables, tune="off", plan_cache=None):
    li = tables["lineitem"]
    with span("suite.q1"):
        with span("suite.q1.to_host"):
            shipdate = np.asarray(li.cols["shipdate"])
            flag = np.asarray(li.cols["flag"])
            revenue = np.asarray(li.cols["revenue"])
        with span("suite.q1.master"):
            keep = shipdate <= Q1_SHIP_CUT
            flag, revenue = flag[keep], revenue[keep]
        with span("suite.q1.to_device"):
            scanned = Table("lineitem_q1", {"flag": jnp.asarray(flag),
                                            "revenue": jnp.asarray(revenue)})
        r = run_query(QuerySpec("groupby", ("flag", "revenue"),
                                dict(d=8, w=4)),
                      scanned, tune=tune, plan_cache=plan_cache)
        return {int(k): float(v) for k, v in r["output"].items()}


def _q1_reference(tables):
    li = tables["lineitem"].cols
    out: dict = {}
    for f, d, r in zip(np.asarray(li["flag"]).tolist(),
                       np.asarray(li["shipdate"]).tolist(),
                       np.asarray(li["revenue"]).tolist()):
        if d <= Q1_SHIP_CUT:
            out[f] = out.get(f, 0.0) + r
    return {int(k): float(v) for k, v in out.items()}


# ------------------------------------------------------------- Q3 bodies
def _q3_run(tables, tune="off", plan_cache=None):
    li, orders = tables["lineitem"], tables["orders"]
    with span("suite.q3"):
        with span("suite.q3.to_host"):
            odate = np.asarray(orders.cols["orderdate"])
        with span("suite.q3.master"):
            odate_ok = odate < Q3_ORDER_CUT
        # switch side: Bloom filter of surviving orderkeys, superset-safe
        with span("suite.q3.join"):
            ok_keys = jnp.where(jnp.asarray(odate_ok),
                                orders.cols["orderkey"],
                                jnp.uint32(0xFFFFFFFF))
            bloom = core.bloom_build(ok_keys, 1 << 16, 3)
            joined = core.bloom_query(bloom, li.cols["orderkey"])
        with span("suite.q3.to_host"):
            join_keep = np.asarray(joined)
        # master side: exact membership check on the forwarded superset
        with span("suite.q3.to_host"):
            li_keys = np.asarray(li.cols["orderkey"])
        with span("suite.q3.to_host"):
            o_keys = np.asarray(orders.cols["orderkey"])
        with span("suite.q3.master"):
            exact = np.zeros(li_keys.shape[0], bool)
            exact[join_keep] = np.isin(li_keys[join_keep], o_keys[odate_ok])
        # tunable TOP-N over the joined survivors' extprice
        with span("suite.q3.to_host"):
            extprice = np.asarray(li.cols["extprice"])
        with span("suite.q3.master"):
            vals, keys = extprice[exact], li_keys[exact]
        with span("suite.q3.to_device"):
            vals = jnp.asarray(vals)
        r = _engine("topn_det", (vals,), dict(N=Q3_LIMIT, w=8),
                    tune, plan_cache)
        with span("suite.q3.master"):
            topv, topi = core.master_complete_topn(vals, r.keep, Q3_LIMIT)
            return [(int(keys[i]), float(v))
                    for v, i in zip(np.asarray(topv), np.asarray(topi))]


def _q3_reference(tables):
    li = tables["lineitem"].cols
    orders = tables["orders"].cols
    ok = {k for k, d in zip(np.asarray(orders["orderkey"]).tolist(),
                            np.asarray(orders["orderdate"]).tolist())
          if d < Q3_ORDER_CUT}
    rows = [(k, p) for k, p in zip(np.asarray(li["orderkey"]).tolist(),
                                   np.asarray(li["extprice"]).tolist())
            if k in ok]
    rows.sort(key=lambda kp: -kp[1])
    return [(int(k), float(p)) for k, p in rows[:Q3_LIMIT]]


# ------------------------------------------------------------- Q6 bodies
_Q6_FORMULA = core.And((
    core.Pred("shipdate", "ge", Q6_SHIP_LO),
    core.Pred("shipdate", "lt", Q6_SHIP_HI),
    core.Pred("discount", "ge", Q6_DISC_LO),
    core.Pred("discount", "le", Q6_DISC_HI),
    core.Pred("quantity", "lt", Q6_QTY_LT),
))


def _q6_run(tables, tune="off", plan_cache=None):
    # the filter pruner is stateless — there is no plan to tune, so the
    # knob is accepted (uniform suite API) and ignored
    li = tables["lineitem"]
    cols = {c: li.cols[c] for c in ("shipdate", "discount", "quantity")}
    with span("suite.q6"):
        with span("suite.q6.switch"):
            pr = core.filter_prune(_Q6_FORMULA, cols)
            final = core.master_complete_filter(_Q6_FORMULA, cols, pr.keep)
        with span("suite.q6.to_host"):
            final = np.asarray(final)
        with span("suite.q6.to_host"):
            rev = np.asarray(li.cols["revenue"])
        with span("suite.q6.to_host"):
            disc = np.asarray(li.cols["discount"])
        with span("suite.q6.master"):
            rev, disc = rev.astype(np.int64), disc.astype(np.int64)
            return int((rev[final] * disc[final]).sum())


def _q6_reference(tables):
    li = tables["lineitem"].cols
    total = 0
    for d, disc, q, r in zip(np.asarray(li["shipdate"]).tolist(),
                             np.asarray(li["discount"]).tolist(),
                             np.asarray(li["quantity"]).tolist(),
                             np.asarray(li["revenue"]).tolist()):
        if (Q6_SHIP_LO <= d < Q6_SHIP_HI
                and Q6_DISC_LO <= disc <= Q6_DISC_HI and q < Q6_QTY_LT):
            total += int(r) * disc
    return total


def _engine(algo, streams, params, tune, plan_cache):
    """Tuned-or-analytic engine call shared by the suite bodies: with
    tune="off" the analytic plan still runs (the suite always exercises
    the two-pass family, so off/cached/race differ only in speed)."""
    if tune == "off":
        plan = core.analytic_plan(algo, streams, params)
    else:
        plan = core.resolve_plan(algo, streams, params, tune_mode=tune,
                                 cache=plan_cache).plan
    return core.execute_plan(algo, *streams, plan=plan, **params)


# ---------------------------------------------------------------- suite
@dataclasses.dataclass(frozen=True)
class SuiteQuery:
    """One suite member: a pruned engine path and its plain-Python
    oracle. `run(tables, tune=..., plan_cache=...)` and
    `reference(tables)` return the same normalized Python value
    (dict / list of tuples / int) — compare with ==."""
    name: str
    algo: str        # engine algorithm behind the tunable stage
    run: Callable
    reference: Callable


SUITE = (
    SuiteQuery("q1_pricing", "groupby", _q1_run, _q1_reference),
    SuiteQuery("q3_shipping", "topn_det", _q3_run, _q3_reference),
    SuiteQuery("q6_forecast", "filter", _q6_run, _q6_reference),
)


def get(name: str) -> SuiteQuery:
    for q in SUITE:
        if q.name == name:
            return q
    raise KeyError(name)


# ----------------------------------------------- per-algorithm race beds
def engine_streams(algo: str, tables) -> tuple[tuple, dict]:
    """(streams, params) for racing `algo` on suite data — one bed per
    ``core.ALGORITHMS`` entry, all drawn from the lineitem columns, so
    tuning and the mask-invariance property tests run on the same
    distributions the suite benches."""
    li = tables["lineitem"].cols
    if algo == "topn_det":
        return (li["extprice"],), dict(N=64, w=8)
    if algo == "topn_rand":
        return (li["extprice"],), dict(d=1024, w=8, seed=0)
    if algo == "distinct":
        return (li["orderkey"],), dict(d=4096, w=4)
    if algo == "skyline":
        pts = jnp.stack([li["extprice"],
                         li["quantity"].astype(jnp.float32)], axis=-1)
        return (pts,), dict(w=64, score="aph")
    if algo == "groupby":
        return (li["flag"], li["revenue"]), dict(d=8, w=4)
    if algo == "having":
        bucket = (li["shipdate"] // 100).astype(jnp.uint32)
        return (bucket, li["revenue"]), dict(threshold=100.0, rows=3,
                                             width=1024)
    raise KeyError(algo)
