"""The suite's Q3 (``q3_shipping``): lineitem joined with the orders
placed before a cut (1995-12-07), ORDER BY extprice DESC LIMIT 10, as
(orderkey, extprice), over TPC-H's l_orderkey, l_extendedprice,
o_orderkey and o_orderdate. Equal prices go to the earlier row."""
import numpy as np

BIND = {"lineitem": {"orderkey": "l_orderkey", "extprice": "l_extendedprice"},
        "orders": {"orderkey": "o_orderkey", "orderdate": "o_orderdate"}}


def program(tables):
    from repro.query import workloads

    return workloads.get("q3_shipping").run(tables)


def answer(q, t, dtype=None):
    li, orders = t["lineitem"], t["orders"]
    ok = orders["o_orderkey"][orders["o_orderdate"] < q["q3_order_cut"]]
    member = np.isin(li["l_orderkey"], ok)
    keys, vals = li["l_orderkey"][member], li["l_extendedprice"][member]
    if dtype is not None:
        vals = vals.astype(dtype)
    # largest first, ties to the lower row
    order = np.lexsort((np.arange(vals.shape[0]), -vals.astype(np.float32)))
    top = order[:q["q3_limit"]]
    return [(int(k), float(v)) for k, v in zip(keys[top], vals[top])]
