"""The suite's Q6 (``q6_forecast``): SUM(revenue * discount) over
lineitem under a five-predicate conjunction on shipdate, discount and
quantity, an integer, over TPC-H's columns: the sum is TPC-H Q6's,
l_extendedprice * l_discount (cents times hundredths)."""
import numpy as np

BIND = {"lineitem": {"shipdate": "l_shipdate", "discount": "l_discount",
                     "quantity": "l_quantity", "revenue": "l_extendedprice"}}


def program(tables):
    from repro.query import workloads

    return workloads.get("q6_forecast").run(tables)


def answer(q, t, dtype=None):
    li = t["lineitem"]
    d, disc, qty = li["l_shipdate"], li["l_discount"], li["l_quantity"]
    keep = ((q["q6_ship_lo"] <= d) & (d < q["q6_ship_hi"])
            & (q["q6_disc_lo"] <= disc) & (disc <= q["q6_disc_hi"])
            & (qty < q["q6_qty_lt"]))
    prod = li["l_extendedprice"][keep].astype(np.int64) * disc[keep]
    if dtype is None:
        return int(prod.sum())
    return int(np.add.reduce(prod.astype(dtype)))
