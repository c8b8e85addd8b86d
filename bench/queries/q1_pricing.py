"""The suite's Q1 (``q1_pricing``): SELECT flag, SUM(revenue) FROM
lineitem WHERE shipdate <= cut GROUP BY flag, over TPC-H's columns: the
group key is (l_returnflag, l_linestatus), the cut 1998-09-02 (TPC-H Q1
with DELTA = 90), and the sum is over l_quantity, TPC-H Q1's sum_qty.
Q1's price sums (over 5e11 cents a group at SF0.1) do not fit the int32
state in which the program's GROUP BY sums integers; sum_qty does."""
import numpy as np

BIND = {"lineitem": {"shipdate": "l_shipdate",
                     "flag": "l_returnflag_linestatus",
                     "revenue": "l_quantity"}}


def program(tables):
    from repro.query import workloads

    return workloads.get("q1_pricing").run(tables)


def answer(q, t, dtype=None):
    li = t["lineitem"]
    keep = li["l_shipdate"] <= q["q1_ship_cut"]
    flag = li["l_returnflag_linestatus"][keep]
    qty = li["l_quantity"][keep]
    out = {}
    for k in np.unique(flag).tolist():
        vals = qty[flag == k]
        if dtype is None:
            out[int(k)] = float(vals.astype(np.int64).sum())
        else:
            out[int(k)] = float(np.add.reduce(vals.astype(dtype)))
    return out
