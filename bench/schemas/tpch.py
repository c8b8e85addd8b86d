"""TPC-H's ORDERS and LINEITEM, generated by the rules of the TPC-H
specification v3.0.1, clause 4.2.3, at the configuration's scale factor.

* ``o_orderkey`` is sparse: the i-th order (from 0) has the key
  ``(i // 8) * 32 + i % 8 + 1``, so only the first 8 of every 32 keys
  are used. There are ``SF * 1,500,000`` orders.
* Each order has 1 to 7 lineitems, uniform, numbered from 1; every
  lineitem belongs to exactly one order, and the lineitems of one order
  follow each other, in order of ``o_orderkey``.
* ``o_orderdate`` is uniform over [STARTDATE, ENDDATE - 151 days];
  ``l_shipdate`` = orderdate + [1, 121], ``l_commitdate`` = orderdate +
  [30, 90], ``l_receiptdate`` = shipdate + [1, 30].
* ``l_returnflag`` is 'R' or 'A' at random when the receipt date is not
  after CURRENTDATE (1995-06-17), else 'N'; ``l_linestatus`` is 'O'
  when the ship date is after CURRENTDATE, else 'F'; ``o_orderstatus``
  is 'F' or 'O' when all its lines are, else 'P'.
* ``l_partkey`` is uniform over [1, SF * 200,000]; ``l_suppkey`` is one
  of the part's four suppliers; ``l_quantity`` is uniform over [1, 50];
  ``l_extendedprice`` = quantity * the part's retail price,
  ``(90000 + (partkey // 10) % 20001 + 100 * (partkey % 1000)) / 100``;
  ``l_discount`` is uniform over [0.00, 0.10], ``l_tax`` over
  [0.00, 0.08]; ``o_totalprice`` = sum of extendedprice * (1 + tax) *
  (1 - discount) over the order's lines.
* ``o_custkey`` is uniform over the keys in [1, SF * 150,000] that are
  not multiples of 3, ``o_clerk`` over [1, SF * 1,000]; the priorities,
  ship instructions and ship modes are uniform over the spec's lists.

Held as a column store: dates are int32 day numbers counted from
``EPOCH``, money is held in cents, discount and tax in hundredths, and
the spec's short strings as codes (``l_returnflag`` and ``l_linestatus``
packed into one uint32, the returnflag's character times 256 plus the
linestatus's). ``l_extendedprice`` is integer cents held in float32,
exact below 2**24 (at most 10,494,950). Free-text comments are not held.

What decides a compiled shape is drawn from the configuration's
``template_seed`` and is the same in every run, as the spec's generator
makes one database per scale factor: the number of lines of each order,
the order dates and the ship, commit and receipt offsets (Q1's filtered
length and Q3's join size follow from them). Every other value is drawn
from the run's seed. Each column has a random stream of its own, so the
first rows drawn for a longer table are those of a shorter one:
``make_stream`` continues the configuration's lineitem past its last
order with the same rules.
"""
from __future__ import annotations

import datetime

import numpy as np

from bench.loops import rng_for

EPOCH = datetime.date(1992, 8, 24)


def day(y: int, m: int, d: int) -> int:
    """The int32 day number of a date."""
    return (datetime.date(y, m, d) - EPOCH).days


STARTDATE, CURRENTDATE, ENDDATE = (day(1992, 1, 1), day(1995, 6, 17),
                                   day(1998, 12, 31))
ORDERS_PER_SF = 1_500_000

# the seed's random streams, one per column (the template's are 0..4)
_CUSTKEY, _PARTKEY, _QUANTITY, _DISCOUNT, _TAX, _RETURNFLAG, _SUPP, \
    _INSTRUCT, _MODE, _PRIORITY, _CLERK = range(10, 21)


def n_orders(cfg: dict) -> int:
    return int(round(ORDERS_PER_SF * cfg["scale_factor"]))


def order_keys(i: np.ndarray) -> np.ndarray:
    """The sparse key of the i-th order (from 0)."""
    i = i.astype(np.int64)
    return ((i // 8) * 32 + i % 8 + 1).astype(np.uint32)


def _template(cfg: dict, stream: int) -> np.random.Generator:
    return np.random.default_rng([int(cfg["template_seed"]), stream])


def _lines_per_order(cfg: dict, orders: int) -> np.ndarray:
    return _template(cfg, 0).integers(1, 8, orders).astype(np.int64)


def _retail_cents(partkey: np.ndarray) -> np.ndarray:
    p = partkey.astype(np.int64)
    return 90000 + (p // 10) % 20001 + 100 * (p % 1000)


def _partkey(cfg: dict, seed: int, lines: int) -> np.ndarray:
    parts = int(round(200_000 * cfg["scale_factor"]))
    return rng_for(seed, _PARTKEY).integers(1, parts + 1, lines)


def _quantity(seed: int, lines: int) -> np.ndarray:
    return rng_for(seed, _QUANTITY).integers(1, 51, lines)


def make_tables(cfg: dict, seed: int) -> dict:
    """{"lineitem": {column: array}, "orders": {column: array}}."""
    sf = cfg["scale_factor"]
    no = n_orders(cfg)
    per = _lines_per_order(cfg, no)
    nl = int(per.sum())
    owner = np.repeat(np.arange(no), per)             # order of each line
    first = np.cumsum(per) - per                      # its first line
    linenumber = np.arange(nl) - np.repeat(first, per) + 1

    orderdate = _template(cfg, 1).integers(STARTDATE, ENDDATE - 151 + 1, no)
    shipdate = orderdate[owner] + _template(cfg, 2).integers(1, 122, nl)
    commitdate = orderdate[owner] + _template(cfg, 3).integers(30, 91, nl)
    receiptdate = shipdate + _template(cfg, 4).integers(1, 31, nl)

    partkey = _partkey(cfg, seed, nl)
    suppliers = int(round(10_000 * sf))
    supp_i = rng_for(seed, _SUPP).integers(0, 4, nl)
    suppkey = (partkey + supp_i * (suppliers // 4 + (partkey - 1)
                                   // suppliers)) % suppliers + 1
    quantity = _quantity(seed, nl)
    ext_cents = quantity * _retail_cents(partkey)
    discount = rng_for(seed, _DISCOUNT).integers(0, 11, nl)
    tax = rng_for(seed, _TAX).integers(0, 9, nl)
    returned = np.where(rng_for(seed, _RETURNFLAG).integers(0, 2, nl) == 0,
                        ord("R"), ord("A"))
    returnflag = np.where(receiptdate <= CURRENTDATE, returned, ord("N"))
    open_line = shipdate > CURRENTDATE
    linestatus = np.where(open_line, ord("O"), ord("F"))

    # o_totalprice in cents: extendedprice * (1 + tax) * (1 - discount)
    charge = ext_cents * (100 + tax) * (100 - discount)
    totalprice = (np.add.reduceat(charge, first) + 5000) // 10000
    n_open = np.add.reduceat(open_line.astype(np.int64), first)
    orderstatus = np.where(n_open == per, ord("O"),
                           np.where(n_open == 0, ord("F"), ord("P")))
    customers = int(round(150_000 * sf))
    j = rng_for(seed, _CUSTKEY).integers(0, customers * 2 // 3, no)
    custkey = j + j // 2 + 1                          # skips multiples of 3
    okey = order_keys(np.arange(no))

    lineitem = {
        "l_orderkey": okey[owner],
        "l_partkey": partkey.astype(np.int32),
        "l_suppkey": suppkey.astype(np.int32),
        "l_linenumber": linenumber.astype(np.int32),
        "l_quantity": quantity.astype(np.int32),
        "l_extendedprice": ext_cents.astype(np.float32),
        "l_discount": discount.astype(np.int32),
        "l_tax": tax.astype(np.int32),
        "l_returnflag_linestatus": (returnflag * 256
                                    + linestatus).astype(np.uint32),
        "l_shipdate": shipdate.astype(np.int32),
        "l_commitdate": commitdate.astype(np.int32),
        "l_receiptdate": receiptdate.astype(np.int32),
        "l_shipinstruct": rng_for(seed, _INSTRUCT).integers(
            0, 4, nl).astype(np.uint8),
        "l_shipmode": rng_for(seed, _MODE).integers(0, 7, nl).astype(np.uint8),
    }
    orders = {
        "o_orderkey": okey,
        "o_custkey": custkey.astype(np.uint32),
        "o_orderstatus": orderstatus.astype(np.uint8),
        "o_totalprice": totalprice.astype(np.int32),
        "o_orderdate": orderdate.astype(np.int32),
        "o_orderpriority": rng_for(seed, _PRIORITY).integers(
            0, 5, no).astype(np.uint8),
        "o_clerk": rng_for(seed, _CLERK).integers(
            1, max(int(round(1000 * sf)), 1) + 1, no).astype(np.int32),
        "o_shippriority": np.zeros(no, np.int32),
    }
    return {"lineitem": lineitem, "orders": orders}


def make_stream(cfg: dict, seed: int, table: str, n: int, columns) -> dict:
    """The first ``n`` lineitem rows, in the order the spec generates
    them, of ``columns`` (``l_orderkey`` and ``l_extendedprice``). Past
    the configuration's last order the orders go on by the same rules:
    the next sparse keys, 1 to 7 lines each."""
    if table != "lineitem":
        raise KeyError(f"make_stream streams lineitem, not {table!r}")
    per = _lines_per_order(cfg, max(n // 4 + 4096, n_orders(cfg)))
    while per.sum() < n:        # only for n far past the mean of 4 lines
        per = _lines_per_order(cfg, 2 * per.size)
    last = int(np.searchsorted(np.cumsum(per), n)) + 1
    out = {}
    if "l_orderkey" in columns:
        out["l_orderkey"] = np.repeat(order_keys(np.arange(last)),
                                      per[:last])[:n]
    if "l_extendedprice" in columns:
        out["l_extendedprice"] = (
            _quantity(seed, n) * _retail_cents(_partkey(cfg, seed, n))
        ).astype(np.float32)
    missing = set(columns) - set(out)
    if missing:
        raise KeyError(f"make_stream holds no {sorted(missing)}")
    return out
