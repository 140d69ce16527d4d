"""TPC-H data for Q18 (customer, orders, lineitem) from a seed.

``make(cfg, seed, scale)`` returns ``{table: {column: numpy array}}``
at the spec's column set and widths (TPC-H 1.4), with the value rules of
4.2.3 where Q18 or a later query could see them. ``scale`` shrinks the
tables for CPU tests; the benchmark runs it at 1.
"""
from __future__ import annotations

import numpy as np

import datagen as g

CURRENT_DAY = 1263         # 1995-06-17, TPC-H's CURRENTDATE
END_DAY = 2405             # 1998-08-02, the last order date + 151 days
INSTRUCT = ("DELIVER IN PERSON", "COLLECT COD", "NONE", "TAKE BACK RETURN")


def _rows(cfg: dict, table: str, scale: float) -> int:
    return max(64, int(cfg["rows"][table] * scale))


def _customer(rng, n: int) -> dict:
    key = np.arange(1, n + 1, dtype=np.int32)
    nation = rng.integers(0, 25, n)
    return {"c_custkey": key,
            "c_name": g.numbered("Customer#", key, 9),
            "c_address": g.text(rng, n, 10, 40),
            "c_nationkey": nation.astype(np.int32),
            "c_phone": g.phones(rng, nation),
            "c_acctbal": rng.integers(-99999, 1000000, n).astype(np.int32),
            "c_mktsegment": g.pick(rng, g.SEGMENTS, n),
            "c_comment": g.text(rng, n, 29, 116)}


def make(cfg: dict, seed: int, scale: float = 1.0) -> dict:
    rng = np.random.default_rng(seed)
    n_cust = _rows(cfg, "customer", scale)
    n_orders = _rows(cfg, "orders", scale)
    n_lines = max(n_orders, _rows(cfg, "lineitem", scale))
    customer = _customer(rng, n_cust)

    # orders: custkeys that are not multiples of 3
    okey = g.sparse_orderkeys(n_orders)
    with_orders = np.arange(1, n_cust + 1)
    with_orders = with_orders[with_orders % 3 != 0]
    ocust = with_orders[rng.integers(0, len(with_orders), n_orders)]
    oday = rng.integers(0, END_DAY - 151, n_orders)

    # lineitem
    lines = g.exact_counts(rng, n_orders, 1, 7, n_lines)
    starts = np.cumsum(lines) - lines
    per_order = lambda v: np.repeat(v, lines)           # noqa: E731
    n = n_lines
    partkey = rng.integers(1, 200001, n)
    qty = rng.integers(1, 51, n)
    ext = qty * g.retail_price(partkey)
    discount = rng.integers(0, 11, n)
    tax = rng.integers(0, 9, n)
    ship = per_order(oday) + rng.integers(1, 122, n)
    commit = per_order(oday) + rng.integers(30, 91, n)
    receipt = ship + rng.integers(1, 31, n)
    returned = np.where(rng.random(n) < 0.5, "R", "A")
    returnflag = np.where(receipt <= CURRENT_DAY, returned, "N")
    linestatus = np.where(ship > CURRENT_DAY, "O", "F")
    lineitem = {
        "l_orderkey": per_order(okey),
        "l_partkey": partkey.astype(np.int32),
        "l_suppkey": rng.integers(1, 10001, n).astype(np.int32),
        "l_linenumber": (np.arange(n) - per_order(starts) + 1
                         ).astype(np.int32),
        "l_quantity": qty.astype(np.int32),
        "l_extendedprice": ext.astype(np.int32),
        "l_discount": discount.astype(np.int32),
        "l_tax": tax.astype(np.int32),
        "l_returnflag": returnflag,
        "l_linestatus": linestatus,
        "l_shipdate": g.datekeys(ship),
        "l_commitdate": g.datekeys(commit),
        "l_receiptdate": g.datekeys(receipt),
        "l_shipinstruct": g.pick(rng, INSTRUCT, n),
        "l_shipmode": g.pick(rng, g.SHIPMODES, n),
        "l_comment": g.text(rng, n, 10, 43),
    }

    # o_totalprice: sum of extendedprice * (1 + tax) * (1 - discount)
    charge = ext * (100 + tax) * (100 - discount) // 10000
    n_open = np.add.reduceat((linestatus == "O").astype(np.int64), starts)
    status = np.where(n_open == lines, "O",
                      np.where(n_open == 0, "F", "P"))
    orders = {
        "o_orderkey": okey,
        "o_custkey": ocust.astype(np.int32),
        "o_orderstatus": status,
        "o_totalprice": np.add.reduceat(charge, starts).astype(np.int32),
        "o_orderdate": g.datekeys(oday),
        "o_orderpriority": g.pick(rng, g.PRIORITIES, n_orders),
        "o_clerk": g.numbered("Clerk#", rng.integers(1, 1001, n_orders), 9),
        "o_shippriority": np.zeros(n_orders, dtype=np.int32),
        "o_comment": g.text(rng, n_orders, 19, 78),
    }
    return {"customer": customer, "orders": orders, "lineitem": lineitem}
