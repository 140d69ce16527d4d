"""Star Schema Benchmark data (ssb-dbgen's tables and value sets) from a seed.

``make(cfg, seed, scale)`` returns ``{table: {column: numpy array}}``;
the harness writes it to the catalog and the plain reference reads the
same arrays. ``scale`` shrinks every table but ``date`` for CPU tests;
the benchmark runs it at 1.
"""
from __future__ import annotations

import numpy as np

import datagen as g

WEEKDAYS = ("Thursday", "Friday", "Saturday", "Sunday", "Monday",
            "Tuesday", "Wednesday")   # 1970-01-01 was a Thursday
MONTHS = ("January", "February", "March", "April", "May", "June", "July",
          "August", "September", "October", "November", "December")
SEASONS = ("Winter", "Winter", "Spring", "Spring", "Spring", "Summer",
           "Summer", "Summer", "Fall", "Fall", "Christmas", "Christmas")


def _rows(cfg: dict, table: str, scale: float) -> int:
    n = cfg["rows"][table]
    return n if table == "date" else max(64, int(n * scale))


def _date(n: int) -> dict:
    days = np.arange(n, dtype=np.int64)
    d = g.EPOCH + days.astype("timedelta64[D]")
    year = d.astype("datetime64[Y]").astype(np.int64) + 1970
    month0 = d.astype("datetime64[M]").astype(np.int64) % 12
    dom = (d - d.astype("datetime64[M]")).astype(np.int64) + 1
    doy = (d - d.astype("datetime64[Y]")).astype(np.int64) + 1
    dow = d.astype(np.int64) % 7                 # 0 = Thursday
    daynum_in_week = (dow + 4) % 7 + 1           # Sunday = 1
    last_of_month = (d + np.timedelta64(1, "D")).astype(
        "datetime64[M]") != d.astype("datetime64[M]")
    month_names = np.asarray(MONTHS)[month0]
    return {
        "d_datekey": g.datekeys(days),
        "d_date": np.char.add(np.char.add(month_names, " "),
                              np.char.add(dom.astype("U2"), np.char.add(
                                  ", ", year.astype("U4")))),
        "d_dayofweek": np.asarray(WEEKDAYS)[dow],
        "d_month": month_names,
        "d_year": year.astype(np.int32),
        "d_yearmonthnum": (year * 100 + month0 + 1).astype(np.int32),
        "d_yearmonth": np.char.add(np.char.ljust(month_names, 3).astype(
            "U3"), year.astype("U4")),
        "d_daynuminweek": daynum_in_week.astype(np.int32),
        "d_daynuminmonth": dom.astype(np.int32),
        "d_daynuminyear": doy.astype(np.int32),
        "d_monthnuminyear": (month0 + 1).astype(np.int32),
        "d_weeknuminyear": ((doy - 1) // 7 + 1).astype(np.int32),
        "d_sellingseason": np.asarray(SEASONS)[month0],
        "d_lastdayinweekfl": (daynum_in_week == 7).astype(np.int32),
        "d_lastdayinmonthfl": last_of_month.astype(np.int32),
        "d_holidayfl": ((month0 == 11) & (dom == 25)).astype(np.int32),
        "d_weekdayfl": ((daynum_in_week >= 2) & (daynum_in_week <= 6)
                        ).astype(np.int32),
    }


def _city(nation: np.ndarray, rng: np.random.Generator) -> np.ndarray:
    """ssb-dbgen's city: the nation's first 9 letters, padded, + 0..9."""
    prefix = np.char.ljust(np.asarray(g.NATIONS), 9).astype("U9")[nation]
    return np.char.add(prefix, rng.integers(0, 10, len(nation)).astype("U1"))


def _customer(rng, n: int) -> dict:
    key = np.arange(1, n + 1, dtype=np.int32)
    nation = rng.integers(0, 25, n)
    return {"c_custkey": key,
            "c_name": g.numbered("Customer#", key, 9),
            "c_address": g.text(rng, n, 10, 25),
            "c_city": _city(nation, rng),
            "c_nation": np.asarray(g.NATIONS)[nation],
            "c_region": np.asarray(g.REGIONS)[
                np.asarray(g.NATION_REGION)[nation]],
            "c_phone": g.phones(rng, nation),
            "c_mktsegment": g.pick(rng, g.SEGMENTS, n)}


def _supplier(rng, n: int) -> dict:
    key = np.arange(1, n + 1, dtype=np.int32)
    nation = rng.integers(0, 25, n)
    return {"s_suppkey": key,
            "s_name": g.numbered("Supplier#", key, 9),
            "s_address": g.text(rng, n, 10, 25),
            "s_city": _city(nation, rng),
            "s_nation": np.asarray(g.NATIONS)[nation],
            "s_region": np.asarray(g.REGIONS)[
                np.asarray(g.NATION_REGION)[nation]],
            "s_phone": g.phones(rng, nation)}


def _part(rng, n: int) -> dict:
    key = np.arange(1, n + 1, dtype=np.int32)
    mfgr = rng.integers(1, 6, n)
    cat = rng.integers(1, 6, n)
    brand = rng.integers(1, 41, n)
    category = np.char.add(g.numbered("MFGR#", mfgr, 1), cat.astype("U1"))
    return {"p_partkey": key,
            "p_name": np.char.add(np.char.add(g.pick(rng, g.COLORS, n), " "),
                                  g.pick(rng, g.COLORS, n)),
            "p_mfgr": g.numbered("MFGR#", mfgr, 1),
            "p_category": category,
            "p_brand1": np.char.add(category, np.char.zfill(
                brand.astype("U2"), 2)),
            "p_color": g.pick(rng, g.COLORS, n),
            "p_type": np.char.add(np.char.add(np.char.add(
                g.pick(rng, g.TYPE_1, n), " "), np.char.add(
                g.pick(rng, g.TYPE_2, n), " ")), g.pick(rng, g.TYPE_3, n)),
            "p_size": rng.integers(1, 51, n).astype(np.int32),
            "p_container": np.char.add(np.char.add(
                g.pick(rng, g.CONTAINER_1, n), " "),
                g.pick(rng, g.CONTAINER_2, n))}


def _lineorder(rng, n: int, n_cust: int, n_part: int, n_supp: int,
               n_days: int) -> dict:
    n_orders = (n + 3) // 4
    lines = g.exact_counts(rng, n_orders, 1, 7, n)
    okey = np.repeat(g.sparse_orderkeys(n_orders), lines)
    starts = np.cumsum(lines) - lines
    linenumber = (np.arange(n) - np.repeat(starts, lines) + 1)
    # order dates run to 151 days before the end of the date table, as
    # TPC-H's do, so every commit date still has a date row
    odays = rng.integers(0, n_days - 151, n_orders)
    lo_day = np.repeat(odays, lines)
    per_order = lambda v: np.repeat(v, lines)           # noqa: E731
    partkey = rng.integers(1, n_part + 1, n)
    qty = rng.integers(1, 51, n)
    discount = rng.integers(0, 11, n)
    price = g.retail_price(partkey)
    ext = qty * price
    order_total = np.add.reduceat(ext, starts)
    return {
        "lo_orderkey": okey,
        "lo_linenumber": linenumber.astype(np.int32),
        "lo_custkey": per_order(rng.integers(1, n_cust + 1, n_orders)
                                ).astype(np.int32),
        "lo_partkey": partkey.astype(np.int32),
        "lo_suppkey": rng.integers(1, n_supp + 1, n).astype(np.int32),
        "lo_orderdate": g.datekeys(lo_day),
        "lo_orderpriority": per_order(g.pick(rng, g.PRIORITIES, n_orders)),
        "lo_shippriority": np.zeros(n, dtype=np.int32),
        "lo_quantity": qty.astype(np.int32),
        "lo_extendedprice": ext.astype(np.int64),
        "lo_ordtotalprice": per_order(order_total).astype(np.int64),
        "lo_discount": discount.astype(np.int32),
        "lo_revenue": (ext * (100 - discount) // 100).astype(np.int64),
        "lo_supplycost": (price * 6 // 10).astype(np.int32),
        "lo_tax": rng.integers(0, 9, n).astype(np.int32),
        "lo_commitdate": g.datekeys(lo_day + rng.integers(30, 91, n)),
        "lo_shipmode": g.pick(rng, g.SHIPMODES, n),
    }


def make(cfg: dict, seed: int, scale: float = 1.0) -> dict:
    rng = np.random.default_rng(seed)
    n = {t: _rows(cfg, t, scale) for t in cfg["rows"]}
    return {
        "date": _date(n["date"]),
        "customer": _customer(rng, n["customer"]),
        "supplier": _supplier(rng, n["supplier"]),
        "part": _part(rng, n["part"]),
        "lineorder": _lineorder(rng, n["lineorder"], n["customer"],
                                n["part"], n["supplier"], n["date"]),
    }
