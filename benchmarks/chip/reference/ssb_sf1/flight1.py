"""SSB flight 1 by hand: filter lineorder, look its date up, sum."""
from __future__ import annotations

import numpy as np

import plain


def revenue(tables: dict, keys: dict, lo_filter, dtype=np.int64) -> dict:
    """One row of ``keys`` and SUM(lo_extendedprice * lo_discount) over
    the lineorder rows whose date matches ``keys`` and that pass
    ``lo_filter``; no row when none does. ``dtype`` is the sum's."""
    lo, d = tables["lineorder"], tables["date"]
    m = lo_filter(lo)
    hit = np.ones(len(lo["lo_orderdate"]), dtype=bool)
    for name, want in keys.items():
        got, found = plain.lookup(d["d_datekey"], d[name], lo["lo_orderdate"])
        hit &= found & (got == want)
    m &= hit
    if not m.any():
        return {**{k: np.array([], np.int32) for k in keys},
                "revenue": np.array([], dtype)}
    prod = (lo["lo_extendedprice"][m].astype(dtype)
            * lo["lo_discount"][m].astype(dtype))
    total = np.zeros(1, dtype)
    np.add.at(total, np.zeros(len(prod), np.int64), prod)
    return {**{k: np.array([v], np.int32) for k, v in keys.items()},
            "revenue": total}
