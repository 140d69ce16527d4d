"""Exact comparison of a result table with the plain reference's answer.

Both sides are turned into columns of Python-comparable values; rows are
compared as a sorted multiset, and the columns a unit orders by are also
compared in the order the program returned them. Integer widths may
differ (the program's int32 against numpy's int64), values may not.
"""
from __future__ import annotations

import numpy as np


def columns(table) -> dict[str, np.ndarray]:
    """A program ``Table`` as ``{name: array}``, NULL as ``None``."""
    out = {}
    for name in table.column_names():
        vals = np.asarray(table.column(name))
        valid = np.asarray(table.validity(name), dtype=bool)
        if not valid.all():
            vals = vals.astype(object)
            vals[~valid] = None
        out[name] = vals
    return out


def _sortable(v: np.ndarray) -> np.ndarray:
    v = np.asarray(v)
    if v.dtype == object or v.dtype.kind in "US":
        return np.array(["\0" if x is None else str(x) for x in v.tolist()])
    return v


def _equal(a: np.ndarray, b: np.ndarray) -> bool:
    a, b = _sortable(a), _sortable(b)
    if (a.dtype.kind == "U") != (b.dtype.kind == "U"):
        return False
    return bool(np.array_equal(a, b))


def mismatch(got: dict, want: dict, order_by=()) -> str | None:
    """``None`` when ``got`` holds exactly ``want``'s rows (and, for
    ``order_by``, in its order); otherwise what differs first."""
    if set(got) != set(want):
        return f"columns {sorted(got)} != {sorted(want)}"
    names = sorted(want)
    n_got = len(got[names[0]]) if names else 0
    n_want = len(want[names[0]]) if names else 0
    if n_got != n_want:
        return f"{n_got} rows != {n_want}"
    for name in order_by:
        if not _equal(got[name], want[name]):
            return f"order of {name} differs"
    if n_got == 0 or all(_equal(got[n], want[n]) for n in names):
        return None
    g_idx = np.lexsort([_sortable(got[n]) for n in reversed(names)])
    w_idx = np.lexsort([_sortable(want[n]) for n in reversed(names)])
    for name in names:
        if not _equal(np.asarray(got[name])[g_idx],
                      np.asarray(want[name])[w_idx]):
            return f"values of {name} differ"
    return None
