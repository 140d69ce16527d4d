"""Plain numpy building blocks of the references.

Nothing here imports the program under test: a reference reads the
arrays a configuration's generator made and computes its answer with
sorts, searches and sums that are easy to check by eye.
"""
from __future__ import annotations

import numpy as np


def lookup(keys: np.ndarray, values: np.ndarray, probe: np.ndarray
           ) -> tuple[np.ndarray, np.ndarray]:
    """Inner join of ``probe`` against unique ``keys``: returns
    (``values`` at each probe's matching key, mask of probes that
    matched). Unmatched probes get an arbitrary value and False."""
    order = np.argsort(keys, kind="stable")
    sk = keys[order]
    pos = np.searchsorted(sk, probe)
    pos = np.minimum(pos, len(sk) - 1)
    hit = sk[pos] == probe if len(sk) else np.zeros(len(probe), bool)
    return values[order][pos], hit


def group_sum(keys: np.ndarray, values: np.ndarray, dtype
              ) -> tuple[np.ndarray, np.ndarray]:
    """SUM(values) GROUP BY keys, accumulated in ``dtype`` (wrapping as
    that dtype wraps): returns (distinct keys, sums)."""
    order = np.argsort(keys, kind="stable")
    sk = keys[order]
    starts = np.flatnonzero(np.r_[True, sk[1:] != sk[:-1]])
    if not len(sk):
        return sk, np.zeros(0, dtype)
    sums = np.add.reduceat(values[order].astype(dtype), starts)
    return sk[starts], sums.astype(dtype, copy=False)


def sibling(path: str, name: str):
    """Import ``<name>.py`` from the directory of ``path`` (a helper
    that several references of one configuration share)."""
    import importlib.util
    from pathlib import Path

    file = Path(path).with_name(f"{name}.py")
    spec = importlib.util.spec_from_file_location(
        f"chipbench_ref_{file.parent.name}_{name}", file)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod
