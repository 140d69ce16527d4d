"""SSB Q1.1: d_year = 1993, discount 1 to 3, quantity under 25."""
import numpy as np

import plain

flight1 = plain.sibling(__file__, "flight1")


def answer(tables: dict, params: dict, dtype=np.int64) -> dict:
    """``dtype`` carries the revenue: int64, or lower for the control."""
    return flight1.revenue(
        tables, {"d_year": 1993},
        lambda lo: ((lo["lo_discount"] >= 1) & (lo["lo_discount"] <= 3)
                    & (lo["lo_quantity"] < 25)), dtype)
