"""SSB Q1.3: week 6 of 1994, discount 5 to 7, quantity 26 to 35."""
import numpy as np

import plain

flight1 = plain.sibling(__file__, "flight1")


def answer(tables: dict, params: dict, dtype=np.int64) -> dict:
    """``dtype`` carries the revenue: int64, or lower for the control."""
    return flight1.revenue(
        tables, {"d_weeknuminyear": 6, "d_year": 1994},
        lambda lo: ((lo["lo_discount"] >= 5) & (lo["lo_discount"] <= 7)
                    & (lo["lo_quantity"] >= 26)
                    & (lo["lo_quantity"] <= 35)), dtype)
