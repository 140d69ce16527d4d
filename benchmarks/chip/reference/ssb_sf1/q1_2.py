"""SSB Q1.2: January 1994, discount 4 to 6, quantity 26 to 35."""
import numpy as np

import plain

flight1 = plain.sibling(__file__, "flight1")


def answer(tables: dict, params: dict, dtype=np.int64) -> dict:
    """``dtype`` carries the revenue: int64, or lower for the control."""
    return flight1.revenue(
        tables, {"d_yearmonthnum": 199401},
        lambda lo: ((lo["lo_discount"] >= 4) & (lo["lo_discount"] <= 6)
                    & (lo["lo_quantity"] >= 26)
                    & (lo["lo_quantity"] <= 35)), dtype)
