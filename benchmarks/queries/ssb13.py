"""Plain reference of SSB Q1.3 (the shared engine is `_ssb.py`).
`control` is the same with float32 sums."""

import numpy as np

from . import _ssb as s

Q = s.Star(
    dims={
        "date": ("lo_orderdate", "d_datekey",
                 lambda d: (d["d_weeknuminyear"] == 6)
                 & (d["d_year"] == 1994)),
    },
    fact=(lambda f: (f["lo_discount"] >= 5) & (f["lo_discount"] <= 7)
           & (f["lo_quantity"] >= 26)
           & (f["lo_quantity"] <= 35)),
    groups=[],
    measure=lambda f: f["lo_extendedprice"].astype(np.int64) * f["lo_discount"],
    select=['sum'],
    order=[],
)


def reference(tables, _p):
    return s.reference(tables, Q)


def control(tables, _p):
    return s.control(tables, Q)
