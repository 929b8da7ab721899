"""Plain reference of SSB Q1.2 (the shared engine is `_ssb.py`).
`control` is the same with float32 sums."""

import numpy as np

from . import _ssb as s

Q = s.Star(
    dims={
        "date": ("lo_orderdate", "d_datekey",
                 lambda d: d["d_yearmonthnum"] == 199401),
    },
    fact=(lambda f: (f["lo_discount"] >= 4) & (f["lo_discount"] <= 6)
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
