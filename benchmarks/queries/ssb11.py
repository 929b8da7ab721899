"""Plain reference of SSB Q1.1 (the shared engine is `_ssb.py`).
`control` is the same with float32 sums."""

import numpy as np

from . import _ssb as s

Q = s.Star(
    dims={
        "date": ("lo_orderdate", "d_datekey",
                 lambda d: d["d_year"] == 1993),
    },
    fact=(lambda f: (f["lo_discount"] >= 1) & (f["lo_discount"] <= 3)
           & (f["lo_quantity"] < 25)),
    groups=[],
    measure=lambda f: f["lo_extendedprice"].astype(np.int64) * f["lo_discount"],
    select=['sum'],
    order=[],
)


def reference(tables, _p):
    return s.reference(tables, Q)


def control(tables, _p):
    return s.control(tables, Q)
