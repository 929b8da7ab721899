"""Plain reference of SSB Q3.1 (the shared engine is `_ssb.py`).
`control` is the same with float32 sums."""

import numpy as np

from . import _ssb as s

Q = s.Star(
    dims={
        "customer": ("lo_custkey", "c_custkey",
                     lambda c: c["c_region"] == s.code("c_region", "ASIA")),
        "supplier": ("lo_suppkey", "s_suppkey",
                     lambda t: t["s_region"] == s.code("s_region", "ASIA")),
        "date": ("lo_orderdate", "d_datekey",
                 lambda d: (d["d_year"] >= 1992) & (d["d_year"] <= 1997)),
    },
    fact=None,
    groups=[('customer', 'c_nation'),
            ('supplier', 's_nation'),
            ('date', 'd_year')],
    measure=lambda f: f["lo_revenue"].astype(np.int64),
    select=[0, 1, 2, 'sum'],
    order=[(2, False), (3, True), (0, False), (1, False)],
)


def reference(tables, _p):
    return s.reference(tables, Q)


def control(tables, _p):
    return s.control(tables, Q)
