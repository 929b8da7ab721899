"""Plain reference of SSB Q3.3 (the shared engine is `_ssb.py`).
`control` is the same with float32 sums."""

import numpy as np

from . import _ssb as s

Q = s.Star(
    dims={
        "customer": ("lo_custkey", "c_custkey",
                     lambda c: np.isin(c["c_city"], s.codes("c_city", ['UNITED KI1', 'UNITED KI5']))),
        "supplier": ("lo_suppkey", "s_suppkey",
                     lambda t: np.isin(t["s_city"], s.codes("s_city", ['UNITED KI1', 'UNITED KI5']))),
        "date": ("lo_orderdate", "d_datekey",
                 lambda d: (d["d_year"] >= 1992) & (d["d_year"] <= 1997)),
    },
    fact=None,
    groups=[('customer', 'c_city'),
            ('supplier', 's_city'),
            ('date', 'd_year')],
    measure=lambda f: f["lo_revenue"].astype(np.int64),
    select=[0, 1, 2, 'sum'],
    order=[(2, False), (3, True), (0, False), (1, False)],
)


def reference(tables, _p):
    return s.reference(tables, Q)


def control(tables, _p):
    return s.control(tables, Q)
