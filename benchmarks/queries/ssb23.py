"""Plain reference of SSB Q2.3 (the shared engine is `_ssb.py`).
`control` is the same with float32 sums."""

import numpy as np

from . import _ssb as s

Q = s.Star(
    dims={
        "date": ("lo_orderdate", "d_datekey",
                 None),
        "part": ("lo_partkey", "p_partkey",
                 lambda p: p["p_brand1"] == s.code("p_brand1", "MFGR#2239")),
        "supplier": ("lo_suppkey", "s_suppkey",
                     lambda t: t["s_region"] == s.code("s_region", "EUROPE")),
    },
    fact=None,
    groups=[('date', 'd_year'),
            ('part', 'p_brand1')],
    measure=lambda f: f["lo_revenue"].astype(np.int64),
    select=['sum', 0, 1],
    order=[(1, False), (2, False)],
)


def reference(tables, _p):
    return s.reference(tables, Q)


def control(tables, _p):
    return s.control(tables, Q)
