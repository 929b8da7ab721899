"""Plain reference of SSB Q4.3 (the shared engine is `_ssb.py`).
`control` is the same with float32 sums."""

import numpy as np

from . import _ssb as s

Q = s.Star(
    dims={
        "date": ("lo_orderdate", "d_datekey",
                 lambda d: np.isin(d["d_year"], [1997, 1998])),
        "customer": ("lo_custkey", "c_custkey",
                     lambda c: c["c_region"] == s.code("c_region", "AMERICA")),
        "supplier": ("lo_suppkey", "s_suppkey",
                     lambda t: t["s_nation"] == s.code("s_nation", "UNITED STATES")),
        "part": ("lo_partkey", "p_partkey",
                 lambda p: p["p_category"] == s.code("p_category", "MFGR#14")),
    },
    fact=None,
    groups=[('date', 'd_year'),
            ('supplier', 's_city'),
            ('part', 'p_brand1')],
    measure=lambda f: f["lo_revenue"].astype(np.int64) - f["lo_supplycost"],
    select=[0, 1, 2, 'sum'],
    order=[(0, False), (1, False), (2, False)],
)


def reference(tables, _p):
    return s.reference(tables, Q)


def control(tables, _p):
    return s.control(tables, Q)
