"""Plain reference of SSB Q4.2 (the shared engine is `_ssb.py`).
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
                     lambda t: t["s_region"] == s.code("s_region", "AMERICA")),
        "part": ("lo_partkey", "p_partkey",
                 lambda p: np.isin(p["p_mfgr"], s.codes("p_mfgr", ['MFGR#1', 'MFGR#2']))),
    },
    fact=None,
    groups=[('date', 'd_year'),
            ('supplier', 's_nation'),
            ('part', 'p_category')],
    measure=lambda f: f["lo_revenue"].astype(np.int64) - f["lo_supplycost"],
    select=[0, 1, 2, 'sum'],
    order=[(0, False), (1, False), (2, False)],
)


def reference(tables, _p):
    return s.reference(tables, Q)


def control(tables, _p):
    return s.control(tables, Q)
