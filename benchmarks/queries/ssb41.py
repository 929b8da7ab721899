"""Plain reference of SSB Q4.1 (the shared engine is `_ssb.py`).
`control` is the same with float32 sums."""

import numpy as np

from . import _ssb as s

Q = s.Star(
    dims={
        "date": ("lo_orderdate", "d_datekey",
                 None),
        "customer": ("lo_custkey", "c_custkey",
                     lambda c: c["c_region"] == s.code("c_region", "AMERICA")),
        "supplier": ("lo_suppkey", "s_suppkey",
                     lambda t: t["s_region"] == s.code("s_region", "AMERICA")),
        "part": ("lo_partkey", "p_partkey",
                 lambda p: np.isin(p["p_mfgr"], s.codes("p_mfgr", ['MFGR#1', 'MFGR#2']))),
    },
    fact=None,
    groups=[('date', 'd_year'),
            ('customer', 'c_nation')],
    measure=lambda f: f["lo_revenue"].astype(np.int64) - f["lo_supplycost"],
    select=[0, 1, 'sum'],
    order=[(0, False), (1, False)],
)


def reference(tables, _p):
    return s.reference(tables, Q)


def control(tables, _p):
    return s.control(tables, Q)
