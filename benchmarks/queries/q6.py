"""Plain reference of TPC-H Q6: one exact integer sum.  `control` is the
same in float32."""

from decimal import Decimal

import numpy as np

from . import _common as c


def _sum(li, p, dtype):
    d0 = c.days(p["date"])
    d1 = c.days(str(int(p["date"][:4]) + 1) + p["date"][4:])
    disc = int(Decimal(p["discount"]) * 100)
    total, n = 0, 0
    for b in c.blocks(len(li["l_shipdate"])):
        ship, dsc = li["l_shipdate"][b], li["l_discount"][b]
        idx = np.flatnonzero((ship >= d0) & (ship < d1)
                             & (dsc >= disc - 1) & (dsc <= disc + 1)
                             & (li["l_quantity"][b] < int(p["quantity"]) * 100))
        s = (li["l_extendedprice"][b][idx].astype(dtype)
             * dsc[idx].astype(dtype)).sum(dtype=dtype)
        total += int(s) if dtype is np.int64 else dtype(s)
        n += len(idx)
    return total, n


def reference(tables, p):
    total, n = _sum(tables["lineitem"], p, np.int64)
    return [(c.dec(total, 4) if n else None,)]


def control(tables, p):
    total, n = _sum(tables["lineitem"], p, np.float32)
    return [(c.from_float(total / 1e4, 4) if n else None,)]
