"""Plain reference of TPC-H Q1 over the benchmark's own arrays: exact
integer sums by group and ship day, added up to the cutoff as Python
integers.  `control` is Q1 in float32 all the way, the precision a later
change would be tempted by; it breaks the configuration's "decimals are
exact"."""

import numpy as np

from . import _common as c
from harness.datagen import DICTIONARIES


def _groups(li, p, dtype):
    """{group: [sum qty, price, disc_price, charge, disc, count]} by passes
    over blocks of rows; the control's path (float32 all the way)."""
    cutoff = c.days("1998-12-01") - int(p["delta"])
    acc = {}
    for b in c.blocks(len(li["l_shipdate"])):
        sel = li["l_shipdate"][b] <= cutoff
        gid = li["l_returnflag"][b].astype(np.int16) * 2 + li["l_linestatus"][b]
        qty = li["l_quantity"][b].astype(dtype)
        price = li["l_extendedprice"][b].astype(dtype)
        disc = li["l_discount"][b].astype(dtype)
        tax = li["l_tax"][b].astype(dtype)
        disc_price = price * (100 - disc)
        charge = disc_price * (100 + tax)
        for g in np.unique(gid[sel]):
            idx = np.flatnonzero(sel & (gid == g))
            a = acc.setdefault(int(g), [dtype(0)] * 5 + [0])
            for i, col in enumerate((qty, price, disc_price, charge, disc)):
                a[i] += col[idx].sum(dtype=dtype)
            a[5] += len(idx)
    return acc


_BY_DAY = {}


def _by_day(li):
    """Exact sums by (group, ship day), worked out once for all DELTAs:
    Q1's predicate is on the ship day alone.  `np.bincount` adds in
    float64, which is exact while every sum stays under 2^53; the sums of
    one day of one group do (checked), the sums of a group do not, so the
    days are added up as Python integers."""
    key = id(li["l_shipdate"])
    if key not in _BY_DAY:
        day0 = int(li["l_shipdate"].min())
        n_days = int(li["l_shipdate"].max()) - day0 + 1
        sums = np.zeros((6, 6 * n_days))
        for b in c.blocks(len(li["l_shipdate"])):
            gid = li["l_returnflag"][b].astype(np.int64) * 2 + li["l_linestatus"][b]
            cell = gid * n_days + (li["l_shipdate"][b].astype(np.int64) - day0)
            price = li["l_extendedprice"][b].astype(np.int64)
            disc = li["l_discount"][b].astype(np.int64)
            disc_price = price * (100 - disc)
            cols = (li["l_quantity"][b], price, disc_price,
                    disc_price * (100 + li["l_tax"][b].astype(np.int64)),
                    disc, None)
            for i, col in enumerate(cols):
                sums[i] += np.bincount(cell, weights=col,
                                       minlength=6 * n_days)
        assert sums.max() < 2.0 ** 53, "a day's sum left float64's integers"
        _BY_DAY.clear()
        _BY_DAY[key] = (day0, n_days, sums.astype(np.int64))
    return _BY_DAY[key]


def reference(tables, p):
    day0, n_days, sums = _by_day(tables["lineitem"])
    upto = c.days("1998-12-01") - int(p["delta"]) - day0 + 1
    rows = []
    for g in range(6):
        qty, price, dprice, charge, disc, n = (
            sum(int(v) for v in sums[i, g * n_days: g * n_days + max(upto, 0)][
                :n_days]) for i in range(6))
        if n:
            rows.append((DICTIONARIES["l_returnflag"][g // 2],
                         DICTIONARIES["l_linestatus"][g % 2],
                         c.dec(qty, 2), c.dec(price, 2), c.dec(dprice, 4),
                         c.dec(charge, 6), c.avg(qty, n, 2),
                         c.avg(price, n, 2), c.avg(disc, n, 2), n))
    return rows


def control(tables, p):
    rows = []
    f = c.from_float
    for g, (qty, price, dprice, charge, disc, n) in sorted(
            _groups(tables["lineitem"], p, np.float32).items()):
        rows.append((DICTIONARIES["l_returnflag"][g // 2],
                     DICTIONARIES["l_linestatus"][g % 2],
                     f(qty / 1e2, 2), f(price / 1e2, 2), f(dprice / 1e4, 4),
                     f(charge / 1e6, 6), f(qty / 1e2 / n, 6),
                     f(price / 1e2 / n, 6), f(disc / 1e2 / n, 6), n))
    return rows
