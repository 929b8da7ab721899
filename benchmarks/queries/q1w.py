"""Plain reference of TPC-H Q1 that stays exact at any scale factor the
generator can make: `q1.py`'s sums by group and ship day, with every
weight of an `np.bincount` split into halves of 31 bits and added a block
of 2^20 rows at a time, so that no float64 sum can leave float64's
integers; the blocks, the halves and the days are added up as Python
integers.  Independent of `q1.py` (which asserts every day's sum under
2^53 and stops near scale factor 100) and of `tidb_tpu`.  `control` is Q1
in float32 all the way, as `q1.py` has it; it breaks the configuration's
"decimals are exact"."""

import numpy as np

from . import _common as c
from harness.datagen import DICTIONARIES

HALF = 31
#: rows of a block.  A quarter of `_common.BLOCK`: its arrays of 2^22
#: int64 are 32 MB, which the allocator maps anew for every temporary
#: (measured: 17 times slower a row than at 2^20, 191 s of reference at
#: SF100)
ROWS = 1 << 20
#: what the exactness rests on: a block's bincount adds at most ROWS
#: halves, each under 2^HALF, so a block's float64 sum stays under 2^53;
#: and a block's halves put together again stay inside int64 while a
#: weight is under 2^63 / ROWS = 2^43 (the widest here,
#: l_extendedprice * 100 * 108, is under 2^37)
assert ROWS << HALF <= 1 << 53
WEIGHT_MAX = (1 << 63) // ROWS


THREADS = 8


def _blocks(n):
    for s in range(0, n, ROWS):
        yield slice(s, min(s + ROWS, n))


def _groups(li, p, dtype):
    """{group: [sum qty, price, disc_price, charge, disc, count]} by passes
    over blocks of rows; the control's path (float32 all the way)."""
    cutoff = c.days("1998-12-01") - int(p["delta"])
    acc = {}
    for b in _blocks(len(li["l_shipdate"])):
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


def _block_sums(cell, weights, n_cells):
    """Exact sums of one block's int64 `weights` by `cell`, as int64: the
    low halves, and the high where there are any, added in float64 and
    put together as integers.  (The weights go to `np.bincount` as
    float64: handed integers it converts them fifteen times slower.)"""
    if weights is None:
        return np.bincount(cell, minlength=n_cells).astype(np.int64)
    top = int(weights.max())
    assert 0 <= int(weights.min()) and top < WEIGHT_MAX, \
        "a weight left the range the halves are exact in"
    low = (weights & ((1 << HALF) - 1)).astype(np.float64)
    out = np.bincount(cell, weights=low, minlength=n_cells).astype(np.int64)
    if top >> HALF:
        high = (weights >> HALF).astype(np.float64)
        out += np.bincount(cell, weights=high,
                           minlength=n_cells).astype(np.int64) << HALF
    return out


_BY_DAY = {}


def _by_day(li):
    """Exact sums by (group, ship day) as Python integers, worked out once
    for all DELTAs: Q1's predicate is on the ship day alone.  Blocks go
    to a few threads (numpy's elementwise passes let go of the lock);
    their sums are added here, in order."""
    from concurrent.futures import ThreadPoolExecutor

    key = id(li["l_shipdate"])
    if key not in _BY_DAY:
        day0 = int(li["l_shipdate"].min())
        n_days = int(li["l_shipdate"].max()) - day0 + 1
        n_cells = 6 * n_days

        def block(b):
            gid = li["l_returnflag"][b].astype(np.int64) * 2 + li["l_linestatus"][b]
            cell = gid * n_days + (li["l_shipdate"][b].astype(np.int64) - day0)
            price = li["l_extendedprice"][b].astype(np.int64)
            disc = li["l_discount"][b].astype(np.int64)
            disc_price = price * (100 - disc)
            cols = (li["l_quantity"][b].astype(np.int64), price, disc_price,
                    disc_price * (100 + li["l_tax"][b].astype(np.int64)),
                    disc, None)
            return [_block_sums(cell, col, n_cells) for col in cols]

        # Python integers: the blocks' sums add up without wrapping
        sums = np.zeros((6, n_cells), dtype=object)
        with ThreadPoolExecutor(THREADS) as pool:
            for part in pool.map(block, _blocks(len(li["l_shipdate"]))):
                for i, x in enumerate(part):
                    sums[i] += x
        _BY_DAY.clear()
        _BY_DAY[key] = (day0, n_days, sums)
    return _BY_DAY[key]


def reference(tables, p):
    day0, n_days, sums = _by_day(tables["lineitem"])
    upto = c.days("1998-12-01") - int(p["delta"]) - day0 + 1
    rows = []
    for g in range(6):
        qty, price, dprice, charge, disc, n = (
            sum(sums[i][g * n_days: g * n_days + min(max(upto, 0), n_days)])
            for i in range(6))
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
