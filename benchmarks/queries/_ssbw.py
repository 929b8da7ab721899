"""Plain reference of the Star Schema Benchmark's queries at scale factor
100, shared by the `ssbNNw.py` beside it: the `Star` of `ssbNN.py`
answered over the fact table in blocks of ROWS rows, so that no temporary
has the fact's length.  Each dimension becomes one int32 table over its
keys' range (the row of the key where the row passes the dimension's
filter, -1 elsewhere), looked up for the rows still alive, the most
selective dimension first; what passes is grouped by a mixed-radix id over
the group columns' whole ranges, so every block numbers the groups alike,
and summed as exact int64, added across blocks.  `control` is the same
with float32 sums.  It equals `_ssb.py` wherever both run
(`tests/test_ssb_star.py`); `_ssb.py` holds int64 arrays of the fact's
length for every dimension, some 20-30 GB a query at SF100.
"""

from __future__ import annotations

import os
from concurrent.futures import ThreadPoolExecutor

import numpy as np

from . import _ssb as s

# At SF100 the program's host store holds lineorder at 30 bytes a row
# only if it keeps narrow integer blocks (`tidb_tpu.store.blockstore`
# `narrowest`); a program that holds every integer as int64 holds 43 GB
# for lineorder alone, beside the harness's copies and the device's
# staging, near the four-chip host's memory.  Such a program stops here,
# before it loads anything.
try:
    from tidb_tpu.store.blockstore import narrowest  # noqa: F401
except ImportError as e:
    raise ImportError("the Star Schema Benchmark at SF100 needs a store "
                      "that holds narrow integer blocks "
                      "(tidb_tpu.store.blockstore.narrowest), which this "
                      "program has not") from e

#: fact rows a block: temporaries of 4-8 MB, which the allocator reuses (`q1w.py` measured blocks of 32 MB temporaries 17 times slower
#: a row)
ROWS = 1 << 20
#: blocks answered at once (numpy's gathers, sorts and sums release the GIL)
THREADS = max(1, min(16, len(os.sched_getaffinity(0))))
#: the group space a query may have (dense int64 sums over it)
MAX_GROUPS = 1 << 24


def _lut(t: dict, pk: str, where) -> tuple:
    """(the key range's least key, an int32 table over the range: the
    dimension row whose key it is where that row passes `where`, else -1,
    the share of the rows that pass)."""
    keys = t[pk].astype(np.int64)
    lo, hi = int(keys.min()), int(keys.max())
    ok = np.ones(len(keys), dtype=bool) if where is None else where(t)
    lut = np.full(hi - lo + 1, -1, dtype=np.int32)
    lut[keys - lo] = np.where(ok, np.arange(len(keys), dtype=np.int32), -1)
    return lo, lut, float(ok.mean()) if len(ok) else 0.0


def _radix(tables, q: s.Star) -> tuple:
    """((least value, stride, values) of each group column over its
    dimension, the group space)."""
    out, stride = [], 1
    for t, c in reversed(q.groups):
        col = tables[t][c]
        lo, hi = (int(col.min()), int(col.max())) if len(col) else (0, 0)
        out.append((lo, stride, hi - lo + 1))
        stride *= hi - lo + 1
    if stride > MAX_GROUPS:
        raise ValueError(f"a group space of {stride}")
    return out[::-1], stride


class _Rows(dict):
    """The fact's columns at a block's alive rows, each taken when first
    read."""

    def __init__(self, fact, sl, alive):
        super().__init__()
        self.fact, self.sl, self.alive = fact, sl, alive

    def __missing__(self, c):
        v = self[c] = self.fact[c][self.sl][self.alive]
        return v


def _block(tables, q: s.Star, dims, radix, dtype, sl):
    """(group ids, sums, counts) of the groups one block holds."""
    fact = tables[s.FACT]
    if q.fact is None:
        alive = None  # every row of the block
    else:
        alive = np.flatnonzero(q.fact({c: a[sl] for c, a in fact.items()}))
    rows = {}
    for dim, fk, lo, lut in dims:
        at = fact[fk][sl] if alive is None else fact[fk][sl][alive]
        at = at.astype(np.int64) - lo
        inside = (at >= 0) & (at < len(lut))
        row = np.where(inside, lut.take(at, mode="clip"), -1)
        keep = row >= 0
        if alive is None:
            alive = np.arange(sl.stop - sl.start)
        alive = alive[keep]
        rows = {d: r[keep] for d, r in rows.items()}
        rows[dim] = row[keep]
    if alive is None:
        alive = np.arange(sl.stop - sl.start)
    m = q.measure(_Rows(fact, sl, alive)).astype(dtype)
    gid = np.zeros(len(alive), dtype=np.int64)
    for (t, c), (lo, stride, _n) in zip(q.groups, radix):
        gid += (tables[t][c][rows[t]].astype(np.int64) - lo) * stride
    uniq, inv = np.unique(gid, return_inverse=True)
    if dtype == np.int64 and len(m) and \
            int(np.abs(m).max()) * len(m) < 1 << 53:
        # float64 adds these integers exactly
        sums = np.rint(np.bincount(inv, weights=m, minlength=len(uniq)))
        sums = sums.astype(np.int64)
    else:
        sums = np.zeros(len(uniq), dtype=dtype)
        np.add.at(sums, inv, m)
    return uniq, sums, np.bincount(inv, minlength=len(uniq))


def _answer(tables, q: s.Star, dtype) -> list:
    n = len(tables[s.FACT]["lo_orderdate"])
    dims = []
    for dim, (fk, pk, where) in q.dims.items():
        lo, lut, share = _lut(tables[dim], pk, where)
        dims.append((share, dim, fk, lo, lut))
    dims = [d[1:] for d in sorted(dims, key=lambda d: d[0])]
    radix, space = _radix(tables, q)
    sums = np.zeros(space, dtype=dtype)
    counts = np.zeros(space, dtype=np.int64)
    blocks = [slice(a, min(a + ROWS, n)) for a in range(0, n, ROWS)]
    with ThreadPoolExecutor(THREADS) as pool:
        for uniq, bsums, bcounts in pool.map(
                lambda sl: _block(tables, q, dims, radix, dtype, sl),
                blocks):
            sums[uniq] += bsums
            counts[uniq] += bcounts
    if not q.groups:
        return [(s._num(sums[0], dtype) if counts[0] else None,)]
    out = []
    for g in np.flatnonzero(counts):
        vals = [s._value(c, int(g // stride % card) + lo)
                for (_t, c), (lo, stride, card) in zip(q.groups, radix)]
        out.append(tuple(s._num(sums[g], dtype) if x == "sum" else vals[x]
                         for x in q.select))
    return s._sorted(out, q.order)


def reference(tables, q: s.Star) -> list:
    return _answer(tables, q, np.int64)


def control(tables, q: s.Star) -> list:
    return _answer(tables, q, np.float32)
