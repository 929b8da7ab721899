"""Plain reference of the Star Schema Benchmark's queries, shared by the
thirteen `ssbNN.py` beside it: each dimension the query joins is filtered
into a mask over its rows, the fact rows are joined to it by key, what
passes is grouped by the dimension values and summed as exact int64, and
the groups are sorted as the query says.  `control` is the same with
float32 sums.

A query is described by a `Star`:

- `dims`: dimension table -> (fact foreign key, dimension key, filter),
  the filter a function of the dimension's columns giving a bool mask, or
  None;
- `fact`: a function of the fact's columns giving a bool mask, or None;
- `groups`: the (table, column) of each group key, in SELECT order;
- `measure`: a function of the fact's columns (at the rows that pass)
  giving the summed integers;
- `select`: the SELECT list as group key positions and "sum";
- `order`: (SELECT position, descending) pairs.

String columns come as codes into the generator's sorted dictionaries and
leave decoded.
"""

from __future__ import annotations

from dataclasses import dataclass
from typing import Callable, Dict, List, Optional, Tuple

import numpy as np

from generators.ssb import DICTIONARIES

# Flights 2-4 need a fact table's lookups into all of its dimensions chained
# in one device program: a `DeviceJoinReader` with one `DeviceJoinBuild` a
# dimension.  A program without it serves them through the MPP join tree,
# whose programs for one such query compile for over 25 minutes at SF1 for
# a TPU v5e (PERF.md section 6), so no run of the cell could end: such a
# program stops here, before it loads anything.
try:
    from tidb_tpu.planner.physical import DeviceJoinBuild  # noqa: F401
except ImportError as e:
    raise ImportError("the Star Schema Benchmark's queries need the star "
                      "join (tidb_tpu.planner.physical.DeviceJoinBuild), "
                      "which this program has not") from e

FACT = "lineorder"


@dataclass
class Star:
    dims: Dict[str, Tuple[str, str, Optional[Callable]]]
    fact: Optional[Callable]
    groups: List[Tuple[str, str]]
    measure: Callable
    select: List
    order: List[Tuple[int, bool]]


def code(column: str, text: str) -> int:
    """A string's code in its column's dictionary."""
    return DICTIONARIES[column].index(text)


def codes(column: str, texts) -> list:
    return [code(column, t) for t in texts]


def _rows_by_key(keys: np.ndarray, fk: np.ndarray) -> np.ndarray:
    """The dimension row each foreign key names, -1 where none: a table
    over the keys' range, as the keys are dense."""
    keys = keys.astype(np.int64)
    lo, hi = int(keys.min()), int(keys.max())
    lut = np.full(hi - lo + 1, -1, dtype=np.int64)
    lut[keys - lo] = np.arange(len(keys))
    fk = fk.astype(np.int64)
    inside = (fk >= lo) & (fk <= hi)
    row = np.full(len(fk), -1, dtype=np.int64)
    row[inside] = lut[fk[inside] - lo]
    return row


def _answer(tables, q: Star, dtype) -> list:
    fact = tables[FACT]
    n = len(fact["lo_orderdate"])
    keep = np.ones(n, dtype=bool) if q.fact is None else q.fact(fact)
    rows = {}
    for dim, (fk, pk, where) in q.dims.items():
        t = tables[dim]
        row = _rows_by_key(t[pk], fact[fk])
        hit = row >= 0
        if where is not None:
            hit &= where(t)[np.maximum(row, 0)]
        keep &= hit
        rows[dim] = row
    idx = np.flatnonzero(keep)
    m = q.measure({c: a[idx] for c, a in fact.items()}).astype(dtype)
    if not q.groups:
        total = (np.sum(m, dtype=dtype) if len(idx) else None)
        return [(None if total is None else _num(total, dtype),)]
    keys = [tables[t][c][rows[t][idx]].astype(np.int64)
            for t, c in q.groups]
    gid = np.zeros(len(idx), dtype=np.int64)
    for k in keys:
        gid = gid * (int(k.max(initial=0)) + 1) + k
    uniq, inv = np.unique(gid, return_inverse=True)
    order = np.argsort(inv, kind="stable")
    starts = np.flatnonzero(np.r_[True, np.diff(inv[order]) != 0]) \
        if len(inv) else np.zeros(0, dtype=np.int64)
    sums = np.add.reduceat(m[order], starts, dtype=dtype) \
        if len(inv) else np.zeros(0, dtype=dtype)
    first = order[starts]
    out = []
    for g in range(len(uniq)):
        vals = [_value(c, int(k[first[g]])) for (_t, c), k in
                zip(q.groups, keys)]
        out.append(tuple(_num(sums[g], dtype) if s == "sum" else vals[s]
                         for s in q.select))
    return _sorted(out, q.order)


def _value(column: str, v: int):
    return DICTIONARIES[column][v] if column in DICTIONARIES else v


def _num(x, dtype):
    """An exact sum as an int; the control's float32 one as the integer
    that float32 holds."""
    return int(x) if dtype is np.int64 else int(np.float32(x))


def _sorted(rows: list, order) -> list:
    def key(r):
        return tuple((-r[i] if desc else r[i]) for i, desc in order)

    return sorted(rows, key=key)


def reference(tables, q: Star) -> list:
    return _answer(tables, q, np.int64)


def control(tables, q: Star) -> list:
    return _answer(tables, q, np.float32)
