"""Statistics lifecycle: build on ANALYZE, cache per table version, feed the
planner's row estimates.

Reference: statistics/handle (load/update cache handle.go:148, auto-analyze
NeedAnalyzeTable update.go:621-639), statistics/selectivity.go.

The build path is columnar: ANALYZE pulls each column's base blocks (plus the
delta overlay) and builds Histogram + CMSketch + null/NDV counts with numpy —
the pushdown-ANALYZE shape of executor/analyze.go, minus the RPC hop.
"""

from __future__ import annotations

import os
import threading
import time
from concurrent.futures import ThreadPoolExecutor
from dataclasses import dataclass, field
from typing import Dict, List, Optional

import numpy as np

from ..types import TypeKind
from .histogram import sorted_ints, CMSketch, FMSketch, Histogram
from ..util_concurrency import make_rlock

#: threads an ANALYZE builds with (numpy's sorts and scans release the
#: GIL), at most the CPUs the process may run on
ANALYZE_THREADS = 16
#: base rows one thread sorts: a longer integer column is counted in parts
#: whose distinct values and counts are then merged
ANALYZE_PART_ROWS = 1 << 24
#: the widest range of values whose parts' counts merge in one dense array
DENSE_MERGE_RANGE = 1 << 26


@dataclass
class ColumnStats:
    hist: Histogram
    cms: Optional[CMSketch]
    null_count: int
    ndv: int


@dataclass
class TableStats:
    table_id: int
    version: int  # storage base_version + delta size at build time
    row_count: int
    columns: Dict[int, ColumnStats] = field(default_factory=dict)
    build_time: float = 0.0
    modify_count: int = 0
    # ANALYZE-built NDV per index (keyed by the tuple of store column
    # offsets, in index order): correlated multi-column selectivity
    # (statistics/index.go histogram NDV role)
    index_ndv: Dict[tuple, int] = field(default_factory=dict)


def _counted(store, ci: int, n_base: int) -> bool:
    """Whether ANALYZE counts column `ci` in parts: base rows held as
    integers (strings as their dictionary codes)."""
    if not n_base:
        return False
    _off, (a,), _v = next(store.iter_base_blocks([ci], 0, 1, narrow=True))
    return a.dtype.kind in "ib" or a.dtype.kind == "u" and a.itemsize < 8


def _part_counts(store, ci: int, start: int, end: int):
    """(the distinct values of integer column `ci` in base rows
    [start, end), sorted, as int64; the rows of each; the NULLs)."""
    parts, nulls = [], 0
    for _off, (a,), (v,) in store.iter_base_blocks([ci], start, end,
                                                   narrow=True):
        if v is not None:
            nulls += int(len(v) - np.count_nonzero(v))
            a = a[v]
        parts.append(a)
    # a byte-wide column sorts as int16: numpy's vectorised sort takes
    # 16 bits and up, and sorts bytes twenty times slower
    dt = np.result_type(*parts)
    vals = np.concatenate(parts, dtype=np.int16 if dt.itemsize == 1 else dt)
    if not len(vals):
        return np.zeros(0, np.int64), np.zeros(0, np.int64), nulls
    vals.sort()
    first = np.flatnonzero(np.concatenate(([True], vals[1:] != vals[:-1])))
    return (vals[first].astype(np.int64),
            np.diff(np.append(first, len(vals))), nulls)


def _merge_counts(parts) -> tuple:
    """One column's (distinct values, counts) from its parts'."""
    parts = [(u, c) for u, c, _n in parts if len(u)]
    if len(parts) <= 1:
        return parts[0] if parts else (np.zeros(0, np.int64),
                                       np.zeros(0, np.int64))
    lo = min(int(u[0]) for u, _c in parts)
    hi = max(int(u[-1]) for u, _c in parts)
    if hi - lo < DENSE_MERGE_RANGE:
        dense = np.zeros(hi - lo + 1, dtype=np.int64)
        for u, c in parts:
            dense[u - lo] += c  # a part's values are distinct
        nz = np.flatnonzero(dense)
        return nz + lo, dense[nz]
    u = np.concatenate([u for u, _c in parts])
    c = np.concatenate([c for _u, c in parts])
    order = np.argsort(u, kind="stable")
    u, c = u[order], c[order]
    first = np.flatnonzero(np.concatenate(([True], u[1:] != u[:-1])))
    return u[first], np.add.reduceat(c, first)


def _from_parts(parts, n_buckets: int) -> ColumnStats:
    """A column's stats from its parts' counts (`_part_counts`): the
    histogram and sketch the whole column's sort gives."""
    values, counts = _merge_counts(parts)
    nulls = sum(n for _u, _c, n in parts)
    hist = Histogram.from_counts(values, counts, nulls, n_buckets)
    cms = CMSketch()
    if len(values):
        cms.insert_batch(values, counts)
    return ColumnStats(hist, cms, nulls, hist.ndv)


class StatsHandle:
    def __init__(self, storage):
        from .feedback import QueryFeedback

        self.storage = storage
        self._cache: Dict[int, TableStats] = {}
        self._mu = make_rlock("statistics.handle:StatsHandle._mu")
        self.auto_analyze_ratio = 0.5
        # learned whole-conjunction selectivities (statistics/feedback.go
        # role): consulted before histogram math in estimate_selectivity
        self.feedback = QueryFeedback()

    # ------------------------------------------------------------------
    epoch = 0  # bumped per analyze: plan-cache invalidation

    def analyze_table(self, table_id: int, n_buckets: int = 64) -> TableStats:
        self.epoch += 1
        self.feedback.invalidate_table(table_id)
        return self._analyze_table(table_id, n_buckets)

    def analyze(self, table_info, n_buckets: int = 64) -> TableStats:
        """ANALYZE entry taking schema metadata: partitioned tables analyze
        every partition store (stats cached per physical id) plus a merged
        row-count entry under the logical id for planner cardinality
        (statistics/handle.go's partition-table GlobalStats, row-count
        level)."""
        index_offsets = [
            tuple(table_info.col_offsets(ix.columns))
            for ix in table_info.indexes
        ]
        if table_info.partition_info is None:
            self.epoch += 1
            self.feedback.invalidate_table(table_info.id)
            return self._analyze_table(table_info.id, n_buckets,
                                       index_offsets)
        self.epoch += 1
        for pid in table_info.physical_ids():
            self.feedback.invalidate_table(pid)
        total, version = 0, 0
        for pd in table_info.partition_info.defs:
            st = self._analyze_table(pd.id, n_buckets, index_offsets)
            total += st.row_count
            version = version * 1_000_003 + st.version
        merged = TableStats(table_info.id, version, total,
                            build_time=time.time())
        with self._mu:
            self._cache[table_info.id] = merged
        return merged

    @staticmethod
    def _column_stats(store, ci: int, n_base: int, dele, inserted,
                      n_buckets: int) -> "ColumnStats":
        """One column's histogram, sketch and NULL count over the base rows
        (read as the store holds them: integers narrow) and the committed
        delta."""
        meta = store.cols[ci]
        chunk = store.base_chunk([ci], 0, n_base, decode_strings=False,
                                 narrow=True)
        col = chunk.col(0)
        data = col.data
        valid = col.validity()
        if dele:
            keep = np.ones(n_base, dtype=np.bool_)
            keep[list(dele)] = False
            data, valid = data[keep], valid[keep]
        if col.valid is None and not dele:
            vals, nulls = data, 0  # no NULL: the column as it is
        else:
            vals = data[valid]
            nulls = int((~valid).sum())
        if inserted:
            # fold committed delta rows in (strings -> dict codes)
            dvals = []
            for row in inserted.values():
                x = row[ci]
                if x is None:
                    nulls += 1
                    continue
                if meta.ftype.kind == TypeKind.STRING:
                    code = store.encode_dict_const(ci, str(x)) \
                        if meta.dictionary is not None else \
                        hash(str(x)) & 0x7FFFFFFF
                    dvals.append(code)
                else:
                    dvals.append(x)
            if dvals:
                vals = np.concatenate([
                    vals.astype(np.float64, copy=False),
                    np.asarray(dvals, dtype=np.float64),
                ])
        if meta.ftype.kind == TypeKind.STRING and vals.dtype == object:
            # shouldn't happen (dict-encoded), but guard
            vals = np.array([hash(x) & 0x7FFFFFFF for x in vals],
                            dtype=np.int64)
        cms = CMSketch()
        if vals.dtype.kind in "iub" and len(vals):
            # one sort serves both: the histogram reads the sorted
            # column, the sketch each distinct value with its count
            sv = sorted_ints(vals)
            hist = Histogram.build(sv, nulls, n_buckets, presorted=True)
            first = np.flatnonzero(
                np.concatenate(([True], sv[1:] != sv[:-1])))
            cms.insert_batch(
                sv[first].astype(np.int64),
                np.diff(np.append(first, len(sv))))
        else:
            hist = Histogram.build(vals.astype(np.float64, copy=False),
                                   nulls, n_buckets)
            if len(vals):
                cms.insert_batch(vals.astype(np.int64, copy=False)
                                 if vals.dtype != np.float64
                                 else vals.view(np.int64))
        return ColumnStats(hist, cms, nulls, hist.ndv)

    def _analyze_table(self, table_id: int, n_buckets: int = 64,
                       index_offsets=None) -> TableStats:
        store = self.storage.table(table_id)
        ts = self.storage.current_ts()
        deleted, inserted = store.delta_overlay(ts, 0, 1 << 62)
        dele = set(deleted)
        n_base = store.base_rows
        stats = TableStats(
            table_id,
            version=store.base_version * 1_000_003 + len(store.delta),
            row_count=n_base - len(dele) + len(inserted),
            build_time=time.time(),
        )
        # an integer column of the base rows alone is sorted in parts of
        # ANALYZE_PART_ROWS, all columns' parts at once, and its stats
        # built from the merged distinct values and counts; any other
        # column is read and sorted whole
        threads = max(1, min(ANALYZE_THREADS, len(os.sched_getaffinity(0))))
        with ThreadPoolExecutor(threads) as pool:
            work = {}
            for ci in range(store.n_cols):
                if not dele and not inserted and _counted(store, ci, n_base):
                    work[ci] = [pool.submit(_part_counts, store, ci, a,
                                            min(a + ANALYZE_PART_ROWS,
                                                n_base))
                                for a in range(0, n_base,
                                               ANALYZE_PART_ROWS)]
                else:
                    work[ci] = pool.submit(self._column_stats, store, ci,
                                           n_base, dele, inserted,
                                           n_buckets)
            for ci, w in work.items():
                if isinstance(w, list):
                    w = pool.submit(_from_parts, [f.result() for f in w],
                                    n_buckets)
                work[ci] = w
            for ci, w in work.items():
                stats.columns[ci] = w.result()
        for offs in (index_offsets or ()):
            offs = tuple(offs)
            if not offs or any(o >= store.n_cols for o in offs):
                continue
            stats.index_ndv[offs] = self._combined_ndv(store, offs, dele,
                                                       inserted)
        with self._mu:
            self._cache[table_id] = stats
        return stats

    @staticmethod
    def _combined_ndv(store, offs, dele, inserted) -> int:
        """Distinct count of the column tuple (index key NDV).  NULL-bearing
        keys are excluded (MySQL index cardinality convention); delta rows'
        raw string values encode to the same dictionary codes the base
        chunk carries so both sides compare in one domain."""
        from ..types import TypeKind

        chunk = store.base_chunk(list(offs), 0, store.base_rows,
                                 decode_strings=False)
        cols = [chunk.col(i).data for i in range(len(offs))]
        valids = [chunk.col(i).validity() for i in range(len(offs))]
        keep = np.ones(chunk.num_rows, dtype=np.bool_)
        for v in valids:
            keep &= v
        if dele:
            keep[[h for h in dele if h < chunk.num_rows]] = False
        cols = [c[keep] for c in cols]
        if not inserted and all(c.dtype.kind in "iub" for c in cols):
            # integer key tuples of the base rows alone: sort, count the
            # places where a row differs from the one before
            if not len(cols[0]):
                return 1
            order = np.lexsort(cols[::-1]) if len(cols) > 1 else None
            new = np.zeros(len(cols[0]) - 1, dtype=np.bool_)
            for c in cols:
                c = np.sort(c) if order is None else c[order]
                new |= c[1:] != c[:-1]
            return int(new.sum()) + 1
        seen = set(zip(*(c.tolist() for c in cols)))
        dict_cols = store.dict_encoded_cols()
        for row in inserted.values():
            key = []
            for o in offs:
                x = row[o]
                if x is None:
                    key = None
                    break
                if o in dict_cols:
                    code = store.encode_dict_const(o, str(x))
                    x = code if code >= 0 else ("\x00new", str(x))
                key.append(x)
            if key is not None:
                seen.add(tuple(key))
        return max(len(seen), 1)

    def drop(self, table_id: int):
        with self._mu:
            self._cache.pop(table_id, None)
        try:
            # the layout autotuner forgets the dropped table's columns
            # (its store may outlive the drop for MVCC, so the drop
            # notification — not store GC — is the liveness signal)
            from ..layout import LAYOUT

            LAYOUT.forget_table(table_id)
        except Exception:
            pass  # layout upkeep must never fail a DDL

    def get(self, table_id: int) -> Optional[TableStats]:
        with self._mu:
            return self._cache.get(table_id)

    def cache_snapshot(self):
        """Point-in-time copy of the stats cache for introspection (SHOW
        ANALYZE STATUS / mysql.stats_meta) — iteration outside the lock
        would race concurrent ANALYZE inserts."""
        with self._mu:
            return dict(self._cache)

    # ------------------------------------------------------------------
    def need_auto_analyze(self, table_id: int) -> bool:
        """update.go:621-639 NeedAnalyzeTable: analyze when modified rows
        exceed ratio * row_count or no stats exist for a non-empty table."""
        store = self.storage.table(table_id)
        st = self.get(table_id)
        cur_rows = store.base_rows + len(store.delta)
        if st is None:
            return cur_rows > 0
        cur_version = store.base_version * 1_000_003 + len(store.delta)
        if cur_version == st.version:
            return False
        modified = abs(cur_rows - st.row_count) + len(store.delta)
        return modified > max(st.row_count, 1) * self.auto_analyze_ratio

    # ------------------------------------------------------------------
    # selectivity (statistics/selectivity.go, simplified to per-conjunct
    # independence like the reference's fallback path)
    # ------------------------------------------------------------------
    def record_feedback(self, table_id: int, conds, actual_sel: float):
        """Executor-side entry: learn the observed selectivity of a fully
        drained scan's conjunction (statistics/feedback.go role)."""
        from .feedback import conds_digest

        dg = conds_digest(conds)
        if dg is None:
            return
        baseline = self.estimate_selectivity(table_id, conds,
                                             use_feedback=False)
        self.feedback.record(table_id, dg, actual_sel, baseline)
        self._feed_layout(table_id, conds, actual_sel)

    def _feed_layout(self, table_id: int, conds, actual_sel: float):
        """Forward the learned per-scan selectivity to the layout
        autotuner (tidb_tpu/layout) for every store column the
        conjunction touches — one of the tuner's observation planes."""
        try:
            from ..layout import LAYOUT, layout_enabled

            if not layout_enabled():
                return
            store = self.storage.table(table_id)
            refs: set = set()
            for c in conds:
                c.collect_columns(refs)
            for ci in refs:
                if 0 <= ci < store.n_cols:
                    LAYOUT.observe(store, ci, "filter", sel=actual_sel)
        except Exception:
            pass  # observation is advisory, never a query failure

    def estimate_selectivity(self, table_id: int, conds,
                             use_feedback: bool = True) -> float:
        """Per-conjunct selectivity with two sharpenings over naive
        independence (statistics/selectivity.go):

        - range conds on ONE column intersect into a single histogram
          range estimate (a > 5 AND a < 10 is one interval, not 0.25^2)
        - an eq-conjunction covering an ANALYZEd index's columns uses the
          index's combined NDV (correlated columns stop multiplying)
        """
        from ..expr.expression import ColumnExpr, Constant, ScalarFunc

        st = self.get(table_id)
        if st is None or st.row_count == 0:
            return 0.25 ** min(len(conds), 2) if conds else 1.0
        if conds and use_feedback:
            # learned truth from prior executions beats histogram math
            from .feedback import conds_digest

            dg = conds_digest(conds)
            if dg is not None:
                learned = self.feedback.lookup(table_id, dg)
                if learned is not None:
                    return max(min(learned, 1.0), 1e-6)
        try:
            store = self.storage.table(table_id)
        except Exception:
            store = None
        ranges: Dict[int, list] = {}
        eq_cols: Dict[int, object] = {}
        rest = []
        for c in conds:
            trip = _col_const(c) if isinstance(c, ScalarFunc) else (
                None, None, False)
            col, const, flipped = trip
            name = getattr(c, "name", "")
            if col is not None and name in ("<", "<=", ">", ">=", "="):
                op = name if not flipped else _FLIP.get(name, name)
                if op == "=":
                    eq_cols[col.index] = (c, const)
                else:
                    ranges.setdefault(col.index, []).append((c, op, const))
                continue
            rest.append(c)
        sel = 1.0
        # one interval estimate per ranged column
        for ci, items in ranges.items():
            if len(items) == 1 or ci in eq_cols:
                for c, _op, _k in items:
                    sel *= self._cond_selectivity(st, c, store)
            else:
                sel *= self._interval_selectivity(st, ci, items, store)
        # eq conds: covered-index NDV beats independence when available
        eq_left = dict(eq_cols)
        for offs, ndv in sorted(st.index_ndv.items(),
                                key=lambda kv: -len(kv[0])):
            if offs and all(o in eq_left for o in offs):
                sel *= 1.0 / max(ndv, 1)
                for o in offs:
                    del eq_left[o]
        for ci, (c, _const) in eq_left.items():
            sel *= self._cond_selectivity(st, c, store)
        for c in rest:
            sel *= self._cond_selectivity(st, c, store)
        return max(min(sel, 1.0), 1e-6)

    def _interval_selectivity(self, st: "TableStats", ci: int, items,
                              store) -> float:
        """Intersect all range conds on one column into [lo, hi] and read
        the histogram once."""
        cs = st.columns.get(ci)
        if cs is None or cs.hist.row_count() == 0:
            return 0.25
        lo = hi = None
        for c, op, const in items:
            v = const.value
            if isinstance(v, str):
                if store is None:
                    return 0.25
                meta = store.cols[ci] if ci < store.n_cols else None
                if meta is None or meta.dictionary is None:
                    return 0.25
                v = store.dict_bound(
                    ci, v, "left" if op in ("<", ">=") else "right")
            if not isinstance(v, (int, float)):
                return 0.25
            x = float(v)
            if op in (">", ">="):
                lo = x if lo is None else max(lo, x)
            else:
                hi = x if hi is None else min(hi, x)
        h = cs.hist
        total = float(h.row_count())
        hi_cnt = total if hi is None else (
            h.less_row_count(hi) + h.equal_row_count(hi))
        lo_cnt = 0.0 if lo is None else h.less_row_count(lo)
        return max(min((hi_cnt - lo_cnt) / total, 1.0), 0.0)

    def _cond_selectivity(self, st: TableStats, cond, store=None) -> float:
        from ..expr.expression import ColumnExpr, Constant, ScalarFunc

        default = 0.8  # unknown predicate shapes barely filter
        if not isinstance(cond, ScalarFunc):
            return default
        name = cond.name
        if name in ("and",):
            a, b = cond.args
            return self._cond_selectivity(st, a, store) * \
                self._cond_selectivity(st, b, store)
        if name in ("or",):
            a, b = cond.args
            sa = self._cond_selectivity(st, a, store)
            sb = self._cond_selectivity(st, b, store)
            return min(sa + sb, 1.0)
        col, const, flipped = _col_const(cond)
        if col is None:
            return 0.25 if name in ("=", "<", "<=", ">", ">=", "in",
                                    "like") else default
        # callers remap ColumnExpr.index to the STORE column offset before
        # asking for selectivity (see planner/physical._selectivity)
        cs = st.columns.get(col.index)
        if cs is None or cs.hist.row_count() == 0:
            return 0.25
        total = float(cs.hist.row_count())
        op = name if not flipped else _FLIP.get(name, name)
        v = const.value
        if isinstance(v, str) and store is not None:
            # stats are over dictionary codes; encode the literal using the
            # EFFECTIVE (flip-adjusted) operator's bound side
            meta = store.cols[col.index] if col.index < store.n_cols else None
            if meta is None or meta.dictionary is None:
                return 0.25
            if op == "=":
                v = store.encode_dict_const(col.index, v)
                if v < 0:
                    return 0.0
            else:
                v = store.dict_bound(
                    col.index, v,
                    "left" if op in ("<", ">=") else "right",
                )
            const = type(const)(v, const.ftype)
        x = _const_as_float(const)
        if x is None:
            return 0.25
        h = cs.hist
        if op == "=":
            # point predicates: Count-Min beats the histogram's in-bucket
            # average when the value is an integer representation
            v = const.value
            if cs.cms is not None and cs.cms.count > 0 and \
                    isinstance(v, int):
                return min(cs.cms.query(v) / total, 1.0)
            return min(h.equal_row_count(x) / total, 1.0)
        if op == "!=":
            return max(1.0 - h.equal_row_count(x) / total, 0.0)
        if op == "<":
            return min(h.less_row_count(x) / total, 1.0)
        if op == "<=":
            return min((h.less_row_count(x) + h.equal_row_count(x)) / total, 1.0)
        if op == ">":
            return max(1.0 - (h.less_row_count(x) + h.equal_row_count(x))
                       / total, 0.0)
        if op == ">=":
            return max(1.0 - h.less_row_count(x) / total, 0.0)
        if op == "isnull":
            return cs.null_count / total
        if op == "isnotnull":
            return 1.0 - cs.null_count / total
        return default


_FLIP = {"<": ">", "<=": ">=", ">": "<", ">=": "<="}


def _col_const(cond):
    from ..expr.expression import ColumnExpr, Constant

    if cond.name in ("isnull", "isnotnull") and len(cond.args) == 1 and \
            isinstance(cond.args[0], ColumnExpr):
        return cond.args[0], Constant(0, None), False
    if len(getattr(cond, "args", ())) != 2:
        return None, None, False
    a, b = cond.args
    if isinstance(a, ColumnExpr) and isinstance(b, Constant):
        return a, b, False
    if isinstance(b, ColumnExpr) and isinstance(a, Constant):
        return b, a, True
    return None, None, False


def _const_as_float(c) -> Optional[float]:
    v = getattr(c, "value", None)
    if v is None:
        return None
    if isinstance(v, (int, float)):
        ft = getattr(c, "ftype", None)
        if ft is not None and getattr(ft, "kind", None) == TypeKind.DECIMAL:
            return float(v)  # scaled-int repr matches stored values
        return float(v)
    return None
