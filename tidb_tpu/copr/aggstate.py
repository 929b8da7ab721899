"""Aggregate partial-state kernels (host/numpy side).

Shared by: the CPU cop engine (producing partials), the root HashAgg
(merging partials / final agg), and tests as the oracle for the jax engine.
Reference pattern: executor/aggfuncs PartialResult + AggFuncToPBExpr
partial/final split.

All functions are vectorized over a group-index array ``gidx`` (values in
[0, G)); states are lists of numpy arrays of length G.
"""

from __future__ import annotations

from typing import Dict, List, Optional, Tuple

import numpy as np

from ..chunk import Chunk, Column, concat_chunks
from ..errors import ExecutorError
from ..expr.aggregation import AggDesc, avg_type, sum_type
from ..expr.vec import Vec
from ..types import FieldType, TypeKind
from ..types.values import decimal_round_half_up


def _sum_repr(v: Vec, st: FieldType) -> np.ndarray:
    """Arg values in the sum-state representation (scaled int64 / float64)."""
    from ..expr.builtins import cast_vec

    return cast_vec(v, st).data


#: mixed-radix group codes stay below this, so a product never wraps int64
_CODE_SPAN_MAX = 1 << 62
#: an integer key column whose values span less than this is coded by its
#: offset from the minimum (no sort); a wider one by np.unique
_OFFSET_SPAN_MAX = 1 << 31


def _count_rowwise(n: int) -> None:
    """Count rows an aggregate walked one at a time in Python (the paths
    no array form covers); 0 where the array forms served."""
    if n:
        from ..metrics import REGISTRY

        REGISTRY.inc("agg_rowwise_groups_total", float(n))


def group_indices(cols: List[Column]) -> Tuple[np.ndarray, np.ndarray, int]:
    """Map rows to dense group ids.  Returns (gidx, first, G): group ids
    in first-appearance order and, per group, the row of its first
    appearance (ascending), from which `group_key_column` makes the
    group-key output columns.
    NULL is its own group per column, -0.0 and 0.0 are one float key,
    strings group by value.

    Every row's key becomes one int64 (a single fixed-width column's own
    bits; otherwise a code per column, combined mixed-radix), which the
    native open-addressing hash (tidb_tpu/native) numbers in
    first-appearance order — the C-speed replacement for the reference's
    row-at-a-time agg hash maps; without the native library np.unique
    does.  Nothing here runs once a row or once a group in Python."""
    from ..native import available

    n = len(cols[0])
    if n == 0:
        return np.zeros(0, dtype=np.int64), np.zeros(0, dtype=np.int64), 0
    native = available()
    if native and len(cols) == 1 and cols[0].data.dtype != object:
        keys, valid = cols[0].data, cols[0].valid
        if keys.dtype.kind == "f":
            keys = np.where(keys == 0.0, 0.0, keys).astype(
                np.float64, copy=False).view(np.int64)
    else:
        keys, valid = _combined_codes(cols), None  # NULL is in the code
    if native:
        return _hashed_groups(keys.astype(np.int64, copy=False), valid)
    _u, first, inv = np.unique(keys, return_index=True, return_inverse=True)
    # np.unique numbers groups by key value; renumber by first appearance
    order = np.argsort(first)
    rank = np.empty(len(order), dtype=np.int64)
    rank[order] = np.arange(len(order), dtype=np.int64)
    return rank[inv.reshape(-1)], first[order].astype(np.int64), len(order)


def group_key_column(c: Column, first: np.ndarray,
                     ftype: Optional[FieldType] = None) -> Column:
    """The group-key output column: `c` at each group's first row, with
    the type's placeholder under a NULL (as `Column.from_values` leaves
    it), so nothing downstream meets what the source held there."""
    data = c.data[first]
    valid = None if c.valid is None else c.valid[first]
    if valid is not None and not valid.all():
        data[~valid] = (Column._object_fill(c.ftype)
                        if data.dtype == object else 0)
    return Column(ftype or c.ftype, data, valid)


def _combined_codes(cols: List[Column]) -> np.ndarray:
    """One int64 a row that is equal exactly where every key column is."""
    combined, span = None, 1  # codes in [0, span)
    for c in cols:
        codes, card = _factorize_column(c)
        if combined is None:
            combined, span = codes, card
            continue
        if span * card > _CODE_SPAN_MAX:
            # re-factorize what is combined so far: its span falls to the
            # number of distinct prefixes (at most n) and cannot wrap
            uniq, combined = np.unique(combined, return_inverse=True)
            combined, span = combined.reshape(-1), len(uniq)
        combined = combined * card + codes
        span *= card
    return combined


def _hashed_groups(keys: np.ndarray, valid: Optional[np.ndarray]
                   ) -> Tuple[np.ndarray, np.ndarray, int]:
    """group_indices of int64 keys by the native key table."""
    from ..native import KeyTable

    # codes in first-appearance order; NULL -1
    codes = KeyTable(min(len(keys), 1 << 20)).upsert(keys, valid)
    # a row opens a group exactly where the running maximum rises
    top = np.maximum.accumulate(codes)
    first = np.flatnonzero(np.diff(top, prepend=-1) > 0)
    if valid is None or codes.min() >= 0:
        return codes, first, len(first)
    # NULL is a group of its own, numbered where it first appears
    null = codes < 0
    null_first = int(np.argmax(null))
    g_null = int(np.searchsorted(first, null_first))
    gidx = codes + (codes >= g_null)
    gidx[null] = g_null
    return gidx, np.insert(first, g_null, null_first), len(first) + 1


def _factorize_column(c: Column) -> Tuple[np.ndarray, int]:
    """(codes, cardinality) for one key column: codes in [0, cardinality),
    equal keys equal codes, NULL rows coded 0 apart from every value.
    Integers of a narrow range are coded by offset, everything else by
    np.unique (str and numeric payloads); exotic object payloads (mixed
    types that don't compare) fall back to a hash-map pass, which counts
    itself."""
    n = len(c)
    data, valid = c.data, c.valid
    live = data if valid is None else data[valid]
    base = 0 if valid is None else 1  # code 0 is NULL's where there is one
    if data.dtype.kind in "ib" and len(live):
        lo, hi = int(live.min()), int(live.max())
        if hi - lo < _OFFSET_SPAN_MAX:
            codes = data.astype(np.int64) + (base - lo)
            if valid is not None:
                codes[~valid] = 0
            return codes, hi - lo + 1 + base
    try:
        if data.dtype.kind == "f":
            live = np.where(live == 0.0, 0.0, live)  # -0.0 is 0.0's key
        uniq, iv = np.unique(live, return_inverse=True)
        iv = iv.reshape(-1).astype(np.int64, copy=False)
        if valid is None:
            return iv, max(len(uniq), 1)
        inv = np.zeros(n, dtype=np.int64)
        inv[valid] = iv + 1
        return inv, len(uniq) + 1
    except TypeError:
        _count_rowwise(n)
        seen: Dict[object, int] = {}
        inv = np.zeros(n, dtype=np.int64)
        for i, x in enumerate(data.tolist()):
            if valid is not None and not valid[i]:
                continue  # NULL keeps code 0
            g = seen.get(x)
            if g is None:
                g = seen[x] = len(seen) + 1
            inv[i] = g
        return inv, len(seen) + 1


def partial_states(agg: AggDesc, arg_vecs: List[Vec], gidx: np.ndarray,
                   G: int) -> List[Column]:
    """Compute per-group partial state columns from raw rows."""
    name = agg.name
    pts = agg.partial_types()
    if name == "count":
        if not agg.args or isinstance(arg_vecs[0], type(None)):
            cnt = np.bincount(gidx, minlength=G).astype(np.int64)
        else:
            v = arg_vecs[0]
            cnt = np.bincount(gidx, weights=v.validity().astype(np.float64),
                              minlength=G).astype(np.int64)
        return [Column(pts[0], cnt)]
    v = arg_vecs[0]
    valid = v.validity()
    if name in ("sum", "avg"):
        st = pts[0]
        data = _sum_repr(v, st)
        acc = np.zeros(G, dtype=st.np_dtype)
        masked = np.where(valid, data, 0)
        np.add.at(acc, gidx, masked)
        cnt = np.bincount(gidx, weights=valid.astype(np.float64),
                          minlength=G).astype(np.int64)
        sum_col = Column(st, acc, (cnt > 0))
        if name == "sum":
            return [sum_col]
        return [sum_col, Column(pts[1], cnt)]
    if name in ("min", "max"):
        st = pts[0]
        gi, live = gidx[valid], v.data[valid]
        if st.np_dtype == object:  # strings, wide decimals: by value
            acc = np.empty(G, dtype=object)
            acc[:] = Column._object_fill(st)
        else:
            acc = np.zeros(G, dtype=st.np_dtype)
            live = live.astype(acc.dtype, copy=False)
        # any of its values starts a group, so no type needs an identity
        acc[gi] = live
        (np.minimum if name == "min" else np.maximum).at(acc, gi, live)
        ovalid = np.zeros(G, dtype=np.bool_)
        ovalid[gi] = True
        return [Column(st, acc, ovalid)]
    if name == "first_row":
        st = pts[0]
        # each group's first row, NULL or not (a group without rows, the
        # scalar aggregate over nothing, reads NULL)
        n = len(gidx)
        first = np.full(G, n, dtype=np.int64)
        np.minimum.at(first, gidx, np.arange(n, dtype=np.int64))
        groups = np.flatnonzero(first < n)
        first = first[groups]
        if st.np_dtype == object:
            data = np.empty(G, dtype=object)
            data[:] = Column._object_fill(st)
        else:
            data = np.zeros(G, dtype=st.np_dtype)
        ovalid = np.zeros(G, dtype=np.bool_)
        data[groups] = v.data[first]
        ovalid[groups] = valid[first]
        return [Column(st, data, ovalid)]
    if name in ("bit_and", "bit_or", "bit_xor"):
        ident = -1 if name == "bit_and" else 0
        acc = np.full(G, ident, dtype=np.int64)
        masked = np.where(valid, v.data.astype(np.int64), ident)
        op = {"bit_and": np.bitwise_and, "bit_or": np.bitwise_or,
              "bit_xor": np.bitwise_xor}[name]
        op.at(acc, gidx, masked)
        return [Column(pts[0], acc)]
    if name in ("var_pop", "stddev_pop", "var_samp", "stddev_samp"):
        from ..expr.builtins import _to_float

        x = np.where(valid, _to_float(v), 0.0)
        s = np.zeros(G)
        np.add.at(s, gidx, x)
        s2 = np.zeros(G)
        np.add.at(s2, gidx, x * x)
        cnt = np.bincount(gidx, weights=valid.astype(np.float64),
                          minlength=G).astype(np.int64)
        return [Column(pts[0], s), Column(pts[1], s2), Column(pts[2], cnt)]
    if name == "group_concat":
        from ..expr.builtins import _str_data

        sep = agg.ftype and ","  # MySQL default separator
        strs = _str_data(v)
        _count_rowwise(len(gidx))
        parts: List[List[str]] = [[] for _ in range(G)]
        for i in range(len(gidx)):
            if valid[i]:
                parts[gidx[i]].append(str(strs[i]))
        out = np.empty(G, dtype=object)
        ovalid = np.zeros(G, dtype=np.bool_)
        for g in range(G):
            if parts[g]:
                out[g] = ",".join(parts[g])
                ovalid[g] = True
            else:
                out[g] = ""
        return [Column(pts[0], out, ovalid)]
    raise ExecutorError(f"partial_states: unsupported agg {name}")


def merge_states(agg: AggDesc, state_cols: List[Column], gidx: np.ndarray,
                 G: int) -> List[Column]:
    """Merge partial-state rows into G groups (final-merge accumulation)."""
    name = agg.name
    pts = agg.partial_types()
    if name == "count":
        acc = np.zeros(G, dtype=np.int64)
        np.add.at(acc, gidx, state_cols[0].data)
        return [Column(pts[0], acc)]
    if name in ("sum", "avg"):
        st = pts[0]
        acc = np.zeros(G, dtype=st.np_dtype)
        sv = state_cols[0]
        np.add.at(acc, gidx, np.where(sv.validity(), sv.data, 0))
        if name == "sum":
            cnt = np.zeros(G, dtype=np.int64)
            np.add.at(cnt, gidx, sv.validity().astype(np.int64))
            return [Column(st, acc, cnt > 0)]
        cnt = np.zeros(G, dtype=np.int64)
        np.add.at(cnt, gidx, state_cols[1].data)
        return [Column(st, acc, cnt > 0), Column(pts[1], cnt)]
    if name in ("min", "max", "first_row"):
        # reuse row-accumulation on the state column
        sub = AggDesc(name, agg.args, agg.distinct, agg.ftype)
        return partial_states(sub, [Vec.from_column(state_cols[0])], gidx, G)
    if name in ("bit_and", "bit_or", "bit_xor"):
        ident = -1 if name == "bit_and" else 0
        acc = np.full(G, ident, dtype=np.int64)
        op = {"bit_and": np.bitwise_and, "bit_or": np.bitwise_or,
              "bit_xor": np.bitwise_xor}[name]
        op.at(acc, gidx, state_cols[0].data)
        return [Column(pts[0], acc)]
    if name in ("var_pop", "stddev_pop", "var_samp", "stddev_samp"):
        s = np.zeros(G)
        np.add.at(s, gidx, state_cols[0].data)
        s2 = np.zeros(G)
        np.add.at(s2, gidx, state_cols[1].data)
        cnt = np.zeros(G, dtype=np.int64)
        np.add.at(cnt, gidx, state_cols[2].data)
        return [Column(pts[0], s), Column(pts[1], s2), Column(pts[2], cnt)]
    if name == "group_concat":
        parts: List[List[str]] = [[] for _ in range(G)]
        sv = state_cols[0]
        valid = sv.validity()
        _count_rowwise(len(gidx))
        for i in range(len(gidx)):
            if valid[i]:
                parts[gidx[i]].append(str(sv.data[i]))
        out = np.empty(G, dtype=object)
        ovalid = np.zeros(G, dtype=np.bool_)
        for g in range(G):
            if parts[g]:
                out[g] = ",".join(parts[g])
                ovalid[g] = True
            else:
                out[g] = ""
        return [Column(pts[0], out, ovalid)]
    raise ExecutorError(f"merge_states: unsupported agg {name}")


def merge_partials_to_final(n_keys: int, aggs: List[AggDesc],
                            chunks: List[Chunk]) -> Optional[Chunk]:
    """Merge partial-state chunks ([keys..., states...] layout) from many
    shards/engines into one final chunk [keys..., finals...].

    Returns None when there are no input rows AND n_keys > 0 (empty group-by
    result); for scalar agg (n_keys == 0) the caller handles the
    one-row-from-nothing case."""
    whole = concat_chunks(
        [c for c in chunks if c is not None and c.num_rows > 0])
    if whole is None:
        return None
    key_cols = [whole.col(i) for i in range(n_keys)]
    if key_cols:
        gidx, first, G = group_indices(key_cols)
    else:  # scalar aggregation: every row merges into the one group
        gidx, first, G = np.zeros(whole.num_rows, dtype=np.int64), None, 1
    out_cols: List[Column] = [group_key_column(c, first)
                              for c in key_cols]
    off = n_keys
    for a in aggs:
        width = len(a.partial_types())
        states = [whole.col(off + j) for j in range(width)]
        off += width
        merged = merge_states(a, states, gidx, G)
        out_cols.append(finalize(a, merged))
    return Chunk(out_cols)


def empty_final_row(aggs: List[AggDesc]) -> Chunk:
    """The one row a scalar aggregation yields over zero input rows:
    COUNT -> 0, SUM/AVG/MIN/MAX -> NULL."""
    cols = []
    for a in aggs:
        if a.name == "count":
            cols.append(Column(a.ftype, np.zeros(1, dtype=np.int64)))
        elif a.name in ("bit_or", "bit_xor"):
            cols.append(Column(a.ftype, np.zeros(1, dtype=np.int64)))
        elif a.name == "bit_and":
            cols.append(Column(a.ftype, np.full(1, -1, dtype=np.int64)))
        else:
            cols.append(Column.nulls(a.ftype, 1))
    return Chunk(cols)


def finalize(agg: AggDesc, states: List[Column]) -> Column:
    """Final value from merged states."""
    name = agg.name
    ft = agg.ftype
    if name == "count":
        return Column(ft, states[0].data.astype(np.int64))
    if name == "sum":
        s = states[0]
        return Column(ft, s.data.astype(ft.np_dtype) if ft.np_dtype != s.data.dtype
                      else s.data, s.valid)
    if name == "avg":
        s, c = states
        cnt = c.data
        safe = np.where(cnt > 0, cnt, 1)
        if ft.kind == TypeKind.FLOAT:
            data = s.data.astype(np.float64) / safe
        else:
            # decimal: state scale -> result scale with round-half-up
            st = sum_type(agg.args[0].ftype)
            mul = 10 ** max(ft.scale - st.scale, 0)
            if s.data.dtype == object and any(
                    abs(int(x)) * mul >= 1 << 63 for x in s.data):
                # a sum whose rescale passes int64 (TPC-H Q1's avg_price
                # at SF100): the few such groups as Python integers
                data = np.array(
                    [(-1 if int(x) < 0 else 1)
                     * ((abs(int(x)) * mul + int(n) // 2) // int(n))
                     for x, n in zip(s.data, safe)], dtype=object)
            else:
                num = s.data.astype(np.int64) * mul
                sign = np.sign(num)
                data = sign * ((np.abs(num) + safe // 2) // safe)
        return Column(ft, data.astype(ft.np_dtype), (cnt > 0))
    if name in ("min", "max", "first_row"):
        s = states[0]
        return Column(ft, s.data, s.valid)
    if name in ("bit_and", "bit_or", "bit_xor"):
        return Column(ft, states[0].data)
    if name in ("var_pop", "stddev_pop", "var_samp", "stddev_samp"):
        s, s2, c = (x.data for x in states)
        cnt = np.where(c > 0, c, 1).astype(np.float64)
        mean = s / cnt
        var = s2 / cnt - mean * mean
        var = np.maximum(var, 0.0)
        if name in ("var_samp", "stddev_samp"):
            denom = np.where(c > 1, c - 1, 1).astype(np.float64)
            var = var * cnt / denom
            valid = c > 1
        else:
            valid = c > 0
        data = np.sqrt(var) if name.startswith("stddev") else var
        return Column(ft, data, valid)
    if name == "group_concat":
        s = states[0]
        return Column(ft, s.data, s.valid)
    raise ExecutorError(f"finalize: unsupported agg {name}")
