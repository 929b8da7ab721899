"""JAX coprocessor engine: executes DAG fragments on the device.

This is the component that replaces TiKV's native coprocessor (SURVEY.md
header: "the thing we must build natively is the coprocessor execution
engine itself").  Design:

- Base rows stream through in fixed TILE-row batches (padding + row masks),
  so every tile runs the *same* jitted XLA program — no dynamic shapes.
- Tiles of immutable base blocks are cached on device keyed by
  (table, base_version, column), so repeated scans never re-transfer over
  PCIe/DCN (the block-cache role of TiKV's RocksDB cache).
- Selection compiles the whole predicate tree into one fused elementwise
  program (jax_eval); aggregation lowers to dense segment reductions over
  mixed-radix group codes (ops/segment.py); TopN lowers to lax.top_k.
- Anything non-compilable raises JaxUnsupported and the caller falls back
  to the CPU engine — planner pushdown gating means this is rare.

Multi-device: the distsql layer shards *regions* across devices with
shard_map (parallel/); this module is the per-shard program.
"""

from __future__ import annotations

import json
from typing import Dict, List, Optional, Sequence, Tuple

import numpy as np

from .. import ops  # noqa: F401  (configures x64)
import jax
import jax.numpy as jnp

from ..chunk import Chunk, Column
from ..expr.aggregation import AggDesc
from ..expr.expression import ColumnExpr, Constant, Expression, ScalarFunc
from ..types import FieldType, TypeKind, ty_int
from .ir import (
    DAG,
    AggregationIR,
    JoinLookupIR,
    JoinProbeIR,
    LimitIR,
    ProjectionIR,
    SelectionIR,
    TableScanIR,
    TopNIR,
    serialize_expr,
)
from .jax_eval import JaxUnsupported, _np_dtype_for  # noqa: F401
from .aggstate import finalize as agg_finalize

import os as _os

# rows per device dispatch; env-overridable so tests exercise multi-tile
# paths with small tables (TIDB_TPU_TILE=1024 in tests/conftest.py)
TILE = int(_os.environ.get("TIDB_TPU_TILE", 1 << 20))
MAX_GROUPS = 1 << 16  # cap on dense group-code space


# ---------------------------------------------------------------------------
# dictionary rewrite: string constants -> codes
# ---------------------------------------------------------------------------

_RANGE_OPS = {"<", "<=", ">", ">="}


def rewrite_for_dict(e: Expression, table, scan: TableScanIR) -> Expression:
    """Rewrite string-vs-constant comparisons over dict-encoded columns into
    integer code comparisons.  Raises JaxUnsupported for raw string use."""
    return rewrite_for_dict_resolved(e, _scan_resolver(table, scan))


def _scan_resolver(table, scan: TableScanIR):
    """resolve(col_index) -> (table, scan, scan_pos): the single-side
    identity resolver; the join-tree engine (mpp/jointree.py) supplies a
    multi-side one that maps pair-layout positions onto each owning
    side's (table, scan)."""

    def resolve(idx: int):
        if 0 <= idx < len(scan.columns):
            return table, scan, idx
        return None

    return resolve


def rewrite_for_dict_resolved(e: Expression, resolve) -> Expression:
    if isinstance(e, (ColumnExpr, Constant)):
        return e
    assert isinstance(e, ScalarFunc)
    name = e.name
    if name in ("=", "!=") or name in _RANGE_OPS or name == "in":
        col, consts, col_first = _split_col_consts(e)
        if col is not None and col.ftype.kind == TypeKind.STRING:
            where = resolve(col.index)
            if where is None:
                raise JaxUnsupported("string column not resolvable to a "
                                     "dict-encoded store column")
            table, scan, sp = where
            store_ci = scan.columns[sp]
            if store_ci not in table.dict_encoded_cols():
                raise JaxUnsupported("string column not dict-encoded")
            if name in ("=", "!="):
                code = table.encode_dict_const(store_ci, str(consts[0].value))
                return ScalarFunc(
                    name,
                    [col, Constant(code, col.ftype)] if col_first
                    else [Constant(code, col.ftype), col],
                    e.ftype, e.meta,
                )
            if name == "in":
                items = [
                    Constant(table.encode_dict_const(store_ci, str(c.value)),
                             col.ftype)
                    for c in consts
                ]
                return ScalarFunc("in", [col] + items, e.ftype, e.meta)
            # range op on sorted dictionary
            op = name if col_first else _flip(name)
            s = str(consts[0].value)
            if op == "<":
                bound, newop = table.dict_bound(store_ci, s, "left"), "<"
            elif op == "<=":
                bound, newop = table.dict_bound(store_ci, s, "right"), "<"
            elif op == ">":
                bound, newop = table.dict_bound(store_ci, s, "right"), ">="
            else:  # >=
                bound, newop = table.dict_bound(store_ci, s, "left"), ">="
            return ScalarFunc(
                newop, [col, Constant(bound, col.ftype)], e.ftype, e.meta
            )
    from ..expr.pushdown import DICT_PRED_HEADS, dict_pred_source

    if name in DICT_PRED_HEADS and dict_pred_source(e) is not None:
        # computed predicate over ONE dict column (LIKE patterns,
        # SUBSTR/LENGTH comparisons, ISSUE 12): the host evaluates the
        # predicate once per DICTIONARY entry and the device tests CODE
        # membership — a range conjunction when the matching codes are
        # contiguous (prefix patterns on sorted dictionaries), an
        # in-list otherwise
        return _lower_dict_pred(e, resolve)
    new_args = [rewrite_for_dict_resolved(a, resolve) for a in e.args]
    return ScalarFunc(e.name, new_args, e.ftype, e.meta)


def _reindex_expr(e: Expression, mapping) -> Expression:
    """Clone `e` with every ColumnExpr index passed through `mapping`."""
    from .ir import deserialize_expr, serialize_expr

    e2 = deserialize_expr(serialize_expr(e))

    def walk(x):
        if isinstance(x, ColumnExpr):
            x.index = mapping(x.index)
        elif isinstance(x, ScalarFunc):
            for a in x.args:
                walk(a)

    walk(e2)
    return e2


#: largest non-contiguous dict-predicate code set lowered as an in-list
#: (one Constant per code rides the program AND its fingerprint; sorted
#: dictionaries keep prefix patterns contiguous, so real LIKE-prefix
#: shapes never reach this cap — only mid-string matches over
#: high-cardinality dictionaries do, and those belong on the host lane)
DICT_PRED_IN_MAX = 256


def _lower_dict_pred(e: ScalarFunc, resolve) -> Expression:
    from . import fusion
    from ..expr.pushdown import dict_pred_source

    cols = dict_pred_source(e)
    src = cols[0]
    where = resolve(src.index)
    if where is None:
        raise JaxUnsupported("dict predicate column not resolvable")
    table, scan, sp = where
    # evaluate in the owning side's scan layout (the predicate reads ONE
    # column, so reindexing every leaf to `sp` is exact), then emit the
    # lowered comparison against the ORIGINAL position
    shifted = _reindex_expr(e, lambda _i: sp)
    _idx, codes, nd = fusion.dict_pred_codes(table, scan, shifted)
    col = ColumnExpr(src.index, src.ftype, src.name, -1)
    if len(codes) == 0:
        # never-matching comparison, NOT a bare FALSE constant: the
        # column's validity plane must keep riding (NULL rows evaluate
        # to NULL, so `NOT <pred>` stays NULL instead of flipping TRUE)
        return ScalarFunc("=", [col, Constant(-1, col.ftype)],
                          e.ftype, {})
    # no all-match shortcut: the code comparison must keep carrying the
    # column's validity plane (a NULL row never matches a predicate)
    lo, hi = int(codes[0]), int(codes[-1])
    if hi - lo + 1 == len(codes):
        # contiguous code range (sorted dictionaries make every prefix
        # pattern contiguous): two comparisons instead of a member scan
        if lo == hi:
            return ScalarFunc("=", [col, Constant(lo, col.ftype)],
                              e.ftype, {})
        return ScalarFunc("and", [
            ScalarFunc(">=", [col, Constant(lo, col.ftype)], e.ftype, {}),
            ScalarFunc("<=", [col, Constant(hi, col.ftype)], e.ftype, {}),
        ], e.ftype, {})
    if len(codes) > DICT_PRED_IN_MAX:
        # a non-contiguous match set over a high-cardinality dictionary
        # (e.g. `%needle%` on a near-unique comment column) would embed
        # one Constant per code into the traced program AND its
        # fingerprint — decline so the host lane serves it instead
        raise JaxUnsupported("dict predicate code set too large")
    return ScalarFunc(
        "in", [col] + [Constant(int(c), col.ftype) for c in codes],
        e.ftype, {})


def _string_leaf(e: Expression) -> bool:
    """Does the expression read any STRING-typed column?"""
    if isinstance(e, ColumnExpr):
        return e.ftype.kind == TypeKind.STRING
    if isinstance(e, ScalarFunc):
        return any(_string_leaf(a) for a in e.args)
    return False


def _split_col_consts(e: ScalarFunc):
    args = e.args
    if isinstance(args[0], ColumnExpr) and all(
        isinstance(a, Constant) for a in args[1:]
    ):
        return args[0], list(args[1:]), True
    if len(args) == 2 and isinstance(args[1], ColumnExpr) and isinstance(
        args[0], Constant
    ):
        return args[1], [args[0]], False
    return None, [], True


def _flip(op: str) -> str:
    return {"<": ">", "<=": ">=", ">": "<", ">=": "<="}[op]


# ---------------------------------------------------------------------------
# device block cache
# ---------------------------------------------------------------------------


class _DeviceCache:
    """(table_id, base_version, store_col, tile_idx) -> (data, valid) on device."""

    def __init__(self, capacity_bytes: int = 8 << 30):
        from .cache import ByteCapCache

        self._c = ByteCapCache(capacity_bytes, name="tile")

    def get_tile(self, table, store_ci: int, tile_idx: int, start: int,
                 end: int, device=None):
        key = (table.store_uid, table.base_version, store_ci, tile_idx,
               None if device is None else device.id)

        def load():
            from ..trace import span

            with span("copr.transfer", col=store_ci, tile=tile_idx) as sp:
                data, valid = _gather_tile(table, store_ci, start, end)
                sp.set(bytes=data.nbytes + valid.nbytes)
                if device is not None:
                    sp.set(device=device.id)
                return (jax.device_put(data, device),
                        jax.device_put(valid, device))

        return self._c.get_or_load(key, load)

    def clear(self):
        """Drop every resident tile (HBM-OOM recovery path)."""
        self._c.clear()


def _gather_tile(table, store_ci: int, start: int, end: int):
    """Host-side: concatenate block slices for [start,end) and pad to TILE."""
    meta = table.cols[store_ci]
    dt = np.int32 if meta.ftype.kind in (TypeKind.DATE, TypeKind.STRING) else (
        np.float64 if meta.ftype.kind == TypeKind.FLOAT else np.int64
    )
    parts, vparts = [], []
    for _, arrs, vals in table.iter_base_blocks([store_ci], start, end):
        parts.append(arrs[0])
        v = vals[0]
        vparts.append(v if v is not None else np.ones(len(arrs[0]), np.bool_))
    if parts:
        data = np.concatenate(parts).astype(dt, copy=False)
        valid = np.concatenate(vparts)
    else:
        data = np.zeros(0, dtype=dt)
        valid = np.zeros(0, dtype=np.bool_)
    n = len(data)
    if n < TILE:
        data = np.pad(data, (0, TILE - n))
        valid = np.pad(valid, (0, TILE - n))
    return data, valid


DEVICE_CACHE = _DeviceCache()

_ALL_TRUE: Dict[object, object] = {}


def _all_true(device=None):
    """Device-resident all-true TILE mask, transferred once per device."""
    m = _ALL_TRUE.get(device)
    if m is None:
        m = _ALL_TRUE[device] = jax.device_put(
            np.ones(TILE, dtype=np.bool_), device
        )
    return m


# ---------------------------------------------------------------------------
# DAG analysis
# ---------------------------------------------------------------------------


class _Analyzed:
    def __init__(self, dag: DAG, table):
        self.scan: TableScanIR = dag.scan
        self.selections: List[SelectionIR] = []
        self.probes: List[JoinProbeIR] = []
        self.lookups: List[JoinLookupIR] = []
        self.projection: Optional[ProjectionIR] = None
        self.agg: Optional[AggregationIR] = None
        self.topn: Optional[TopNIR] = None
        self.limit: Optional[int] = None
        for ex in dag.executors[1:]:
            if isinstance(ex, SelectionIR):
                if self.agg or self.topn or self.projection:
                    raise JaxUnsupported("selection after agg/topn on device")
                self.selections.append(ex)
            elif isinstance(ex, JoinProbeIR):
                if self.agg or self.topn or self.projection:
                    raise JaxUnsupported("join probe after agg/topn on device")
                self.probes.append(ex)
            elif isinstance(ex, JoinLookupIR):
                if self.agg or self.topn or self.projection:
                    raise JaxUnsupported("join lookup after agg/topn")
                self.lookups.append(ex)
            elif isinstance(ex, ProjectionIR):
                if self.agg or self.topn:
                    raise JaxUnsupported("projection after agg/topn on device")
                self.projection = ex
            elif isinstance(ex, AggregationIR):
                if self.agg or self.topn or self.projection:
                    raise JaxUnsupported("late aggregation on device")
                if ex.mode != "partial":
                    raise JaxUnsupported("device agg is partial-only")
                self.agg = ex
            elif isinstance(ex, TopNIR):
                if self.agg or self.topn:
                    raise JaxUnsupported("topn after agg on device")
                self.topn = ex
            elif isinstance(ex, LimitIR):
                self.limit = ex.limit if self.limit is None else min(
                    self.limit, ex.limit
                )
            else:
                raise JaxUnsupported(f"device executor {ex!r}")
        # pushability gate (defense in depth; the planner already gates)
        from ..expr.pushdown import can_push_agg, can_push_expr

        dict_scan_idx = {
            i for i, ci in enumerate(self.scan.columns)
            if ci in table.dict_encoded_cols()
        }
        all_exprs: List[Expression] = [
            c for s in self.selections for c in s.conditions
        ] + [p.key for p in self.probes] + [lk.key for lk in self.lookups]
        if self.lookups and self.agg is None:
            # the mesh filter/topn readback paths gather rows from the
            # TABLE, which has no payload columns — lookups are only
            # device-run under a partial aggregation (the planner only
            # emits that shape; fan-out CPU regions handle the rest)
            raise JaxUnsupported("join lookup without device aggregation")
        if self.projection is not None:
            all_exprs += self.projection.exprs
        if self.topn is not None:
            all_exprs += [e for e, _ in self.topn.order_by]
        for ex2 in all_exprs:
            if not can_push_expr(ex2, dict_cols=dict_scan_idx):
                raise JaxUnsupported(f"expr not device-eligible: {ex2}")
        if self.agg is not None:
            for a in self.agg.aggs:
                if not can_push_agg(a, dict_cols=dict_scan_idx):
                    raise JaxUnsupported(f"agg not device-eligible: {a}")
        # rewrite dict-encoded string constants
        self.conds = [
            rewrite_for_dict(c, table, self.scan)
            for s in self.selections
            for c in s.conditions
        ]
        if self.projection is not None:
            self.proj_exprs = [
                rewrite_for_dict(p, table, self.scan)
                for p in self.projection.exprs
            ]
        else:
            self.proj_exprs = None
        if self.agg is not None:
            # rewrite agg ARGS and group keys for dict codes too (ISSUE
            # 12: CASE-heavy aggregate arguments with string comparisons
            # — `sum(case when prio = '1-URGENT' ...)` — compile against
            # integer codes).  A fresh AggregationIR: the DAG's own IR
            # keeps the original string constants for the host engines.
            self.agg = AggregationIR(
                [rewrite_for_dict(g, table, self.scan)
                 for g in self.agg.group_by],
                [AggDesc(a.name,
                         [rewrite_for_dict(x, table, self.scan)
                          for x in a.args],
                         a.distinct, a.ftype)
                 for a in self.agg.aggs],
                mode=self.agg.mode, stream=self.agg.stream)
        # group-key layout for device aggregation
        self.group_cols: List[int] = []  # scan-output indices
        self.group_card: List[Tuple[int, int]] = []  # (lo, card) per key
        # 'dense': mixed-radix int codes + segment reduce (small key spaces);
        # 'sort': per-shard lexsort + boundary segments (arbitrary NDV,
        #         float/NULLable keys) — mesh path only
        self.agg_mode = "dense"
        #: per-group-key dict-code remaps (computed string keys lowered
        #: to code-space gathers, ISSUE 11) — None when no key needs one
        self.key_remaps = None
        #: packed lexicographic multi-column TopN spec, else None
        self.topn_pack = None
        #: dense mode: scan column -> (lo, hi, nullable) for the integer
        #: columns the aggregates' arguments read (_bound_agg_args)
        self.agg_bounds: dict = {}
        #: what the dense emitter makes of them (fusion.agg_lanes), lazily
        self.agg_lanes: Optional[str] = None
        #: the same columns' own minima and maxima, and the table's base
        #: rows: what decides whether a sum can pass int64
        #: (fusion.wide_sums, lazily in `agg_wide`)
        self.agg_stats: dict = {}
        self.agg_rows = 0
        self.agg_wide: Optional[dict] = None
        if self.agg is not None:
            width = len(self.scan.columns)
            for a in self.agg.aggs:
                if a.distinct:
                    raise JaxUnsupported("distinct agg on device")
                if a.name not in ("count", "sum", "avg", "min", "max",
                                  "first_row"):
                    raise JaxUnsupported(f"device agg {a.name}")
                if a.name == "first_row" and self.lookups:
                    refs: set = set()
                    for x in a.args:
                        x.collect_columns(refs)
                    if any(i >= width for i in refs):
                        # first_row partials resolve via a TABLE gather,
                        # which has no payload columns
                        raise JaxUnsupported("first_row over join payload")
            try:
                self._analyze_dense_keys(table)
                self._bound_agg_args(table)
            except JaxUnsupported:
                # high-NDV / float / NULLable / non-column keys: the mesh
                # engine groups by sorting — keys only need to be
                # device-compilable.  Computed STRING keys over a
                # dict-encoded column lower to code-space gathers
                # (fusion.build_key_remap): the host evaluates the string
                # function once per DICTIONARY entry and the device
                # re-maps row codes through a runtime operand — no host
                # tail, no decode (ISSUE 11; closes MPP follow-up (d)).
                from .fusion import build_key_remap

                remaps = []
                for k in self.agg.group_by:
                    if not isinstance(k, ColumnExpr) and (
                            k.ftype.kind == TypeKind.STRING
                            or _string_leaf(k)):
                        # computed key READING a string column: STRING
                        # outputs remap into an output dictionary;
                        # INT-valued ones (LENGTH/ASCII, ISSUE 12) remap
                        # straight to computed values
                        remaps.append(
                            build_key_remap(table, self.scan, k))
                        continue
                    if not can_push_expr(k, dict_cols=dict_scan_idx):
                        raise
                    remaps.append(None)
                # (min/max STRING args need no guard here: can_push_agg
                # already rejects non-column STRING args upstream)
                if any(r is not None for r in remaps):
                    self.key_remaps = remaps
                self.agg_mode = "sort"
                self.num_groups = 0
                self.group_cols = []
                self.group_card = []
                self.agg_bounds = {}
                self.agg_stats = {}
        if self.topn is not None:
            if len(self.topn.order_by) != 1:
                # exact compound ordering: pack every key's stats-bounded
                # rank into ONE integer sort key (fusion.compound_topn_key)
                # so multi-column TopN runs on device; unpackable key sets
                # raise with the compound-order split reason
                self._analyze_compound_topn(table)

    def _analyze_compound_topn(self, table):
        """Build the packed lexicographic sort-key spec for a multi-column
        TopN: per key (col_idx, lo, hi, slots, desc, has_null) with a NULL
        rank slot when the column is nullable.  The slot product is capped
        at 2**52 so the f64 top_k comparison stays exact."""
        pack = []
        total = 1
        for e, desc in self.topn.order_by:
            if not isinstance(e, ColumnExpr):
                raise JaxUnsupported(
                    f"compound order key must be a plain column: {e}")
            if e.ftype.kind == TypeKind.FLOAT:
                raise JaxUnsupported(
                    "compound order over unbounded float sort key")
            if e.index >= len(self.scan.columns):
                raise JaxUnsupported(
                    "compound order key over join payload")
            store_ci = self.scan.columns[e.index]
            lo, hi, has_null = table.column_stats(store_ci)
            if hi < lo:
                lo, hi = 0, 0
            slots = (hi - lo + 1) + (1 if has_null else 0)
            total *= slots
            if total > (1 << 52):
                raise JaxUnsupported(
                    "compound order key space too large for a packed "
                    "sort key")
            pack.append((e.index, int(lo), int(hi), int(slots),
                         bool(desc), bool(has_null)))
        self.topn_pack = pack

    def _analyze_dense_keys(self, table):
        g = 1
        group_cols: List[int] = []
        group_card: List[Tuple[int, int]] = []
        for k in self.agg.group_by:
            if not isinstance(k, ColumnExpr):
                raise JaxUnsupported("dense group key must be a column")
            if k.ftype.kind == TypeKind.FLOAT:
                # dense int codes would truncate: 1.2 and 1.4 collapse
                raise JaxUnsupported("float group key on device")
            if k.index >= len(self.scan.columns):
                # payload column (join lookup): no base stats — sort mode
                raise JaxUnsupported("payload group key needs sort agg")
            store_ci = self.scan.columns[k.index]
            lo, hi, has_null = table.column_stats(store_ci)
            if has_null:
                # NULL is its own group in SQL; the dense-code space has
                # no slot for it
                raise JaxUnsupported("NULLable group key on device")
            if hi < lo:
                lo, hi = 0, 0
            card = hi - lo + 1
            if card <= 0 or card > MAX_GROUPS:
                raise JaxUnsupported("group key cardinality too large")
            g *= card
            if g > MAX_GROUPS:
                raise JaxUnsupported("combined group space too large")
            group_cols.append(k.index)
            group_card.append((lo, card))
        self.group_cols = group_cols
        self.group_card = group_card
        self.num_groups = max(g, 1)

    def _bound_agg_args(self, table):
        """Bounds of the columns under the aggregates' arguments, from the
        same statistics `_wire_dtype` narrows by, each rounded out to a
        power of two: the dense emitter (fusion.dense_agg_results) picks
        int32 or int64 arithmetic and the limbs of every sum from them,
        so they are in the fingerprint, and a load compiles a new program
        only when it carries a column past a power of two."""
        refs: set = set()
        for a in self.agg.aggs:
            for x in a.args:
                x.collect_columns(refs)
        for i in sorted(refs):
            if i >= len(self.scan.columns) or self.scan.ftypes[i].kind in (
                    TypeKind.FLOAT, TypeKind.STRING):
                continue
            lo, hi, has_null = table.column_stats(self.scan.columns[i])
            if hi < lo:
                lo = hi = 0
            self.agg_bounds[i] = (
                min(-(1 << (-lo - 1).bit_length()), 0) if lo < 0 else 0,
                (1 << hi.bit_length()) - 1 if hi > 0 else 0,
                bool(has_null))
            self.agg_stats[i] = (lo, hi)
        self.agg_rows = table.base_rows

    def needed_cols(self) -> List[int]:
        """Scan-output col indices the device actually needs (payload
        indices from join lookups are aux-fed, not scanned — dropped)."""
        need: set = set()
        for c in self.conds:
            c.collect_columns(need)
        for p in self.probes:
            p.key.collect_columns(need)
        for lk in self.lookups:
            lk.key.collect_columns(need)
        if self.agg is not None:
            need.update(self.group_cols)
            for k in self.agg.group_by:
                k.collect_columns(need)
            for a in self.agg.aggs:
                for x in a.args:
                    x.collect_columns(need)
        if self.proj_exprs is not None:
            for p in self.proj_exprs:
                p.collect_columns(need)
        if self.topn is not None:
            for e, _d in self.topn.order_by:
                e.collect_columns(need)
        width = len(self.scan.columns)
        return sorted(i for i in need if i < width)


# ---------------------------------------------------------------------------
# compiled tile programs
# ---------------------------------------------------------------------------

from .cache import ProgramCache  # noqa: E402

_COMPILED = ProgramCache("tile")


def _fingerprint(an: _Analyzed, kind: str) -> str:
    from .pallas import pallas_enabled

    payload = {
        "kind": kind,
        # the Pallas tier changes the traced program BODY (kernel calls
        # vs jnp compositions), so the comparator flip must never reuse
        # a cached program built under the other setting
        "pallas": pallas_enabled(),
        "conds": [serialize_expr(c) for c in an.conds],
        "probes": [[serialize_expr(p.key), p.filter_id] for p in an.probes],
        "lookups": [
            [serialize_expr(lk.key), lk.filter_id,
             [int(f.kind) for f in lk.payload_ftypes]]
            for lk in an.lookups
        ],
        "proj": [serialize_expr(p) for p in an.proj_exprs]
        if an.proj_exprs is not None
        else None,
        "scan_ft": [int(f.kind) for f in an.scan.ftypes],
    }
    if an.agg is not None:
        payload["agg"] = {
            "mode": an.agg_mode,
            "keys": an.group_cols,
            "card": an.group_card,
            "group_by": [serialize_expr(g) for g in an.agg.group_by],
            "aggs": [
                {"name": a.name, "args": [serialize_expr(x) for x in a.args]}
                for a in an.agg.aggs
            ],
        }
        if an.agg_mode == "dense":
            from .fusion import agg_lanes

            # the lanes follow from the bounds; both shape the program
            payload["agg"]["bounds"] = sorted(an.agg_bounds.items())
            payload["agg"]["lanes"] = agg_lanes(an)
    if an.topn is not None:
        from ..serving import topn_budget

        e, desc = an.topn.order_by[0]
        # pow2-bucketed device budget: LIMIT 5 and LIMIT 7 share one
        # compiled kernel; the exact limit re-applies at the host merge
        payload["topn"] = {
            "key": serialize_expr(e), "desc": desc,
            "k": topn_budget(an.topn.limit),
        }
        if an.topn_pack is not None:
            # packed compound ordering: every key + its static rank
            # layout (lo/slots are compiled constants derived from
            # column stats) shapes the program
            payload["topn"]["keys"] = [
                [serialize_expr(e2), bool(d2)]
                for e2, d2 in an.topn.order_by
            ]
            payload["topn"]["pack"] = [
                [p[1], p[3], p[4], p[5]] for p in an.topn_pack
            ]
    if getattr(an, "key_remaps", None):
        # remap operand arity + pow2 caps shape the program; mapping
        # CONTENTS stay runtime operands
        payload["remaps"] = [r.cap if r is not None else None
                             for r in an.key_remaps]
    return json.dumps(payload, sort_keys=True, default=str)


def _agg_tags(agg_ir) -> List[str]:
    """Static result layout: tag per agg (jit returns arrays only)."""
    tags = []
    for a in agg_ir.aggs:
        if a.name == "count":
            tags.append("count")
        elif a.name in ("sum", "avg"):
            tags.append("sumcount")
        elif a.name in ("min", "max"):
            tags.append("minmax")
        else:
            tags.append("argfirst")
    return tags


def _tile_core(an: _Analyzed, kind: str, col_order: List[int],
               with_params: bool = False):
    """The raw (un-jitted) per-tile program, composed from the fusion
    phase emitters (copr/fusion.py) so every pushed phase — filter,
    project, agg, topN — emits into one shared tracing context and the
    whole fragment is ONE program.

    Signature: fn(datas, valids, lo, hi, del_mask[, pi, pf]) — the pi/pf
    trailing args (hoisted predicate parameters, serving/params.py) are
    present only when `with_params`; the micro-batcher vmaps this same
    core over stacked parameter vectors.
    """
    from . import fusion

    if an.lookups:
        # the broadcast lookup join runs in the mesh engine only; the
        # per-tile fallback hands these regions to the CPU interpreter
        raise JaxUnsupported("join lookup needs the mesh engine")
    n = TILE

    def region_ctx(datas, valids, lo, hi, del_mask, params):
        env = {
            ci: (datas[j], valids[j]) for j, ci in enumerate(col_order)
        }
        env["__wire__"] = {ci: datas[j] for j, ci in enumerate(col_order)}
        if with_params and params is not None:
            env["__params__"] = params
        # tile-local row numbers fit int32 lanes (an int64 comparison is
        # several lane operations on the TPU)
        ar = jnp.arange(n, dtype=jnp.int32)
        ctx = fusion.RegionContext(
            an=an, cols=env, n=n,
            mask=((ar >= jnp.asarray(lo).astype(jnp.int32))
                  & (ar < jnp.asarray(hi).astype(jnp.int32)) & del_mask))
        fusion.selection_mask(ctx)
        return ctx

    if kind == "filter":
        def fn(datas, valids, lo, hi, del_mask, *params):
            ctx = region_ctx(datas, valids, lo, hi, del_mask, params)
            outs = None
            if an.proj_exprs is not None:
                outs = fusion.projection_outputs(ctx)
            return ctx.mask, outs

        return fn

    if kind == "agg":
        def fn(datas, valids, lo, hi, del_mask, *params):
            ctx = region_ctx(datas, valids, lo, hi, del_mask, params)
            gidx = fusion.dense_group_codes(ctx)
            return fusion.dense_agg_results(ctx, gidx)

        return fn

    if kind == "topn":
        from ..serving import topn_budget

        desc = fusion.topn_desc(an)
        k = min(topn_budget(an.topn.limit), TILE)

        def fn(datas, valids, lo, hi, del_mask, *params):
            ctx = region_ctx(datas, valids, lo, hi, del_mask, params)
            key = fusion.topn_key(ctx)
            idx, cnt = ops.masked_top_k(key, ctx.mask, k, desc)
            return idx, cnt

        return fn

    raise JaxUnsupported(kind)


def _build_tile_fn(an: _Analyzed, kind: str, col_order: List[int],
                   with_params: bool = False):
    """Returns a jitted fn(datas, valids, lo, hi, del_mask[, pi, pf]).

    The row mask is built ON DEVICE from the [lo, hi) scalars (region clip
    within the tile) AND'd with del_mask (a cached device-resident all-true
    array unless the tile has MVCC-deleted rows).  Keeping masks device-side
    means a steady-state query moves ZERO scan data host-to-device: tiles
    are cached device arrays (keyed on base_version), and only G-sized
    partials come back.
    """
    core = _tile_core(an, kind, col_order, with_params=with_params)
    if kind != "agg":
        return jax.jit(core)
    tags = _agg_tags(an.agg)
    jitted = jax.jit(core)

    def wrapped(datas, valids, lo, hi, del_mask, *params):
        gcount, results = jitted(datas, valids, lo, hi, del_mask, *params)
        return gcount, list(zip(tags, results))

    return wrapped


def _to_state_dtype(d, src_ft: FieldType, state_ft: FieldType):
    if state_ft.kind == TypeKind.FLOAT:
        if src_ft.kind == TypeKind.DECIMAL:
            return d.astype(jnp.float64) / (10.0 ** src_ft.scale)
        return d.astype(jnp.float64)
    # decimal state: rescale ints
    if src_ft.kind == TypeKind.DECIMAL:
        ds = state_ft.scale - src_ft.scale
        if ds > 0:
            return d.astype(jnp.int64) * (10 ** ds)
        return d.astype(jnp.int64)
    return d.astype(jnp.int64) * (10 ** state_ft.scale)


# ---------------------------------------------------------------------------
# engine entry
# ---------------------------------------------------------------------------


def _tile_devices():
    """Devices the per-tile path may place work on: the visible set minus
    tripped breakers (ROADMAP PR-2 follow-up (a) — this path used to pin
    the default device even while its breaker was open).  Multi-process
    runs skip filtering, same rule as the mesh (copr/parallel.py
    _eligible_devices); an all-tripped set falls back to the full list
    (the distsql layer steps down to the CPU engine on failure)."""
    devs = list(jax.devices())
    if jax.process_count() > 1:
        return devs
    from .device_health import DEVICE_HEALTH

    healthy = DEVICE_HEALTH.select_devices(devs)
    return healthy if healthy else devs


def run_base_jax(table, dag: DAG, start: int, end: int,
                 deleted: Sequence[int], aux=None, an=None) -> List[Chunk]:
    """Execute `dag` over base rows [start, end) on the device; returns
    result chunks (partial-agg rows, topn rows, or filtered rows).
    `an` lets the fusion ladder pass its already-built analysis instead
    of paying a second _Analyzed walk per cop task."""
    if an is None:
        an = _Analyzed(dag, table)
    if an.agg is not None and an.agg_mode != "dense":
        # sort-based grouping needs the mesh program (copr/parallel.py);
        # the per-tile fallback path hands these to the CPU engine
        raise JaxUnsupported("sort-mode agg runs on the mesh path only")
    if an.probes:
        # runtime join filters run on the mesh path; per-region fallback
        # evaluates them on the CPU engine
        raise JaxUnsupported("join probe runs on the mesh path only")
    kind = "agg" if an.agg is not None else (
        "topn" if an.topn is not None else "filter"
    )
    from ..trace import NOOP, span

    col_order = an.needed_cols()
    # hoist predicate constants into runtime parameter slots (serving):
    # the fingerprint below serializes SLOTS, so parameter-different
    # queries of the same shape class share one compiled tile program
    from ..serving import hoist_conds

    hoisted = hoist_conds(an)
    pextra = ()
    if hoisted is not None:
        pi, pf = hoisted
        pextra = (jnp.asarray(pi), jnp.asarray(pf))
    fp = (_fingerprint(an, kind) + f"|cols={col_order}"
          + (f"|hp={len(hoisted[0])},{len(hoisted[1])}"
             if hoisted is not None else ""))
    from .fusion import compile_attrs, note_agg_dispatch

    cattrs = compile_attrs(an, kind)
    if cattrs:
        note_agg_dispatch(an)
    fn = _COMPILED.get(fp)
    compiled_now = fn is None
    if fn is None:
        fn = _build_tile_fn(an, kind, col_order,
                            with_params=hoisted is not None)
        _COMPILED.put(fp, fn)
    else:
        # zero-duration marker: the DAG fingerprint hit the program cache
        with span("copr.compile", cache="hit", kind=kind, **cattrs):
            pass

    del_arr = np.fromiter(sorted(deleted), dtype=np.int64,
                          count=len(deleted))
    out_chunks: List[Chunk] = []
    agg_accum = None
    topn_parts: List[Chunk] = []
    remaining_limit = an.limit

    from ..lifecycle import chunk_admission, scope_check
    from ..store.fault import FAILPOINTS

    devices = _tile_devices()
    used_ids: set = set()
    for tile_start in range((start // TILE) * TILE, end, TILE):
        # host-side cancellation seam: an in-flight XLA dispatch cannot
        # be interrupted, so KILL/deadline land between tile dispatches
        # (strictly host Python — never traced into the compiled program)
        scope_check()
        t0 = max(tile_start, start)
        t1 = min(tile_start + TILE, end)
        if t0 >= t1:
            continue
        tile_idx = tile_start // TILE
        # each tile is a dispatch of its own on the fallback path: the
        # mesh dispatcher's pre-dispatch failpoint, and resource-group
        # admission re-acquired per tile below
        FAILPOINTS.hit("copr/chunk_dispatch", kind="tile", chunk=tile_idx,
                       total=0, start=t0, end=t1)
        # tiles are ALWAYS the aligned, device-cached arrays; the region
        # clip [t0,t1) and deletions become the mask, so repeat queries and
        # sub-tile regions reuse resident device data (no re-transfer).
        # Multi-chip: tiles round-robin across devices — async dispatch
        # runs per-tile kernels concurrently (DP over shards, SURVEY §2.6)
        dev = devices[tile_idx % len(devices)] if len(devices) > 1 else (
            devices[0] if devices[0].id != jax.devices()[0].id else None)
        used_ids.add(devices[0].id if dev is None else dev.id)
        datas, valids = [], []
        for j, ci in enumerate(col_order):
            store_ci = an.scan.columns[ci]
            d, v = DEVICE_CACHE.get_tile(
                table, store_ci, tile_idx, tile_start,
                min(tile_start + TILE, table.base_rows), device=dev,
            )
            datas.append(d)
            valids.append(v)
        base0 = tile_start
        lo = np.int64(t0 - base0)
        hi = np.int64(t1 - base0)
        del_mask = _all_true(dev)
        if len(del_arr):
            dd = del_arr[(del_arr >= base0) & (del_arr < base0 + TILE)] - base0
            if len(dd):
                dm = np.ones(TILE, dtype=np.bool_)
                dm[dd] = False
                del_mask = jax.device_put(dm, dev)

        # first post-miss dispatch IS the XLA compile (jit compiles
        # lazily): label it, as the mesh path does; the seconds JAX
        # reports compiling land on the execute span inside the label
        # (`compile_ns`), which is how they reach the compile phase
        label = (span("copr.compile", cache="miss", kind=kind, **cattrs)
                 if compiled_now else NOOP)
        # per-trace HBM attribution (ISSUE 13): resident tile-cache
        # bytes at dispatch time ride the execute span
        dattr = {"hbm_bytes": DEVICE_CACHE._c._bytes}
        compiled_now = False
        if kind == "filter":
            with label, span("copr.device.execute", kind=kind,
                             tile=tile_idx, **dattr):
                with chunk_admission():
                    m, outs = fn(datas, valids, lo, hi, del_mask,
                                 *pextra)
            with span("copr.readback") as rsp:
                mh = _np_tree(m)
                rsp.set(bytes=mh.nbytes)
            sel = np.flatnonzero(mh)
            if remaining_limit is not None:
                sel = sel[:remaining_limit]
            if len(sel) == 0:
                continue
            if outs is not None:
                cols = []
                with span("copr.readback") as rsp:
                    nb = 0
                    for (dv, vv), p in zip(outs, an.proj_exprs):
                        dv, vv = _np_tree((dv, vv))
                        nb += dv.nbytes + vv.nbytes
                        cols.append(Column(p.ftype, dv[sel], vv[sel]))
                    rsp.set(bytes=nb)
                chunk = Chunk(cols)
            else:
                chunk = _gather_rows(table, an.scan, base0, sel)
            out_chunks.append(chunk)
            if remaining_limit is not None:
                remaining_limit -= chunk.num_rows
                if remaining_limit <= 0:
                    break
        elif kind == "agg":
            with label, span("copr.device.execute", kind=kind,
                             tile=tile_idx, **dattr):
                with chunk_admission():
                    gcount, results = fn(datas, valids, lo, hi, del_mask,
                                         *pextra)
            with span("copr.readback") as rsp:
                gh = _np_tree(gcount)
                rh = [(t, _np_tree(r)) for t, r in results]
                rsp.set(bytes=gh.nbytes + sum(
                    (x.nbytes if not isinstance(x, tuple)
                     else sum(y.nbytes for y in x)) for _t, x in rh))
            agg_accum = _merge_device_agg(agg_accum, gh, rh, table, an,
                                          base0)
        else:  # topn
            with label, span("copr.device.execute", kind=kind,
                             tile=tile_idx, **dattr):
                with chunk_admission():
                    idx, cnt = fn(datas, valids, lo, hi, del_mask,
                                  *pextra)
            with span("copr.readback") as rsp:
                idx = _np_tree(idx)[: int(cnt)]
                rsp.set(bytes=idx.nbytes)
            if len(idx):
                topn_parts.append(_gather_rows(table, an.scan, base0, idx))

    # every tile kernel completed: reset error streaks for the devices
    # that ACTUALLY ran a tile — a half-open chip the round-robin never
    # touched must not have its breaker closed by someone else's scan
    from .device_health import DEVICE_HEALTH

    DEVICE_HEALTH.record_success(sorted(used_ids))

    if kind == "agg":
        if agg_accum is None:
            return []
        return [_device_agg_to_chunk(agg_accum, table, an)]
    if kind == "topn":
        if not topn_parts:
            return []
        from .cpu_engine import run_topn

        merged = topn_parts[0]
        for p in topn_parts[1:]:
            merged = merged.append(p)
        return [run_topn(an.topn.order_by, an.topn.limit, merged)]
    return out_chunks


def _np_tree(r):
    if isinstance(r, tuple):
        return tuple(np.asarray(x) for x in r)
    return np.asarray(r)


def _gather_rows(table, scan: TableScanIR, base0: int, sel: np.ndarray) -> Chunk:
    """Host gather of scan-output rows at tile-local indices `sel` —
    per-block sparse gather, not a contiguous-span materialization."""
    return table.gather_chunk(list(scan.columns), base0 + sel)


def _merge_device_agg(accum, gcount: np.ndarray, results, table, an: _Analyzed,
                      base0: int):
    """Accumulate per-tile dense G-arrays into running host arrays."""
    if accum is None:
        accum = {"gcount": gcount.copy(), "states": []}
        for tag, r in results:
            if tag == "argfirst":
                # resolve indices to values host-side now (per tile)
                accum["states"].append(["argfirst", None, None])
            else:
                accum["states"].append([tag, None, None])
    else:
        accum["gcount"] += gcount
    for si, (tag, r) in enumerate(results):
        slot = accum["states"][si]
        if tag == "count":
            slot[1] = r if slot[1] is None else slot[1] + r
        elif tag == "sumcount":
            s, c = r
            if slot[1] is None:
                # integer sums add up across tiles as Python integers: a
                # tile's partial fits int64 where the table's sum may not
                slot[1] = s.astype(object) if s.dtype.kind == "i" \
                    else s.copy()
                slot[2] = c.copy()
            else:
                slot[1] += s
                slot[2] += c
        elif tag == "minmax":
            v, c = r
            if slot[1] is None:
                slot[1], slot[2] = v.copy(), c.copy()
            else:
                a = an.agg.aggs[si]
                pick = np.minimum if a.name == "min" else np.maximum
                have_old = slot[2] > 0
                have_new = c > 0
                both = have_old & have_new
                merged = np.where(both, pick(slot[1], v),
                                  np.where(have_new, v, slot[1]))
                slot[1] = merged
                slot[2] += c
        elif tag == "argfirst":
            # r: per-group first row index in tile (TILE if none)
            a = an.agg.aggs[si]
            arg = a.args[0]
            idx = r
            have = idx < TILE
            vals, valid = _resolve_first_values(table, an, arg, base0, idx, have)
            if slot[1] is None:
                slot[1], slot[2] = vals, valid
            else:
                need = ~slot[2] & valid
                slot[1] = np.where(need, vals, slot[1])
                slot[2] = slot[2] | valid
    return accum


def _resolve_first_values(table, an, arg, base0, idx, have):
    sel = np.flatnonzero(have)
    G = an.num_groups
    st = arg.ftype
    if st.kind == TypeKind.STRING:
        vals = np.empty(G, dtype=object)
        vals[:] = ""
    else:
        vals = np.zeros(G, dtype=st.np_dtype)
    valid = np.zeros(G, dtype=np.bool_)
    if len(sel):
        rows = _gather_rows(table, an.scan, base0, idx[sel])
        v = arg.eval(rows)
        vals[sel] = v.data
        valid[sel] = v.validity()
    return vals, valid


def _device_agg_to_chunk(accum, table, an: _Analyzed) -> Chunk:
    """Dense per-group arrays -> partial chunk [keys..., states...] with
    empty groups dropped (matches the CPU engine layout)."""
    gcount = accum["gcount"]
    present = np.flatnonzero(gcount > 0)
    if an.agg.group_by and len(present) == 0:
        return Chunk.empty(
            [g.ftype for g in an.agg.group_by]
            + [t for a in an.agg.aggs for t in a.partial_types()]
        )
    if not an.agg.group_by:
        present = np.array([0], dtype=np.int64)
    cols: List[Column] = []
    # decode mixed-radix codes back to key values
    code = present.copy()
    for kcol, (lo, card), g in zip(an.group_cols, an.group_card,
                                   an.agg.group_by):
        vals = (code % card) + lo
        code = code // card
        store_ci = an.scan.columns[kcol]
        meta = table.cols[store_ci]
        if meta.ftype.kind == TypeKind.STRING:
            d = meta.dictionary or []
            obj = np.empty(len(vals), dtype=object)
            for i, c in enumerate(vals):
                obj[i] = d[c] if 0 <= c < len(d) else ""
            cols.append(Column(g.ftype, obj))
        else:
            cols.append(Column(g.ftype, vals.astype(meta.ftype.np_dtype)))
    for a, slot in zip(an.agg.aggs, accum["states"]):
        tag = slot[0]
        pts = a.partial_types()
        if tag == "count":
            cols.append(Column(pts[0], slot[1][present].astype(np.int64)))
        elif tag == "sumcount":
            s = slot[1][present]
            c = slot[2][present]
            sum_col = Column(pts[0], s.astype(pts[0].np_dtype), c > 0)
            if a.name == "sum":
                cols.append(sum_col)
            else:
                cols.append(sum_col)
                cols.append(Column(pts[1], c.astype(np.int64)))
        elif tag == "minmax":
            v = slot[1][present]
            c = slot[2][present]
            arg_ft = a.args[0].ftype
            if arg_ft.kind == TypeKind.STRING:
                # values are dict codes; decode
                colexpr = a.args[0]
                store_ci = an.scan.columns[colexpr.index]
                d = table.cols[store_ci].dictionary or []
                obj = np.empty(len(v), dtype=object)
                for i, cd in enumerate(v):
                    obj[i] = d[int(cd)] if 0 <= int(cd) < len(d) else ""
                cols.append(Column(pts[0], obj, c > 0))
            else:
                cols.append(
                    Column(pts[0], v.astype(pts[0].np_dtype), c > 0)
                )
        elif tag == "argfirst":
            cols.append(Column(pts[0], slot[1][present], slot[2][present]))
    return Chunk(cols)
