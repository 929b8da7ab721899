"""Plan/schema typechecker — `go vet` for physical plans.

The planner's rewrites (partial-agg pushdown, the JoinLookupIR device-join
rewrite, index-join variants) re-index column references across schema
boundaries and trust the result on faith; a single off-by-one re-map reads
the wrong column with no error until (at best) a dtype blowup deep inside
the engine.  DrJAX-style abstract checking applies here without any
device: walk the physical tree once at plan-build time and verify

* every operator's output schema width/dtype propagation against its
  children (positional re-maps are where planner bugs live);
* every column reference is in range for the chunk it will be given;
* every expression pushed into a cop DAG is in the TPU-executable
  registry (expr/pushdown.py PUSHABLE_FUNCS / PUSHABLE_AGGS) — the
  planner gates pushdown on `can_push_*`, and this re-checks the OUTPUT
  of the rewrite rather than its input;
* the device-join reader's payload dtypes line up with the build plan.

Hooked into `planner.optimizer.finish_plan` behind the session var
``tidb_check_plan`` (PhysicalContext.check_plan; on by default).  Also
runnable standalone over a canonical plan corpus: `python -m
tidb_tpu.lint --passes plan`.
"""

from __future__ import annotations

from typing import List, Optional

from ..errors import PlanError
from ..types import FieldType, TypeKind
from . import Finding


class PlanCheckError(PlanError):
    """A physical plan failed schema/dtype verification."""


# Kind pairs that legitimately alias through planner re-maps (codes and
# scaled ints share a wire representation; NULLTYPE is untyped).
_COMPAT = {
    frozenset((TypeKind.INT, TypeKind.UINT)),
    frozenset((TypeKind.INT, TypeKind.BOOL)),
    frozenset((TypeKind.INT, TypeKind.ENUM)),
    frozenset((TypeKind.INT, TypeKind.SET)),
    frozenset((TypeKind.INT, TypeKind.BIT)),
    frozenset((TypeKind.INT, TypeKind.TIME)),
    frozenset((TypeKind.DATE, TypeKind.DATETIME)),
}


def _kinds_ok(a: Optional[FieldType], b: Optional[FieldType]) -> bool:
    if a is None or b is None:
        return True
    ka, kb = a.kind, b.kind
    if ka == kb or TypeKind.NULLTYPE in (ka, kb):
        return True
    return frozenset((ka, kb)) in _COMPAT


class _Checker:
    def __init__(self):
        self.problems: List[str] = []

    def fail(self, node, msg: str):
        self.problems.append(f"{type(node).__name__}_{getattr(node, 'id', '?')}: {msg}")

    # ------------------------------------------------------------------
    # expression-level checks against an input ftype vector
    # ------------------------------------------------------------------
    def check_expr(self, node, e, input_fts: List[FieldType], where: str,
                   registry: bool = False):
        from ..expr.expression import ColumnExpr, Constant, ScalarFunc

        if isinstance(e, ColumnExpr):
            if not (0 <= e.index < len(input_fts)):
                self.fail(node, f"{where}: column ref #{e.index} out of "
                                f"range for input width {len(input_fts)}")
            elif not _kinds_ok(e.ftype, input_fts[e.index]):
                self.fail(node, f"{where}: column ref #{e.index} typed "
                                f"{e.ftype.kind.name} but input column is "
                                f"{input_fts[e.index].kind.name}")
            return
        if isinstance(e, Constant):
            return
        if isinstance(e, ScalarFunc):
            if registry:
                from ..expr.pushdown import PUSHABLE_FUNCS, dict_pred_source

                if dict_pred_source(e) is not None:
                    # computed dict-column predicate (LIKE / SUBSTR /
                    # LENGTH comparisons, ISSUE 12): lowers to a
                    # code-set membership test at analysis time — the
                    # function head is registry-exempt, but the column
                    # refs below still verify
                    for a in e.args:
                        self.check_expr(node, a, input_fts, where,
                                        registry=False)
                    return
                if e.name not in PUSHABLE_FUNCS:
                    self.fail(node, f"{where}: function {e.name!r} is in a "
                                    "cop DAG but not in the TPU-executable "
                                    "registry (PUSHABLE_FUNCS)")
            for a in e.args:
                self.check_expr(node, a, input_fts, where, registry)

    # ------------------------------------------------------------------
    # cop DAG: simulate width/dtype propagation executor by executor
    # ------------------------------------------------------------------
    def check_dag(self, node, dag, table):
        from ..copr.ir import (AggregationIR, JoinLookupIR, JoinProbeIR,
                               LimitIR, ProjectionIR, SelectionIR,
                               TableScanIR, TopNIR)
        from ..expr.pushdown import PUSHABLE_AGGS

        scan = dag.executors[0]
        if not isinstance(scan, TableScanIR):
            self.fail(node, "cop DAG does not start with a TableScan")
            return None
        store_cols = table.storage_columns()
        if len(scan.columns) != len(scan.ftypes):
            self.fail(node, "TableScan columns/ftypes length mismatch")
            return None
        for out_i, store_ci in enumerate(scan.columns):
            if not (0 <= store_ci < len(store_cols)):
                self.fail(node, f"TableScan store offset {store_ci} out of "
                                f"range ({len(store_cols)} storage columns)")
            elif not _kinds_ok(scan.ftypes[out_i], store_cols[store_ci][1]):
                self.fail(
                    node,
                    f"TableScan output #{out_i} typed "
                    f"{scan.ftypes[out_i].kind.name} but storage column "
                    f"{store_cols[store_ci][0]!r} is "
                    f"{store_cols[store_ci][1].kind.name}")
        fts = list(scan.ftypes)
        for ex in dag.executors[1:]:
            if isinstance(ex, SelectionIR):
                for c in ex.conditions:
                    self.check_expr(node, c, fts, "cop Selection",
                                    registry=True)
            elif isinstance(ex, JoinProbeIR):
                self.check_expr(node, ex.key, fts, "cop JoinProbe key",
                                registry=True)
            elif isinstance(ex, JoinLookupIR):
                self.check_expr(node, ex.key, fts, "cop JoinLookup key",
                                registry=True)
                fts = fts + list(ex.payload_ftypes)
            elif isinstance(ex, ProjectionIR):
                for e in ex.exprs:
                    self.check_expr(node, e, fts, "cop Projection",
                                    registry=True)
                fts = [e.ftype for e in ex.exprs]
            elif isinstance(ex, AggregationIR):
                from ..expr.pushdown import (_computed_dict_tree_columns,
                                             dict_computable_columns)

                out = []
                for g in ex.group_by:
                    # computed STRING (or INT-valued, ISSUE 12) keys
                    # built from dictionary-computable functions over
                    # ONE string column lower via device dict-code
                    # re-mapping: registry-exempt (same shared walker as
                    # the planner gate), but column refs/widths verify
                    cols = dict_computable_columns(g)
                    if cols is None:
                        cols = _computed_dict_tree_columns(g)
                    remap_ok = (cols is not None
                                and len({c.index for c in cols}) == 1)
                    self.check_expr(node, g, fts, "cop Agg group key",
                                    registry=not remap_ok)
                    out.append(g.ftype)
                for a in ex.aggs:
                    if a.name not in PUSHABLE_AGGS:
                        self.fail(node, f"cop Agg: {a.name!r} not in the "
                                        "TPU-executable registry "
                                        "(PUSHABLE_AGGS)")
                    for x in a.args:
                        self.check_expr(node, x, fts, f"cop Agg {a.name}",
                                        registry=True)
                    if ex.mode == "partial":
                        out.extend(a.partial_types())
                    else:
                        out.append(a.ftype)
                fts = out
            elif isinstance(ex, (TopNIR,)):
                for e, _desc in ex.order_by:
                    self.check_expr(node, e, fts, "cop TopN key",
                                    registry=True)
            elif isinstance(ex, LimitIR):
                pass
        return fts

    # ------------------------------------------------------------------
    # physical-tree walk
    # ------------------------------------------------------------------
    def check(self, p):
        name = type(p).__name__
        handler = getattr(self, f"_chk_{name}", None)
        for c in getattr(p, "children", ()):
            self.check(c)
        if handler is not None:
            handler(p)

    def _child_fts(self, p, i=0) -> List[FieldType]:
        return p.children[i].schema.ftypes()

    def _chk_PhysTableReader(self, p):
        out = self.check_dag(p, p.dag, p.cop.table)
        if out is not None and len(out) != len(p.schema):
            self.fail(p, f"reader schema width {len(p.schema)} != cop DAG "
                         f"output width {len(out)}")
        elif out is not None:
            for i, (ft, sc) in enumerate(zip(out, p.schema.cols)):
                if not _kinds_ok(ft, sc.ftype):
                    self.fail(p, f"reader schema col #{i} "
                                 f"{sc.ftype.kind.name} != DAG output "
                                 f"{ft.kind.name}")

    def _chk_PhysDeviceJoinReader(self, p):
        from ..copr.ir import JoinLookupIR

        self.check(p.reader)
        lookups = {ex.filter_id: ex for ex in p.reader.dag.executors
                   if isinstance(ex, JoinLookupIR)}
        if not lookups:
            self.fail(p, "device join reader DAG carries no JoinLookupIR")
            return
        if sorted(lookups) != sorted(b.filter_id for b in p.builds):
            self.fail(p, f"JoinLookupIR filter ids {sorted(lookups)} != "
                         f"build sides {[b.filter_id for b in p.builds]}")
            return
        for b in p.builds:
            build_fts = b.plan.schema.ftypes()
            if not (0 <= b.key_pos < len(build_fts)):
                self.fail(p, f"build key_pos {b.key_pos} out of range "
                             f"for build schema width {len(build_fts)}")
            for pos in b.payload_pos:
                if not (0 <= pos < len(build_fts)):
                    self.fail(p, f"payload pos {pos} out of range for "
                                 f"build schema width {len(build_fts)}")
            lk = lookups[b.filter_id]
            if len(lk.payload_ftypes) != len(b.payload_pos):
                self.fail(p, f"JoinLookupIR ships "
                             f"{len(lk.payload_ftypes)} payload cols but "
                             f"the build plan provides "
                             f"{len(b.payload_pos)}")
                continue
            for j, pos in enumerate(b.payload_pos):
                if pos < len(build_fts) and not _kinds_ok(
                        lk.payload_ftypes[j], build_fts[pos]):
                    self.fail(p, f"payload col {j} typed "
                                 f"{lk.payload_ftypes[j].kind.name} but "
                                 f"build schema col is "
                                 f"{build_fts[pos].kind.name}")

    # the sender IS a table reader (schema == cop DAG output); receivers
    # are pass-through markers
    _chk_PhysExchangeSender = _chk_PhysTableReader

    def _chk_PhysExchangeReceiver(self, p):
        if len(p.schema) != len(self._child_fts(p)):
            self.fail(p, "exchange receiver must preserve sender schema")

    def _chk_PhysMPPJoin(self, p):
        lfts = self._child_fts(p, 0)
        rfts = self._child_fts(p, 1)
        probe = p.probe_sender
        build = p.build_sender
        if len(probe.key_pos) != len(build.key_pos) or not probe.key_pos:
            self.fail(p, f"join key count mismatch: {len(probe.key_pos)} "
                         f"probe vs {len(build.key_pos)} build")
            return
        for kp, kb in zip(probe.key_pos, build.key_pos):
            if not (0 <= kp < len(probe.schema)):
                self.fail(p, f"probe key pos {kp} out of range")
                continue
            if not (0 <= kb < len(build.schema)):
                self.fail(p, f"build key pos {kb} out of range")
                continue
            pkft = probe.schema.col(kp).ftype
            bkft = build.schema.col(kb).ftype
            if pkft.kind != bkft.kind or pkft.scale != bkft.scale:
                self.fail(p, f"join key domains differ: {pkft.kind.name}"
                             f"(s{pkft.scale}) vs {bkft.kind.name}"
                             f"(s{bkft.scale})")
        if p.aggs is not None:
            joined = list(probe.schema.ftypes()) + list(build.schema.ftypes())
            for i, g in enumerate(p.group_by or ()):
                self.check_expr(p, g, joined, f"mpp group key #{i}")
            width = sum(len(a.partial_types()) for a in p.aggs) \
                + len(p.group_by or ())
            if len(p.schema) != width:
                self.fail(p, f"partial-agg schema width {len(p.schema)} "
                             f"!= {width} group key + partial state cols")
            return
        if len(p.schema) != len(lfts) + len(rfts):
            self.fail(p, f"join schema width {len(p.schema)} != "
                         f"{len(lfts)} + {len(rfts)} child cols")
        for i, (ft, sc) in enumerate(zip(lfts + rfts, p.schema.cols)):
            if not _kinds_ok(ft, sc.ftype):
                self.fail(p, f"join schema col #{i} {sc.ftype.kind.name} "
                             f"!= child output {ft.kind.name}")

    def _chk_PhysMPPJoinTree(self, p):
        """The rung ladder (ISSUE 12): senders are table readers (their
        own check covers the cop DAGs); verify every rung's key slots /
        build positions resolve with matching int domains, slot sources
        are in range, and the output schema width matches rows-mode
        slots or the partial-agg layout."""
        slot_fts = []
        for side, sp in p.slot_src:
            if not (0 <= side < len(p.children)):
                self.fail(p, f"slot source side {side} out of range")
                return
            sch = p.children[side].schema
            if not (0 <= sp < len(sch)):
                self.fail(p, f"slot source pos {sp} out of range for "
                             f"side {side}")
                return
            slot_fts.append(sch.col(sp).ftype)
        for i, r in enumerate(p.rungs):
            side = p.children[r["side"]].schema
            if len(r["left_slots"]) != len(r["build_pos"]):
                self.fail(p, f"rung {i}: key count mismatch")
                continue
            for s, kp in zip(r["left_slots"], r["build_pos"]):
                if not (0 <= s < len(slot_fts)):
                    self.fail(p, f"rung {i}: left slot {s} out of range")
                    continue
                if not (0 <= kp < len(side)):
                    self.fail(p, f"rung {i}: build pos {kp} out of range")
                    continue
                lft, bft = slot_fts[s], side.col(kp).ftype
                if lft.kind != bft.kind or lft.scale != bft.scale:
                    self.fail(p, f"rung {i}: key domains differ: "
                                 f"{lft.kind.name}(s{lft.scale}) vs "
                                 f"{bft.kind.name}(s{bft.scale})")
        if p.aggs is not None:
            for i, g in enumerate(p.group_by or ()):
                from ..expr.pushdown import (_computed_dict_tree_columns,
                                             dict_computable_columns)

                cols = dict_computable_columns(g)
                if cols is None:
                    cols = _computed_dict_tree_columns(g)
                remap_ok = (cols is not None
                            and len({c.index for c in cols}) == 1)
                self.check_expr(p, g, slot_fts, f"tree group key #{i}",
                                registry=not remap_ok)
            width = sum(len(a.partial_types()) for a in p.aggs) \
                + len(p.group_by or ())
            if len(p.schema) != width:
                self.fail(p, f"partial-agg schema width {len(p.schema)} "
                             f"!= {width} group key + partial state cols")
            return
        if len(p.schema) != len(p.out_slots):
            self.fail(p, f"rows schema width {len(p.schema)} != "
                         f"{len(p.out_slots)} output slots")
            return
        for i, (slot, sc) in enumerate(zip(p.out_slots, p.schema.cols)):
            if not (0 <= slot < len(slot_fts)):
                self.fail(p, f"output slot {slot} out of range")
            elif not _kinds_ok(slot_fts[slot], sc.ftype):
                self.fail(p, f"rows schema col #{i} {sc.ftype.kind.name} "
                             f"!= slot {slot} {slot_fts[slot].kind.name}")

    def _chk_PhysProjection(self, p):
        fts = self._child_fts(p)
        if len(p.exprs) != len(p.schema):
            self.fail(p, f"projection emits {len(p.exprs)} exprs but "
                         f"schema has {len(p.schema)} columns")
        for i, e in enumerate(p.exprs):
            self.check_expr(p, e, fts, f"expr #{i}")
            if i < len(p.schema) and not _kinds_ok(e.ftype,
                                                   p.schema.col(i).ftype):
                self.fail(p, f"expr #{i} produces {e.ftype.kind.name} but "
                             f"schema col is "
                             f"{p.schema.col(i).ftype.kind.name}")

    def _chk_PhysSelection(self, p):
        fts = self._child_fts(p)
        if len(p.schema) != len(fts):
            self.fail(p, "selection must preserve child schema width")
        for c in p.conds:
            self.check_expr(p, c, fts, "condition")

    def _chk_PhysSort(self, p):
        fts = self._child_fts(p)
        if len(p.schema) != len(fts):
            self.fail(p, "sort must preserve child schema width")
        for e, _d in p.items:
            self.check_expr(p, e, fts, "sort key")

    def _chk_PhysTopN(self, p):
        fts = self._child_fts(p)
        if len(p.schema) != len(fts):
            self.fail(p, "topn must preserve child schema width")
        for e, _d in p.items:
            self.check_expr(p, e, fts, "topn key")

    def _chk_PhysLimit(self, p):
        if len(p.schema) != len(self._child_fts(p)):
            self.fail(p, "limit must preserve child schema width")

    def _agg_io(self, p):
        fts = self._child_fts(p)
        if p.partial_input:
            want = len(p.group_by) + sum(
                len(a.partial_types()) for a in p.aggs)
            if len(fts) != want:
                self.fail(p, f"final agg expects {want} partial-state "
                             f"columns from its child, got {len(fts)}")
        else:
            for g in p.group_by:
                self.check_expr(p, g, fts, "group key")
            for a in p.aggs:
                for x in a.args:
                    self.check_expr(p, x, fts, f"agg {a.name} arg")
        if len(p.schema) != len(p.group_by) + len(p.aggs):
            self.fail(p, f"agg schema width {len(p.schema)} != "
                         f"{len(p.group_by)} keys + {len(p.aggs)} aggs")

    _chk_PhysHashAgg = _agg_io
    _chk_PhysStreamAgg = _agg_io

    def _chk_PhysHashJoin(self, p):
        lf, rf = self._child_fts(p, 0), self._child_fts(p, 1)
        if len(p.left_keys) != len(p.right_keys):
            self.fail(p, "join key arity mismatch")
        for k in p.left_keys:
            self.check_expr(p, k, lf, "left key")
        for k in p.right_keys:
            self.check_expr(p, k, rf, "right key")
        for c in p.other_conds:
            self.check_expr(p, c, lf + rf, "other cond")

    def _chk_PhysMergeJoin(self, p):
        self._chk_PhysHashJoin(p)

    def _chk_PhysIndexJoin(self, p):
        fts = self._child_fts(p)
        for k in p.outer_keys:
            self.check_expr(p, k, fts, "outer key")
        ncols = len(p.table.columns)
        for off in list(p.index_offsets) + list(p.fetch_offsets):
            if not (0 <= off < ncols):
                self.fail(p, f"inner column offset {off} out of range for "
                             f"{p.table.name} ({ncols} columns)")

    def _chk_PhysUnion(self, p):
        w = len(p.schema)
        for i, c in enumerate(p.children):
            if len(c.schema) != w:
                self.fail(p, f"union child #{i} width {len(c.schema)} != "
                             f"union schema width {w}")

    # ------------------------------------------------------------------
    # DML plans: write-column maps (lint follow-up (b)).  INSERT's
    # col_offsets and UPDATE's assignment offsets re-map positions onto
    # the table's column layout exactly like the read-side re-maps this
    # pass exists for — an off-by-one writes the wrong column silently.
    # Value-kind coercion is legal in SQL (SET a = '5'), so only the
    # positional maps and expression references are verified.
    # ------------------------------------------------------------------
    def _full_row_fts(self, t) -> List[FieldType]:
        return [c.ftype for c in t.columns]

    def _chk_PhysInsert(self, p):
        plan = p.plan
        ncols = len(plan.table.columns)
        for off in plan.col_offsets:
            if not (0 <= off < ncols):
                self.fail(p, f"insert column offset {off} out of range "
                             f"for {plan.table.name} ({ncols} columns)")
        if len(set(plan.col_offsets)) != len(plan.col_offsets):
            self.fail(p, "insert column offsets repeat a target column")
        if plan.rows is not None:
            for i, r in enumerate(plan.rows):
                if len(r) != len(plan.col_offsets):
                    self.fail(p, f"insert row #{i} has {len(r)} values "
                                 f"for {len(plan.col_offsets)} columns")
                    break
        if p.children:
            w = len(p.children[0].schema)
            if w != len(plan.col_offsets):
                self.fail(p, f"INSERT..SELECT provides {w} columns for "
                             f"{len(plan.col_offsets)} targets")
        # on-dup exprs evaluate over [old row cols ++ VALUES() pseudo
        # cols] (build_insert / InsertExec._apply_on_dup contract)
        fts = self._full_row_fts(plan.table)
        dup_fts = fts + fts
        for off, e in plan.on_dup_update:
            if not (0 <= off < ncols):
                self.fail(p, f"ON DUPLICATE KEY UPDATE offset {off} out "
                             "of range")
            self.check_expr(p, e, dup_fts, "on-dup-update expr")

    def _chk_PhysUpdate(self, p):
        plan = p.plan
        ncols = len(plan.table.columns)
        fts = self._full_row_fts(plan.table)
        for off, e in plan.assignments:
            if not (0 <= off < ncols):
                self.fail(p, f"update assignment offset {off} out of "
                             f"range for {plan.table.name} "
                             f"({ncols} columns)")
            self.check_expr(p, e, fts, "update assignment")
        for c in plan.conditions:
            self.check_expr(p, c, fts, "update condition")

    def _chk_PhysDelete(self, p):
        fts = self._full_row_fts(p.plan.table)
        for c in p.plan.conditions:
            self.check_expr(p, c, fts, "delete condition")

    def _chk_PhysWindow(self, p):
        fts = self._child_fts(p)
        for _uid, f in p.funcs:
            for a in f.args:
                self.check_expr(p, a, fts, f"window {f.name} arg")
        for e in p.partition_by:
            self.check_expr(p, e, fts, "partition key")
        for e, _d in p.order_by:
            self.check_expr(p, e, fts, "order key")


def check_plan(phys) -> List[str]:
    """Verify one physical plan; returns a list of problem strings."""
    ck = _Checker()
    ck.check(phys)
    return ck.problems


def assert_plan(phys):
    """Plan-build-time hook (finish_plan): raise on any problem."""
    problems = check_plan(phys)
    if problems:
        raise PlanCheckError(
            "plan failed schema/dtype verification: "
            + "; ".join(problems))


# ---------------------------------------------------------------------------
# standalone corpus check for `python -m tidb_tpu.lint --passes plan`
# ---------------------------------------------------------------------------

_CANONICAL_QUERIES = [
    # Q1 shape: dense-key partial agg pushdown
    "select l_returnflag, l_linestatus, sum(l_quantity), avg(l_discount),"
    " count(*) from lineitem where l_shipdate <= '1998-09-02'"
    " group by l_returnflag, l_linestatus order by l_returnflag",
    # Q6 shape: scalar agg over selection
    "select sum(l_extendedprice * l_discount) from lineitem"
    " where l_discount between 0.05 and 0.07 and l_quantity < 24",
    # projection + topn pushdown
    "select l_orderkey, l_extendedprice * (1 - l_discount) from lineitem"
    " order by l_extendedprice desc limit 5",
    # join shapes: hash/index/device-join candidates
    "select o_orderpriority, count(*) from orders join lineitem"
    " on l_orderkey = o_orderkey where o_totalprice > 1000"
    " group by o_orderpriority",
    "select count(*) from lineitem, orders where l_orderkey = o_orderkey",
    # window + union + subquery
    "select l_orderkey, rank() over (partition by l_returnflag"
    " order by l_quantity) from lineitem limit 7",
    "select l_orderkey from lineitem union all select o_orderkey from orders",
    "select o_orderkey from orders where o_totalprice >"
    " (select avg(o_totalprice) from orders)",
    # DML shapes: write-column maps (INSERT targets, INSERT..SELECT
    # arity, UPDATE assignment offsets) — lint follow-up (b)
    "insert into lineitem (l_orderkey, l_quantity) values (1, 2.0)",
    "insert into orders select l_orderkey, l_extendedprice, 'P0'"
    " from lineitem where l_quantity < 2",
    "update lineitem set l_quantity = l_quantity + 1 where l_orderkey = 3",
    "delete from orders where o_totalprice < 0",
]


_CORPUS_SESSION = None


def _canonical_session():
    """Memoized: one bootstrap (640-row insert + compact + analyze)
    serves both plancheck (plans only) and kernelcheck (also executes
    the corpus — harmless to planning) in a full lint run."""
    global _CORPUS_SESSION
    if _CORPUS_SESSION is not None:
        return _CORPUS_SESSION
    from ..session import Domain

    dom = Domain()
    s = dom.new_session()
    s.execute("create table lineitem (l_orderkey bigint, l_quantity double,"
              " l_extendedprice double, l_discount double, l_tax double,"
              " l_returnflag varchar(1), l_linestatus varchar(1),"
              " l_shipdate date)")
    s.execute("create table orders (o_orderkey bigint primary key,"
              " o_totalprice double, o_orderpriority varchar(15))")
    import numpy as np

    rng = np.random.default_rng(7)
    n = 512
    rows = ", ".join(
        f"({int(k)}, {q:.1f}, {ep:.2f}, {di:.2f}, 0.04, "
        f"'{'ANR'[k % 3]}', '{'OF'[k % 2]}', '199{k % 8}-0{1 + k % 9}-15')"
        for k, q, ep, di in zip(
            rng.integers(1, 128, n), rng.uniform(1, 50, n),
            rng.uniform(10, 1000, n), rng.uniform(0.01, 0.09, n)))
    s.execute("insert into lineitem values " + rows)
    orows = ", ".join(f"({k}, {1000 + 10 * k}.5, 'P{k % 5}')"
                      for k in range(1, 129))
    s.execute("insert into orders values " + orows)
    for t in ("lineitem", "orders"):
        tid = dom.catalog.info_schema().table("test", t).id
        dom.storage.maybe_compact(tid, threshold=0)
    s.execute("analyze table lineitem")
    s.execute("analyze table orders")
    _CORPUS_SESSION = s
    return s


def lint_canonical_plans() -> List[Finding]:
    """Plan every canonical query and typecheck the result; each failure
    is one finding keyed on the query ordinal (stable)."""
    from ..parser import parse_one

    findings: List[Finding] = []
    s = _canonical_session()
    for qi, sql in enumerate(_CANONICAL_QUERIES):
        try:
            phys = s._plan(parse_one(sql))
            problems = check_plan(phys)
        except Exception as e:  # noqa: BLE001 — each query isolated
            problems = [f"planning raised {type(e).__name__}: {e}"]
        for msg in problems:
            findings.append(Finding(
                rule="plan-schema", path="tidb_tpu/planner",
                line=0, scope=f"canonical-q{qi}", token="plan",
                message=f"{msg} (query: {sql[:60]}...)"))
    return findings
