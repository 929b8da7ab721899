"""Reader executors: the bridge from root execution to the pushdown boundary.

Reference: executor/table_reader.go:93-155 (TableReader builds kv.Request from
ranges+DAG and consumes SelectResult), executor/point_get.go:87 (PointGet
bypasses distsql entirely), executor/union_scan.go + mem_reader.go (merging
the txn's uncommitted buffer over snapshot reads).
"""

from __future__ import annotations

from typing import List, Optional

import numpy as np

from ..catalog import TableInfo
from ..chunk import Chunk, Column
from ..copr.ir import DAG
from ..distsql import SelectResult, select_dag
from ..expr.expression import Expression, eval_bool_mask
from ..store.kv import KeyRange
from ..store.regions import INF
from .base import ExecContext, Executor


class TableReaderExec(Executor):
    """Fan a DAG out over the table's regions; stream result chunks."""

    def __init__(self, ctx: ExecContext, dag: DAG, ranges: List[KeyRange],
                 ftypes, keep_order: bool = False, plan_id: int = -1):
        super().__init__(ctx, ftypes, [], plan_id)
        self.dag = dag
        self.ranges = ranges
        self.keep_order = keep_order
        self._result: Optional[SelectResult] = None
        self._aux: Optional[dict] = None

    def set_runtime_aux(self, aux: dict):
        """Attach runtime payloads (e.g. join-probe key sets) before open;
        the hash join calls this between its build and probe phases."""
        self._aux = dict(aux) if self._aux is None else {**self._aux, **aux}

    def _open(self):
        engine = self.ctx.engine
        self._cost_routed = False
        if engine == "tpu":
            engine = self._route(engine)
        from ..distsql.backoff import DEFAULT_BUDGET_MS

        budget = (self.ctx.vars.get_int("tidb_backoff_budget_ms",
                                        DEFAULT_BUDGET_MS)
                  if self.ctx.vars else DEFAULT_BUDGET_MS)
        self._result = select_dag(
            self.ctx.storage, self.dag, self.ranges, self.ctx.snapshot_ts(),
            concurrency=self.ctx.distsql_concurrency,
            keep_order=self.keep_order, engine=engine,
            aux=self._aux, backoff_budget_ms=budget,
        )

    def _route(self, engine: str) -> str:
        """First cost model for TPU-vs-host routing: a device scan pays a
        fixed dispatch+readback latency, the
        host pays per-row; route small scans to the host (the reference's
        per-operator cop-vs-root cost split, planner/core/task.go)."""
        v = self.ctx.vars
        if v is None:
            return engine
        dispatch_us = v.get_int("tidb_opt_device_dispatch_us")
        if dispatch_us <= 0:
            return engine
        rows = 0
        for kr in self.ranges:
            try:
                hi = min(kr.end, self.ctx.storage.table(kr.table_id).base_rows)
            except Exception:
                return engine
            rows += max(hi - kr.start, 0)
        host_us = rows / max(v.get_int("tidb_opt_host_rows_per_us"), 1)
        dev_us = dispatch_us + rows / max(
            v.get_int("tidb_opt_device_rows_per_us"), 1)
        dev_us *= self._layout_cost_factor()
        if host_us < dev_us:
            self._cost_routed = True
            from ..metrics import REGISTRY

            REGISTRY.inc("cost_routed_host_total")
            return "cpu"
        return engine

    # cold-resident columns decode in-register inside the fused kernel —
    # cheap, but not free: a few extra VPU ops per row per cold column.
    # The routing cost model scales device time by this per-column factor
    # so a fully-cold scan prices honestly against the host path.
    COLD_DECODE_FACTOR = 0.15

    def _layout_cost_factor(self) -> float:
        """1 + COLD_DECODE_FACTOR * (cold fraction of scanned columns):
        the layout-aware scan-cost adjustment (tidb_tpu/layout)."""
        try:
            from ..layout import LAYOUT, layout_enabled

            if not layout_enabled():
                return 1.0
            scan = self.dag.scan
            table = self.ctx.storage.table(scan.table_id)
            cols = list(scan.columns) or [0]
            cold = sum(
                1 for ci in cols
                if LAYOUT.plan_for(table, ci).tier == "cold")
            return 1.0 + self.COLD_DECODE_FACTOR * cold / len(cols)
        except Exception:
            return 1.0  # cost advice must never fail a scan

    def _next(self) -> Optional[Chunk]:
        chunk = self._result.next_chunk()
        if chunk is None:
            self._exhausted = True
        else:
            self._out_rows += chunk.num_rows
        return chunk

    _exhausted = False
    _out_rows = 0

    def _record_feedback(self):
        """Feed the observed whole-scan selectivity back into the stats
        (statistics/feedback.go role).  Only for fully-drained plain
        scan[+selection] DAGs over the whole table — partial drains
        (LIMIT/kill) and aggregated outputs would poison the signal."""
        from ..copr.ir import SelectionIR

        if not self._exhausted:
            return
        if getattr(self.ctx, "historical", False):
            return  # tidb_snapshot reads observe the PAST, not the present
        execs = self.dag.executors
        conds = []
        for ex in execs[1:]:
            if not isinstance(ex, SelectionIR):
                return  # agg/topn/limit/lookup outputs aren't row counts
            conds.extend(ex.conditions)
        if not conds:
            return
        stats = getattr(self.ctx, "domain", None)
        stats = stats.stats if stats is not None else None
        if stats is None:
            return
        tid = self.dag.scan.table_id
        if any(kr.table_id != tid or kr.start > 0 for kr in self.ranges):
            return  # partitioned / clipped scan: rows aren't the table's
        try:
            store = self.ctx.storage.table(tid)
        except Exception:
            return
        # denominator = rows VISIBLE AT THE SCAN'S SNAPSHOT, not the
        # current store size: a historical read (tidb_snapshot / old txn)
        # over a since-mutated table must not learn a wrongly-scaled
        # selectivity that poisons future plans
        ts = self.ctx.snapshot_ts()
        deleted, inserted = store.delta_overlay(ts, 0, 1 << 62)
        visible_base = store.base_rows if store.base_ts <= ts else 0
        total = visible_base - len(deleted) + len(inserted)
        if total <= 0:
            return
        # digest over STORE offsets (same key the planner computes)
        scan = self.dag.scan
        pos_to_store = {i: ci for i, ci in enumerate(scan.columns)}
        from ..copr.ir import deserialize_expr, serialize_expr

        # strip planner uids first (remap keys on uid when present; these
        # in-memory IR exprs still carry them) so the scan-position ->
        # store-offset remap actually applies
        remapped = [
            deserialize_expr(serialize_expr(c)).remap_columns(pos_to_store)
            for c in conds
        ]
        stats.record_feedback(tid, remapped, self._out_rows / total)

    def _close(self):
        try:
            self._record_feedback()
        except Exception:
            pass  # advisory: never fail a query on stats upkeep
        if self._result is not None:
            if self.plan_id >= 0:
                r = self._result
                eng = r.scan_engine
                if eng == "tile-fanout" and r.fallback_tasks:
                    eng += f" ({r.fallback_tasks}/{r.total_tasks} cpu-retry)"
                reason = getattr(r.req, "mesh_reject_reason", None)
                if reason and eng != "mesh":
                    eng += f" [mesh rejected: {reason}]"
                if getattr(self, "_cost_routed", False):
                    eng += " (cost-routed)"
                self.ctx.op_stats(self.plan_id).engine = eng
            self._result.close()
            self._result = None


class PointGetExec(Executor):
    """Single-handle read, no distsql, no plan search (point_get.go:87)."""

    def __init__(self, ctx: ExecContext, table: TableInfo, handle: int,
                 col_offsets: List[int], plan_id: int = -1):
        ftypes = [table.columns[o].ftype for o in col_offsets]
        super().__init__(ctx, ftypes, [], plan_id)
        self.table = table
        self.handle = handle
        self.col_offsets = col_offsets
        self._done = False

    def _open(self):
        self._done = False

    def _next(self) -> Optional[Chunk]:
        if self._done:
            return None
        self._done = True
        txn = self.ctx.txn
        if txn is not None:
            row = txn.get(self.table.id, self.handle)
        else:
            store = self.ctx.storage.table(self.table.id)
            row = store.read_row(self.handle, self.ctx.snapshot_ts())
        if row is None:
            return self.empty_chunk()
        vals = [row[o] for o in self.col_offsets]
        return Chunk([
            Column.from_values(ft, [v])
            for ft, v in zip(self.ftypes, vals)
        ])


class UnionScanExec(Executor):
    """Scan that sees the session txn's uncommitted writes.

    Used instead of TableReaderExec when the current txn has dirty rows for
    the table (executor/union_scan.go).  Reads base+committed delta through
    the store, overlays the txn buffer, emits (handle?, cols...) chunks and
    applies residual conditions host-side.  Pushdown is disabled on dirty
    tables by the planner, so the DAG here is scan-only semantics.
    """

    def __init__(self, ctx: ExecContext, table: TableInfo,
                 col_offsets: List[int], conditions: List[Expression],
                 with_handle: bool = False, ranges: Optional[List[KeyRange]] = None,
                 plan_id: int = -1):
        from ..types import ty_int

        ftypes = [table.columns[o].ftype for o in col_offsets]
        if with_handle:
            ftypes = [ty_int(False)] + ftypes
        super().__init__(ctx, ftypes, [], plan_id)
        self.table = table
        self.col_offsets = col_offsets
        self.conditions = conditions
        self.with_handle = with_handle
        self.ranges = ranges or [KeyRange(table.id, 0, INF)]
        self._batches: Optional[List[Chunk]] = None
        self._pos = 0

    def _open(self):
        self._batches = None
        self._pos = 0

    def _build(self) -> List[Chunk]:
        store = self.ctx.storage.table(self.table.id)
        ts = self.ctx.snapshot_ts()
        txn = self.ctx.txn
        out: List[Chunk] = []
        buffer = {}
        if txn is not None:
            for (tid, h), m in txn.buffer.items():
                if tid == self.table.id:
                    buffer[h] = m
        for kr in self.ranges:
            start, end = kr.start, min(kr.end, INF)
            deleted, inserted = store.delta_overlay(ts, start, end)
            dele = set(deleted)
            # base rows in chunks
            base_end = min(end, store.base_rows)
            CH = 1 << 16
            for t0 in range(start, max(base_end, start), CH):
                t1 = min(t0 + CH, base_end)
                if t0 >= t1:
                    break
                chunk = store.base_chunk(self.col_offsets, t0, t1)
                handles = np.arange(t0, t1, dtype=np.int64)
                keep = np.ones(t1 - t0, dtype=np.bool_)
                for h in dele:
                    if t0 <= h < t1:
                        keep[h - t0] = False
                for h in buffer:
                    if t0 <= h < t1:
                        keep[h - t0] = False  # overridden by txn buffer
                chunk, handles = chunk.filter(keep), handles[keep]
                out.append(self._finish_chunk(chunk, handles))
            # committed-delta inserts + txn buffer rows, as one tail chunk
            rows, handles = [], []
            for h in sorted(set(inserted) | set(buffer)):
                if not (start <= h < end):
                    continue
                if h in buffer:
                    m = buffer[h]
                    if m.op == "put":
                        rows.append(tuple(m.values[o] for o in self.col_offsets))
                        handles.append(h)
                elif h in inserted:
                    # covers both new handles (>= base_rows) and committed
                    # updates of base handles: the base loop removed the old
                    # version via `dele`, the new version is emitted here
                    rows.append(tuple(inserted[h][o] for o in self.col_offsets))
                    handles.append(h)
            if rows:
                cols = []
                base_fts = self.ftypes[1:] if self.with_handle else self.ftypes
                for i, ft in enumerate(base_fts):
                    cols.append(Column.from_values(ft, [r[i] for r in rows]))
                out.append(self._finish_chunk(
                    Chunk(cols), np.asarray(handles, dtype=np.int64)
                ))
        return [c for c in out if c.num_rows]

    def _finish_chunk(self, chunk: Chunk, handles: np.ndarray) -> Chunk:
        if self.conditions:
            mask = eval_bool_mask(self.conditions, chunk)
            chunk, handles = chunk.filter(mask), handles[mask]
        if self.with_handle:
            from ..types import ty_int

            return Chunk([Column(ty_int(False), handles)] + chunk.columns)
        return chunk

    def _next(self) -> Optional[Chunk]:
        if self._batches is None:
            self._batches = self._build()
        if self._pos >= len(self._batches):
            return None
        c = self._batches[self._pos]
        self._pos += 1
        return c


class DeviceJoinReaderExec(Executor):
    """Broadcast lookup join completed inside the cop task: drain the
    (small, unique-key) build side, ship its sorted keys + payload columns
    to the probe reader's device DAG (JoinLookupIR), then stream the
    reader's joined/aggregated chunks.

    The role of the reference's HashJoinExec build phase + probe worker
    pool (executor/join.go:232-414), but the probe+join+partial-agg all
    execute in the device shard program; only aggregated partials return.
    Build-key uniqueness is guaranteed at plan time
    (planner/physical.py _build_key_unique)."""

    def __init__(self, ctx: ExecContext, reader: Executor, build: Executor,
                 build_key_pos: int, payload_pos: List[int],
                 filter_id: int = 0, plan_id: int = -1):
        super().__init__(ctx, reader.ftypes, [build, reader], plan_id)
        self.reader = reader
        self.build = build
        self.build_key_pos = build_key_pos
        self.payload_pos = payload_pos
        self.filter_id = filter_id

    def open(self):
        from ..copr.ir import key_bits_int64
        from ..chunk import concat_chunks
        from ..errors import ExecutorError

        self.build.open()
        chunks = []
        while True:
            c = self.build.next()
            if c is None:
                break
            if c.num_rows:
                chunks.append(c)
        self.build.close()
        from ..trace import span

        # the build side's host work between the rows it drained and the
        # probe program: sort here, upload in the mesh dispatch
        # (`join.build` phase="upload", copr/parallel.py)
        with span("join.build", phase="sort") as sp:
            if chunks:
                built = concat_chunks(chunks)
                kcol = built.col(self.build_key_pos)
                valid = kcol.validity()
                if not valid.all():
                    built = built.filter(valid)  # NULL keys never match
                    kcol = built.col(self.build_key_pos)
                bits = key_bits_int64(kcol.data)
                order = np.argsort(bits, kind="stable")
                keys = bits[order]
                if len(keys) > 1 and (keys[1:] == keys[:-1]).any():
                    raise ExecutorError(
                        "device join: build keys not unique (planner "
                        "uniqueness inference violated)")
                payload, pvalid = [], []
                for pos in self.payload_pos:
                    col = built.col(pos)
                    payload.append(col.data[order])
                    v = col.validity()
                    pvalid.append(None if v.all() else v[order])
            else:
                keys = np.zeros(0, dtype=np.int64)
                payload = [np.zeros(0, dtype=np.int64)
                           for _ in self.payload_pos]
                pvalid = [None for _ in self.payload_pos]
            sp.set(rows=len(keys),
                   bytes=keys.nbytes + sum(p.nbytes for p in payload))
        fid = self.filter_id
        self.reader.set_runtime_aux({
            f"probe_keys_{fid}": np.ascontiguousarray(keys, dtype=np.int64),
            f"payload_{fid}": payload,
            f"payload_valid_{fid}": pvalid,
        })
        self.reader.open()
        self._opened = True

    def _next(self):
        return self.reader.next()

    def close(self):
        try:
            self.build.close()  # no-op when already closed after the drain
        except Exception:
            pass
        self.reader.close()
        self._opened = False
