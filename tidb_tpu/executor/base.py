"""Root executor framework: Volcano-with-chunks.

Reference: executor/executor.go:177-212 — `Executor` iface Open/Next(chunk)/
Close plus the Next wrapper that checks the kill flag, records per-operator
runtime stats (rows/loops/duration) for EXPLAIN ANALYZE, and traces.
"""

from __future__ import annotations

import time
from typing import Dict, List, Optional

from ..chunk import Chunk, DEFAULT_CHUNK_SIZE
from ..errors import QueryKilledError
from ..types import FieldType

# per-operator runtime stats live in the trace subsystem now — EXPLAIN
# ANALYZE, TRACE, the slow log and the statement summary all read the
# same QueryTrace, so there is ONE execution-stats collection path
# (re-exported here for executor-facing callers)
from ..trace import OperatorStats  # noqa: F401


class ExecContext:
    """Per-statement execution context (stmtctx.StatementContext analog).

    Carries the storage handle, the session's txn (or read-ts for autocommit
    reads), tuning vars, the kill flag and the runtime-stats collector.
    """

    def __init__(self, storage, infoschema=None, sess_vars=None, txn=None,
                 read_ts: int = 0):
        self.storage = storage
        self.infoschema = infoschema
        self.vars = sess_vars
        self.txn = txn
        self.read_ts = read_ts
        self.killed = False
        # the statement's lifecycle scope (deadline + cancel event),
        # captured from the contextvar plane the session activated —
        # check_killed() honors it between chunks, and fan-out layers
        # carry it onto worker threads
        from ..lifecycle import current_scope

        self.scope = current_scope()
        self.warnings: List[str] = []
        # when a trace is active, the operator-stats map IS the trace's
        # (EXPLAIN ANALYZE and the span tree share one store)
        from ..trace import current_trace

        tr = current_trace()
        self.stats: Dict[int, OperatorStats] = (
            tr.op_stats if tr is not None else {})
        self.affected_rows = 0
        self.last_insert_id = 0
        self.found_rows = 0
        from ..util_memory import MemTracker

        quota = sess_vars.get_int("tidb_mem_quota_query") if sess_vars else 0
        action = "cancel"
        if sess_vars and sess_vars.get("tidb_oom_action"):
            action = sess_vars.get("tidb_oom_action")
        self.mem_tracker = MemTracker("query", quota, action=action)

    # tuning knobs with reference defaults (sessionctx/variable/tidb_vars.go)
    @property
    def chunk_size(self) -> int:
        return self.vars.get_int("tidb_max_chunk_size") if self.vars else DEFAULT_CHUNK_SIZE

    @property
    def distsql_concurrency(self) -> int:
        return self.vars.get_int("tidb_distsql_scan_concurrency") if self.vars else 8

    def _conc(self, name: str, default: int) -> int:
        """Concurrency knob with tidb_executor_concurrency as the umbrella
        default (tidb_vars.go semantics: per-op vars register as -1 =
        ConcurrencyUnset, so the umbrella applies until a per-op override)."""
        if not self.vars:
            return default
        v = self.vars.get_int(name)
        if v <= 0:
            v = self.vars.get_int("tidb_executor_concurrency")
        return max(1, v)

    @property
    def hash_join_concurrency(self) -> int:
        return self._conc("tidb_hash_join_concurrency", 5)

    @property
    def hashagg_partial_concurrency(self) -> int:
        return self._conc("tidb_hashagg_partial_concurrency", 4)

    @property
    def projection_concurrency(self) -> int:
        return self._conc("tidb_projection_concurrency", 4)

    @property
    def engine(self) -> str:
        if self.vars and not self.vars.get_bool("tidb_use_tpu"):
            return "cpu"
        return "tpu"

    def check_killed(self):
        # scope first: it raises the TYPED termination error (timeout/
        # shutdown subclasses) where the legacy flag can only say killed
        self.scope.check()
        if self.killed:
            raise QueryKilledError()

    def op_stats(self, plan_id: int) -> OperatorStats:
        st = self.stats.get(plan_id)
        if st is None:
            st = self.stats[plan_id] = OperatorStats()
        return st

    # current-read statements (DML, SELECT FOR UPDATE) read at the txn's
    # pessimistic lock horizon when it advanced past start_ts — the
    # for_update_ts current-read rule (executor/adapter.go pessimistic
    # statement retry semantics); plain SELECTs keep the snapshot.
    current_read = False

    def snapshot_ts(self) -> int:
        if self.txn is not None:
            if self.current_read:
                return max(self.txn.start_ts,
                           getattr(self.txn, "for_update_ts",
                                   self.txn.start_ts))
            return self.txn.start_ts
        return self.read_ts


class Executor:
    """Base executor.  Subclasses implement _open/_next/_close; next() wraps
    with kill-check + stats (executor.go:196-212)."""

    def __init__(self, ctx: ExecContext, ftypes: List[FieldType],
                 children: Optional[List["Executor"]] = None, plan_id: int = -1):
        self.ctx = ctx
        self.ftypes = ftypes
        self.children = children or []
        self.plan_id = plan_id
        self._opened = False

    # ---- public API ----------------------------------------------------
    def open(self):
        for c in self.children:
            c.open()
        self._open()
        self._opened = True

    def next(self) -> Optional[Chunk]:
        """Return the next chunk, or None when exhausted."""
        self.ctx.check_killed()
        t0 = time.perf_counter_ns()
        chunk = self._next()
        dur = time.perf_counter_ns() - t0
        if self.plan_id >= 0:
            self.ctx.op_stats(self.plan_id).record(
                chunk.num_rows if chunk is not None else 0, dur
            )
        return chunk

    def close(self):
        self._close()
        for c in self.children:
            c.close()
        self._opened = False

    # ---- subclass hooks ------------------------------------------------
    def _open(self):
        pass

    def _next(self) -> Optional[Chunk]:
        raise NotImplementedError

    def _close(self):
        pass

    # ---- helpers -------------------------------------------------------
    def child(self, i: int = 0) -> "Executor":
        return self.children[i]

    def drain_child(self, i: int = 0) -> List[Chunk]:
        """Pull the child to exhaustion (blocking materialization)."""
        out = []
        while True:
            c = self.children[i].next()
            if c is None:
                return out
            if c.num_rows:
                out.append(c)

    def empty_chunk(self) -> Chunk:
        return Chunk.empty(self.ftypes)


def operator_times(exe: Executor) -> list:
    """[[plan_id, operator, rows, loops, self_ms], ...] of an executor
    tree, root first, from the OperatorStats its `next()` calls kept:
    self_ms is the operator's time in `next()` less its children's, so a
    reader's is its wait for the cop result and a root operator's is its
    own work.  Executors without a plan id (internal helpers) are left
    out and their time stays with their parent."""
    out: list = []

    def walk(e: Executor) -> int:
        """Time to take off the parent: this operator's, or (no plan id,
        so no stats of its own) its children's."""
        st = e.ctx.stats.get(e.plan_id) if e.plan_id >= 0 else None
        if st is not None:
            at = len(out)
            out.append(None)
        below = sum(walk(c) for c in e.children)
        if st is None:
            return below
        out[at] = [e.plan_id, type(e).__name__, st.rows, st.loops,
                   max(st.time_ns - below, 0) / 1e6]
        return st.time_ns

    walk(exe)
    return out


def collect_all(exe: Executor) -> List[Chunk]:
    """Open/drain/close an executor tree (statement driver helper).
    Root open/next/close are traced (executor.go:196-212's trace region,
    mapped onto the span recorder; no-ops when tracing is off); the
    `executor.next` span carries the drained tree's `operator_times` as
    `ops`."""
    from ..trace import NOOP, span

    with span("executor.open"):
        exe.open()
    try:
        out = []
        with span("executor.next") as sp:
            n = 0
            while True:
                c = exe.next()
                if c is None:
                    if sp is not NOOP:
                        sp.set(rows=n, ops=operator_times(exe))
                    return out
                if c.num_rows:
                    n += c.num_rows
                    out.append(c)
    finally:
        with span("executor.close"):
            exe.close()


class OrderedPipeline:
    """Order-preserving worker pipeline over a chunk stream.

    The TPU-first root executors are numpy-vectorized, and numpy releases
    the GIL inside kernels — a small thread pool genuinely overlaps chunk
    transforms.  This is the reference's projection/join worker-ring shape
    (projection.go:185-217, join.go:307-414): up to `workers` transforms in
    flight, results yielded in submission order so row order matches the
    serial executor exactly.
    """

    def __init__(self, workers: int, source, fn):
        import collections

        self.workers = max(1, workers)
        self.source = source  # () -> Optional[Chunk]
        self.fn = fn  # Chunk -> Optional[Chunk]
        self._pool = None  # spun up lazily: only multi-chunk streams pay
        self._pending = collections.deque()
        self._exhausted = False
        self._started = False

    def _pull(self):
        while True:
            c = self.source()
            if c is None:
                self._exhausted = True
                return None
            if c.num_rows:
                return c

    def _fill(self):
        while (not self._exhausted
               and len(self._pending) < self.workers * 2):
            c = self._pull()
            if c is None:
                return
            self._pending.append(self._pool.submit(self.fn, c))

    def _next_raw(self):
        if self.workers <= 1:
            c = self._pull()
            return None if c is None else self.fn(c)
        if not self._started:
            self._started = True
            a = self._pull()
            if a is None:
                return None
            b = self._pull()
            if b is None:
                # single-chunk stream (point lookups, small LIMITs): run
                # inline — no threads to spawn, nothing to overlap
                return self.fn(a)
            from concurrent.futures import ThreadPoolExecutor

            from ..metrics import REGISTRY

            self._pool = ThreadPoolExecutor(max_workers=self.workers)
            REGISTRY.inc("executor_parallel_workers_total", self.workers)
            self._pending.append(self._pool.submit(self.fn, a))
            self._pending.append(self._pool.submit(self.fn, b))
        if self._pool is None:
            return None
        self._fill()
        if not self._pending:
            return None
        return self._pending.popleft().result()

    def next(self):
        """Next transformed chunk in order; None at end of stream."""
        while True:
            out = self._next_raw()
            if out is None and self._exhausted and not self._pending:
                return None
            if out is not None and out.num_rows:
                return out

    def close(self):
        if self._pool is not None:
            for f in self._pending:
                f.cancel()
            self._pending.clear()
            self._pool.shutdown(wait=False)
            self._pool = None
