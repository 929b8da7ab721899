"""Domain: the per-process singleton owning storage, catalog and globals.

Reference: domain/domain.go:60 — Domain owns the infoschema cache, DDL,
stats handle, sysvar cache, background loops.  In-process here: the catalog
IS the schema authority (no lease/reload loop needed), globals are a dict.
"""

from __future__ import annotations

import logging
import threading
from typing import Dict, Optional

from ..catalog import Catalog
from ..statistics import StatsHandle
from ..store.storage import BlockStorage
from .vars import SessionVars


import re as _re
from ..util_concurrency import make_rlock

_NUM_RE = _re.compile(r"\b\d+(?:\.\d+)?\b")
_STR_RE = _re.compile(r"'(?:[^'\\]|\\.)*'|\"(?:[^\"\\]|\\.)*\"")
_WS_RE = _re.compile(r"\s+")
_OP_RE = _re.compile(r"\s*(<=|>=|<>|!=|=|<|>)\s*")
_IN_RE = _re.compile(r"in\s*\((?:\s*\?\s*,?)+\)")


def sql_digest(sql: str) -> str:
    """Normalized statement text: literals -> ?, IN lists collapsed,
    whitespace folded, lowercased (parser.Normalize + DigestHash role)."""
    s = _STR_RE.sub("?", sql)
    s = _NUM_RE.sub("?", s)
    s = _OP_RE.sub(r" \1 ", s)
    s = _WS_RE.sub(" ", s).strip().lower()
    s = _IN_RE.sub("in (...)", s)
    return s[:512]


class Domain:
    def __init__(self, storage: Optional[BlockStorage] = None,
                 data_dir: Optional[str] = None):
        if storage is not None and data_dir is not None:
            # an injected storage has no persisters attached — accepting
            # data_dir here would persist the catalog but silently lose
            # table data on restart
            raise ValueError(
                "pass data_dir to BlockStorage(...) when injecting storage"
            )
        self.data_dir = data_dir
        self.storage = storage or BlockStorage(data_dir=data_dir)
        self.catalog = Catalog(self.storage)
        self.stats = StatsHandle(self.storage)
        from .priv import PrivManager

        self.priv = PrivManager(data_dir)
        self.catalog.on_table_dropped = self.stats.drop
        # per-domain resource-control plane (ISSUE 17): named groups
        # with device-time token buckets; statements resolve their
        # group at scope-creation time (session.execute)
        from ..lifecycle import ResourceGroupRegistry

        self.resgroups = ResourceGroupRegistry()
        self.global_vars: Dict[str, str] = {}
        self._mu = make_rlock("session.domain:Domain._mu")
        # ring buffer of recent log records -> information_schema.
        # cluster_log (executor/cluster_reader.go memtable role); ONE
        # process-wide handler — re-pointed at the newest Domain's ring so
        # discarded domains don't accumulate handlers or leak deques
        import collections

        self.log_ring = collections.deque(maxlen=512)
        _attach_log_ring(self.log_ring)
        self._conn_counter = 0
        self.sessions: Dict[int, object] = {}  # conn_id -> Session (weak-ish)
        self.digest_summary = {}  # digest -> per-statement-shape aggregates
        # LOCK TABLES registry: (db, table) -> {"mode": read|write,
        # "owners": {conn_id}} — read locks shard across sessions, write
        # locks have one owner (reference: ddl/table_lock.go role)
        self.table_locks: Dict[tuple, dict] = {}
        # structured slow-query log (trace/slowlog.py): file-backed when
        # the domain persists, memory-ring otherwise; feeds
        # INFORMATION_SCHEMA.SLOW_QUERY with per-phase columns
        from ..trace import SlowQueryLog

        slow_path = None
        if data_dir:
            import os as _os

            _os.makedirs(data_dir, exist_ok=True)
            slow_path = _os.path.join(data_dir, "slow_query.log")
        self.slow_log = SlowQueryLog(
            slow_path, max_bytes=self._slow_log_max_bytes())
        # continuous profiler (ISSUE 13): every finished trace folds
        # into the rotating flame windows; chains onto the trace export
        # hook (never replacing a coord plane's forwarder), idempotent
        from ..trace import install_gc_spans, install_profiler

        install_profiler()
        # the collector's pauses as `py.gc` spans and counters, likewise
        # once a process
        install_gc_spans()
        if data_dir:
            self._recover(data_dir)
        self._bootstrap()
        from .maintenance import MaintenanceWorker

        self.maintenance = MaintenanceWorker(self)
        self.maintenance.start()

    def _recover(self, data_dir: str):
        """Reload catalog + table data persisted by a previous process
        (SURVEY.md §3.4: recovery = reload; no local checkpoints beyond
        the store itself)."""
        import os

        os.makedirs(data_dir, exist_ok=True)
        meta = os.path.join(data_dir, "catalog.json")
        if os.path.exists(meta):
            with open(meta) as f:
                self.catalog.load_json(f.read())
            self.storage.load_persisted()
            resume_jobs = True
        else:
            resume_jobs = False

        def persist(catalog):
            tmp = meta + ".tmp"
            with open(tmp, "w") as f:
                f.write(catalog.to_json())
            os.replace(tmp, meta)

        self.catalog.on_ddl = persist
        if resume_jobs:
            # finish DDL jobs a dead process left mid-ladder (owner resume,
            # ddl_worker.go:362): backfills continue from their checkpoint
            self.catalog.resume_pending_jobs()
        self._purge_orphan_files(data_dir)

    def _purge_orphan_files(self, data_dir: str):
        """Remove table files no catalog entry references: the recycle
        bin (RECOVER TABLE flashback) is process-lifetime, so a restart
        within the GC window would otherwise leak dropped tables' files
        on disk forever."""
        import os
        import re

        tdir = os.path.join(data_dir, "tables")
        if not os.path.isdir(tdir):
            return
        live: set = set()
        isc = self.catalog.info_schema()
        for db in isc.schema_names():
            for t in isc.tables(db):
                live.update(t.physical_ids())
        for fn in os.listdir(tdir):
            m = re.match(r"t(\d+)\.(base\.npz|delta\.log)$", fn)
            if m and int(m.group(1)) not in live:
                try:
                    os.remove(os.path.join(tdir, fn))
                except OSError:
                    pass

    def _bootstrap(self):
        """Create system schemas (session/bootstrap.go analog)."""
        for db in ("test", "mysql", "information_schema"):
            if not self.catalog.info_schema().has_schema(db):
                self.catalog.create_database(db, if_not_exists=True)

    def new_session(self):
        from .session import Session

        with self._mu:
            self._conn_counter += 1
            s = Session(self, conn_id=self._conn_counter)
            self.sessions[self._conn_counter] = s
            return s

    def kill(self, conn_id: int, query_only: bool = True):
        s = self.sessions.get(conn_id)
        if s is not None:
            s.kill(query_only)

    def maybe_auto_analyze(self, table_ids):
        """Post-DML auto-analyze check (update.go:621-639 analog, run inline
        instead of on a background ticker).  A touched partition refreshes
        the whole partitioned table so the merged logical-id row count the
        planner reads stays current."""
        isc = self.catalog.info_schema()
        done = set()
        for tid in table_ids:
            try:
                if not self.stats.need_auto_analyze(tid):
                    continue
                owner = isc.table_by_id(tid)
                if owner is not None and owner.id not in done:
                    # schema-aware analyze keeps index NDV stats fresh
                    # (a bare analyze_table would silently drop them)
                    done.add(owner.id)
                    self.stats.analyze(owner)
                else:
                    self.stats.analyze_table(tid)
            except Exception:
                pass  # stats are advisory; never fail the statement

    def _slow_log_max_bytes(self) -> int:
        from .vars import SYSVAR_DEFAULTS

        try:
            return int(self.global_vars.get(
                "tidb_tpu_slow_log_max_bytes",
                SYSVAR_DEFAULTS["tidb_tpu_slow_log_max_bytes"][0]))
        except (TypeError, ValueError):
            return 0

    def _digest_row(self, digest: str, sql: str) -> dict:
        """Get-or-create one statement summary row; caller holds _mu.
        Bounded like the reference's stmtsummary cap."""
        st = self.digest_summary.get(digest)
        if st is None:
            if len(self.digest_summary) >= 5000:
                self.digest_summary.clear()
            st = self.digest_summary[digest] = {
                "count": 0, "sum_latency": 0.0, "max_latency": 0.0,
                "sum_rows": 0, "sample": sql[:256],
            }
        return st

    def record_stmt(self, sql: str, dur_s: float, rows: int):
        from ..metrics import REGISTRY

        REGISTRY.inc("statements_total")
        REGISTRY.observe("statement_duration_seconds", dur_s)
        digest = sql_digest(sql)
        with self._mu:
            # per-digest aggregates (util/stmtsummary/statement_summary.go
            # :59,:213 — keyed on the normalized statement)
            st = self._digest_row(digest, sql)
            st["count"] += 1
            st["sum_latency"] += dur_s
            st["max_latency"] = max(st["max_latency"], dur_s)
            st["sum_rows"] += rows

    def record_termination(self, sql: str, term: str):
        """Per-digest abnormal-ending counts for the statement summary
        (expensivequery.go's kill accounting, folded into stmtsummary).
        'ok'/'error' endings are the count/latency aggregates' job; only
        lifecycle terminations are tallied here."""
        if term in ("ok", "error"):
            return
        digest = sql_digest(sql)
        with self._mu:
            # terminated statements may never reach record_stmt: get-or-
            # create the digest row so the termination is not invisible
            st = self._digest_row(digest, sql)
            tm = st.setdefault("terminations", {})
            tm[term] = tm.get(term, 0) + 1

    def record_trace(self, tr, totals: dict, dur_ms: float, slow: bool):
        """Fold a finished QueryTrace into the per-digest statement
        summary (phase aggregates from the span tree — the one
        execution-stats path) and, when it crossed the threshold, build
        the structured slow-log entry with per-phase columns."""
        digest = sql_digest(tr.sql)
        with self._mu:
            st = self.digest_summary.get(digest)
            if st is not None:
                ph = st.setdefault("phases", {
                    "compile_ms": 0.0, "device_ms": 0.0,
                    "transfer_bytes": 0, "readback_ms": 0.0,
                    "backoff_ms": 0.0})
                ph["compile_ms"] += totals["compile_ms"]
                ph["device_ms"] += totals["device_ms"]
                ph["transfer_bytes"] += totals["transfer_bytes"]
                ph["readback_ms"] += totals["readback_ms"]
                ph["backoff_ms"] += totals["backoff_ms"]
        if not slow:
            return
        import time as _time

        entry = {
            "time": _time.strftime("%Y-%m-%d %H:%M:%S",
                                   _time.localtime(tr.start_time)),
            "conn_id": tr.conn_id,
            "query": tr.sql[:512],
            "query_time": round(dur_ms / 1000.0, 6),
            "parse_ms": round(totals["parse_ms"], 3),
            "plan_ms": round(totals["plan_ms"], 3),
            "compile_ms": round(totals["compile_ms"], 3),
            "compile_hits": totals["compile_hits"],
            "compile_misses": totals["compile_misses"],
            "transfer_bytes": totals["transfer_bytes"],
            "device_ms": round(totals["device_ms"], 3),
            "readback_ms": round(totals["readback_ms"], 3),
            "readback_bytes": totals["readback_bytes"],
            "backoff_ms": round(totals["backoff_ms"], 3),
            "backfill_ms": round(totals.get("backfill_ms", 0.0), 3),
            "cop_tasks": totals["cop_tasks"],
            "engines": totals["engines"],
            "devices": totals["devices"],
            "rows": totals.get("result_rows", 0),
            "termination": (tr.root.attrs or {}).get("termination", "ok"),
        }
        # the rotation cap is a GLOBAL sysvar; refresh it on the write
        # path so SET GLOBAL takes effect without a restart
        self.slow_log.max_bytes = self._slow_log_max_bytes()
        self.slow_log.record(entry)
        from ..metrics import REGISTRY

        REGISTRY.inc("slow_queries_total")


class _RingLogHandler(logging.Handler):
    """Process-wide singleton handler feeding the newest Domain's ring."""

    def __init__(self):
        super().__init__()
        self.ring = None

    def emit(self, record):
        ring = self.ring
        if ring is None:
            return
        try:
            ring.append((record.created, record.levelname,
                         record.name, record.getMessage()[:400]))
        except Exception:  # noqa: BLE001 - logging must never raise
            pass


_RING_HANDLER = _RingLogHandler()


def _attach_log_ring(ring):
    logger = logging.getLogger("tidb_tpu")
    if _RING_HANDLER not in logger.handlers:
        logger.addHandler(_RING_HANDLER)
    _RING_HANDLER.ring = ring
