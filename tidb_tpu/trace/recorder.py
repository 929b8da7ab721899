"""Span-tree recorder: the low-overhead core of the trace subsystem.

A QueryTrace is a tree of Spans rooted at one statement execution.  The
CURRENT span travels in a contextvar; `span(name)` opens a child under
it.  Worker threads do not inherit the contextvar automatically — the
fan-out layers capture `current_span()` on the submitting thread and
re-enter with `attach(parent)` (the reference's opentracing
span-context propagation, contextvar-shaped).

Phase attribution: span names beginning with a known phase prefix (see
PHASES) aggregate into the per-phase totals the slow log, the statement
summary and the /metrics histograms consume; byte counts ride in span
attrs (`bytes=`), engine/rung attribution in `engine=` attrs.

Pre-timed spans (`QueryTrace.add_span`) stand where their caller says
the work began (`start_ns=`), so the server's envelope around the root
(`wire.read`, `admission.wait`, `server.handoff` before it;
`session.account`, `server.respond` and `wire.write` after it) lies
OUTSIDE the root's interval: `rows()` / `to_dict()` render offsets
before the root's start as negative numbers.  The three spans after the
root are appended once `finish_trace` has run, so the slow log, the
phase histograms and the export to a coordinator never see them; a
holder of the finished QueryTrace (the ring, `Session.last_trace`, a
chained export hook that keeps the object) does.  With `parent=` a
pre-timed span hangs under another span than the root: the fan-out's two
thread hand-offs (`distsql.spawn`, `distsql.wake`).  Two things happen
under no `span(...)` of the program's own and are recorded at the
bottom of this module: XLA compiles (`note_compile`, from JAX's
monitoring events: `compile_ns` on the span that compiled) and the
collector's pauses (`_on_gc`: a `py.gc` span under the current one).
"""

from __future__ import annotations

import gc
import itertools
import threading
import time
import uuid
import zlib
from collections import deque
from contextvars import ContextVar
from dataclasses import dataclass
from typing import Dict, List, Optional
from ..metrics import REGISTRY
from ..util_concurrency import make_lock

#: per-process statement-trace sequence: multi-controller SPMD runs the
#: same statement stream in every process, so (sql crc, seq) — the qid —
#: correlates one statement's traces ACROSS hosts (trace/export.py
#: grafts a worker's forwarded tree under the coordinator's by qid)
_TRACE_SEQ = itertools.count()
_TRACE_UID = itertools.count()
_PROC_TOKEN = uuid.uuid4().hex[:12]


@dataclass
class OperatorStats:
    """Per-operator runtime stats (rows/loops/time) for EXPLAIN ANALYZE —
    owned by the trace subsystem so the span tree and the operator table
    are one collection path (util/execdetails RuntimeStatsColl role)."""

    rows: int = 0
    loops: int = 0
    time_ns: int = 0
    # engine attribution (which engine actually served a cop task, incl.
    # mesh-rejection reasons — execdetails.go:326-396 analog)
    engine: str = ""

    def record(self, rows: int, dur_ns: int):
        self.rows += rows
        self.loops += 1
        self.time_ns += dur_ns


class Span:
    """One timed operation.  Children append under the owning trace's
    lock (fan-out workers record concurrently); attrs are written only
    by the thread inside the span, so they need no lock."""

    __slots__ = ("name", "start_ns", "dur_ns", "attrs", "children",
                 "_trace")

    def __init__(self, name: str, trace: "QueryTrace"):
        self.name = name
        self.start_ns = time.perf_counter_ns()
        self.dur_ns = 0
        self.attrs: Optional[Dict[str, object]] = None
        self.children: List["Span"] = []
        self._trace = trace

    def set(self, **attrs):
        if self.attrs is None:
            self.attrs = {}
        self.attrs.update(attrs)
        return self

    def add(self, key: str, value):
        """Accumulate a numeric attr (bytes, backoff_ms, ...)."""
        if self.attrs is None:
            self.attrs = {}
        self.attrs[key] = self.attrs.get(key, 0) + value

    def finish(self):
        self.dur_ns = time.perf_counter_ns() - self.start_ns


class QueryTrace:
    """The span tree of one statement execution plus its EXPLAIN ANALYZE
    operator stats — the single execution-stats carrier."""

    def __init__(self, sql: str, conn_id: int = 0,
                 imported: bool = False):
        self.sql = sql
        self.conn_id = conn_id
        self.start_time = time.time()
        self._mu = make_lock("trace.recorder:QueryTrace._mu")
        self.root = Span("session.execute", self)
        self.op_stats: Dict[int, OperatorStats] = {}
        self.finished = False
        # cross-host correlation id + import provenance (coord plane).
        # Imported shells (trace/export.py rebuilding a forwarded tree)
        # MUST NOT consume the sequence: SPMD correlation relies on every
        # process assigning the same seq to the same statement, and an
        # ingest that advanced the coordinator's counter would desync
        # qids from the workers' forever after the first forwarded trace.
        self.imported_from: Optional[int] = None
        # process-unique identity: with forwarding now BATCHED and
        # backgrounded (coord follow-up (c)), a trace may already sit in
        # this process's ring when its own payload flushes — the graft
        # step uses the uid to never graft a trace under itself.  The
        # token is RANDOM per process, not the pid: containerized SPMD
        # hosts all run as pid 1 with lockstep statement counters, and a
        # pid-based uid would collide across hosts and wrongly suppress
        # cross-host grafts.
        self.uid = f"{_PROC_TOKEN}-{next(_TRACE_UID)}"
        if imported:
            self.seq = -1
            self.qid: Optional[str] = None
        else:
            self.seq = next(_TRACE_SEQ)
            crc = zlib.crc32(sql.encode("utf-8", "replace")) & 0xFFFFFFFF
            self.qid = f"{crc:08x}-{self.seq}"

    # ---- tree assembly --------------------------------------------------
    def child(self, parent: Span, name: str) -> Span:
        s = Span(name, self)
        with self._mu:
            parent.children.append(s)
        return s

    def add_span(self, name: str, dur_ns: int = 0,
                 start_ns: Optional[int] = None,
                 parent: Optional[Span] = None, **attrs) -> Span:
        """Append a pre-timed span after the fact, under `parent` (left
        out, the root), at `start_ns` on `perf_counter_ns` (the moment
        the work BEGAN; left out, the moment of the append) — the wire
        layer records result write time onto the already-finished trace
        (the statement ended before the rows hit the socket), the
        fan-out its two thread hand-offs under the span that waited."""
        s = Span(name, self)
        if start_ns is not None:
            s.start_ns = start_ns
        s.dur_ns = dur_ns
        if attrs:
            s.set(**attrs)
        with self._mu:
            (parent or self.root).children.append(s)
        return s

    # ---- rendering ------------------------------------------------------
    def duration_ms(self) -> float:
        return (self.root.dur_ns or
                (time.perf_counter_ns() - self.root.start_ns)) / 1e6

    def rows(self, indent_root: bool = True) -> List[tuple]:
        """(operation, start_offset_ms, duration_ms) rows, depth-first,
        with two-space indentation showing the tree (TRACE row format)."""
        out: List[tuple] = []
        t0 = self.root.start_ns

        def walk(s: Span, depth: int):
            dur = s.dur_ns or (time.perf_counter_ns() - s.start_ns)
            label = "  " * depth + s.name
            if s.attrs:
                kv = ", ".join(f"{k}: {v}" for k, v in sorted(s.attrs.items()))
                label += f" {{{kv}}}"
            out.append((label, f"{(s.start_ns - t0) / 1e6:.3f}ms",
                        f"{dur / 1e6:.3f}ms"))
            for c in s.children:
                walk(c, depth + 1)

        walk(self.root, 0)
        return out

    def to_dict(self) -> dict:
        def walk(s: Span) -> dict:
            d = {
                "name": s.name,
                "start_us": (s.start_ns - self.root.start_ns) // 1000,
                "duration_us": (s.dur_ns or 0) // 1000,
            }
            if s.attrs:
                d["attrs"] = {k: (v if isinstance(v, (int, float, str, bool))
                                  else str(v))
                              for k, v in s.attrs.items()}
            if s.children:
                d["children"] = [walk(c) for c in s.children]
            return d

        return {"sql": self.sql[:512], "conn_id": self.conn_id,
                "start_time": self.start_time, "root": walk(self.root)}

    # ---- phase aggregation ---------------------------------------------
    def phase_totals(self) -> dict:
        """Aggregate the tree into the per-phase columns SLOW_QUERY and
        the statement summary expose.  ms totals per phase prefix, byte
        totals for transfer/readback, backoff from attr accumulation,
        and engine/rung attribution collected from span attrs."""
        tot = {
            "parse_ms": 0.0, "plan_ms": 0.0, "compile_ms": 0.0,
            "transfer_ms": 0.0, "transfer_bytes": 0,
            "device_ms": 0.0, "readback_ms": 0.0, "readback_bytes": 0,
            "backoff_ms": 0.0, "exchange_ms": 0.0, "commit_ms": 0.0,
            "backfill_ms": 0.0, "throttle_ms": 0.0, "chunks": 0,
            "compile_hits": 0, "compile_misses": 0, "cop_tasks": 0,
            "wire_bytes": 0, "result_rows": 0,
            "hbm_peak_bytes": 0,
            "engines": set(), "devices": set(),
        }

        def walk(s: Span):
            ms = (s.dur_ns or 0) / 1e6
            a = s.attrs or {}
            n = s.name
            # what JAX reported compiling under this span (`compile_ns`,
            # note_compile): a jitted call traces, lowers and compiles
            # inside its own `copr.device.execute`, so that span gives
            # the compile its seconds and keeps the rest as device time
            comp = a.get("compile_ns", 0) / 1e6
            tot["compile_ms"] += comp
            if n in PHASES:
                tot[PHASES[n]] += max(ms - comp, 0.0)
            if n == "copr.compile":
                if a.get("cache") == "hit":
                    tot["compile_hits"] += 1
                else:
                    tot["compile_misses"] += 1
            elif n in ("copr.transfer",):
                tot["transfer_bytes"] += int(a.get("bytes", 0))
            elif n == "copr.readback":
                tot["readback_bytes"] += int(a.get("bytes", 0))
            elif n == "cop.task":
                tot["cop_tasks"] += 1
            elif n == "copr.chunk":
                # the statement's mesh dispatches (one per mesh program
                # run) for EXPLAIN ANALYZE / slow log
                tot["chunks"] += 1
            elif n.startswith("wire."):
                tot["wire_bytes"] += int(a.get("bytes", 0))
            tot["wire_bytes"] += int(a.get("wire_read_bytes", 0))
            tot["backoff_ms"] += float(a.get("backoff_ms", 0.0))
            # device-memory telemetry (ISSUE 13): dispatch sites stamp
            # the resident HBM bytes (hot mesh cache + cold tier) on the
            # execute span — the trace-level high-water mark feeds
            # EXPLAIN ANALYZE's per-statement HBM attribution
            hbm = a.get("hbm_bytes")
            if hbm is not None and int(hbm) > tot["hbm_peak_bytes"]:
                tot["hbm_peak_bytes"] = int(hbm)
            eng = a.get("engine") or a.get("rung")
            if eng:
                tot["engines"].add(str(eng))
            for d in a.get("device_ids", ()) or ():
                tot["devices"].add(int(d))
            if "device" in a:
                tot["devices"].add(int(a["device"]))
            for c in s.children:
                walk(c)

        walk(self.root)
        # result rows = the TOP-LEVEL drain loops' row counts (nested
        # subplan drains during planning don't count toward the result)
        tot["result_rows"] = sum(
            int((c.attrs or {}).get("rows", 0))
            for c in self.root.children if c.name == "executor.next")
        tot["engines"] = ",".join(sorted(tot["engines"]))
        tot["devices"] = ",".join(str(d) for d in sorted(tot["devices"]))
        return tot


#: span name -> phase-total key (ms sums)
PHASES = {
    "parse": "parse_ms",
    "plan": "plan_ms",
    "copr.transfer": "transfer_ms",
    # one fused XLA launch per mesh dispatch (whole-fragment fusion)
    "copr.device.execute": "device_ms",
    "copr.readback": "readback_ms",
    "mpp.exchange": "exchange_ms",
    "txn.prewrite": "commit_ms",
    "txn.commit": "commit_ms",
    # online DDL index builds (ddl.backfill spans per batch)
    "ddl.backfill": "backfill_ms",
    # resource-group admission wait before a dispatch
    "resgroup.throttle": "throttle_ms",
}

#: phases surfaced as /metrics histograms on every finished trace
_METRIC_PHASES = ("parse_ms", "plan_ms", "compile_ms", "transfer_ms",
                  "device_ms", "readback_ms", "backoff_ms", "backfill_ms")

# the CURRENT span (None = tracing disabled for this context)
_CUR: ContextVar[Optional[Span]] = ContextVar("tidb_tpu_trace", default=None)

#: most recent finished traces (process-global; /status + tests)
TRACE_RING: deque = deque(maxlen=32)

#: cross-host span forwarding hook: a worker-side coordination plane
#: (tidb_tpu/coord) installs its forward_trace here so every finished
#: trace ships to the coordinator at query end; None (the default)
#: keeps finish_trace allocation-free
TRACE_EXPORT_HOOK = None

#: chain participants behind TRACE_EXPORT_HOOK (chain_export_hook /
#: unchain_export_hook below).  While the list is empty the seam stays
#: None so the disabled finish_trace path costs one global read.
_EXPORT_CHAIN: list = []
_EXPORT_MU = make_lock("trace.recorder:_EXPORT_MU")


def _dispatch_export(tr):
    """The single installed hook while any participant is chained: fan
    the finished trace to every participant in chain order, isolating
    failures (a broken forwarder must not starve the profiler, or vice
    versa).  Dispatch runs on a snapshot, outside _EXPORT_MU, so a
    participant may itself take locks freely."""
    for fn in list(_EXPORT_CHAIN):
        try:
            fn(tr)
        except Exception:
            pass


def chain_export_hook(fn):
    """Add `fn` to the export chain (idempotent).  A hook installed
    directly on TRACE_EXPORT_HOOK (tests, third parties) is adopted
    into the chain rather than dropped."""
    global TRACE_EXPORT_HOOK
    with _EXPORT_MU:
        cur = TRACE_EXPORT_HOOK
        if (cur is not None and cur is not _dispatch_export
                and cur not in _EXPORT_CHAIN):
            _EXPORT_CHAIN.append(cur)
        if fn not in _EXPORT_CHAIN:
            _EXPORT_CHAIN.append(fn)
        TRACE_EXPORT_HOOK = _dispatch_export


def unchain_export_hook(fn):
    """Remove `fn` wherever it sits in the chain — list removal, NOT
    restore-if-top, so a stopped participant always leaves regardless
    of install order.  Unknown hooks are a no-op."""
    global TRACE_EXPORT_HOOK
    with _EXPORT_MU:
        try:
            _EXPORT_CHAIN.remove(fn)
        except ValueError:
            pass
        if not _EXPORT_CHAIN and TRACE_EXPORT_HOOK is _dispatch_export:
            TRACE_EXPORT_HOOK = None


def clear_export_hooks():
    """Drop every chained participant and null the seam (plane reset /
    test isolation)."""
    global TRACE_EXPORT_HOOK
    with _EXPORT_MU:
        _EXPORT_CHAIN.clear()
        TRACE_EXPORT_HOOK = None


class _NoopSpan:
    """Singleton returned when tracing is off: every operation is a
    no-op, so the disabled path costs one contextvar read."""

    __slots__ = ()

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        return False

    def set(self, **attrs):
        return self

    def add(self, key, value):
        return self


NOOP = _NoopSpan()


class _SpanCtx:
    """Context manager entering/leaving one real span."""

    __slots__ = ("span", "_token")

    def __init__(self, s: Span):
        self.span = s
        self._token = None

    def __enter__(self):
        self._token = _CUR.set(self.span)
        return self.span

    def __exit__(self, *exc):
        self.span.finish()
        _CUR.reset(self._token)
        return False


def tracing_active() -> bool:
    return _CUR.get() is not None


def current_span() -> Optional[Span]:
    return _CUR.get()


def current_trace() -> Optional[QueryTrace]:
    s = _CUR.get()
    return s._trace if s is not None else None


def span(name: str, **attrs):
    """Open a child span under the current one; no-op when disabled."""
    cur = _CUR.get()
    if cur is None:
        return NOOP
    s = cur._trace.child(cur, name)
    if attrs:
        s.set(**attrs)
    return _SpanCtx(s)


def annotate(**attrs):
    """Attach attrs to the current span; no-op when disabled."""
    cur = _CUR.get()
    if cur is not None:
        cur.set(**attrs)


def attach(parent: Optional[Span]):
    """Re-enter a span context on another thread (fan-out workers):
    `with attach(parent): ...` makes `parent` the current span there.
    Passing None or the no-op (captured while tracing was off) no-ops."""
    if not isinstance(parent, Span):
        return NOOP
    return _AttachCtx(parent)


def run_attached(parent: Optional[Span], fn, *args, **kwargs):
    """Run fn under a re-attached span context (thread-pool submit
    wrapper for the transfer/fan-out pools)."""
    with attach(parent):
        return fn(*args, **kwargs)


class _AttachCtx:
    __slots__ = ("_parent", "_token")

    def __init__(self, parent: Span):
        self._parent = parent
        self._token = None

    def __enter__(self):
        self._token = _CUR.set(self._parent)
        return self._parent

    def __exit__(self, *exc):
        _CUR.reset(self._token)
        return False


def start_trace(sql: str, conn_id: int = 0) -> tuple:
    """Begin a trace for one statement execution; returns (trace, token).
    The caller MUST pass both to finish_trace (try/finally)."""
    tr = QueryTrace(sql, conn_id)
    token = _CUR.set(tr.root)
    return tr, token


def finish_trace(tr: QueryTrace, token):
    """Close the root span, restore the context, publish the ring entry
    and the per-phase metrics histograms."""
    _CUR.reset(token)
    tr.root.finish()
    tr.finished = True
    hook = TRACE_EXPORT_HOOK
    if hook is not None:
        # worker plane active: the finished tree rejoins the
        # coordinator's ring (failures count, never raise into the
        # query).  Fires BEFORE the local ring append so an in-process
        # coordinator grafts under ITS trace, never under this one.
        try:
            hook(tr)
        except Exception:
            pass
    TRACE_RING.append(tr)
    totals = tr.phase_totals()
    # real log2-bucket histograms (ISSUE 13): p50/p95/p99 per phase on
    # /metrics and /status instead of the old _count/_sum/_max triple
    for key in _METRIC_PHASES:
        v = totals.get(key, 0)
        if v:
            REGISTRY.observe_hist(f"trace_phase_{key}", float(v))
    if totals["transfer_bytes"]:
        REGISTRY.inc("trace_transfer_bytes_total",
                     float(totals["transfer_bytes"]))
    if totals["readback_bytes"]:
        REGISTRY.inc("trace_readback_bytes_total",
                     float(totals["readback_bytes"]))
    return totals


# ---------------------------------------------------------------------------
# what runs under no `span(...)` of the program's own: XLA compiles (JAX
# reports them) and the collector's pauses (CPython reports them)
# ---------------------------------------------------------------------------

#: the three stages of one jit compile, in the order they end
_COMPILE_EVENTS = (
    "/jax/core/compile/jaxpr_trace_duration",
    "/jax/core/compile/jaxpr_to_mlir_module_duration",
    "/jax/core/compile/backend_compile_duration",
)
_COMPILING = threading.local()


def note_compile(event: str, secs: float):
    """The program's one `jax.monitoring` duration listener (registered
    where `tidb_tpu/ops` configures JAX).  It runs on the thread that
    compiles, when a stage ends: the seconds go to
    `xla_compile_seconds_total` (a compile is counted at its last
    stage) and, as `compile_ns`, onto the span current there — the
    `copr.device.execute` a jitted call compiles inside — from which
    `phase_totals` takes `compile_ms`.  Stages nest (a jit traced inside
    an outer trace, an eager op compiled under one), and JAX reports each
    whole: a stage is counted less the stages that ended inside it, so
    the total is wall time."""
    if event not in _COMPILE_EVENTS:
        return
    done = getattr(_COMPILING, "done", None)
    if done is None:
        done = _COMPILING.done = []
    start = time.perf_counter() - secs
    inner = 0.0
    while done and done[-1][0] >= start:
        inner += done.pop()[1]
    done.append((start, secs))
    del done[:-64]  # stages that no later one encloses
    own = max(secs - inner, 0.0)
    REGISTRY.inc("xla_compile_seconds_total", own)
    if event == _COMPILE_EVENTS[-1]:
        REGISTRY.inc("xla_compiles_total")
    cur = _CUR.get()
    if cur is not None:
        cur.add("compile_ns", int(own * 1e9))


_GC_COUNTERS = ("py_gc_pause_seconds_total", "py_gc_collections_total")
_gc_state = [0, 0, 0]  # start of the running collection; ns, count so far


def _on_gc(phase: str, info: dict):
    """`gc.callbacks` entry: a collection, from `start` to `stop`, as a
    pre-timed `py.gc` span under the span current on the thread it ran
    on, and in the two counters whether a trace is open there or not.
    CPython runs a collection between any two bytecodes of whichever
    thread tripped it, so that thread may hold ANY lock: nothing here
    takes one (collections do not nest, so this entry is its own only
    writer; a list append and a dict store are atomic under the GIL)."""
    if phase == "start":
        _gc_state[0] = time.perf_counter_ns()
        return
    t0 = _gc_state[0]
    dur = time.perf_counter_ns() - t0
    _gc_state[1] += dur
    _gc_state[2] += 1
    REGISTRY.publish(_GC_COUNTERS[0], _gc_state[1] / 1e9)
    REGISTRY.publish(_GC_COUNTERS[1], float(_gc_state[2]))
    cur = _CUR.get()
    if cur is not None:
        s = Span("py.gc", cur._trace)
        s.start_ns, s.dur_ns = t0, dur
        s.attrs = {"gen": info.get("generation"),
                   "collected": info.get("collected")}
        cur.children.append(s)


def install_gc_spans():
    """Put `_on_gc` among `gc.callbacks`, once a process."""
    if _on_gc not in gc.callbacks:
        for name in _GC_COUNTERS:
            REGISTRY.inc(name, 0.0)  # the keys exist before `publish`
        gc.callbacks.append(_on_gc)
