"""Query tracing: span trees from the wire protocol down to XLA.

Reference: util/tracing (the reference's opentracing shim feeding
executor/trace.go's `TRACE <stmt>`), infoschema/slow_log.go (the
structured slow-query log) and util/execdetails (per-phase runtime
stats).  On a TPU backend the phases that matter are different from
TiKV's — XLA compile vs. program-cache hit, host->device transfer,
device execute, and the packed readback round trip — so the
span vocabulary is TPU-native while the three surfaces mirror the
reference: `TRACE [FORMAT='row'|'json'] <stmt>` over the wire,
INFORMATION_SCHEMA.SLOW_QUERY with per-phase columns, and aggregate
per-phase histograms on /metrics with recent traces on /status.

Design constraints (README "Observability"):

- contextvar-carried: spans nest through the session call stack with no
  plumbing; worker threads (distsql fan-out, transfer pool) re-attach
  explicitly via `attach(parent)`.
- strictly zero-cost when disabled: `span()` is one contextvar read +
  one `is None` test returning a no-op singleton; nothing allocates.
- ring buffer of recent query traces (process-global, bounded) backs
  /status and post-hoc inspection without unbounded growth.
- ONE execution-stats collection path: the per-operator stats EXPLAIN
  ANALYZE shows, the statement summary's phase aggregates and the slow
  log all read the same finished QueryTrace.
"""

from .recorder import (  # noqa: F401
    TRACE_RING,
    OperatorStats,
    QueryTrace,
    Span,
    annotate,
    attach,
    current_span,
    current_trace,
    finish_trace,
    install_gc_spans,
    run_attached,
    span,
    start_trace,
    tracing_active,
)
from .recorder import NOOP  # noqa: F401
from .export import (  # noqa: F401
    graft_or_append,
    import_trace,
    trace_payload,
)
from .slowlog import SlowQueryLog  # noqa: F401
from .profiler import (  # noqa: F401
    PROFILER,
    Profiler,
    install_profiler,
    stmt_class,
)
