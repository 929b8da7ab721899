"""System variables.

Reference: sessionctx/variable — SessionVars with ~607 MySQL-style sysvars
(sysvar.go:118), TiDB-specific tuning knobs incl. all parallelism degrees
(tidb_vars.go:367-423).  A registry of defaults; sessions overlay their own
values over the domain's globals, exactly like MySQL SESSION vs GLOBAL scope.
"""

from __future__ import annotations

from typing import Dict, Optional

# name -> (default, kind)  kind in {int, bool, str, float}
SYSVAR_DEFAULTS = {
    "autocommit": ("1", "bool"),
    # MySQL row-lock wait budget (seconds; MySQL default 50)
    "innodb_lock_wait_timeout": ("50", "int"),
    "sql_mode": ("ONLY_FULL_GROUP_BY,STRICT_TRANS_TABLES", "str"),
    "max_execution_time": ("0", "int"),
    # GC retention (seconds; gc_worker.go gcDefaultLifeTime is 10m) and
    # the expensive-query log threshold (seconds, expensivequery.go)
    "tidb_gc_life_time": ("600", "str"),
    "tidb_expensive_query_time_threshold": ("60", "str"),
    "tx_isolation": ("REPEATABLE-READ", "str"),
    "transaction_isolation": ("REPEATABLE-READ", "str"),
    "time_zone": ("SYSTEM", "str"),
    "wait_timeout": ("28800", "int"),
    "interactive_timeout": ("28800", "int"),
    "max_allowed_packet": ("67108864", "int"),
    "version_comment": ("tidb-tpu", "str"),
    "character_set_client": ("utf8mb4", "str"),
    "character_set_results": ("utf8mb4", "str"),
    "character_set_connection": ("utf8mb4", "str"),
    "collation_connection": ("utf8mb4_bin", "str"),
    "lower_case_table_names": ("2", "int"),
    # --- TiDB-style knobs (tidb_vars.go) ------------------------------
    "tidb_max_chunk_size": ("1024", "int"),
    "tidb_init_chunk_size": ("32", "int"),
    "tidb_distsql_scan_concurrency": ("8", "int"),
    "tidb_executor_concurrency": ("5", "int"),
    "tidb_hash_join_concurrency": ("-1", "int"),
    "tidb_hashagg_partial_concurrency": ("-1", "int"),
    # accepted for TiDB's clients; the final merge is one vectorised pass
    "tidb_hashagg_final_concurrency": ("-1", "int"),
    "tidb_projection_concurrency": ("-1", "int"),
    "tidb_index_lookup_concurrency": ("4", "int"),
    "tidb_index_lookup_join_concurrency": ("4", "int"),
    "tidb_opt_prefer_merge_join": ("0", "bool"),
    "tidb_opt_enable_index_join": ("1", "bool"),
    # index join scheduling variant: lookup (ordered, sequential batches) |
    # hash (concurrent batch workers) | merge (key-ordered probes) —
    # INL_JOIN / INL_HASH_JOIN / INL_MERGE_JOIN hint analog
    "tidb_index_join_variant": ("lookup", "str"),
    # cost-based TPU-vs-host scan routing (optimizer.go:162-184 cost split
    # analog).  The defaults below were chosen on a former backend and
    # are not re-measured on the attached chip.  dispatch_us=0 disables
    # routing (always device); set it to the measured fixed cost of one
    # dispatch+readback to route small scans to the host.
    "tidb_opt_device_dispatch_us": ("0", "int"),
    "tidb_opt_host_rows_per_us": ("1", "int"),
    "tidb_opt_device_rows_per_us": ("50", "int"),
    "tidb_mem_quota_query": (str(32 << 30), "int"),
    "tidb_oom_action": ("cancel", "str"),
    "tidb_retry_limit": ("10", "int"),
    # total per-cop-task retry sleep budget (ms) — backoff.go's maxSleep,
    # configurable instead of the old hard-coded 10s (distsql/backoff.py)
    "tidb_backoff_budget_ms": ("10000", "int"),
    "tidb_disable_txn_auto_retry": ("0", "bool"),
    "tidb_snapshot": ("", "str"),
    # domain-wide cProfile collector -> information_schema.tidb_profile
    "tidb_profiling": ("0", "bool"),
    # --- query tracing / slow log (tidb_tpu/trace) --------------------
    # enable: every statement records a span tree (wire -> parse -> plan
    # -> executor -> distsql -> copr compile/transfer/execute/readback);
    # threshold: statements at or above this many ms land in
    # INFORMATION_SCHEMA.SLOW_QUERY with per-phase columns (0 logs all).
    # Disabled, span hooks are a single contextvar read (zero-cost).
    "tidb_enable_slow_log": ("1", "bool"),
    "tidb_slow_log_threshold": ("300", "int"),
    # size-capped slow-log rotation (ISSUE 13): when the active file
    # exceeds this many bytes it rotates (atomic rename) into
    # slow_query.log.1..N (N = TIDB_TPU_SLOW_LOG_KEEP env, default 3);
    # 0 disables rotation.  GLOBAL scope — the log file is a domain
    # resource.  Torn-tail recovery applies to the active file only.
    "tidb_tpu_slow_log_max_bytes": (str(64 << 20), "int"),
    # --- per-statement-class SLO thresholds (ISSUE 13) ----------------
    # end-to-end latency SLO per statement class (point/agg/join/DML);
    # every finished traced statement observes a log2-bucket histogram
    # `stmt_latency_<class>_ms` and, when its class threshold is > 0,
    # bumps `slo_<class>_{ok,breach}_total` — the error-budget burn
    # counters the /status "slo" section reports.  0 disables burn
    # accounting for a class (the histogram still records).  The string
    # 'auto' (GLOBAL scope) derives the threshold from the observed
    # rolling p99 instead (trace.slo: headroom x merged-window p99,
    # inert until the windows hold enough samples).
    "tidb_tpu_slo_point_ms": ("100", "int"),
    "tidb_tpu_slo_agg_ms": ("1000", "int"),
    "tidb_tpu_slo_join_ms": ("5000", "int"),
    "tidb_tpu_slo_dml_ms": ("500", "int"),
    "tidb_tpu_slo_other_ms": ("0", "int"),
    # auto-capture plan baselines for repeated statements
    # (bindinfo/handle.go:545 CaptureBaselines)
    "tidb_capture_plan_baselines": ("0", "bool"),
    "tidb_opt_agg_push_down": ("1", "bool"),
    "tidb_opt_distinct_agg_push_down": ("0", "bool"),
    # --- MPP exchange engine (tidb_vars.go TiDBAllowMPP/TiDBEnforceMPP,
    # TiDBBroadcastJoinThresholdCount) -------------------------------
    # allow: planner may pick the device shuffle join; enforce: pick it
    # whenever structurally eligible regardless of the cost threshold;
    # threshold: build sides at or below this row estimate stay on the
    # broadcast-lookup / host lanes (no exchange)
    "tidb_allow_mpp": ("1", "bool"),
    "tidb_enforce_mpp": ("0", "bool"),
    "tidb_broadcast_join_threshold_count": ("10240", "int"),
    # plan-cache capacity per session (planner/core/cache.go's
    # plan-cache-size; used to be a hard-coded 128)
    "tidb_plan_cache_size": ("128", "int"),
    # periodic server-side eager session checkpointing (lifecycle
    # follow-up (d)): every N seconds the server parks all prepared
    # sessions' handoff state on the coordination plane, so even a
    # SIGKILLed process loses at most one interval of session churn.
    # 0 disables (drain-time handoff still runs).  GLOBAL scope — the
    # checkpoint loop is a server resource.
    "tidb_tpu_handoff_checkpoint_s": ("0", "int"),
    # --- shape-bucketed serving & micro-batching (tidb_tpu/serving) ---
    # shape buckets: compiled programs and plan-cache entries key on
    # pow2 shape CLASSES (row-count buckets, hoisted predicate params,
    # bucketed TopN budgets) instead of literal shapes/constants
    "tidb_tpu_shape_buckets": ("1", "bool"),
    # micro-batching window (ms; 0 disables): identical-fingerprint
    # point/agg statements arriving within the window coalesce into one
    # vmapped device dispatch.  Process-wide knobs (the batcher is a
    # server-level resource, like max_connections).
    "tidb_tpu_microbatch_window_ms": ("0", "int"),
    "tidb_tpu_microbatch_max": ("32", "int"),
    # the session's resource group (admitted and charged per dispatch);
    # empty = the user's binding (ALTER USER ... RESOURCE GROUP) or
    # "default"
    "tidb_tpu_resource_group": ("", "str"),
    # --- TPU-native knobs ---------------------------------------------
    "tidb_use_tpu": ("1", "bool"),  # per-session engine routing (cpu|tpu)
    # background device-cache warming after bulk loads (LOAD DATA):
    # the first analytic query finds columns resident on the mesh
    "tidb_tpu_prefetch": ("1", "bool"),
    "tidb_tpu_block_rows": (str(1 << 20), "int"),
    "tidb_allow_batch_cop": ("1", "bool"),
    "tidb_enable_pushdown": ("1", "bool"),
    # schema/dtype-verify every finished physical plan (lint.plancheck) —
    # the vet-for-plans gate over planner rewrites; cheap host-side walk,
    # runs only on plan-cache misses, so it stays on by default
    "tidb_check_plan": ("1", "bool"),
}


class SessionVars:
    def __init__(self, globals_map: Optional[Dict[str, str]] = None):
        self._globals = globals_map if globals_map is not None else {}
        self._session: Dict[str, str] = {}
        # user-defined @vars
        self.user_vars: Dict[str, object] = {}

    # ---- typed getters -------------------------------------------------
    def get(self, name: str) -> Optional[str]:
        name = name.lower()
        if name in self._session:
            return self._session[name]
        if name in self._globals:
            return self._globals[name]
        d = SYSVAR_DEFAULTS.get(name)
        return d[0] if d else None

    def get_int(self, name: str, default: int = 0) -> int:
        v = self.get(name)
        try:
            return int(v)
        except (TypeError, ValueError):
            return default

    def get_global_int(self, name: str, default: int = 0) -> int:
        """GLOBAL-scope read (skips any session override): for shared
        resources — SLO burn counters, the slow log — where every
        session must act on the same value /status reports."""
        name = name.lower()
        v = self._globals.get(name)
        if v is None:
            d = SYSVAR_DEFAULTS.get(name)
            v = d[0] if d else None
        try:
            return int(v)
        except (TypeError, ValueError):
            return default

    def get_global_str(self, name: str, default: str = "") -> str:
        """GLOBAL-scope raw read (skips session overrides, no type
        coercion): for sysvars carrying sentinel strings on an int-kind
        knob — `tidb_tpu_slo_<class>_ms = 'auto'` selects the derived
        rolling-p99 threshold (trace.slo) and must read the same on
        every session and on /status."""
        name = name.lower()
        v = self._globals.get(name)
        if v is None:
            d = SYSVAR_DEFAULTS.get(name)
            v = d[0] if d else None
        return v if v is not None else default

    def get_bool(self, name: str) -> bool:
        v = self.get(name)
        return str(v).lower() in ("1", "on", "true", "yes")

    # ---- setters -------------------------------------------------------
    def set_session(self, name: str, value):
        self._session[name.lower()] = _norm(value)

    def set_global(self, name: str, value):
        self._globals[name.lower()] = _norm(value)

    def known(self, name: str) -> bool:
        name = name.lower()
        return (name in SYSVAR_DEFAULTS or name in self._globals
                or name in self._session)

    def all_vars(self) -> Dict[str, str]:
        out = {k: v[0] for k, v in SYSVAR_DEFAULTS.items()}
        out.update(self._globals)
        out.update(self._session)
        return out


def _norm(value) -> str:
    if isinstance(value, bool):
        return "1" if value else "0"
    if value is None:
        return ""
    return str(value)
