"""Metrics registry (Prometheus-style counters/gauges/histograms, pull-only).

Reference: metrics/metrics.go:60 (100 collectors registered centrally,
exposed on the status port).  Here: a process-global registry surfaced
through information_schema.metrics and the HTTP status endpoint.

Histograms (ISSUE 13) are bounded log2-bucket distributions: one int
counter per power-of-two upper edge, so p50/p95/p99 estimation is exact
to within one log2 bucket, merging across hosts is a bucket-wise add,
and the whole structure is a few hundred bytes per metric no matter how
many observations land.  `/metrics` exposes them in the standard
Prometheus `_bucket{le=...}/_sum/_count` form.
"""

from __future__ import annotations

import math
import threading
from collections import defaultdict
from typing import Dict, Optional
from .util_concurrency import make_lock

#: log2 bucket range: upper edges 2**MIN_EXP .. 2**MAX_EXP.  Covers
#: sub-microsecond ms values (2^-20 ms ~ 1ns) through byte counts in the
#: terabytes (2^40); observations outside clamp into the edge buckets,
#: so the structure stays bounded by construction.
HIST_MIN_EXP = -20
HIST_MAX_EXP = 40
_NBUCKETS = HIST_MAX_EXP - HIST_MIN_EXP + 1


def _bucket_exp(value: float) -> int:
    """Smallest e with value <= 2**e (the log2 bucket upper edge),
    clamped to [HIST_MIN_EXP, HIST_MAX_EXP]."""
    if value <= 0.0:
        return HIST_MIN_EXP
    m, e = math.frexp(value)  # value = m * 2**e, 0.5 <= m < 1
    if m == 0.5:  # exact power of two sits on its own edge
        e -= 1
    return min(max(e, HIST_MIN_EXP), HIST_MAX_EXP)


class Histogram:
    """One bounded log2-bucket histogram (mutated under the registry
    lock; never locked on its own)."""

    __slots__ = ("counts", "sum", "count")

    def __init__(self):
        self.counts = [0] * _NBUCKETS
        self.sum = 0.0
        self.count = 0

    def observe(self, value: float):
        self.counts[_bucket_exp(value) - HIST_MIN_EXP] += 1
        self.sum += value
        self.count += 1

    def quantile(self, q: float) -> float:
        """Upper edge of the bucket holding the q-quantile observation —
        within one log2 bucket of the true quantile by construction.
        0.0 when empty."""
        if self.count == 0:
            return 0.0
        rank = max(math.ceil(q * self.count), 1)
        seen = 0
        for i, c in enumerate(self.counts):
            seen += c
            if seen >= rank:
                return 2.0 ** (i + HIST_MIN_EXP)
        return 2.0 ** HIST_MAX_EXP

    def to_payload(self) -> dict:
        """JSON-safe sparse form (fleet snapshots): only nonzero
        buckets travel."""
        return {
            "buckets": {str(i + HIST_MIN_EXP): c
                        for i, c in enumerate(self.counts) if c},
            "sum": self.sum,
            "count": self.count,
        }

    def merge_payload(self, payload: dict):
        """Bucket-wise add of a `to_payload` dict (fleet merge)."""
        for exp_s, c in (payload.get("buckets") or {}).items():
            try:
                i = min(max(int(exp_s), HIST_MIN_EXP),
                        HIST_MAX_EXP) - HIST_MIN_EXP
            except ValueError:
                continue
            self.counts[i] += int(c)
        self.sum += float(payload.get("sum", 0.0))
        self.count += int(payload.get("count", 0))


class Registry:
    def __init__(self):
        self._mu = make_lock("metrics:Registry._mu")
        self._counters: Dict[str, float] = defaultdict(float)
        self._hists: Dict[str, Histogram] = {}

    def inc(self, name: str, value: float = 1.0):
        with self._mu:
            self._counters[name] += value

    def observe(self, name: str, value: float):
        """Histogram-lite: tracks _count/_sum/_max."""
        with self._mu:
            self._counters[name + "_count"] += 1
            self._counters[name + "_sum"] += value
            if value > self._counters[name + "_max"]:
                self._counters[name + "_max"] = value

    def observe_hist(self, name: str, value: float):
        """Real histogram: bounded log2 buckets with p50/p95/p99
        estimation and Prometheus _bucket/_sum/_count exposition."""
        with self._mu:
            h = self._hists.get(name)
            if h is None:
                h = self._hists[name] = Histogram()
            h.observe(float(value))

    def set(self, name: str, value: float):
        with self._mu:
            self._counters[name] = value

    def publish(self, name: str, value: float):
        """Overwrite one counter WITHOUT the lock: for the one caller
        that may run while its own thread holds it (the collector's
        callback, trace/recorder.py::_on_gc).  Sound for a key that
        exists and has that single writer: a dict store is atomic under
        the GIL and every reader copies the dict in one C call."""
        self._counters[name] = value

    def get(self, name: str, default: float = 0.0) -> float:
        """Point read of one counter/gauge (cheaper than snapshot())."""
        with self._mu:
            return self._counters.get(name, default)

    def snapshot(self) -> Dict[str, float]:
        """Counters/gauges plus derived histogram families: each
        histogram contributes `<name>_count/_sum` (the names the old
        pseudo-histogram observe() exposed, so information_schema.metrics
        consumers keep working across the observe->observe_hist switch)
        and `<name>_p50/_p95/_p99`."""
        with self._mu:
            out = dict(self._counters)
            for name, h in self._hists.items():
                out[name + "_count"] = float(h.count)
                out[name + "_sum"] = round(h.sum, 6)
                out[name + "_p50"] = h.quantile(0.50)
                out[name + "_p95"] = h.quantile(0.95)
                out[name + "_p99"] = h.quantile(0.99)
            return out

    # ---- histogram reads ------------------------------------------------
    def quantile(self, name: str, q: float, default: float = 0.0) -> float:
        with self._mu:
            h = self._hists.get(name)
            return h.quantile(q) if h is not None else default

    def hist_stats(self, name: str) -> Optional[dict]:
        """{count, sum, p50, p95, p99} for one histogram; None when it
        has never been observed."""
        with self._mu:
            h = self._hists.get(name)
            if h is None:
                return None
            return {
                "count": h.count,
                "sum": round(h.sum, 6),
                "p50": h.quantile(0.50),
                "p95": h.quantile(0.95),
                "p99": h.quantile(0.99),
            }

    def prometheus_lines(self, prefix: str = "tidb_tpu_") -> list:
        """The /metrics body: counters/gauges as before, histograms in
        cumulative `_bucket{le=...}` + `_sum` + `_count` form."""
        with self._mu:
            counters = dict(self._counters)
            hists = {n: (list(h.counts), h.sum, h.count)
                     for n, h in self._hists.items()}
        lines = []
        for name, val in sorted(counters.items()):
            lines.append(f"{prefix}{name} {val}")
        for name in sorted(hists):
            counts, total, count = hists[name]
            cum = 0
            for i, c in enumerate(counts):
                if not c:
                    continue
                cum += c
                lines.append(f'{prefix}{name}_bucket{{le="'
                             f'{2.0 ** (i + HIST_MIN_EXP):g}"}} {cum}')
            lines.append(f'{prefix}{name}_bucket{{le="+Inf"}} {count}')
            lines.append(f"{prefix}{name}_sum {total}")
            lines.append(f"{prefix}{name}_count {count}")
        return lines

    # ---- fleet aggregation (ISSUE 13) -----------------------------------
    def export_fleet_payload(self) -> dict:
        """This process's snapshot as shipped to the coordinator
        piggybacked on span batches: counters/gauges + sparse
        histograms, all JSON-safe."""
        with self._mu:
            return {
                "counters": dict(self._counters),
                "hists": {n: h.to_payload()
                          for n, h in self._hists.items()},
            }


def merge_fleet(snapshots: Dict[int, dict]) -> dict:
    """Merge per-host `export_fleet_payload` dicts: `_total`-suffixed
    counters SUM across hosts, everything else stays a per-host gauge
    (an epoch or queue depth summed across hosts is meaningless), and
    histograms merge bucket-wise so fleet quantiles are exact to one
    log2 bucket.  Returns the /status "fleet" payload shape."""
    counters: Dict[str, float] = defaultdict(float)
    gauges: Dict[str, Dict[str, float]] = {}
    hists: Dict[str, Histogram] = {}
    for host in sorted(snapshots):
        snap = snapshots[host] or {}
        for name, val in (snap.get("counters") or {}).items():
            if name.endswith("_total"):
                counters[name] += float(val)
            else:
                gauges.setdefault(name, {})[str(host)] = float(val)
        for name, payload in (snap.get("hists") or {}).items():
            h = hists.get(name)
            if h is None:
                h = hists[name] = Histogram()
            h.merge_payload(payload)
    return {
        "hosts": sorted(str(h) for h in snapshots),
        "counters": dict(counters),
        "gauges": gauges,
        "hists": {
            name: {
                "count": h.count,
                "sum": round(h.sum, 6),
                "p50": h.quantile(0.50),
                "p95": h.quantile(0.95),
                "p99": h.quantile(0.99),
            }
            for name, h in hists.items()
        },
    }


REGISTRY = Registry()

#: lookup joins chained inside the mesh programs dispatched (one a
#: lookup a dispatch: a star's fact scan counts each of its dimensions)
STAR_LOOKUPS = "star_lookups_total"
#: those of them that compared the probe key with every build key
#: (`fusion.compare_lookup`) instead of gathering
LOOKUP_COMPARES = "lookup_compares_total"
#: gauge: the bytes every live table store's base blocks hold, as held
#: (integers at their narrow widths), set at the end of each bulk load
#: and compaction
STORE_BASE_BYTES = "store_base_bytes"

#: statement classes carrying per-class end-to-end latency histograms
#: (`stmt_latency_<class>_ms`) and SLO threshold sysvars
#: (`tidb_tpu_slo_<class>_ms`) with error-budget burn counters
#: (`slo_<class>_{ok,breach}_total`)
STMT_CLASSES = ("point", "agg", "join", "dml", "other")

#: coordination-plane counters (tidb_tpu/coord) surfaced as one group on
#: the /status endpoint.  The registry itself is dynamic; this tuple is
#: the stable contract between the plane, http_status and the tests:
#: epoch/membership churn, cross-host span forwarding (with the per-host
#: byte-cap drop counter), and rolling-restart session handoff.
COORD_STATUS_METRICS = (
    "coord_epoch_bumps_total",
    "coord_epoch_mismatch_total",
    "coord_members_expired_total",
    "coord_spans_forwarded_total",
    "coord_span_batches_total",
    "coord_spans_ingested_total",
    "coord_spans_grafted_total",
    "coord_spans_dropped_total",
    "coord_span_bytes_total",
    "coord_handoff_put_total",
    "coord_handoff_replayed_total",
    "coord_handoff_failed_total",
    "coord_handoff_checkpoint_total",
    "coord_rpc_errors_total",
    "coord_metrics_snapshots_total",
)

#: adaptive-layout counters (tidb_tpu/layout) surfaced as one group on
#: /status: cold-tier traffic (hits = packed columns served with no
#: host reload, loads = first compressions, promotions/demotions = tier
#: moves, fallbacks = chaos/compression failures served hot) and the
#: autotuner's layout-class churn (retunes bump the layout epoch and may
#: refingerprint; suppressed = rate-limited flips)
LAYOUT_STATUS_METRICS = (
    "layout_cold_hits_total",
    "layout_cold_loads_total",
    "layout_cold_promotions_total",
    "layout_cold_demotions_total",
    "layout_cold_fallbacks_total",
    "layout_retunes_total",
    "layout_retunes_suppressed_total",
    "layout_demote_code_readback_bytes",
)
