"""Layout autotuner: observe per-column access patterns, choose layouts.

Observation sources (the planes PRs 4-8 built):

- scan frequency  — every mesh column load records a scan observation
  (`copr/parallel.load_layout_columns`);
- predicate selectivity — the statistics feedback plane
  (`statistics/handle.record_feedback`) forwards the learned per-scan
  selectivity to every column the conjunction touches;
- agg-vs-probe usage — the fragment analysis records which columns
  serve as group keys, aggregate arguments and join-probe keys
  (`copr/parallel._run_mesh_once`);
- NDV / value range — the store's own `column_stats` plus the cold
  tier's compression probe.

Decisions (`ColumnPlan`) per column: **encoding** (dictionary codes vs
direct values on device), **packed code width** (1/2/4/8 bits; 0 = not
packable), **residency tier** (hot wire arrays vs compressed cold
blocks), **priority** (value-weighted eviction order), and per table a
**tile-size bucket** (padded shape classes, an eighth of a power of two
apart — program reuse as the table grows — vs exact tiling, which stops
paying HBM for padding exactly when capacity is the scarce resource).

Layout CLASS changes (encoding/width/tier/tiling) may refingerprint
compiled programs, so they are RATE-LIMITED (`TIDB_TPU_LAYOUT_RETUNE_S`
minimum seconds between class changes per column) and each bump counts
in `layout_retunes_total`; suppressed flips count in
`layout_retunes_suppressed_total`.  Dictionary VALUES ride runtime
operands, so within a class the tuner moves nothing that recompiles.

This module is jax-free (pure host bookkeeping) and purity-linted.
"""

from __future__ import annotations

import os
import threading
import time
import weakref
from dataclasses import dataclass, field
from typing import Dict, Optional, Tuple
from ..util_concurrency import make_lock


@dataclass
class ColumnObs:
    """Per-column access counters (the tuner's workload signal)."""

    scans: int = 0
    filters: int = 0
    agg_keys: int = 0
    agg_args: int = 0
    probe_keys: int = 0
    last_sel: Optional[float] = None
    last_access: float = 0.0


@dataclass
class ColumnPlan:
    """One column's chosen device layout."""

    encoding: str        # 'dict' (coded) | 'direct'
    bits: int            # packed code width (0 = not packable)
    dict_cap: int        # pow2 dictionary capacity class (0 when direct)
    tier: str            # 'hot' | 'cold'
    priority: float      # residency priority (higher = keep hot)
    tile_bucket: str     # table-level: 'pow2' | 'exact'
    version: int = 0     # bumps on layout-CLASS change
    base_version: int = 0
    gen: int = 0         # tuner generation the plan was computed under
    computed_at: float = 0.0  # monotonic time: re-tune cadence anchor


def _class_key(p: "ColumnPlan") -> tuple:
    """The refingerprint-relevant part of a plan (priority moves freely)."""
    return (p.encoding, p.bits, p.dict_cap, p.tier, p.tile_bucket)


def retune_min_s() -> float:
    return float(os.environ.get("TIDB_TPU_LAYOUT_RETUNE_S", "5"))


class LayoutEngine:
    """Process-global observation store + per-column layout decisions."""

    def __init__(self):
        self._mu = make_lock("layout.autotuner:LayoutEngine._mu")
        #: (store_uid, store_ci) -> ColumnObs
        self._obs: Dict[Tuple[int, int], ColumnObs] = {}
        #: (store_uid, store_ci) -> ColumnPlan (recomputed lazily)
        self._plans: Dict[Tuple[int, int], ColumnPlan] = {}
        #: (store_uid, store_ci) -> monotonic time of last CLASS change
        self._last_change: Dict[Tuple[int, int], float] = {}
        #: columns the eviction path demoted: cold-preferred until the
        #: tuner decides pressure is gone
        self._demoted: set = set()
        #: (store_uid, base_version) -> (gen, computed_at, cold ci set)
        self._cold_sets: Dict[Tuple[int, int], tuple] = {}
        #: store_uid -> live TableStore (demote/promote need host blocks)
        self._stores = weakref.WeakValueDictionary()
        #: column display metadata for /status + information_schema
        self._names: Dict[Tuple[int, int], Tuple[int, str]] = {}
        self.epoch = 0

    # ------------------------------------------------------------------
    # observation
    # ------------------------------------------------------------------
    _KINDS = ("scan", "filter", "agg_key", "agg_arg", "probe_key")

    def observe(self, table, store_ci: int, kind: str,
                sel: Optional[float] = None):
        """Record one access observation for (table, column)."""
        key = (table.store_uid, store_ci)
        with self._mu:
            self._stores[table.store_uid] = table
            self._obs_calls += 1
            if self._obs_calls % self._PRUNE_EVERY == 0:
                self._prune_locked()
            if store_ci < len(table.cols):
                self._names[key] = (table.table_id,
                                    table.cols[store_ci].name)
            o = self._obs.get(key)
            if o is None:
                o = self._obs[key] = ColumnObs()
            if kind == "scan":
                o.scans += 1
            elif kind == "filter":
                o.filters += 1
            elif kind == "agg_key":
                o.agg_keys += 1
            elif kind == "agg_arg":
                o.agg_args += 1
            elif kind == "probe_key":
                o.probe_keys += 1
            if sel is not None:
                o.last_sel = float(sel)
            o.last_access = time.monotonic()

    def store_ref(self, store_uid: int):
        """Live TableStore for a cache key's uid (eviction demote path);
        None once the store was dropped."""
        return self._stores.get(store_uid)

    def forget_table(self, table_id: int):
        """DROP-table hook (chained off the catalog's drop notification
        via StatsHandle.drop): forget every column of the dropped table
        NOW — the store object itself may outlive the drop for MVCC, so
        the weak registry alone cannot prune it."""
        with self._mu:
            uids = {uid for uid, t in self._stores.items()
                    if getattr(t, "table_id", None) == table_id}
            uids |= {k[0] for k, (tid, _n) in self._names.items()
                     if tid == table_id}
            for m in (self._obs, self._plans, self._last_change,
                      self._names):
                for k in [k for k in m if k[0] in uids]:
                    del m[k]
            self._demoted = {k for k in self._demoted if k[0] not in uids}
            for k in [k for k in self._cold_sets if k[0] in uids]:
                del self._cold_sets[k]
            for uid in uids:
                self._stores.pop(uid, None)

    _PRUNE_EVERY = 1024

    def _prune_locked(self):
        """Drop bookkeeping for stores that no longer exist (the weak
        registry is the liveness authority): without this, DROP/truncate
        churn grows the maps without bound and dropped tables haunt the
        decision surfaces forever."""
        live = set(self._stores.keys())
        for m in (self._obs, self._plans, self._last_change, self._names):
            for k in [k for k in m if k[0] not in live]:
                del m[k]
        self._demoted = {k for k in self._demoted if k[0] in live}
        for k in [k for k in self._cold_sets if k[0] not in live]:
            del self._cold_sets[k]

    _obs_calls = 0

    def note_demoted(self, store_uid: int, store_ci: int):
        """Eviction demoted this column to the cold tier: prefer cold on
        the next plan until the tuner sees headroom again."""
        with self._mu:
            self._demoted.add((store_uid, store_ci))
            self._plans.pop((store_uid, store_ci), None)

    #: bumped by invalidate_plans: plans recompute lazily but the OLD
    #: plan stays around for the class comparison, so a recompute is
    #: still subject to the re-tune rate limit
    _gen = 0

    def invalidate_plans(self):
        """Recompute every decision on next access (cap moved, tests)."""
        with self._mu:
            self._gen += 1

    # ------------------------------------------------------------------
    # decisions
    # ------------------------------------------------------------------
    def priority(self, store_uid: int, store_ci: int) -> float:
        """Residency priority: usage-weighted access counts.  Group /
        probe keys weigh double — they are re-read by every fused kernel
        that touches the fragment, so keeping them hot saves the most
        decode work."""
        with self._mu:
            o = self._obs.get((store_uid, store_ci))
        if o is None:
            return 0.0
        return (o.scans + o.filters
                + 2.0 * (o.agg_keys + o.probe_keys) + o.agg_args)

    def _table_pressure(self, table) -> bool:
        """True when the table's hot wire bytes cannot fit the hot cap —
        the signal that flips compressible columns cold and the table's
        tiling to exact."""
        from . import hot_cap_bytes

        return _table_wire_bytes(table) > hot_cap_bytes()

    #: hot-budget headroom: residency packing targets this fraction of
    #: the cap so loads never start an eviction storm at exactly 100%
    HOT_FILL = 0.9

    def _cold_columns(self, table) -> frozenset:
        """The PACKABLE columns that do not fit the hot budget, chosen
        by residency priority: unpackable columns are hot by necessity,
        then packables keep hot slots in priority order until the budget
        is spent — the remainder are the cold set.  Cached per
        (store, base version, tuner generation) for one re-tune window
        (`TIDB_TPU_LAYOUT_RETUNE_S`), after which fresh observations
        re-rank it."""
        from . import hot_cap_bytes
        from .coldtier import pack_info

        ck = (table.store_uid, table.base_version)
        now = time.monotonic()
        with self._mu:
            cached = self._cold_sets.get(ck)
            if cached is not None and cached[0] == self._gen \
                    and now - cached[1] < retune_min_s():
                return cached[2]
        budget = hot_cap_bytes() * self.HOT_FILL
        packable, spent = [], 0.0
        for ci in range(table.n_cols):
            if pack_info(table, ci) is None:
                spent += _column_wire_bytes(table, ci)
            else:
                packable.append(ci)
        packable.sort(key=lambda ci: (-self.priority(table.store_uid, ci),
                                      ci))
        cold = set()
        for ci in packable:
            nb = _column_wire_bytes(table, ci)
            if spent + nb <= budget:
                spent += nb  # keeps its hot slot
            else:
                cold.add(ci)
        out = frozenset(cold)
        with self._mu:
            self._cold_sets[ck] = (self._gen, now, out)
            # superseded base versions of this store drop out
            for k in [k for k in self._cold_sets
                      if k[0] == ck[0] and k[1] != ck[1]]:
                del self._cold_sets[k]
        return out

    def _hot_headroom(self, col_bytes: int) -> bool:
        """True when the live hot tier could absorb `col_bytes` more."""
        from . import hot_cap_bytes
        from ..copr.parallel import MESH_CACHE

        return MESH_CACHE._c._bytes + col_bytes <= hot_cap_bytes()

    def tile_bucket(self, table) -> str:
        """Table-level tiling decision consulted by `parallel._layout`:
        'pow2', the bucketed shard of `serving.tile_bucket` (steps of an
        eighth of a power of two: program reuse as tables grow), by
        default; EXACT tiling (whole groups of 8 tiles a shard) under
        capacity pressure — padding wastes HBM exactly when HBM is what
        ran out."""
        plan = self.plan_for(table, 0) if table.n_cols else None
        return plan.tile_bucket if plan is not None else "pow2"

    def plan_for(self, table, store_ci: int) -> ColumnPlan:
        """The column's current layout decision (lazily recomputed; class
        changes rate-limited)."""
        from ..metrics import REGISTRY
        from .coldtier import pack_info

        key = (table.store_uid, store_ci)
        now = time.monotonic()
        with self._mu:
            cur = self._plans.get(key)
            if cur is not None and cur.base_version == table.base_version \
                    and cur.gen == self._gen \
                    and now - cur.computed_at < retune_min_s():
                # fresh enough: serve the cached decision.  Once the
                # re-tune window lapses the plan recomputes from the
                # LATEST observations — this is what makes the tuner
                # workload-adaptive on a long-running server, with the
                # same window rate-limiting any class churn.
                return cur
            self._stores[table.store_uid] = table
            if store_ci < len(table.cols):
                self._names[key] = (table.table_id,
                                    table.cols[store_ci].name)
            demoted = key in self._demoted
        pressure = self._table_pressure(table)
        pi = pack_info(table, store_ci)
        meta = table.cols[store_ci]
        encoding = "dict" if (pi is not None
                              or meta.dictionary is not None) else "direct"
        bits = pi.bits if pi is not None else 0
        cap = pi.cap if pi is not None else 0
        prio = self.priority(*key)
        tier = "hot"
        if pi is not None and (store_ci in self._cold_columns(table)
                               or demoted):
            tier = "cold"
            if demoted and \
                    store_ci not in self._cold_columns(table) and \
                    self._hot_headroom(_column_wire_bytes(table, store_ci)):
                # the squeeze that demoted this column has passed and the
                # hot tier has room again: promote on next access
                tier = "hot"
        plan = ColumnPlan(
            encoding=encoding, bits=bits, dict_cap=cap, tier=tier,
            priority=prio, tile_bucket="exact" if pressure else "pow2",
            base_version=table.base_version,
        )
        now = time.monotonic()
        plan.computed_at = now
        with self._mu:
            plan.gen = self._gen
            cur = self._plans.get(key)
            if cur is not None and _class_key(cur) != _class_key(plan):
                # layout-CLASS change: refingerprints compiled programs,
                # so rate-limit it — a flapping signal must not become a
                # recompile storm
                last = self._last_change.get(key, 0.0)
                if now - last < retune_min_s():
                    REGISTRY.inc("layout_retunes_suppressed_total")
                    kept = ColumnPlan(**{**cur.__dict__,
                                         "priority": plan.priority,
                                         "gen": self._gen,
                                         "computed_at": now,
                                         "base_version": table.base_version})
                    self._plans[key] = kept
                    return kept
                plan.version = cur.version + 1
                self._last_change[key] = now
                self.epoch += 1
                REGISTRY.inc("layout_retunes_total")
            elif cur is None:
                self._last_change.setdefault(key, now)
            else:
                plan.version = cur.version
            if plan.tier == "hot":
                self._demoted.discard(key)
            self._plans[key] = plan
        return plan

    # ------------------------------------------------------------------
    # introspection (/status + information_schema)
    # ------------------------------------------------------------------
    def decisions_snapshot(self) -> list:
        with self._mu:
            self._prune_locked()  # never surface dropped tables
            plans = dict(self._plans)
            obs = dict(self._obs)
            names = dict(self._names)
        out = []
        for (uid, ci), p in sorted(plans.items()):
            o = obs.get((uid, ci), ColumnObs())
            tid, cname = names.get((uid, ci), (-1, f"col{ci}"))
            out.append({
                "store_uid": uid, "table_id": tid, "column": cname,
                "store_ci": ci, "encoding": p.encoding, "bits": p.bits,
                "dict_cap": p.dict_cap, "tier": p.tier,
                "tile_bucket": p.tile_bucket,
                "priority": round(p.priority, 3), "version": p.version,
                "scans": o.scans, "filters": o.filters,
                "agg_keys": o.agg_keys, "probe_keys": o.probe_keys,
                "last_selectivity": o.last_sel,
            })
        return out

    def reset(self):
        """Test hook: forget every observation and decision."""
        with self._mu:
            self._obs.clear()
            self._plans.clear()
            self._last_change.clear()
            self._demoted.clear()
            self._cold_sets.clear()
            self._names.clear()
            self._gen += 1
            self.epoch += 1


def _pad_ratio(table) -> float:
    """Device arrays are [n_pad, TILE]-shaped (shard-padded, bucketed
    in eighths of a power of two by default), so the RESIDENT footprint
    exceeds raw wire bytes — the pressure signal must budget what
    actually occupies HBM.  Uses the default bucketed layout (not the
    table's own tile-bucket decision) to stay recursion-free."""
    try:
        import jax

        from ..copr import jax_engine as je
        from ..copr.parallel import _layout

        S = max(len(jax.devices()), 1)
        _, n_pad, _ = _layout(table.base_rows, S)
        return max(n_pad * je.TILE / max(table.base_rows, 1), 1.0)
    except Exception:
        return 1.0


def _column_wire_bytes(table, store_ci: int) -> int:
    from ..copr.parallel import _wire_dtype

    try:
        per_row = int(_wire_dtype(table, store_ci).itemsize)
    except Exception:
        # host-only payloads (JSON/object blocks) have no wire form and
        # never reach the device caches; bill them at full width
        per_row = 8
    return int(per_row * table.base_rows * _pad_ratio(table))


def _table_wire_bytes(table) -> int:
    return sum(_column_wire_bytes(table, ci) for ci in range(table.n_cols))


LAYOUT = LayoutEngine()
