"""Byte-capped FIFO cache for device-resident arrays.

Shared by the per-tile device cache (jax_engine._DeviceCache) and the
mesh-sharded column cache (parallel.MESH_CACHE) — one eviction policy, one
bookkeeping implementation.  The role of TiKV's block cache: immutable base
data keyed on (store_uid, base_version, ...), so a version bump naturally
invalidates without explicit eviction.
"""

from __future__ import annotations

import os
import threading
from collections import OrderedDict
from typing import Callable, Dict, List, Optional, Tuple
from ..util_concurrency import make_lock


class _InFlight:
    """One pending load: waiters block on `ev` and read the outcome off
    the record, so a doomed (evicted-mid-load) value still reaches every
    current waiter WITHOUT any of them restarting the load against a
    condemned device set."""

    __slots__ = ("ev", "value", "failed")

    def __init__(self):
        self.ev = threading.Event()
        self.value: Optional[tuple] = None
        self.failed = False


_MISSES = threading.local()


def thread_misses() -> int:
    """`get_or_load` calls of the calling thread that did not find
    their key resident, over every ByteCapCache: the difference around
    a fetch says whether it was served from the device's memory
    (`mesh.columns` counts `resident` by it)."""
    return getattr(_MISSES, "n", 0)


class ByteCapCache:
    """key -> tuple of device arrays (anything with .nbytes)."""

    def __init__(self, capacity_bytes: int, name: Optional[str] = None):
        self._cache: Dict[tuple, tuple] = {}
        self._order: List[tuple] = []
        self._bytes = 0
        self.capacity = capacity_bytes
        # device-memory telemetry (ISSUE 13): hwm_bytes is the
        # high-water mark since process start (or the last clear) — the
        # "how close did we get to the cap" gauge
        self.name = name
        self.hwm_bytes = 0
        self._mu = make_lock("copr.cache:ByteCapCache._mu")
        # value-weighted eviction policy (layout autotuner): priority_fn
        # ranks resident keys (lowest evicts first; None = FIFO) and
        # demote_fn gets each victim BEFORE it is dropped — the hook that
        # re-homes a column into the compressed cold tier instead of
        # losing it outright
        self._priority_fn: Optional[Callable[[tuple], float]] = None
        self._demote_fn: Optional[Callable[[tuple, tuple], None]] = None
        # per-key in-flight records: a background prefetch and a query
        # racing on the same column must not BOTH push it over the link
        # (transfers are the expensive part; see _MeshCache)
        self._inflight: Dict[tuple, _InFlight] = {}
        # keys evicted WHILE their load was in flight: the finished value
        # must not be cached (it may be placed on a dead device)
        self._doomed: set = set()
        # named caches register for the /status "memory" section and the
        # fleet metric snapshots — LAST, fully constructed: memory_stats
        # on another thread may iterate the registry immediately
        if name is not None:
            BYTE_CAP_CACHES[name] = self

    def set_policy(self, priority_fn=None, demote_fn=None):
        """Install the value-weighted eviction policy (both optional)."""
        with self._mu:
            self._priority_fn = priority_fn
            self._demote_fn = demote_fn

    def _eviction_order_locked(self) -> List[tuple]:
        """Victim order for one eviction pass: priorities are ranked
        ONCE (one priority_fn call per resident, not per victim) so a
        multi-victim eviction holds the mutex for O(N log N), never
        O(V*N) cross-lock lookups.  FIFO fallback when no policy (or a
        broken one — a bad policy must never wedge the cache)."""
        if self._priority_fn is not None:
            try:
                return sorted(self._order, key=self._priority_fn)
            except Exception:
                pass
        return list(self._order)

    def get_or_load(self, key: tuple, loader: Callable[[], Tuple]) -> tuple:
        while True:
            with self._mu:
                hit = self._cache.get(key)
                if hit is not None:
                    return hit
                # not resident: this thread loads or waits for a load
                _MISSES.n = thread_misses() + 1
                rec = self._inflight.get(key)
                if rec is None:
                    rec = self._inflight[key] = _InFlight()
                    break  # we are the loader
            rec.ev.wait()  # another thread is loading this key
            if not rec.failed:
                return rec.value  # loaded (cached, or doomed-uncached)
            # the loader failed: loop and possibly become the new loader
        try:
            value = loader()  # outside the lock: loads transfer data
        except BaseException:
            with self._mu:
                rec.failed = True
                self._inflight.pop(key, None)
                self._doomed.discard(key)
            rec.ev.set()
            raise
        nbytes = sum(v.nbytes for v in value if v is not None)
        victims: List[Tuple[tuple, tuple]] = []
        with self._mu:
            rec.value = value
            doomed = key in self._doomed
            self._doomed.discard(key)
            self._inflight.pop(key, None)
            if not doomed:
                ranked: Optional[List[tuple]] = None
                while self._bytes + nbytes > self.capacity and self._order:
                    if ranked is None:
                        ranked = self._eviction_order_locked()
                    old = ranked.pop(0)
                    self._order.remove(old)
                    ov = self._cache.pop(old)
                    self._bytes -= sum(v.nbytes for v in ov if v is not None)
                    victims.append((old, ov))
                self._cache[key] = value
                self._order.append(key)
                self._bytes += nbytes
                if self._bytes > self.hwm_bytes:
                    self.hwm_bytes = self._bytes
            demote = self._demote_fn
            # doomed: hand the value to this caller and every waiter
            # (their mesh is already condemned and will retry) but never
            # cache it for a future, possibly-restored mesh
        rec.ev.set()
        if demote is not None:
            # outside the lock: demotion compresses + transfers, and a
            # demote hook that loads through ANOTHER cache must not hold
            # this one's lock
            for vk, vv in victims:
                try:
                    demote(vk, vv)
                except Exception:
                    pass  # demotion is best-effort; the drop already won
        return value

    def peek(self, key: tuple):
        """Resident value for key (no load, no ordering effect); None on
        miss.  Used for tier bookkeeping (cold-hit/promotion metrics)."""
        with self._mu:
            return self._cache.get(key)

    def evict_if(self, pred: Callable[[tuple], bool]) -> int:
        """Drop every entry whose key satisfies pred (device-failover
        eviction: keys carrying a dead device's id must never serve a
        rebuilt mesh).  In-flight loads matching pred are doomed: their
        results are handed to the loading caller but never cached.
        Returns the number of resident entries evicted."""
        with self._mu:
            victims = [k for k in self._cache if pred(k)]
            for k in victims:
                v = self._cache.pop(k)
                self._order.remove(k)
                self._bytes -= sum(x.nbytes for x in v if x is not None)
            for k in self._inflight:
                if pred(k):
                    self._doomed.add(k)
        return len(victims)

    def clear(self):
        with self._mu:
            self._cache.clear()
            self._order.clear()
            self._bytes = 0
            self._doomed.update(self._inflight)  # don't cache mid-flight loads

    def __len__(self):
        return len(self._cache)

    def stats(self) -> dict:
        with self._mu:
            return {"entries": len(self._cache), "bytes": self._bytes,
                    "capacity_bytes": self.capacity,
                    "watermark_bytes": self.hwm_bytes}

    @property
    def items_view(self):
        return self._cache


#: named ByteCapCache instances (mesh column cache, cold tier, per-tile
#: device cache) — one registry so the /status "memory" section and the
#: fleet metric snapshots see every device-resident byte pool
BYTE_CAP_CACHES: Dict[str, "ByteCapCache"] = {}


def memory_stats() -> Dict[str, dict]:
    """Byte/capacity/watermark stats for every named device cache, also
    refreshed into REGISTRY gauges (`cache_<name>_bytes` etc.) so fleet
    snapshots and /metrics carry them without a pull from each cache."""
    from ..metrics import REGISTRY

    out = {}
    for name, cache in sorted(BYTE_CAP_CACHES.items()):
        st = cache.stats()
        out[name] = st
        REGISTRY.set(f"cache_{name}_bytes", float(st["bytes"]))
        REGISTRY.set(f"cache_{name}_capacity_bytes",
                     float(st["capacity_bytes"]))
        REGISTRY.set(f"cache_{name}_watermark_bytes",
                     float(st["watermark_bytes"]))
        REGISTRY.set(f"cache_{name}_entry_count", float(st["entries"]))
    return out


#: every ProgramCache registers here so /status can report one
#: compiled-cache section across the tile/mesh/MPP/micro-batch engines
PROGRAM_CACHES: List["ProgramCache"] = []


class ProgramCache:
    """LRU-bounded compiled-program cache (the `_COMPILED` dicts, bounded).

    Unbounded program caches were a slow leak: every new fingerprint —
    parameter-different before hoisting, shape-different before
    bucketing, every rebuilt mesh — pinned a compiled XLA executable
    forever.  With shape buckets the steady-state key population is
    small, so a modest LRU cap holds the working set while long-tail
    shapes age out.  Counters feed `compiled_programs_{hits,misses,
    evictions}_total` and the /status compiled-cache section.
    """

    def __init__(self, name: str, capacity: Optional[int] = None):
        self.name = name
        self.capacity = capacity if capacity is not None else int(
            os.environ.get("TIDB_TPU_PROGRAM_CACHE_SIZE", "256"))
        self._d: "OrderedDict" = OrderedDict()
        self._mu = make_lock("copr.cache:ProgramCache._mu")
        self.hits = self.misses = self.evictions = 0
        PROGRAM_CACHES.append(self)

    def get(self, key):
        from ..metrics import REGISTRY

        with self._mu:
            fn = self._d.get(key)
            if fn is not None:
                self._d.move_to_end(key)
                self.hits += 1
            else:
                self.misses += 1
        REGISTRY.inc("compiled_programs_hits_total" if fn is not None
                     else "compiled_programs_misses_total")
        return fn

    def put(self, key, fn):
        from ..metrics import REGISTRY

        evicted = 0
        with self._mu:
            self._d[key] = fn
            self._d.move_to_end(key)
            while len(self._d) > max(self.capacity, 1):
                self._d.popitem(last=False)
                self.evictions += 1
                evicted += 1
        if evicted:
            REGISTRY.inc("compiled_programs_evictions_total", evicted)

    def stats(self) -> dict:
        with self._mu:
            return {"size": len(self._d), "capacity": self.capacity,
                    "hits": self.hits, "misses": self.misses,
                    "evictions": self.evictions}

    def clear(self):
        with self._mu:
            self._d.clear()

    def __len__(self):
        with self._mu:
            return len(self._d)

    def __iter__(self):
        with self._mu:
            return iter(list(self._d))

    def __contains__(self, key):
        with self._mu:
            return key in self._d
