"""Sharded base tables: each host materializes only the partitions it
owns, and re-shards orphaned partitions onto survivors on epoch bumps.

The mechanics deliberately reuse the storage engine instead of growing a
parallel one: partition p of table T becomes a REAL `TableStore` under a
synthetic table id, attached to the host's `BlockStorage` — so the
device scan path, the CPU oracle, delta overlays, region routing and the
dispatch seams all work on partitions unchanged
(`run_dag_on_region` resolves the store from the range's table id, never
the DAG's).  The partition slice keeps the source table's sorted string
dictionaries and ingests pre-coded int32 codes (`bulk_load_arrays`
coded path), so sharding never pays a per-row re-encode.

Re-shard replay prefers the persisted bit-packed form (`pack_codes`,
the cold tier's 1/2/4/8-bit layout — 8–64x smaller than the raw
dictionary codes) over re-slicing the in-RAM source, mirroring the
paper's observation that packed codes are the cheap thing to move when
a host dies.  `dataplane/reshard` is the chaos site: the harness arms
it to fail a replay mid-re-shard and asserts parity after the retry.
"""

from __future__ import annotations

import os
from typing import Dict, List, Optional, Tuple

import numpy as np

from ..metrics import REGISTRY
from ..store.blockstore import TableStore
from ..store.fault import FAILPOINTS
from ..types import TypeKind
from ..util_concurrency import make_lock
from .partition import (PartitionMap, build_partition_map, default_parts,
                        default_rf)

_DIR_ENV = "TIDB_TPU_DATAPLANE_DIR"
#: "1" defers secondary-replica materialization to first touch (the
#: failover rung that needs it); default is eager — secondaries load at
#: shard/re-shard time so a promotion never touches the cold tier
_LAZY_ENV = "TIDB_TPU_DATAPLANE_LAZY_REPLICAS"

#: synthetic table-id namespace for partition stores — far above any
#: catalog id (catalogs number from 100) and wide enough that
#: (table_id, partition) pairs never collide
_PART_TID_BASE = 1 << 28
_PART_STRIDE = 4096


def partition_tid(table_id: int, part: int) -> int:
    return _PART_TID_BASE + table_id * _PART_STRIDE + part


class ShardedTable:
    """One table's shard state on one host: the immutable base snapshot
    metadata (bounds, schema, source base version) plus the mutable set
    of locally materialized partitions."""

    def __init__(self, table_id: int, columns, n_rows: int, base_ts: int,
                 base_version: int, n_parts: int):
        self.table_id = table_id
        self.columns = columns  # [(name, FieldType)]
        self.n_rows = n_rows
        self.base_ts = base_ts
        #: source-store base_version at shard time: a later bulk load or
        #: compaction invalidates the snapshot (queries bypass until
        #: re-sharded)
        self.base_version = base_version
        self.n_parts = n_parts
        #: partition -> (global_lo, global_hi): contiguous handle ranges,
        #: so partition order IS handle order (keep_order for free)
        self.bounds: List[Tuple[int, int]] = []
        per = n_rows / n_parts if n_parts else 0
        for p in range(n_parts):
            lo = int(round(p * per))
            hi = int(round((p + 1) * per)) if p + 1 < n_parts else n_rows
            self.bounds.append((lo, hi))
        #: locally materialized partitions: part -> synthetic table id
        self.loaded: Dict[int, int] = {}

    def part_range(self, part: int) -> Tuple[int, int]:
        return self.bounds[part]


def _pack_column(codes: np.ndarray, card: int):
    """(payload, bits): bit-packed when the dictionary is narrow enough
    for the cold tier's 1/2/4/8-bit layout, raw int32 codes otherwise."""
    from ..layout.coldtier import _bits_for, pack_codes

    bits = _bits_for(card) if card > 0 else None
    if bits is None:
        return np.ascontiguousarray(codes, dtype=np.int32), 0
    vpb = 8 // bits
    pad = (-len(codes)) % vpb
    if pad:
        codes = np.concatenate(
            [codes, np.zeros(pad, dtype=codes.dtype)])
    return pack_codes(codes.astype(np.uint8), bits), bits


def _unpack_column(payload: np.ndarray, bits: int, n: int) -> np.ndarray:
    if bits == 0:
        return payload[:n].astype(np.int32)
    vpb = 8 // bits
    if vpb == 1:
        return payload[:n].astype(np.int32)
    shifts = (np.arange(vpb, dtype=np.uint8) * bits).astype(np.uint8)
    mask = np.uint8((1 << bits) - 1)
    out = ((payload[:, None] >> shifts) & mask).reshape(-1)
    return out[:n].astype(np.int32)


class _SoloView:
    """Degenerate single-host membership: `LocalPlane.view()` carries no
    member rows (membership-only deployments never register), so the
    dataplane substitutes itself as the sole owner — SAME map/ownership/
    re-shard code path, one pid in it."""

    __slots__ = ("epoch", "members", "addrs", "formed")

    def __init__(self, epoch: int, pid: int):
        self.epoch = epoch
        self.members = {pid: ()}
        self.addrs = {}
        self.formed = True


class Dataplane:
    """Per-host shard manager: derives the `PartitionMap` from the
    membership broadcast, materializes owned partitions as attached
    `TableStore`s, persists every partition's packed base blocks, and
    re-shards on epoch bumps.

    Locking: `_mu` (rank 97, in front of the storage band) protects the
    map + per-table shard state.  It is NEVER held across a dispatch —
    `route()` copies what the engine needs and releases; re-shard holds
    it while attaching stores (rank 100/110 nest above it cleanly)."""

    def __init__(self, storage, plane, pid: int,
                 data_dir: Optional[str] = None,
                 n_parts: Optional[int] = None,
                 rf: Optional[int] = None,
                 lazy_replicas: Optional[bool] = None):
        self.storage = storage
        self.plane = plane
        self.pid = pid
        self.data_dir = data_dir or os.environ.get(_DIR_ENV) or None
        self.n_parts = n_parts or default_parts()
        self.rf = rf if rf is not None else default_rf()
        self.lazy_replicas = (lazy_replicas if lazy_replicas is not None
                              else os.environ.get(_LAZY_ENV) == "1")
        self._mu = make_lock("dataplane.shard:Dataplane._mu")
        self._tables: Dict[int, ShardedTable] = {}
        self._map: Optional[PartitionMap] = None
        if self.data_dir:
            os.makedirs(self.data_dir, exist_ok=True)

    # ------------------------------------------------------------------
    # sharding
    # ------------------------------------------------------------------
    def shard_table(self, table_id: int) -> ShardedTable:
        """Snapshot the table's base blocks into hash partitions: persist
        every partition's packed form (so ANY host can replay it later),
        then materialize every partition this host appears in the chain
        for — primaries always, secondaries unless `lazy_replicas`
        defers them to first touch."""
        src = self.storage.table(table_id)
        view = self.plane.view()
        if not view.members:
            view = _SoloView(view.epoch, self.pid)
        pmap = build_partition_map(view, self.n_parts, rf=self.rf)
        st = ShardedTable(table_id, [(c.name, c.ftype) for c in src.cols],
                          src.base_rows, src.base_ts, src.base_version,
                          self.n_parts)
        cols, valids = _materialize_base(src)
        # persist all partitions BEFORE taking _mu: file writes must not
        # run under a ranked lock, and a crash mid-persist just leaves
        # replayable extras
        if self.data_dir:
            for p in range(st.n_parts):
                self._persist_partition(src, st, p, cols, valids)
        primary = set(pmap.owned_by(self.pid))
        secondary = set(pmap.replica_of(self.pid)) - primary
        with self._mu:
            self._map = pmap
            self._tables[table_id] = st
            for p in sorted(primary):
                self._load_partition_locked(st, p, src=(cols, valids))
            if not self.lazy_replicas:
                for p in sorted(secondary):
                    self._fill_replica_locked(st, p, src=(cols, valids))
        REGISTRY.inc("dataplane_tables_sharded_total")
        return st

    def current_map(self) -> Optional[PartitionMap]:
        with self._mu:
            return self._map

    def lookup(self, table_id: int) -> Optional[ShardedTable]:
        with self._mu:
            return self._tables.get(table_id)

    def sync(self) -> Optional[PartitionMap]:
        """Re-derive the map from the CURRENT broadcast; on an epoch
        bump, re-shard before returning.  Called at the top of every
        dataplane dispatch — the `check_epoch` analog one layer up."""
        view = self.plane.view()
        if not view.formed:
            return None
        if not view.members:
            view = _SoloView(view.epoch, self.pid)
        with self._mu:
            cur = self._map
        if cur is not None and cur.epoch == view.epoch:
            return cur
        return self.re_shard(view)

    # ------------------------------------------------------------------
    # re-shard (epoch bump: host joined or died)
    # ------------------------------------------------------------------
    def re_shard(self, view) -> PartitionMap:
        """Install the ownership map for `view`'s epoch.  Partitions
        whose chain no longer includes this host detach; partitions
        newly PRIMARY here either promote (a surviving replica is
        already materialized — `dataplane_replica_promotions_total`,
        zero cold-tier work) or replay from the cold tier
        (`dataplane_cold_reloads_total`: persisted packed codes first,
        live source slice as fallback); new secondary-replica slots
        fill eagerly (or defer to first touch under `lazy_replicas`)."""
        pmap = build_partition_map(view, self.n_parts, rf=self.rf)
        with self._mu:
            old = self._map
            tables = dict(self._tables)
        if old is not None and old.owners == pmap.owners \
                and old.chains == pmap.chains:
            with self._mu:
                self._map = pmap
            return pmap  # same ownership, only the epoch moved
        old_primary = set(old.owned_by(self.pid)) if old else set()
        moved = 0
        try:
            for tid, st in tables.items():
                mine_primary = set(pmap.owned_by(self.pid))
                mine_any = set(pmap.replica_of(self.pid))
                with self._mu:
                    have = set(st.loaded)
                for p in sorted(have - mine_any):
                    with self._mu:
                        ptid = st.loaded.pop(p, None)
                    if ptid is not None:
                        self.storage.drop_table(ptid)
                        moved += 1
                for p in sorted(mine_primary - old_primary):
                    # the chaos site: armed failures surface here, mid
                    # re-shard, and the retry ladder above must converge
                    # to parity anyway
                    FAILPOINTS.hit("dataplane/reshard", table_id=tid,
                                   part=p, epoch=pmap.epoch)
                    if p in have:
                        # a live replica survives the loss: promote it —
                        # the whole point of RF>=2 (no cold-tier decode
                        # on the recovery's critical path)
                        REGISTRY.inc("dataplane_replica_promotions_total")
                    else:
                        with self._mu:
                            self._load_partition_locked(st, p)
                        REGISTRY.inc("dataplane_cold_reloads_total")
                    moved += 1
                if not self.lazy_replicas:
                    for p in sorted(mine_any - mine_primary - have):
                        with self._mu:
                            if self._fill_replica_locked(st, p):
                                moved += 1
        except Exception:
            # a torn re-shard must not look installed: clear the map so
            # the NEXT sync() replays the whole transition (loads are
            # idempotent, drops are already durable)
            with self._mu:
                self._map = None
            raise
        # install only after every movement landed — a map is a promise
        # that its owned partitions are materialized
        with self._mu:
            self._map = pmap
        if moved:
            REGISTRY.inc("dataplane_reshards_total")
            REGISTRY.inc("dataplane_partitions_moved_total", moved)
        return pmap

    # ------------------------------------------------------------------
    # partition materialization
    # ------------------------------------------------------------------
    def _fill_replica_locked(self, st: ShardedTable, part: int,
                             src=None) -> bool:
        """Materialize a SECONDARY replica (called with `_mu` held).
        Non-fatal by design: a replica is availability headroom, not
        correctness — on failure the partition simply stays cold here
        (the failover ladder's later rungs and the local bypass still
        answer) and the next touch retries.  `dataplane/replica_load`
        is the chaos site."""
        if part in st.loaded:
            return False
        try:
            FAILPOINTS.hit("dataplane/replica_load",
                           table_id=st.table_id, part=part)
            self._load_partition_locked(st, part, src=src)
        except Exception:
            REGISTRY.inc("dataplane_replica_fill_errors_total")
            return False
        REGISTRY.inc("dataplane_replica_fills_total")
        return True

    def ensure_replica(self, table_id: int, part: int) -> Optional[int]:
        """First-touch materialization for lazy secondaries: when this
        host is in `part`'s chain but has not loaded it yet, load it
        now and return the partition store's table id (None when the
        fill failed or this host is not a replica)."""
        with self._mu:
            st = self._tables.get(table_id)
            pmap = self._map
            if st is None or pmap is None:
                return None
            if part in st.loaded:
                return st.loaded[part]
            if self.pid not in pmap.chain(part):
                return None
            self._fill_replica_locked(st, part)
            return st.loaded.get(part)

    def _load_partition_locked(self, st: ShardedTable, part: int,
                               src=None):
        if part in st.loaded:
            return
        ptid = partition_tid(st.table_id, part)
        lo, hi = st.part_range(part)
        data = None
        if src is None:
            data = self._replay_persisted(st, part)
            if data is not None:
                REGISTRY.inc("dataplane_replay_packed_total")
        if data is None:
            # replay from the live source store (every host keeps the
            # pre-shard base, so this is always available in-process)
            s = self.storage.table(st.table_id)
            cols, valids = src if src is not None else _materialize_base(s)
            data = ([c[lo:hi] for c in cols],
                    [v[lo:hi] if v is not None else None for v in valids])
            if src is None:
                REGISTRY.inc("dataplane_replay_source_total")
        arrays, valids = data
        store = TableStore(ptid, list(st.columns))
        dicts = {}
        s = self.storage.table(st.table_id) \
            if self.storage.has_table(st.table_id) else None
        for ci, (_nm, ft) in enumerate(st.columns):
            if ft.kind == TypeKind.STRING:
                d = s.cols[ci].dictionary if s is not None else None
                dicts[ci] = d if d is not None else []
        store.bulk_load_arrays(arrays, valids, ts=st.base_ts,
                               dictionaries=dicts or None)
        self.storage.attach_table(ptid, store)
        st.loaded[part] = ptid
        REGISTRY.inc("dataplane_partitions_loaded_total")

    # ------------------------------------------------------------------
    # persistence (packed base blocks)
    # ------------------------------------------------------------------
    def _part_path(self, st: ShardedTable, part: int) -> str:
        return os.path.join(
            self.data_dir, f"t{st.table_id}_p{part}of{st.n_parts}.npz")

    def _persist_partition(self, src, st: ShardedTable, part: int,
                           cols, valids):
        lo, hi = st.part_range(part)
        n = hi - lo
        payload = {"n_rows": np.int64(n)}
        for ci, (_nm, ft) in enumerate(st.columns):
            a = cols[ci][lo:hi]
            if ft.kind == TypeKind.STRING:
                card = len(src.cols[ci].dictionary or ())
                packed, bits = _pack_column(a, card)
                payload[f"c{ci}"] = packed
                payload[f"c{ci}_bits"] = np.int64(bits)
            else:
                payload[f"c{ci}"] = a
            v = valids[ci]
            if v is not None:
                payload[f"c{ci}_valid"] = np.packbits(v[lo:hi])
        path = self._part_path(st, part)
        # tmp name is per-process: every member persists every partition
        # of the same deterministic build into the SHARED replay dir, so
        # concurrent writers must never collide on the staging file (the
        # final rename is last-writer-wins over identical bytes)
        tmp = "%s.%d.tmp" % (path, os.getpid())
        np.savez(tmp, **payload)
        # numpy appends .npz to names without it
        os.replace(tmp if os.path.exists(tmp) else tmp + ".npz", path)
        REGISTRY.inc("dataplane_persisted_bytes_total",
                     os.path.getsize(path))

    def _replay_persisted(self, st: ShardedTable, part: int):
        if not self.data_dir:
            return None
        path = self._part_path(st, part)
        if not os.path.exists(path):
            return None
        try:
            with np.load(path) as z:
                n = int(z["n_rows"])
                lo, hi = st.part_range(part)
                if n != hi - lo:
                    return None  # stale layout (n_parts changed)
                arrays, valids = [], []
                for ci, (_nm, ft) in enumerate(st.columns):
                    a = z[f"c{ci}"]
                    if ft.kind == TypeKind.STRING:
                        a = _unpack_column(a, int(z[f"c{ci}_bits"]), n)
                    arrays.append(a)
                    vk = f"c{ci}_valid"
                    valids.append(np.unpackbits(z[vk])[:n].astype(bool)
                                  if vk in z.files else None)
            REGISTRY.inc("dataplane_replay_bytes_total",
                         os.path.getsize(path))
            return arrays, valids
        except Exception:
            REGISTRY.inc("dataplane_replay_errors_total")
            return None

    # ------------------------------------------------------------------
    # introspection
    # ------------------------------------------------------------------
    def snapshot(self) -> dict:
        with self._mu:
            pmap = self._map
            tables = {
                tid: {
                    "n_parts": st.n_parts,
                    "n_rows": st.n_rows,
                    "loaded": sorted(st.loaded),
                }
                for tid, st in self._tables.items()
            }
        return {
            "pid": self.pid,
            "epoch": pmap.epoch if pmap else None,
            "members": list(pmap.members) if pmap else [],
            "owners": list(pmap.owners) if pmap else [],
            "chains": [list(ch) for ch in pmap.chains] if pmap else [],
            "rf": self.rf,
            "tables": tables,
        }

    def close(self):
        """Detach every partition store (tests: no leaked catalog
        entries) and drop the shard state."""
        with self._mu:
            tables = dict(self._tables)
            self._tables.clear()
            self._map = None
        for st in tables.values():
            for ptid in list(st.loaded.values()):
                try:
                    self.storage.drop_table(ptid)
                except Exception:
                    pass
            st.loaded.clear()


def _materialize_base(src) -> Tuple[List[np.ndarray], List]:
    """Concatenate the source store's base blocks per column (strings as
    int32 dictionary codes — never decoded)."""
    n_cols = src.n_cols
    parts: List[List[np.ndarray]] = [[] for _ in range(n_cols)]
    vparts: List[List] = [[] for _ in range(n_cols)]
    any_valid = [False] * n_cols
    for _off, arrs, vals in src.iter_base_blocks(
            list(range(n_cols)), 0, src.base_rows):
        for ci in range(n_cols):
            parts[ci].append(arrs[ci])
            vparts[ci].append(vals[ci])
            if vals[ci] is not None:
                any_valid[ci] = True
    cols, valids = [], []
    for ci in range(n_cols):
        if parts[ci]:
            cols.append(np.concatenate(parts[ci]))
        else:
            cols.append(np.zeros(0, dtype=np.int64))
        if any_valid[ci]:
            valids.append(np.concatenate([
                v if v is not None else np.ones(len(a), dtype=np.bool_)
                for a, v in zip(parts[ci], vparts[ci])]))
        else:
            valids.append(None)
    return cols, valids
