"""Columnar block store: the TPU-native storage engine for one table.

This is the component the reference does NOT contain (TiKV's storage engine,
in Rust, outside the repo) and which we must build natively (SURVEY.md header
note).  Design:

- **Base**: immutable fixed-capacity column blocks (numpy; BLOCK_SIZE rows)
  with implicit handles [0..base_rows).  Fixed shapes are what XLA wants:
  a scan stacks blocks into [n_blocks, BLOCK_SIZE] device arrays with
  row-validity masks, so every block compiles to the same program.
- **Strings** are dictionary-encoded at load with a *sorted* dictionary
  (order-preserving: code comparisons = string comparisons), codes int32.
- **Integers** (every column whose logical dtype is an integer: INT,
  DECIMAL, DATE, ...) are held block by block in the narrowest of int8,
  int16, int32 and int64 that holds the block's values; every reader that
  hands values out widens them to the column's logical dtype, and the
  device path (`iter_base_blocks(narrow=True)`) takes them as they are.
- **Delta**: an MVCC row store (handle -> version chain) for DML after load,
  with Percolator locks — the moral equivalent of TiDB's membuffer+TiKV MVCC
  (kv/memdb + mocktikv/mvcc_leveldb.go).  Scans overlay delta on base like
  UnionScan (executor/union_scan.go) merges txn buffer over snapshot.
- **compact()** merges committed delta into new base blocks (delta-merge,
  the TiFlash idea) and rebuilds dictionaries sorted.
"""

from __future__ import annotations

import bisect
import itertools
import threading
import weakref
from dataclasses import dataclass, field
from typing import Dict, Iterator, List, Optional, Sequence, Tuple

import numpy as np

from ..chunk import Chunk, Column
from ..errors import KVError, LockedError, TxnConflictError
from ..types import FieldType, TypeKind
from ..util_concurrency import make_rlock

BLOCK_SIZE = 1 << 16  # 65536 rows per block

_STORE_SEQ = itertools.count(1)  # process-unique store tokens (cache keys)

#: every live store, for the `store_base_bytes` gauge
_LIVE_STORES: "weakref.WeakSet[TableStore]" = weakref.WeakSet()

_NARROW_DTYPES = tuple(np.dtype(t) for t in
                       (np.int8, np.int16, np.int32, np.int64))


def narrowest(a: np.ndarray) -> np.ndarray:
    """Integers `a` in the narrowest of int8, int16, int32 and int64 that
    holds their values (`a` itself where it already is)."""
    if a.dtype.kind == "u" and a.dtype.itemsize == 8:
        a = a.astype(np.int64)
    lo, hi = (int(a.min()), int(a.max())) if len(a) else (0, 0)
    for dt in _NARROW_DTYPES:
        info = np.iinfo(dt)
        if info.min <= lo and hi <= info.max:
            return a if a.dtype == dt else a.astype(dt)
    return a.astype(np.int64)


def _narrows(meta: "ColumnMeta") -> bool:
    """Whether the store holds the column's blocks narrow (`narrowest`):
    every column whose logical dtype is an integer (strings' int32 codes
    and wide decimals' objects are held as they are)."""
    return (meta.ftype.kind != TypeKind.STRING
            and np.dtype(meta.ftype.np_dtype).kind == "i")


def _publish_base_bytes():
    """The `store_base_bytes` gauge: the base blocks every live store
    holds, in bytes."""
    from ..metrics import REGISTRY, STORE_BASE_BYTES

    REGISTRY.set(STORE_BASE_BYTES,
                 float(sum(s._held for s in list(_LIVE_STORES))))


@dataclass
class ColumnMeta:
    name: str
    ftype: FieldType
    # sorted dictionary for string columns (base blocks store int32 codes)
    dictionary: Optional[List[str]] = None


@dataclass
class Lock:
    start_ts: int
    primary: Tuple[int, int]  # (table_id, handle)
    op: str  # 'put' | 'del' | 'lock'
    values: Optional[tuple]
    ttl_ms: int = 3000


@dataclass
class Version:
    commit_ts: int
    start_ts: int
    op: str  # 'put' | 'del'
    values: Optional[tuple]  # full row tuple for 'put'


class TableStore:
    def __init__(self, table_id: int, columns: List[Tuple[str, FieldType]]):
        self.table_id = table_id
        # process-unique token: table ids repeat across Domains (each catalog
        # numbers from 100), so shared caches MUST key on this, not table_id
        self.store_uid = next(_STORE_SEQ)
        self.cols: List[ColumnMeta] = [ColumnMeta(n, t) for n, t in columns]
        self.base_rows = 0
        # per column: list of numpy blocks + validity blocks
        self._blocks: List[List[np.ndarray]] = [[] for _ in self.cols]
        self._valids: List[List[Optional[np.ndarray]]] = [[] for _ in self.cols]
        self.base_ts = 0  # commit_ts of the base snapshot
        # delta: handle -> ascending-commit_ts version chain
        self.delta: Dict[int, List[Version]] = {}
        self.locks: Dict[int, Lock] = {}
        self.next_handle = 0
        self._mu = make_rlock("store.blockstore:TableStore._mu")
        # bumped on bulk load / compact: device caches key on this
        self.base_version = 0
        self._col_stats: Dict[int, Tuple[int, int, bool]] = {}
        # bytes of the base blocks held (`nbytes`), kept as blocks change
        self._held = 0
        _LIVE_STORES.add(self)
        # durability hook (store/persist.TablePersister); None = RAM-only
        self.persister = None
        self.on_mutate = None  # storage-level data-version bump (plan cache)
        self.mutations = 0  # per-store committed-write counter (plan cache)
        from .index import IndexManager

        self.indexes = IndexManager()

    # ------------------------------------------------------------------
    # schema helpers
    # ------------------------------------------------------------------
    @property
    def n_cols(self) -> int:
        return len(self.cols)

    def col_index(self, name: str) -> int:
        for i, c in enumerate(self.cols):
            if c.name == name:
                return i
        raise KVError(f"no column {name!r} in table {self.table_id}")

    def ftypes(self) -> List[FieldType]:
        return [c.ftype for c in self.cols]

    def dict_encoded_cols(self) -> set:
        return {
            i for i, c in enumerate(self.cols) if c.dictionary is not None
        }

    def encode_dict_const(self, col_idx: int, s: str) -> int:
        """String constant -> dictionary code; -1 if absent (matches nothing,
        but keeps comparisons well-defined because codes are >= 0)."""
        d = self.cols[col_idx].dictionary
        if d is None:
            raise KVError("column not dict-encoded")
        j = bisect.bisect_left(d, s)
        if j < len(d) and d[j] == s:
            return j
        return -1
    def dict_bound(self, col_idx: int, s: str, side: str) -> int:
        """Code bound for range predicates on sorted dictionaries:
        side='left' -> first code with value >= s; 'right' -> first > s."""
        d = self.cols[col_idx].dictionary
        return (bisect.bisect_left if side == "left" else bisect.bisect_right)(d, s)

    # ------------------------------------------------------------------
    # bulk load (build base blocks)
    # ------------------------------------------------------------------
    def bulk_load_arrays(self, arrays: Sequence[np.ndarray],
                         valids: Optional[Sequence[Optional[np.ndarray]]] = None,
                         ts: int = 0,
                         dictionaries: Optional[dict] = None):
        """Append columnar data to base.  String columns take object arrays
        and are dictionary-encoded here — OR, Arrow-dictionary style, the
        caller passes `dictionaries[ci] = sorted unique values` and
        `arrays[ci]` as int codes into it (bulk generators/loaders skip
        the per-row encode entirely)."""
        with self._mu:
            n = len(arrays[0])
            assert all(len(a) == n for a in arrays), "ragged load"
            # New base rows take handles [base_rows, base_rows+n).  Delta
            # inserts committed before this load may already own handles in
            # that range (alloc_handle starts at next_handle); left alone,
            # their versions would shadow the loaded rows as phantom updates.
            # Fold the committed delta into base first so every existing row
            # gets a fresh sub-base_rows handle and the append region is free.
            if self.delta and (self.next_handle > self.base_rows
                               or any(h >= self.base_rows for h in self.delta)):
                if self.locks:
                    raise KVError(
                        "bulk load would collide with uncommitted rows")
                fold_ts = max(
                    [ts] + [c[-1].commit_ts for c in self.delta.values() if c])
                self.compact(fold_ts)
            # validate EVERY coded column before any block is appended: a
            # failure mid-loop would leave ragged columns (torn store)
            if dictionaries:
                for ci, new_dict in dictionaries.items():
                    self._validate_coded_locked(ci, arrays[ci], new_dict)
            for ci, (meta, arr) in enumerate(zip(self.cols, arrays)):
                valid = valids[ci] if valids else None
                if meta.ftype.kind == TypeKind.STRING:
                    if dictionaries is not None and ci in dictionaries:
                        arr = self._ingest_coded_locked(ci, meta, arr,
                                                 dictionaries[ci])
                    else:
                        codes, dictionary = _dict_encode_merge(
                            arr, meta.dictionary, self._blocks[ci]
                        )
                        meta.dictionary = dictionary
                        arr = codes
                else:
                    arr = np.asarray(arr)
                    if not (_narrows(meta) and arr.dtype.kind in "iu"):
                        arr = np.ascontiguousarray(
                            arr, dtype=meta.ftype.np_dtype)
                self._append_blocks_locked(ci, arr, valid)
            self.base_rows += n
            self.next_handle = max(self.next_handle, self.base_rows)
            self.base_ts = max(self.base_ts, ts)
            self.base_version += 1
            self._col_stats.clear()
            self.mutations += 1
            if self.on_mutate is not None:
                self.on_mutate()
            if self.persister is not None:
                self.persister.save_base(self)
        _publish_base_bytes()

    def _validate_coded_locked(self, ci: int, codes: np.ndarray, new_dict):
        """Pure validation for Arrow-style coded ingest (no mutation)."""
        if ci >= len(self.cols) or \
                self.cols[ci].ftype.kind != TypeKind.STRING:
            raise KVError(f"column {ci} is not a string column")
        new_dict = [str(x) for x in new_dict]
        if sorted(set(new_dict)) != new_dict:
            raise KVError("dictionary must be sorted unique strings")
        codes = np.asarray(codes)
        if len(codes) and (int(codes.min()) < 0
                           or int(codes.max()) >= len(new_dict)):
            raise KVError("dictionary codes out of range")
        if self.cols[ci].dictionary is None and self._blocks[ci]:
            raise KVError(
                "existing un-coded blocks: cannot attach a dictionary")

    def _ingest_coded_locked(self, ci: int, meta, codes: np.ndarray,
                      new_dict) -> np.ndarray:
        """Pre-encoded string ingest (validated up front by
        _validate_coded_locked): merge with the existing dictionary, remapping
        old blocks when code order shifts — same contract as
        _dict_encode_merge, minus the per-row encode."""
        new_dict = [str(x) for x in new_dict]
        codes = np.ascontiguousarray(codes, dtype=np.int32)
        if meta.dictionary is None or meta.dictionary == new_dict:
            meta.dictionary = new_dict
            return codes
        to_merged, merged = _merge_dictionary(meta.dictionary, new_dict,
                                              self._blocks[ci])
        meta.dictionary = merged
        return to_merged[codes]

    def _append_blocks_locked(self, ci: int, arr: np.ndarray, valid: Optional[np.ndarray]):
        blocks, valids = self._blocks[ci], self._valids[ci]
        narrow = narrowest if _narrows(self.cols[ci]) \
            else np.ascontiguousarray
        off = 0
        n = len(arr)
        # fill the last partial block first (it widens as np.concatenate
        # promotes it, where the new values need more)
        if blocks and len(blocks[-1]) < BLOCK_SIZE:
            space = BLOCK_SIZE - len(blocks[-1])
            take = min(space, n)
            old = blocks[-1]
            blocks[-1] = np.concatenate([old, narrow(arr[:take])])
            self._held += _block_bytes(blocks[-1]) - _block_bytes(old)
            if valids[-1] is not None or (valid is not None and not valid[:take].all()):
                old_v = (
                    valids[-1]
                    if valids[-1] is not None
                    else np.ones(len(blocks[-1]) - take, dtype=np.bool_)
                )
                new_v = (
                    valid[:take]
                    if valid is not None
                    else np.ones(take, dtype=np.bool_)
                )
                valids[-1] = np.concatenate([old_v, new_v])
            off = take
        while off < n:
            take = min(BLOCK_SIZE, n - off)
            blk = np.ascontiguousarray(narrow(arr[off : off + take]))
            blocks.append(blk)
            self._held += _block_bytes(blk)
            v = None
            if valid is not None and not valid[off : off + take].all():
                v = valid[off : off + take].copy()
            valids.append(v)
            off += take

    # ------------------------------------------------------------------
    # base block access (device scan path)
    # ------------------------------------------------------------------
    def iter_base_blocks(
        self, col_idx: Sequence[int], start: int, end: int,
        narrow: bool = False,
    ) -> Iterator[Tuple[int, List[np.ndarray], List[Optional[np.ndarray]]]]:
        """Yield (handle_offset, [col arrays], [col valids]) for each base
        block slice intersecting [start, end): each array in its column's
        logical dtype, or with `narrow` as the store holds it (an integer
        column's block in the narrowest type that holds it: for a caller
        that casts to a wire width of its own)."""
        # snapshot the block lists under the lock, then iterate the
        # locals: base blocks are append-only (compaction replaces the
        # whole lists), so the slices stay valid without holding the
        # mutex across yields
        with self._mu:
            end = min(end, self.base_rows)
            if start >= end:
                return
            blocks = {ci: list(self._blocks[ci]) for ci in col_idx}
            valids = {ci: list(self._valids[ci]) for ci in col_idx}
        wide = {ci: np.dtype(self.cols[ci].ftype.np_dtype) for ci in col_idx
                if not narrow and _narrows(self.cols[ci])}
        b0, b1 = start // BLOCK_SIZE, (end - 1) // BLOCK_SIZE
        for b in range(b0, b1 + 1):
            lo = max(start - b * BLOCK_SIZE, 0)
            hi = min(end - b * BLOCK_SIZE, BLOCK_SIZE)
            arrs, vals = [], []
            for ci in col_idx:
                blk = blocks[ci][b][lo:hi]
                arrs.append(blk.astype(wide[ci], copy=False) if ci in wide
                            else blk)
                v = valids[ci][b]
                vals.append(v[lo:hi] if v is not None else None)
            yield b * BLOCK_SIZE + lo, arrs, vals

    def base_chunk(self, col_idx: Sequence[int], start: int, end: int,
                   decode_strings: bool = True,
                   narrow: bool = False) -> Chunk:
        """Materialize base rows [start, end) as a host Chunk: integer
        columns in their logical dtype, or with `narrow` in the narrowest
        type that holds every block read (for a reader of the values'
        order and counts alone, as ANALYZE is)."""
        cols: List[Column] = []
        parts: List[List[np.ndarray]] = [[] for _ in col_idx]
        vparts: List[List[np.ndarray]] = [[] for _ in col_idx]
        any_rows = False
        for off, arrs, vals in self.iter_base_blocks(col_idx, start, end,
                                                     narrow=True):
            any_rows = True
            for i, (a, v) in enumerate(zip(arrs, vals)):
                parts[i].append(a)
                vparts[i].append(
                    v if v is not None else np.ones(len(a), dtype=np.bool_)
                )
        for i, ci in enumerate(col_idx):
            meta = self.cols[ci]
            if not any_rows:
                cols.append(Column.from_values(meta.ftype, []))
                continue
            data = np.concatenate(
                parts[i], dtype=meta.ftype.np_dtype
                if _narrows(meta) and not narrow else None)
            valid = np.concatenate(vparts[i])
            if meta.ftype.kind == TypeKind.STRING and decode_strings:
                data = _decode_dict(data, meta.dictionary)
            cols.append(Column(meta.ftype, data, None if valid.all() else valid))
        return Chunk(cols)

    def gather_chunk(self, col_idx: Sequence[int], handles: np.ndarray,
                     decode_strings: bool = True) -> Chunk:
        """Gather specific base rows by handle (vectorized per block) —
        the cheap path for sparse device-selected rows (TopN/filter)."""
        handles = np.asarray(handles, dtype=np.int64)
        n = len(handles)
        blk_ids = handles // BLOCK_SIZE
        offs = handles % BLOCK_SIZE
        uniq_blocks = np.unique(blk_ids)
        with self._mu:
            snap = {ci: (list(self._blocks[ci]), list(self._valids[ci]))
                    for ci in col_idx}
        cols: List[Column] = []
        for ci in col_idx:
            meta = self.cols[ci]
            blocks, valids = snap[ci]
            dt = meta.ftype.np_dtype if _narrows(meta) or not blocks \
                else blocks[0].dtype
            data = np.zeros(n, dtype=dt)
            valid = np.ones(n, dtype=np.bool_)
            for b in uniq_blocks:
                sel = blk_ids == b
                data[sel] = blocks[b][offs[sel]]
                v = valids[b]
                if v is not None:
                    valid[sel] = v[offs[sel]]
            if meta.ftype.kind == TypeKind.STRING and decode_strings:
                data = _decode_dict(data, meta.dictionary)
            cols.append(Column(meta.ftype, data,
                               None if valid.all() else valid))
        return Chunk(cols)

    # ------------------------------------------------------------------
    # MVCC delta (Percolator)
    # ------------------------------------------------------------------
    def prewrite(self, handle: int, op: str, values: Optional[tuple],
                 primary: Tuple[int, int], start_ts: int, ttl_ms: int = 3000,
                 check_ts: Optional[int] = None):
        """check_ts: conflict horizon — defaults to start_ts (optimistic);
        pessimistic lock acquisition and lock-upgrade pass for_update_ts so
        a commit between txn start and lock time is not a conflict
        (2pc.go pessimistic for_update_ts semantics)."""
        with self._mu:
            lk = self.locks.get(handle)
            if lk is not None and lk.start_ts != start_ts:
                raise LockedError((self.table_id, handle), lk.start_ts)
            chain = self.delta.get(handle)
            horizon = check_ts if check_ts is not None else start_ts
            if chain and chain[-1].commit_ts > horizon:
                raise TxnConflictError((self.table_id, handle))
            self.locks[handle] = Lock(start_ts, primary, op, values, ttl_ms)

    def commit(self, handle: int, start_ts: int, commit_ts: int):
        with self._mu:
            lk = self.locks.get(handle)
            if lk is None or lk.start_ts != start_ts:
                # already committed (idempotent) or rolled back
                chain = self.delta.get(handle, [])
                for v in reversed(chain):
                    if v.start_ts == start_ts:
                        return
                raise TxnConflictError((self.table_id, handle))
            del self.locks[handle]
            if lk.op == "lock":
                return
            ver = Version(commit_ts, start_ts, lk.op, lk.values)
            self.delta.setdefault(handle, []).append(ver)
            self.mutations += 1
            if self.on_mutate is not None:
                self.on_mutate()
            if self.persister is not None:
                self.persister.append_delta(handle, ver)

    def rollback(self, handle: int, start_ts: int):
        with self._mu:
            lk = self.locks.get(handle)
            if lk is not None and lk.start_ts == start_ts:
                del self.locks[handle]

    def check_lock(self, handle: int, read_ts: int) -> Optional[Lock]:
        lk = self.locks.get(handle)
        if lk is not None and lk.start_ts <= read_ts and lk.op != "lock":
            return lk
        return None

    def visible_version(self, handle: int, ts: int) -> Optional[Version]:
        chain = self.delta.get(handle)
        if not chain:
            return None
        for v in reversed(chain):
            if v.commit_ts <= ts:
                return v
        return None

    def check_read_horizon(self, ts: int):
        """Fail loudly when a read's TSO predates the base rebuild
        (compaction / bulk load / DDL rebuild): the data the reader should
        see no longer exists, and every read path — copr scan, point get,
        index-side overlay — must surface that rather than returning
        empty/future rows (TiDB's 'snapshot is older than GC safe point')."""
        with self._mu:
            base_ts = self.base_ts
        if 0 < ts < base_ts:
            raise KVError(
                "snapshot is older than the compaction horizon "
                f"(read ts {ts} < base ts {base_ts})")

    def read_row(self, handle: int, ts: int,
                 resolve_locks: bool = True) -> Optional[tuple]:
        """Point read at snapshot ts (None = not found)."""
        with self._mu:
            self.check_read_horizon(ts)
            lk = self.check_lock(handle, ts)
            if lk is not None:
                raise LockedError((self.table_id, handle), lk.start_ts)
            v = self.visible_version(handle, ts)
            if v is not None:
                return v.values if v.op == "put" else None
            if handle < self.base_rows and self.base_ts <= ts:
                return tuple(
                    self.base_chunk(range(self.n_cols), handle, handle + 1).row(0)
                )
            return None

    def delta_overlay(self, ts: int, start: int, end: int):
        """(deleted_base_handles, inserted_rows{handle: values}) visible at ts.

        A 'put' on a base handle counts as delete+insert (update)."""
        deleted: List[int] = []
        inserted: Dict[int, tuple] = {}
        with self._mu:
            for h, chain in self.delta.items():
                if not (start <= h < end):
                    continue
                lk = self.check_lock(h, ts)
                if lk is not None:
                    raise LockedError((self.table_id, h), lk.start_ts)
                v = None
                for ver in reversed(chain):
                    if ver.commit_ts <= ts:
                        v = ver
                        break
                if v is None:
                    continue
                if h < self.base_rows:
                    deleted.append(h)
                if v.op == "put":
                    inserted[h] = v.values
        return deleted, inserted

    def alloc_handle(self) -> int:
        with self._mu:
            h = self.next_handle
            self.next_handle += 1
            return h

    # ------------------------------------------------------------------
    # delta-merge compaction
    # ------------------------------------------------------------------
    def compact(self, ts: int):
        """Fold delta (committed, visible at ts) into fresh base blocks."""
        with self._mu:
            if self.locks:
                raise KVError("cannot compact with live locks")
            deleted, inserted = self.delta_overlay(ts, 0, 1 << 62)
            del_set = set(deleted)
            chunk = self.base_chunk(range(self.n_cols), 0, self.base_rows)
            keep = np.ones(self.base_rows, dtype=np.bool_)
            for h in del_set:
                keep[h] = False
            base = chunk.filter(keep) if self.base_rows else chunk
            extra_rows = [inserted[h] for h in sorted(inserted)]
            arrays, valids = [], []
            for ci, meta in enumerate(self.cols):
                col = base.col(ci)
                data = col.data
                valid = col.validity()
                if extra_rows:
                    ev = [r[ci] for r in extra_rows]
                    evalid = np.array([x is not None for x in ev], dtype=np.bool_)
                    if meta.ftype.kind == TypeKind.STRING:
                        earr = np.empty(len(ev), dtype=object)
                        for j, x in enumerate(ev):
                            earr[j] = x if x is not None else ""
                    else:
                        earr = np.zeros(len(ev), dtype=meta.ftype.np_dtype)
                        for j, x in enumerate(ev):
                            if x is not None:
                                earr[j] = x
                    data = np.concatenate([data, earr])
                    valid = np.concatenate([valid, evalid])
                arrays.append(data)
                valids.append(valid)
            # rebuild
            self._blocks = [[] for _ in self.cols]
            self._valids = [[] for _ in self.cols]
            self._held = 0
            for meta in self.cols:
                meta.dictionary = None
            self.base_rows = 0
            self.delta.clear()
            self.bulk_load_arrays(arrays, valids, ts)
            self.next_handle = self.base_rows

    def gc(self, safepoint: int) -> int:
        """Drop versions no reader at ts >= safepoint can see; returns the
        number of versions pruned (counted under the store lock).

        Reference: store/tikv/gcworker (gc_worker.go:213-289)."""
        pruned = 0
        with self._mu:
            for h in list(self.delta):
                chain = self.delta[h]
                # keep the newest version <= safepoint plus all > safepoint
                keep_from = 0
                for i, v in enumerate(chain):
                    if v.commit_ts <= safepoint:
                        keep_from = i
                pruned += keep_from
                self.delta[h] = chain[keep_from:]
        return pruned

    def column_stats(self, ci: int) -> Tuple[int, int, bool]:
        """(min, max, has_null) over base blocks for numeric/dict columns.
        Used by the device engine to bound group-code spaces and by the
        planner for range estimation.  Cached per base_version."""
        with self._mu:
            cached = self._col_stats.get(ci)
            if cached is not None:
                return cached
            meta = self.cols[ci]
            lo, hi, has_null = 0, -1, False
            if meta.ftype.kind == TypeKind.STRING:
                lo, hi = 0, len(meta.dictionary or []) - 1
                for v in self._valids[ci]:
                    if v is not None and not v.all():
                        has_null = True
                        break
            else:
                first = True
                for blk, v in zip(self._blocks[ci], self._valids[ci]):
                    if v is None:
                        vals = blk
                    else:
                        if not v.all():
                            has_null = True
                        vals = blk[v]
                    if len(vals) == 0:
                        continue
                    bmin = int(np.floor(float(vals.min())))
                    bmax = int(np.ceil(float(vals.max())))
                    if first:
                        lo, hi, first = bmin, bmax, False
                    else:
                        lo, hi = min(lo, bmin), max(hi, bmax)
            out = (lo, hi, has_null)
            self._col_stats[ci] = out
            return out

    def nbytes(self) -> int:
        """Bytes of the base blocks as held (integers at their narrow
        widths; an object value counted as its 8-byte reference)."""
        with self._mu:
            return self._held

    def recount_held(self):
        """Recount `nbytes` after the block lists were replaced whole (the
        persister's restore), and publish the gauge."""
        with self._mu:
            self._held = sum(_block_bytes(b) for blocks in self._blocks
                             for b in blocks)
        _publish_base_bytes()


def _block_bytes(b: np.ndarray) -> int:
    return b.nbytes if b.dtype != object else len(b) * 8


def _decode_dict(codes: np.ndarray, dictionary: Optional[List[str]]) -> np.ndarray:
    """int32 codes -> object array of strings (vectorized; out-of-range -> "")."""
    d = np.asarray(dictionary or [], dtype=object)
    if len(d) == 0:
        out = np.empty(len(codes), dtype=object)
        out[:] = ""
        return out
    safe = np.clip(codes, 0, len(d) - 1)
    out = d[safe]
    bad = (codes < 0) | (codes >= len(d))
    if bad.any():
        out = out.copy()
        out[bad] = ""
    return out


def _merge_dictionary(old_dict, new_values, existing_blocks):
    """Merge sorted dictionaries, remapping existing coded blocks in place
    when code order shifts; returns (to_merged codes map, merged dict).
    The single authority for the sorted-merge invariant (three callers)."""
    merged = sorted(set(old_dict) | set(new_values))
    if merged != old_dict and old_dict:
        remap_old = np.array([merged.index(s) for s in old_dict],
                             dtype=np.int32)
        for i, blk in enumerate(existing_blocks):
            existing_blocks[i] = remap_old[blk]
    to_merged = np.array([merged.index(s) for s in new_values],
                         dtype=np.int32)
    return to_merged, merged


def _categorical_encode_fast(arr: np.ndarray):
    """Low-cardinality object-array encode: one vectorized C-level
    equality pass per distinct value instead of a per-element Python
    loop (~20x on TPC-H flag columns).  Returns (codes_by_discovery,
    values) or None when the fast path doesn't apply (cardinality > 256
    or non-str elements whose str() collides with another element)."""
    n = len(arr)
    # cheap cardinality/type probe: a non-categorical column must not pay
    # up to 256 full passes before bailing (compact() re-encodes every
    # string column through here)
    probe = arr[:2048]
    if len({str(x) for x in probe}) > 64:
        return None
    codes = np.full(n, -1, dtype=np.int32)
    values: List[str] = []
    seen = set()
    while n:
        rem = codes < 0
        idx = int(np.argmax(rem))
        if not rem[idx]:
            break
        x = arr[idx]
        if type(x) is not str:
            # non-str elements: object equality would collapse
            # cross-type-equal values (5 vs 5.0) into one entry — the
            # slow path's str() encoding is the semantic authority
            return None
        if x in seen or len(values) >= 256:
            return None  # high cardinality beyond the probe window
        m = rem & (arr == x)
        seen.add(x)
        codes[m] = len(values)
        values.append(x)
    return codes, values


def _dict_encode_merge(arr: np.ndarray, old_dict: Optional[List[str]],
                       existing_blocks: List[np.ndarray]):
    """Encode object-array strings; if a dictionary already exists and new
    values appear, rebuild the dictionary sorted and remap existing blocks
    in place (keeps code order == string order)."""
    fast = _categorical_encode_fast(arr)
    if fast is not None:
        raw_codes, raw_values = fast
        order = sorted(range(len(raw_values)),
                       key=lambda i: raw_values[i])
        values = [raw_values[i] for i in order]
        recode = np.empty(len(raw_values), dtype=np.int32)
        for new_i, old_i in enumerate(order):
            recode[old_i] = new_i
        sorted_codes = recode[raw_codes]
        if old_dict is None:
            return sorted_codes, values
        to_merged, merged = _merge_dictionary(old_dict, values,
                                              existing_blocks)
        return to_merged[sorted_codes], merged
    values = sorted(set(str(x) for x in arr))
    if old_dict is None:
        dictionary = values
        lookup = {s: i for i, s in enumerate(dictionary)}
        codes = np.fromiter(
            (lookup[str(x)] for x in arr), dtype=np.int32, count=len(arr)
        )
        return codes, dictionary
    merged = sorted(set(old_dict) | set(values))
    if merged != old_dict:
        remap = np.array(
            [merged.index(s) for s in old_dict], dtype=np.int32
        ) if old_dict else np.zeros(0, np.int32)
        for i, blk in enumerate(existing_blocks):
            existing_blocks[i] = remap[blk]
    lookup = {s: i for i, s in enumerate(merged)}
    codes = np.fromiter(
        (lookup[str(x)] for x in arr), dtype=np.int32, count=len(arr)
    )
    return codes, merged
