"""Table data durability: base-block snapshots + committed-delta log.

Recovery model mirrors the reference (SURVEY.md §3.4): all durable state is
reconstructible from the store — a restarting node reloads and serves; device
memory is purely a cache.  Layout per table under <data_dir>/tables/:

    t<id>.base.npz   immutable base blocks (string cols as dict codes +
                     dictionary), written atomically on bulk load / compact
    t<id>.delta.log  append-only JSON lines of committed MVCC versions
                     (prewrite locks are volatile BY DESIGN: a crash aborts
                     in-flight transactions exactly like Percolator's lock
                     resolution path, mvcc_leveldb.go's lock column family)

The delta log truncates whenever the base snapshot is rewritten (compaction
folds the log in, the reference's delta-merge).
"""

from __future__ import annotations

import json
import os
import tempfile
from typing import Optional

import numpy as np

from ..types import TypeKind
from .blockstore import TableStore, Version


class CorruptDeltaLogError(RuntimeError):
    """A delta-log record BEFORE the final line failed to parse: not a
    torn tail (crash-truncation only ever clips the end) but real
    corruption — surfaced instead of silently dropping committed data."""


class TablePersister:
    def __init__(self, data_dir: str, table_id: int):
        self.dir = os.path.join(data_dir, "tables")
        os.makedirs(self.dir, exist_ok=True)
        self.base_path = os.path.join(self.dir, f"t{table_id}.base.npz")
        self.delta_path = os.path.join(self.dir, f"t{table_id}.delta.log")
        self._delta_f = None

    # ---- write side ----------------------------------------------------
    def save_base(self, store: TableStore):
        """Atomic snapshot of the base blocks; truncates the delta log
        (callers hold the store lock or are single-threaded loaders)."""
        arrays = {}
        meta = {
            "base_rows": store.base_rows,
            "base_ts": store.base_ts,
            "next_handle": store.next_handle,
            "dicts": [c.dictionary for c in store.cols],
        }
        for ci, colmeta in enumerate(store.cols):
            blocks = store._blocks[ci]
            valids = store._valids[ci]
            if blocks:
                cat = np.concatenate(blocks)
                if cat.dtype == object:
                    # JSON / wide-decimal columns: pickle-free persistence
                    # as unicode (wide decimals as digit strings)
                    cat = np.array([str(x) for x in cat])
                arrays[f"d{ci}"] = cat
            else:
                arrays[f"d{ci}"] = np.zeros(0, dtype=np.int64)
            vparts = [
                v if v is not None else np.ones(len(b), dtype=np.bool_)
                for b, v in zip(blocks, valids)
            ]
            arrays[f"v{ci}"] = (
                np.concatenate(vparts) if vparts
                else np.zeros(0, dtype=np.bool_)
            )
        fd, tmp = tempfile.mkstemp(dir=self.dir, suffix=".tmp")
        try:
            with os.fdopen(fd, "wb") as f:
                np.savez(f, meta=json.dumps(meta), **arrays)
                f.flush()
                os.fsync(f.fileno())
            os.replace(tmp, self.base_path)
            self._fsync_dir()
        except BaseException:
            if os.path.exists(tmp):
                os.unlink(tmp)
            raise
        # the delta log is NOT simply truncated: committed versions may
        # still live only in memory (e.g. INSERTs followed by a bulk load).
        # Rewrite it from the in-memory delta so base+log always equal the
        # full committed state.
        self._close_delta()
        if store.delta:
            fd, tmp = tempfile.mkstemp(dir=self.dir, suffix=".tmp")
            try:
                with os.fdopen(fd, "w") as f:
                    for h in sorted(store.delta):
                        for ver in store.delta[h]:
                            rec = [h, ver.commit_ts, ver.start_ts, ver.op,
                                   ver.values]
                            f.write(json.dumps(rec, default=_np_scalar) + "\n")
                    f.flush()
                    os.fsync(f.fileno())
                os.replace(tmp, self.delta_path)
                self._fsync_dir()
            except BaseException:
                if os.path.exists(tmp):
                    os.unlink(tmp)
                raise
        elif os.path.exists(self.delta_path):
            os.unlink(self.delta_path)
            self._fsync_dir()

    def append_delta(self, handle: int, ver: Version):
        """Durable-on-commit: the record hits the platters before commit()
        returns, the reference's model (mvcc_leveldb.go:39 — leveldb WAL
        syncs per write batch)."""
        if self._delta_f is None:
            self._delta_f = open(self.delta_path, "a")
        rec = [handle, ver.commit_ts, ver.start_ts, ver.op, ver.values]
        self._delta_f.write(json.dumps(rec, default=_np_scalar) + "\n")
        self._delta_f.flush()
        os.fsync(self._delta_f.fileno())

    def _fsync_dir(self):
        """Make a rename/unlink durable: fsync the containing directory."""
        fd = os.open(self.dir, os.O_RDONLY)
        try:
            os.fsync(fd)
        finally:
            os.close(fd)

    def _close_delta(self):
        if self._delta_f is not None:
            self._delta_f.close()
            self._delta_f = None

    def remove(self):
        self._close_delta()
        for p in (self.base_path, self.delta_path):
            if os.path.exists(p):
                os.unlink(p)

    # ---- read side -----------------------------------------------------
    def load(self, store: TableStore) -> bool:
        """Restore base + delta into a freshly created store; False if
        nothing exists on disk.  A table written only through DML has a
        delta log but no base snapshot — both parts are independent."""
        found = False
        if os.path.exists(self.base_path):
            found = True
            self._load_base(store)
        if os.path.exists(self.delta_path):
            found = True
            # two STREAMED passes (a post-write-burst log can be large;
            # never materialize it): first find the final record's line
            # index — the only one torn-tail tolerance may drop
            last_payload = None
            with open(self.delta_path, "rb") as f:
                for i, bline in enumerate(f):
                    if bline.strip():
                        last_payload = i
            torn_offset = None
            unterminated = False
            with open(self.delta_path, "rb") as f:
                offset = 0
                for i, bline in enumerate(f):
                    line_start = offset
                    offset += len(bline)
                    payload = bline.decode("utf-8", "replace").strip()
                    if not payload:
                        continue
                    if i == last_payload and not bline.endswith(b"\n"):
                        unterminated = True
                    try:
                        h, cts, sts, op, values = json.loads(payload)
                    except (ValueError, TypeError) as e:
                        if i == last_payload:
                            # torn tail: the writer died mid-append — the
                            # record never committed (commit() returns only
                            # after fsync of the FULL line), so dropping it
                            # IS the correct recovery (leveldb WAL
                            # semantics: a truncated final record drops)
                            import logging

                            from ..metrics import REGISTRY

                            REGISTRY.inc("delta_log_torn_tail_total")
                            logging.getLogger("tidb_tpu.store").warning(
                                "dropping torn final delta-log record in "
                                "%s (%d bytes): %s",
                                self.delta_path, len(payload), e)
                            torn_offset = line_start
                            break
                        raise CorruptDeltaLogError(
                            f"{self.delta_path}: corrupt record at line "
                            f"{i + 1} (not the final line): {e}") from e
                    store.delta.setdefault(h, []).append(
                        Version(cts, sts, op,
                                tuple(values) if values is not None else None)
                    )
                    store.next_handle = max(store.next_handle, h + 1)
            if torn_offset is not None or unterminated:
                # REPAIR the log before accepting new appends: the next
                # append_delta opens in 'a' mode, and a record written
                # after torn bytes (or after a complete-but-unterminated
                # final line) would merge into one unparseable line —
                # silently losing committed rows on the following reopen
                with open(self.delta_path, "r+b") as f:
                    if torn_offset is not None:
                        f.truncate(torn_offset)
                    else:
                        f.seek(0, os.SEEK_END)
                        f.write(b"\n")
                    f.flush()
                    os.fsync(f.fileno())
                self._fsync_dir()
        return found

    def _load_base(self, store: TableStore):
        with np.load(self.base_path, allow_pickle=False) as z:
            meta = json.loads(str(z["meta"]))
            for ci, colmeta in enumerate(store.cols):
                data = z[f"d{ci}"]
                valid = z[f"v{ci}"]
                if (colmeta.ftype.np_dtype == object
                        and data.dtype.kind == "U"):
                    wide_dec = colmeta.ftype.kind == TypeKind.DECIMAL
                    obj = np.empty(len(data), dtype=object)
                    for i, txt in enumerate(data):
                        obj[i] = int(txt) if wide_dec else str(txt)
                    data = obj
                store._blocks[ci] = []
                store._valids[ci] = []
                if len(data):
                    # re-block without re-encoding: dictionaries restore
                    # verbatim, so codes stay valid; integer blocks are
                    # held narrow again, as the load held them
                    from .blockstore import BLOCK_SIZE, _narrows, narrowest

                    narrow = narrowest if _narrows(colmeta) \
                        else np.ascontiguousarray
                    for off in range(0, len(data), BLOCK_SIZE):
                        blk = data[off: off + BLOCK_SIZE]
                        vb = valid[off: off + BLOCK_SIZE]
                        store._blocks[ci].append(
                            np.ascontiguousarray(narrow(blk)))
                        store._valids[ci].append(
                            None if vb.all() else vb.copy()
                        )
                colmeta.dictionary = meta["dicts"][ci]
        store.base_rows = meta["base_rows"]
        store.base_ts = meta["base_ts"]
        store.next_handle = meta["next_handle"]
        # secondary indexes rebuild lazily: IndexManager caches are keyed on
        # base_version, which is bumped here
        store.base_version += 1
        store._col_stats.clear()
        store.recount_held()


def _np_scalar(o):
    if hasattr(o, "item"):
        return o.item()
    raise TypeError(f"not JSON serializable: {type(o)}")


class JsonStatePersister:
    """Small durable JSON document with the same crash contract as the
    table persister: atomic tmp-write + rename + dir fsync, torn/corrupt
    files load as `None` instead of crashing the owner.  Backs the
    coordination plane's membership/handoff state (ISSUE 12: a
    coordinator restart replays the epoch instead of starting at 0)."""

    def __init__(self, path: str):
        self.path = path
        self.dir = os.path.dirname(os.path.abspath(path)) or "."
        os.makedirs(self.dir, exist_ok=True)

    def save(self, doc: dict):
        fd, tmp = tempfile.mkstemp(dir=self.dir, suffix=".tmp")
        try:
            with os.fdopen(fd, "w") as f:
                json.dump(doc, f, default=_np_scalar)
                f.flush()
                os.fsync(f.fileno())
            os.replace(tmp, self.path)
            dfd = os.open(self.dir, os.O_RDONLY)
            try:
                os.fsync(dfd)
            finally:
                os.close(dfd)
        except BaseException:
            if os.path.exists(tmp):
                os.unlink(tmp)
            raise

    def load(self) -> Optional[dict]:
        if not os.path.exists(self.path):
            return None
        try:
            with open(self.path, "r", encoding="utf-8") as f:
                doc = json.load(f)
            return doc if isinstance(doc, dict) else None
        except (ValueError, OSError):
            return None  # torn write: the owner starts fresh

    def remove(self):
        if os.path.exists(self.path):
            os.unlink(self.path)
