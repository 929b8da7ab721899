"""Mesh-parallel coprocessor scans: shard_map + XLA collectives.

This is the multi-chip execution path the reference implements with a
distributed scan fan-out + partial/final merge (store/tikv/coprocessor.go:
220-560 buildCopTasks/worker pool; executor/aggregate.go:101-169 the
partial/final agg split).  TPU-native redesign:

- The table's base tiles form ONE global array per column, shape
  [n_tiles, TILE], sharded over a 1-D `jax.sharding.Mesh` ("dp" axis) —
  region → shard assignment is the device placement of tiles.
- The whole scan is ONE compiled XLA program under `shard_map`: each shard
  filters + partially aggregates its local tiles, then the partial/final
  merge happens ON DEVICE via collectives (`psum` / `pmin` / `pmax` over
  ICI), so a steady-state aggregation moves only G-sized finals to host.
- TopN: per-shard `lax.top_k`, gathered per shard, host merge (keep-order
  merge of the reference's copIterator).
- Filter: per-shard mask compute, host gathers selected rows.

On a single chip the same program runs on a mesh of one (psum is identity)
and still beats the per-tile dispatch loop: one XLA dispatch for the whole
table instead of one per tile.

Tests run this on 8 virtual CPU devices (tests/conftest.py); the driver's
`dryrun_multichip` runs the full Domain query path over this module.
"""

from __future__ import annotations

import threading
import time
import zlib
from typing import Dict, List, Optional, Tuple

import numpy as np

from .. import ops  # noqa: F401  (configures x64)
import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
from ..util_concurrency import make_lock

from jax import shard_map

from .. import serving as _serving
from ..chunk import Chunk, Column
from ..coord import CoordEpochMismatch
from ..metrics import REGISTRY
from ..store.fault import FAILPOINTS
from ..store.kv import CopRequest
from ..types import TypeKind
from .device_health import (
    DEVICE_HEALTH,
    DeviceFailure,
    attribute_devices,
    classify_failure,
)
from .cache import thread_misses
from .ir import DAG
from .jax_eval import JaxUnsupported, compile_expr
from . import jax_engine as je
from .jax_engine import _Analyzed, _fingerprint, _gather_tile, _to_state_dtype


# ---------------------------------------------------------------------------
# mesh + sharded tile cache
# ---------------------------------------------------------------------------

_MESH: Optional[Mesh] = None
#: membership epoch the current _MESH was derived from (coord plane):
#: stamped under _MESH_LOCK by get_mesh, compared at every dispatch —
#: a mismatch means some host changed the survivor set after we built,
#: and dispatching anyway risks an XLA collective desync/hang
_MESH_EPOCH: Optional[int] = None
_MESH_LOCK = make_lock("copr.parallel:_MESH_LOCK")
_DIST_INIT = False

# ONE collective program in flight per process: concurrent shard_map
# launches from different server worker threads interleave their XLA
# collective-rendezvous participants and DEADLOCK (observed on the
# 8-virtual-device CPU harness the moment the concurrent-client bench
# drove N connections; a single-stream workload never trips it).  The
# mesh is one shared resource — dispatches serialize on it, and the
# serving layer's micro-batcher is the mechanism that turns that
# serialization back into parallelism (N queries -> one dispatch).
DISPATCH_LOCK = make_lock("copr.parallel:DISPATCH_LOCK")


def _maybe_init_multihost():
    """Multi-host (DCN) bring-up seam: when TIDB_TPU_COORDINATOR is set,
    join the jax.distributed cluster before building the mesh, so
    jax.devices() spans every host's chips and the same shard_map program
    runs dp over ICI within a host and DCN across hosts.  This replaces the
    reference's NCCL/MPI store-client fabric with XLA's collective runtime;
    single-host runs skip it entirely.

    Env: TIDB_TPU_COORDINATOR=host:port, TIDB_TPU_NUM_PROCESSES,
    TIDB_TPU_PROCESS_ID (jax.distributed.initialize contract)."""
    global _DIST_INIT
    if _DIST_INIT:
        return
    import os

    coord = os.environ.get("TIDB_TPU_COORDINATOR")
    if not coord:
        _DIST_INIT = True
        return
    jax.distributed.initialize(
        coordinator_address=coord,
        num_processes=int(os.environ.get("TIDB_TPU_NUM_PROCESSES", "1")),
        process_id=int(os.environ.get("TIDB_TPU_PROCESS_ID", "0")),
    )
    _DIST_INIT = True  # only latched on success (a raise retries next call)
    # coordination plane (ISSUE 9): when TIDB_TPU_COORD_ADDR is also
    # set, the SAME processes form the control plane — process 0 binds,
    # everyone registers its local device ids, and all block until the
    # cluster FORMS so the first mesh derives from one broadcast
    addr = os.environ.get("TIDB_TPU_COORD_ADDR")
    if addr:
        from ..coord import activate_env_plane

        activate_env_plane(
            addr,
            pid=int(os.environ.get("TIDB_TPU_PROCESS_ID", "0")),
            devices=[d.id for d in jax.local_devices()],
            expect=int(os.environ.get("TIDB_TPU_NUM_PROCESSES", "1")),
        )


def _eligible_devices():
    """(mesh-eligible devices, membership epoch they derive from).

    Single-process: the full visible set minus tripped breakers (plus
    half-open probe admissions), published to the coordination plane so
    /status membership stays truthful.  Multi-process: the plane's
    epoch-numbered membership broadcast — every process filters from
    the SAME broadcast, so survivor meshes stay identical across hosts
    (this closes the "health filtering skipped on multi-host" hole: a
    breaker trip on ANY host shrinks everyone's mesh).  Before the
    cluster has formed the full device set is used on every process
    identically, which is the pre-coordination behavior."""
    from ..coord import get_plane

    plane = get_plane()
    devs = list(jax.devices())
    if jax.process_count() > 1:
        # drive the LOCAL breaker state machine even though filtering is
        # membership-driven here: select_devices is what transitions
        # TRIPPED -> PROBING once a cooldown lapses, and that transition
        # publishes through the epoch hook (report -> regrown broadcast
        # -> epoch bump), so a probe-eligible chip rejoins every host's
        # mesh for its half-open trial instead of staying excluded until
        # a process restart
        DEVICE_HEALTH.select_devices(
            [d for d in devs
             if d.process_index == jax.process_index()])
        view = plane.view()
        if view.formed and view.members:
            allowed = view.device_ids()
            sel = [d for d in devs if d.id in allowed]
            if sel:
                return sel, view.epoch
        return devs, view.epoch
    healthy = DEVICE_HEALTH.select_devices(devs)
    chosen = healthy if healthy else devs  # all tripped: callers gate
    plane.publish_local(tuple(d.id for d in chosen))
    return chosen, plane.current_epoch()


def _no_eligible_devices() -> bool:
    """True when every breaker is open with no probe due — the mesh path
    must step down to the per-region rung (checked on entry AND after
    each consumed failure, since a retry may have tripped the last one)."""
    return (jax.process_count() == 1
            and not DEVICE_HEALTH.select_devices(list(jax.devices())))


def get_mesh() -> Mesh:
    """Process-wide 1-D device mesh over every mesh-eligible device (all
    hosts' devices once the multi-host seam has joined the cluster).  The
    mesh REBUILDS whenever the eligible set changes — a tripped breaker
    shrinks it to the survivors, a successful half-open probe restores it
    (region_cache.go invalidateStore -> reload, on devices)."""
    global _MESH, _MESH_EPOCH
    _maybe_init_multihost()
    # serialize check-and-rebuild AND snapshot eligibility under the
    # lock: with breakers changing the eligible set at runtime, a racing
    # producer thread holding a pre-trip snapshot could otherwise
    # reinstate a mesh containing the just-quarantined device
    with _MESH_LOCK:
        devs, epoch = _eligible_devices()
        ids = tuple(d.id for d in devs)
        if _MESH is None or tuple(d.id for d in _MESH.devices.ravel()) != ids:
            if _MESH is not None:
                from ..metrics import REGISTRY

                REGISTRY.inc("mesh_rebuilds_total")
            FAILPOINTS.hit("mesh/rebuild", device_ids=ids)
            _MESH = Mesh(np.array(devs), ("dp",))
        # restamp even when the device set is unchanged: an epoch bump
        # without a visible device change (a lost member whose devices
        # we never saw, a chaos bump) must not leave a stale stamp that
        # fails every later dispatch check
        _MESH_EPOCH = epoch
        return _MESH


def mesh_epoch() -> Optional[int]:
    """Membership epoch the current mesh was built from (tests,
    /status)."""
    return _MESH_EPOCH


def _check_membership_epoch():
    """Dispatch-time epoch guard (coord plane): the chaos site
    coord/member_lost lands a membership change exactly here, and a
    real cross-host change (breaker trip, lease expiry, rejoin) between
    mesh build and dispatch is detected the same way.  Raises the typed
    retriable CoordEpochMismatch — try_run_mesh rebuilds from the new
    broadcast and re-runs — instead of launching into an XLA collective
    whose participant set no longer matches other hosts (a desync that
    presents as a hang)."""
    from ..coord import get_plane

    FAILPOINTS.hit("coord/member_lost", epoch=_MESH_EPOCH)
    ep = get_plane().current_epoch()
    if _MESH_EPOCH is not None and ep != _MESH_EPOCH:
        from ..metrics import REGISTRY

        REGISTRY.inc("coord_epoch_mismatch_total")
        raise CoordEpochMismatch(_MESH_EPOCH, ep)


def _layout(base_rows: int, n_shards: int, table=None
            ) -> Tuple[int, int, int]:
    """(tiles that hold a row, n_tiles_padded, tiles_per_shard) for a
    table.

    With shape buckets on (tidb_tpu_shape_buckets, the default) the tiles
    of ONE SHARD pad up to a class that steps in eighths of its power of
    two (serving.tile_bucket): tables whose row counts fall in the same
    class — and the SAME table as it grows within a class — share one
    compiled shard_map program shape, and every shard holds its
    `tiles_per_shard` of the row order, so the rows spread over all
    shards.  Padded tiles are zeros and always masked (the row mask clips
    to [start, end) which never exceeds base_rows), so results are
    identical; the cost is masked compute, at most 12.5% of the scan (7
    tiles a shard under 64), and a table that grows through a step
    compiles its program classes once more.

    The layout autotuner can flip a table's tiling to EXACT (`table`
    given + the tuner's tile-bucket decision): under HBM pressure the
    padding is pure wasted capacity, so capacity-squeezed tables trade
    program reuse for resident bytes.  Exact or with buckets off, a
    shard of more than 8 tiles is still whole groups of 8 (at most 7
    tiles more): the dense aggregate's blocked row view (_RowView) needs
    them, and without it the program writes its columns out in full."""
    n_tiles = max(-(-base_rows // je.TILE), 1)
    Tl = -(-n_tiles // n_shards)
    if _serving.shape_buckets_enabled() and _tile_bucket(table) == "pow2":
        Tl = _serving.tile_bucket(Tl)
    elif Tl > 8:
        Tl = -(-Tl // 8) * 8
    return n_tiles, Tl * n_shards, Tl


def _tile_bucket(table) -> str:
    """The autotuner's table-level tiling decision: 'pow2' (the default;
    the name is from when the class was a power of two) buckets the
    shard's tiles, 'exact' does not."""
    if table is None:
        return "pow2"
    from ..layout import LAYOUT, layout_enabled

    if not layout_enabled():
        return "pow2"
    try:
        return LAYOUT.tile_bucket(table)
    except Exception:
        return "pow2"  # a tuner hiccup must never reshape a scan


def _full_dtype(kind) -> np.dtype:
    """The dtype compile_expr expects for a column of this kind (what the
    device program casts the wire array to before any arithmetic)."""
    if kind in (TypeKind.DATE, TypeKind.STRING):
        return np.dtype(np.int32)
    if kind == TypeKind.FLOAT:
        return np.dtype(np.float64)
    return np.dtype(np.int64)


def _wire_dtype(table, store_ci: int) -> np.dtype:
    """Narrowest integer dtype that exactly holds the column's base values
    (and 0, the pad value).  Host-to-device transfer time and HBM read
    bandwidth both scale with wire width, so an int64 column whose values
    fit int8 transfers AND scans 8x cheaper (not measured on the attached
    chip); the device
    program widens in-register (XLA fuses the convert into consumers).
    Floats stay f64: value-preserving narrowing is not generally exact."""
    full = _full_dtype(table.cols[store_ci].ftype.kind)
    if full == np.float64:
        return full
    lo, hi, _ = table.column_stats(store_ci)
    if hi < lo:  # empty: stats sentinel
        return np.dtype(np.int8)
    for cand in (np.int8, np.int16, np.int32):
        info = np.iinfo(cand)
        if info.min <= min(lo, 0) and max(hi, 0) <= info.max:
            return np.dtype(cand) if np.dtype(cand).itemsize \
                < full.itemsize else full
    return full


def _hot_priority(key: tuple) -> float:
    """Value-weighted eviction rank for a hot mesh-cache key: the layout
    autotuner's per-column residency priority (lowest evicts first).
    With the layout engine disabled every key ranks equal, which makes
    min() pick the FIFO head — the pre-layout behavior exactly."""
    from ..layout import LAYOUT, layout_enabled

    if not layout_enabled():
        return 0.0
    return LAYOUT.priority(key[0], key[2])


def _hot_demote(key: tuple, _value: tuple):
    """Demote an evicted hot column to the compressed cold tier
    (demote-to-cold before drop).  Only packable columns of a live store
    whose mesh still matches compress; everything else just drops (and
    reloads — possibly cold — on next access).  The evicted device
    arrays in `_value` feed the device-side re-encode (layout
    follow-up (e)) so demotion reads back packed codes, not host
    blocks."""
    from ..layout import COLD_CACHE, LAYOUT, compress_column, layout_enabled
    from ..layout.coldtier import pack_info
    from ..metrics import REGISTRY

    if not layout_enabled():
        return
    store_uid, base_version, store_ci = key[0], key[1], key[2]
    table = LAYOUT.store_ref(store_uid)
    if table is None or table.base_version != base_version:
        return
    info = pack_info(table, store_ci)
    if info is None:
        return
    mesh = _MESH  # snapshot read: a moved mesh skips the demote (the
    if mesh is None:  # next access cold-loads against the new mesh)
        return
    if tuple(d.id for d in mesh.devices.ravel()) != key[3]:
        return
    n_pad = key[5]

    def load():
        # layout follow-up (e): re-encode ON DEVICE from the evicted
        # wire array — only the packed codes (8-64x smaller than raw
        # values) read back for the re-shard, instead of re-reading
        # every host block; layout_demote_code_readback_bytes counts it
        from ..layout.coldtier import recompress_from_device

        try:
            return (recompress_from_device(table, store_ci, mesh, n_pad,
                                           info, _value),)
        except Exception:
            # any device hiccup falls back to the host-block compress
            return (compress_column(table, store_ci, mesh, n_pad,
                                    info),)

    COLD_CACHE.get_or_load(key + ("cold",), load)
    LAYOUT.note_demoted(store_uid, store_ci)
    REGISTRY.inc("layout_cold_demotions_total")


class _MeshCache:
    """(store_uid, base_version, store_ci, device_ids, TILE) -> sharded
    [n_pad, TILE] arrays; device ids in the key so a rebuilt same-size mesh
    never serves arrays placed on a dead device set.

    The cached data array keeps the NARROW wire dtype (see _wire_dtype) and
    the valid slot is None for columns with no NULLs — consumers cast on
    device / substitute a constant mask, so both the link transfer and the
    steady-state HBM traffic shrink to the narrow width.

    This is the HOT tier: capacity is `layout.hot_cap_bytes()` (a share
    of the devices' own memory, or TIDB_TPU_HBM_BYTES), and
    eviction is VALUE-WEIGHTED (layout autotuner): the lowest-priority
    column is the victim, and packable victims DEMOTE to the compressed
    cold tier (tidb_tpu/layout/coldtier) instead of dropping — a table
    bigger than the cap degrades to cheaper representations, not to
    host reloads."""

    def __init__(self, capacity_bytes: Optional[int] = None):
        from .cache import ByteCapCache

        # without a capacity of its own the cache follows
        # `hot_cap_bytes()`, read at each load: the devices cannot be
        # asked while this module is imported
        self._follow = capacity_bytes is None
        self._c = ByteCapCache(8 << 30 if self._follow else capacity_bytes,
                               name="mesh")
        self._c.set_policy(priority_fn=_hot_priority,
                           demote_fn=_hot_demote)

    @property
    def _cache(self):  # introspected by tests / dryrun
        return self._c.items_view

    def get_column(self, mesh: Mesh, table, store_ci: int):
        if self._follow:
            from ..layout import hot_cap_bytes

            self._c.capacity = hot_cap_bytes()
        S = len(mesh.devices.ravel())
        # device ids in the key so a rebuilt same-size mesh never serves
        # arrays placed on a dead device set (matches _ONES_CACHE);
        # n_pad in the key so a shape-bucket policy change never pairs a
        # stale-shaped cached array with a newly laid-out program
        devs = tuple(d.id for d in mesh.devices.ravel())
        _, n_pad, _ = _layout(table.base_rows, S, table=table)
        key = (table.store_uid, table.base_version, store_ci, devs, je.TILE,
               n_pad)

        def load():
            from ..trace import span

            tile = je.TILE
            wire = _wire_dtype(table, store_ci)
            _, _, has_null = table.column_stats(store_ci)
            with span("copr.transfer", col=store_ci,
                      device_ids=list(devs)) as sp:
                # vectorized build: ONE flat buffer filled block-by-block
                # (memcpy + cast per 64k block — no per-tile Python
                # loop), so host prep is bandwidth-bound, not
                # interpreter-bound
                flat = np.zeros(n_pad * tile, dtype=wire)
                off = 0
                vflat = None
                if has_null:
                    vflat = np.zeros(n_pad * tile, dtype=np.bool_)
                for _s, arrs, vals in table.iter_base_blocks(
                        [store_ci], 0, table.base_rows):
                    blk, v = arrs[0], vals[0]
                    n = len(blk)
                    flat[off:off + n] = blk  # casts to wire dtype
                    if vflat is not None:
                        vflat[off:off + n] = True if v is None else v
                    off += n
                nbytes = flat.nbytes + (
                    vflat.nbytes if vflat is not None else 0)
                sp.set(bytes=nbytes)
                sh = NamedSharding(mesh, P("dp"))
                data = jax.device_put(flat.reshape(n_pad, tile), sh)
                valid = None
                if vflat is not None:
                    valid = jax.device_put(vflat.reshape(n_pad, tile), sh)
                return data, valid

        return self._c.get_or_load(key, load)

    def clear(self):
        self._c.clear()

    def evict_device(self, device_id: int) -> int:
        """Drop every cached column placed on a mesh containing this
        device: arrays sharded onto a dead chip are unreadable and must
        never serve a rebuilt mesh (the key's device-id tuple exists for
        exactly this)."""
        return self._c.evict_if(lambda k: device_id in k[3])


MESH_CACHE = _MeshCache()


def _hbm_bytes() -> int:
    """Resident device bytes (hot mesh cache + compressed cold tier) at
    this instant — stamped on execute spans so a finished trace carries
    its HBM high-water mark (EXPLAIN ANALYZE / slow-log attribution)."""
    n = MESH_CACHE._c._bytes
    try:
        from ..layout.coldtier import COLD_CACHE

        n += COLD_CACHE._bytes
    except Exception:
        pass
    return n

# a small shared pool overlaps the host tile build of one column with the
# host-to-device transfer of another, for both foreground queries and the
# background prefetcher (the gain is not measured on the attached chip)
_XFER_POOL = None
_SHUTDOWN = False


def _xfer_pool():
    global _XFER_POOL
    if _XFER_POOL is None:
        import os
        from concurrent.futures import ThreadPoolExecutor

        _XFER_POOL = ThreadPoolExecutor(
            max_workers=int(os.environ.get("TIDB_TPU_XFER_THREADS", "4")),
            thread_name_prefix="tidb-tpu-xfer")
    return _XFER_POOL


def _note_shutdown():
    global _SHUTDOWN
    _SHUTDOWN = True


# threading._register_atexit runs BEFORE Py_Finalize joins non-daemon
# threads (plain atexit runs after the join — too late to stop them);
# this caps the interpreter-exit delay at one in-flight column transfer
import threading as _threading  # noqa: E402

try:
    _threading._register_atexit(_note_shutdown)
except AttributeError:  # pragma: no cover - very old CPython
    import atexit as _atexit

    _atexit.register(_note_shutdown)


#: wall seconds of the `mesh.columns` intervals in which a column was
#: not resident (a statement's wait for transfers; not `prefetch_table`)
COLUMN_LOAD_SECONDS = "mesh_column_load_seconds_total"
REGISTRY.inc(COLUMN_LOAD_SECONDS, 0.0)  # there from the start, at 0


def _fetched(fetch, mesh: Mesh, table, store_ci: int):
    """(the column, whether every cache it came through had it)."""
    n = thread_misses()
    return fetch(mesh, table, store_ci), thread_misses() == n


def _load_many(fetch, mesh: Mesh, table, store_cis) -> list:
    """`fetch` of several columns, concurrently on the transfer pool so
    that the host tile build of one overlaps the transfer of another;
    the results in order.  `mesh.columns` covers the whole lookup, the
    futures included (`copr.transfer` nests in it on a miss); `resident`
    says how many of `cols` the device already held.

    Multi-process meshes load SEQUENTIALLY: every process must issue
    device_puts against the shared mesh in the same deterministic order,
    or the collective fabric sees mismatched ops (observed as gloo
    'received data size doesn't match expected size' aborts)."""
    from ..trace import current_span, run_attached, span

    cis = list(store_cis)
    t0 = time.perf_counter()
    with span("mesh.columns", cols=len(cis)) as sp:
        if len(cis) <= 1 or jax.process_count() > 1:
            got = [_fetched(fetch, mesh, table, ci) for ci in cis]
        else:
            # pool workers re-attach to the submitter's span so transfer
            # spans land in the query's trace (contextvars don't cross
            # threads)
            parent = current_span()
            futs = [_xfer_pool().submit(run_attached, parent, _fetched,
                                        fetch, mesh, table, ci)
                    for ci in cis]
            got = [f.result() for f in futs]
        resident = sum(1 for _col, hit in got if hit)
        sp.set(resident=resident)
    if resident < len(cis):
        REGISTRY.inc(COLUMN_LOAD_SECONDS, time.perf_counter() - t0)
    return [col for col, _hit in got]


def load_columns(mesh: Mesh, table, store_cis):
    """Load several columns into the mesh cache (`_load_many`); returns
    the (data, valid) pairs in order."""
    return _load_many(MESH_CACHE.get_column, mesh, table, store_cis)


def get_layout_column(mesh: Mesh, table, store_ci: int):
    """One column through the adaptive layout: ('hot', (data, valid)) or
    ('cold', ColdColumn).  Cold-tier hits/loads/promotions are counted;
    the chaos site `layout/decompress` (and any compression failure)
    falls back to the hot tier, parity-preserved."""
    from ..layout import layout_enabled

    if not layout_enabled():
        return ("hot", MESH_CACHE.get_column(mesh, table, store_ci))
    from ..errors import TiDBTPUError
    from ..layout import COLD_CACHE, LAYOUT, compress_column
    from ..layout.coldtier import DECOMPRESS_FAILPOINT
    from ..metrics import REGISTRY

    LAYOUT.observe(table, store_ci, "scan")
    plan = LAYOUT.plan_for(table, store_ci)
    S = len(mesh.devices.ravel())
    devs = tuple(d.id for d in mesh.devices.ravel())
    _, n_pad, _ = _layout(table.base_rows, S, table=table)
    cold_key = (table.store_uid, table.base_version, store_ci, devs,
                je.TILE, n_pad, "cold")
    if plan.tier == "cold" and plan.bits:
        try:
            FAILPOINTS.hit(DECOMPRESS_FAILPOINT, col=store_ci,
                           bits=plan.bits)
            hit = COLD_CACHE.peek(cold_key) is not None
            entry = COLD_CACHE.get_or_load(
                cold_key,
                lambda: (compress_column(table, store_ci, mesh, n_pad),),
            )[0]
            REGISTRY.inc("layout_cold_hits_total" if hit
                         else "layout_cold_loads_total")
            return ("cold", entry)
        except TiDBTPUError:
            raise  # kill/deadline/quota keep their meaning
        except Exception:
            # chaos-armed decompress failure or a compression error:
            # serve the column hot — slower, never wrong
            REGISTRY.inc("layout_cold_fallbacks_total")
    elif COLD_CACHE.peek(cold_key) is not None:
        # the tuner re-decided hot (priority rose / pressure passed):
        # promote — drop the compressed copy, load the wire array
        COLD_CACHE.evict_if(lambda k: k == cold_key)
        REGISTRY.inc("layout_cold_promotions_total")
    return ("hot", MESH_CACHE.get_column(mesh, table, store_ci))


def load_layout_columns(mesh: Mesh, table, store_cis):
    """Layout-aware variant of `load_columns`: per-column hot/cold
    entries."""
    return _load_many(get_layout_column, mesh, table, store_cis)


def prefetch_table(storage, table_id: int, min_rows: int = 1 << 20):
    """Warm the mesh column cache for a table in the background (device
    cache warming after bulk load — the TiFlash eager-replica analog).
    Concurrent queries never double-transfer: ByteCapCache latches
    in-flight loads per key.  No-op for small tables."""
    import threading

    try:
        table = storage.table(table_id)
    except Exception:
        return
    if table.base_rows < min_rows:
        return
    try:
        # backend init happens HERE, on the caller thread, not as a
        # first touch from a background thread (not re-tested on the
        # attached chip); the process_count gate needs an initialized
        # backend anyway
        mesh = get_mesh()
        if jax.process_count() > 1:
            # multi-controller SPMD: background transfers would desync the
            # per-process device_put order (see load_columns); queries
            # load deterministically on demand instead
            return
    except Exception:
        return

    def run():
        try:
            version = table.base_version
            for ci in range(len(table.cols)):
                if _SHUTDOWN or table.base_version != version:
                    return  # interpreter exiting / data changed under us
                get_layout_column(mesh, table, ci)  # warms the right tier
        except Exception:
            pass  # prefetch is advisory; queries load on demand

    # NON-daemon: a daemon thread mid-device_put at interpreter exit can
    # crash the runtime client (whether it does on the attached chip is
    # not re-tested); the _SHUTDOWN latch bounds the exit delay to one
    # column transfer
    threading.Thread(
        target=run, daemon=False, name="tidb-tpu-prefetch").start()

# all-true deletion masks, byte-capped like the data cache (they are
# device-resident [n_pad, TILE] bools); keyed on the mesh's device ids so a
# rebuilt mesh never serves arrays placed on a dead device set
_ONES_CACHE = None


def _named_jit(fn, name: Optional[str], **jit_kw):
    """`jax.jit(fn)` under `name`: the XLA module is `jit_<name>`, which
    is how the device trace tells the programs apart."""
    def named(*args):
        return fn(*args)

    if name:
        named.__name__ = named.__qualname__ = name
    return jax.jit(named, **jit_kw)


def _all_true(mesh: Mesh, n_pad: int):
    global _ONES_CACHE
    if _ONES_CACHE is None:
        from .cache import ByteCapCache

        _ONES_CACHE = ByteCapCache(1 << 30)
    devs = tuple(d.id for d in mesh.devices.ravel())
    key = (devs, n_pad, je.TILE)

    def load():
        return (jax.device_put(
            np.ones((n_pad, je.TILE), dtype=np.bool_),
            NamedSharding(mesh, P("dp")),
        ),)

    return _ONES_CACHE.get_or_load(key, load)[0]


# ---------------------------------------------------------------------------
# sharded programs
# ---------------------------------------------------------------------------

class _RowView:
    """How a shard program sees its local tiles of rows.

    Flat (`shape == (n_local,)`) everywhere but in a small-G dense
    aggregate, whose emitters are all elementwise or whole-array
    reductions.  There the [Tl, TILE] arrays are never flattened: in the
    TPU's (8, 128)-tiled layout a flatten is a physical transpose of
    every column and mask (the parent's full-length `copy` operations).
    The rows are viewed as [Tl/8, blocks, 8, rows of a block], which is
    the order the tiles already lie in, so the view is free and the dense
    emitter's block sums (fusion.AGG_BLOCK) reduce the last axis as it
    stands.  Shards that are not whole groups of 8 tiles stay flat: only
    tables under 8 tiles a shard, since _layout keeps every larger shard
    in whole groups."""

    def __init__(self, n_local: int, an: Optional[_Analyzed] = None,
                 kind: str = "", col_layout=None):
        from . import fusion

        T = je.TILE
        self.Tl = Tl = n_local // T
        self.n_local = n_local
        b = min(T, fusion.AGG_BLOCK)
        #: rows of a block; 0 for the flat view
        self.block = b if (
            kind == "agg" and an.agg_mode == "dense"
            and an.num_groups <= ops.UNROLL_G
            and not an.probes and not an.lookups
            and not any(col_layout or ())
            and Tl % 8 == 0 and T % b == 0) else 0
        self.shape = ((Tl // 8, T // b, 8, b) if self.block
                      else (n_local,))

    def __call__(self, x):
        """A [Tl, TILE] operand in the view's shape (as it is, if there
        already)."""
        if self.block and x.ndim == 2:
            return x.reshape(self.Tl // 8, 8, -1, self.block) \
                .transpose(0, 2, 1, 3)
        return x.reshape(self.shape)

    def hold(self, datas, valids, del_mask):
        """The program's row operands in the view's shape.  A blocked
        view is pinned to them by an optimization barrier: without it XLA
        sinks the view below the arithmetic, computes masks and limbs in
        the [Tl, TILE] shape and writes each out in full for the
        reduction on the far side of the bitcast (0.64 of 3.7 GB moved in
        the Q1 program of ISSUE 35, 1.9 of 2.6 GB in Q6's; with the
        barrier the programs read their arguments once and write nothing
        of full length)."""
        if not self.block:
            return datas, valids, del_mask
        held = iter(jax.lax.optimization_barrier(tuple(
            self(x) for x in (*datas, *valids, del_mask) if x is not None)))

        def take(x):
            return None if x is None else next(held)

        return ([take(x) for x in datas], [take(x) for x in valids],
                take(del_mask))

    def flat(self, x):
        """A view-shaped array back in row order."""
        if self.block:
            x = x.transpose(0, 2, 1, 3)
        return x.reshape(self.n_local)

    def local_rows(self, dtype=jnp.int64):
        """Shard-local row number of every element."""
        if not self.block:
            return jnp.arange(self.n_local, dtype=dtype)

        def iota(d):
            return jax.lax.broadcasted_iota(dtype, self.shape, d)

        return ((iota(0) * 8 + iota(2)) * je.TILE
                + iota(1) * self.block + iota(3))


def _cols_env(an: _Analyzed, col_order: List[int], datas, valids,
              view: _RowView, params=None, col_layout=None, lvals=()):
    """Per-shard column environment for compile_expr: widen the narrow
    wire arrays to the canonical dtype in-register (XLA fuses the convert
    into every consumer — HBM reads stay narrow), and substitute a traced
    constant mask for columns cached without a validity array (no NULLs:
    zero transfer, zero HBM).  `params` carries the hoisted predicate
    parameter vectors (pi, pf) for ParamConst slots.  Beside the widened
    arrays, under "__wire__", stand the arrays as they came: the dense
    aggregate computes in int32 from those (jax_eval.bounded_int), and
    XLA drops whichever of the two nothing reads.

    `col_layout[j]` = (bits, cap, kind) marks column j COLD: datas[j] is
    the shard-local bit-packed code bytes and the matching `lvals` entry
    its decode runtime operand (scalar bias for 'range', dictionary
    vector for 'unique') — the decode emitter (fusion.decode_packed)
    unpacks it in-register, fused with every consumer.  Cold columns are
    NULL-free by the tuner's contract."""
    from . import fusion

    env, wire = {}, {}
    lv = 0
    for j, ci in enumerate(col_order):
        lay = col_layout[j] if col_layout is not None else None
        if lay is not None:
            bits, _cap, kind = lay
            d = fusion.decode_packed(datas[j], lvals[lv], bits,
                                     view.n_local, kind=kind)
            lv += 1
            v = jnp.ones(view.n_local, dtype=jnp.bool_)
            env[ci] = (d, v)
            wire[ci] = d
            continue
        d = wire[ci] = view(datas[j])
        target = _full_dtype(an.scan.ftypes[ci].kind)
        if d.dtype != target:
            d = d.astype(target)
        v = valids[j]
        v = (jnp.ones(view.shape, dtype=jnp.bool_) if v is None
             else view(v))
        env[ci] = (d, v)
    env["__wire__"] = wire
    if params is not None:
        env["__params__"] = params
    return env


def _split_operands(an: _Analyzed, ints, pargs, hoisted):
    """The shard program's reading of its int64 operand vector
    (`_bounds_args`): past the range slots stand the key counts of
    `an.probes` then `an.lookups`, then the hoisted int64 parameters.
    `hoisted` is the (pi, pf) lengths, or None when no predicate
    constant was hoisted; a non-empty pf is the last of `pargs`.
    Returns (key counts, pargs without pf, the (pi, pf) params or
    None)."""
    off = 2 * MESH_RANGE_SLOTS
    n_keys = len(an.probes) + len(an.lookups)
    counts = ints[off: off + n_keys]
    if hoisted is None:
        return counts, pargs, None
    pi = ints[off + n_keys:]
    if hoisted[1]:
        return counts, pargs[:-1], (pi, pargs[-1])
    return counts, pargs, (pi, jnp.zeros(0, dtype=jnp.float64))


from .cache import PROGRAM_CACHES, ProgramCache  # noqa: E402,F401

_COMPILED = ProgramCache("mesh")


def _shard_map_norep(fn, mesh, in_specs, out_specs):
    """shard_map with replication checking off: the Pallas kernel tier
    (copr/pallas) has no registered replication rule, and every P()
    output here comes from a psum/all_gather (replicated by
    construction) — semantics are unchanged for these programs."""
    return shard_map(fn, mesh=mesh, in_specs=in_specs,
                     out_specs=out_specs, check_vma=False)


def _n_remaps(an) -> int:
    """Computed-key remap operands riding the lvals tail (after the cold
    dictionary operands)."""
    return sum(1 for r in (getattr(an, "key_remaps", None) or ()) if r)

# max selected rows gathered host-side per streamed chunk (kv.Request
# Streaming / distsql stream.go: bounded-memory result consumption)
STREAM_ROWS = 1 << 16

#: range-bound parameter slots per fused mesh program: EVERY program
#: takes this many (lo, hi) runtime scalars (unused slots are (0, 0),
#: which mask to nothing), so a fragment's range COUNT never enters the
#: program fingerprint — 1-range and 3-range scans of the same shape
#: share one compiled program, and all ranges run in ONE XLA launch
#: instead of one dispatch per range with host glue between them.
MESH_RANGE_SLOTS = 4


def _bounds_args(bounds, scalars=()):
    """[(lo, hi), ...] -> the int64 operand vector of a fused program:
    the 2*MESH_RANGE_SLOTS runtime scalars its range mask reads (pad
    slots are empty ranges), then `scalars`, the call's other int64
    operands (`_split_operands` says which).

    ONE host array, because each host operand of the jitted call is a
    transfer of its own, some 0.2 ms on the chip whatever its size; and
    host, because a `jnp.int64(x)` made before the call is a transfer
    and a device program (`jit_convert_element_type`) per scalar."""
    ints = np.zeros(2 * MESH_RANGE_SLOTS + len(scalars), dtype=np.int64)
    for r, (lo, hi) in enumerate(bounds):
        ints[2 * r], ints[2 * r + 1] = lo, hi
    ints[2 * MESH_RANGE_SLOTS:] = scalars
    return ints


def _mesh_masks(del_mask, bounds, view: _RowView):
    """(global row offsets, live-row mask) for one shard: the union of
    every range slot's [lo, hi) clip, ANDed with the deletion mask.
    `bounds` is the operand vector of `_bounds_args`; only its range
    slots are read here.  The sixteen comparisons a row are made on
    int32 lanes, between the shard-local row number and each slot's
    bounds moved into the shard and clipped to it (an int64 comparison
    is several lane operations on the TPU); the int64 offsets are for
    the emitters that hand row numbers out."""
    n = view.n_local
    base = jax.lax.axis_index("dp").astype(jnp.int64) * n
    gofs = base + view.local_rows()
    narrow = jnp.int32 if n < 1 << 31 else jnp.int64
    rows = view.local_rows(narrow)
    slots = jnp.clip(bounds[: 2 * MESH_RANGE_SLOTS] - base, 0, n) \
        .astype(narrow)
    m = jnp.zeros(view.shape, dtype=jnp.bool_)
    for r in range(MESH_RANGE_SLOTS):
        m = m | ((rows >= slots[2 * r]) & (rows < slots[2 * r + 1]))
    return gofs, m & view(del_mask)


def _key_device(d):
    """Device-side canonical join/group key: float keys stay in VALUE domain
    (-0.0 folded into 0.0), everything else widens to int64.

    The device programs use no 64-bit bitcast-convert (assumed unsafe under
    the TPU's x64 emulation; not re-tested on the attached chip), so the
    host's bit-domain canonicalization (ir.key_bits_int64) is translated
    back to values before it reaches the device — see the pargs
    construction in try_run_mesh.  NaN keys never
    match in value domain (SQL NULLs are tracked separately; NaN data keys
    are pathological and excluded by contract)."""
    if jnp.issubdtype(d.dtype, jnp.floating):
        return jnp.where(d == 0.0, 0.0, d).astype(jnp.float64)
    return d.astype(jnp.int64)


def _apply_probes(an: _Analyzed, cols, m, pargs, counts, n_local: int):
    """AND the runtime join-filter membership tests into the row mask:
    sorted build keys broadcast to every shard, searchsorted probe.
    Then run the broadcast lookup JOINS: drop misses and extend the
    column env with gathered payload rows (JoinLookupIR) — the join
    completes ON DEVICE, inside the same shard program as the scan and
    the partial aggregation."""
    for i, p in enumerate(an.probes):
        keys, kn = pargs[i], counts[i]
        d, v = compile_expr(p.key, cols, n_local)
        k = _key_device(d)
        pos = jnp.searchsorted(keys, k)
        pos_c = jnp.clip(pos, 0, keys.shape[0] - 1)
        hit = (pos < kn) & (keys[pos_c] == k)
        m = m & v & hit
    off = len(an.probes)
    out_idx = len(an.scan.columns)
    for j, lk in enumerate(an.lookups):
        keys, kn = pargs[off], counts[len(an.probes) + j]
        off += 1
        d, v = compile_expr(lk.key, cols, n_local)
        k = d.astype(jnp.int64)
        pos = jnp.searchsorted(keys, k)
        pos_c = jnp.clip(pos, 0, keys.shape[0] - 1)
        hit = (pos < kn) & (keys[pos_c] == k) & v
        m = m & hit
        for _ft in lk.payload_ftypes:
            pl, pv = pargs[off], pargs[off + 1]
            off += 2
            # broadcast gather: matched build row per probe row; misses
            # are dead rows under m, their payload validity is False
            cols[out_idx] = (pl[pos_c], hit & pv[pos_c])
            out_idx += 1
    return m


def _probe_specs(an: _Analyzed, hoisted=None):
    specs = [P()] * len(an.probes)
    for lk in an.lookups:
        # the keys; the payload too, unless the groups ARE the build
        # rows and the host adds it to them (`_fd_lookup`)
        specs += [P()] + ([] if _fd_lookup(an)
                          else [P(), P()] * len(lk.payload_ftypes))
    if hoisted is not None and hoisted[1]:
        specs += [P()]  # the replicated pf parameter vector
    return tuple(specs)


def _readback_sharding(mesh: Mesh):
    """Across processes a result must come back replicated: a host can
    only read an array whose every shard it addresses.  In one process
    the choice stays jit's own (None)."""
    return NamedSharding(mesh, P()) if jax.process_count() > 1 else None


def _launch(jitted, program: Optional[str], args, devices: int = 0):
    """Enqueue one compiled program.  `copr.device.execute` ends when
    the call returns, which is at the enqueue; `program` is the name the
    device trace knows the program by (its XLA module is `jit_<name>`),
    `devices` the shards of the mesh it runs on (a mesh program's)."""
    from ..trace import span

    attrs = {"program": program} if program else {}
    if devices:
        attrs["devices"] = devices
    with span("copr.device.execute", hbm_bytes=_hbm_bytes(), **attrs):
        return jitted(*args)


#: bytes that join programs (MPP exchange and join-tree programs, mesh
#: programs that carry a lookup join) handed back to the host
JOIN_READBACK = "join_readback_bytes_total"


def _read_back(out, join: bool = False) -> np.ndarray:
    """One device result to the host.  `copr.readback` is the wait for
    the device plus the copy; its child `copr.device.wait` ends when the
    device has finished, so `copr.device.execute` + `copr.device.wait` is
    the device's part of a dispatch as the host sees it and the rest of
    `copr.readback` is the copy."""
    from ..trace import span

    with span("copr.readback") as sp:
        with span("copr.device.wait"):
            out.block_until_ready()
        buf = np.asarray(out)
        sp.set(bytes=buf.nbytes)
    if join:
        REGISTRY.inc(JOIN_READBACK, float(buf.nbytes))
    return buf


def _read_back_tree(out):
    """A join program's result to the host, leaf by leaf as it is (rows
    and states in their narrow types, where `_packed_jit`'s one float64
    buffer would be 16 bytes an integer): one `copr.readback` for all the
    leaves, its `bytes` their sum."""
    from ..trace import span

    leaves, treedef = jax.tree_util.tree_flatten(out)
    with span("copr.readback") as sp:
        with span("copr.device.wait"):
            jax.block_until_ready(leaves)
        got = jax.device_get(leaves)
        nbytes = sum(a.nbytes for a in got)
        sp.set(bytes=nbytes)
    REGISTRY.inc(JOIN_READBACK, float(nbytes))
    return jax.tree_util.tree_unflatten(treedef, got)


def _call_args(datas, valids, del_mask, bounds, lvals=(), pargs=(),
               scalars=()) -> tuple:
    """The runtime operands of one mesh dispatch (`copr.args`): the
    resident column tuples, and what is not resident as host numpy
    values that the jitted call itself carries to the device: the int64
    operand vector of `_bounds_args` (range slots, then `scalars`: key
    counts and hoisted int64 parameters) and, last of `pargs`, a
    non-empty float64 parameter vector.  `operands` counts those and
    `bytes` sums them; key sets, payloads and layout operands are device
    arrays already and are not counted."""
    from ..trace import span

    with span("copr.args") as sp:
        ints = _bounds_args(bounds, scalars)
        host = [a for a in (ints, *lvals, *pargs)
                if not isinstance(a, jax.Array)]
        sp.set(operands=len(host), bytes=sum(a.nbytes for a in host))
        return (tuple(datas), tuple(valids), del_mask, ints,
                tuple(lvals), *pargs)


def _program_name(kind: str, fp: str) -> str:
    """`mesh_<kind>_<crc32 of the program-cache fingerprint>`: what the
    jitted callable is called, so the device trace's `XLA Modules` line
    tells the mesh programs apart, and what `copr.device.execute` carries
    as `program=`, so spans and device time can be joined."""
    return f"mesh_{kind}_{zlib.crc32(fp.encode()) & 0xFFFFFFFF:08x}"


def _packed_jit(fn, mesh: Mesh, name: Optional[str] = None, merge=None,
                join: bool = False):
    """jit `fn` (whose output is a pytree of 64-bit-wide arrays) so the whole
    result crosses device->host as ONE flat float64 buffer.

    Every `np.asarray(leaf)` is a device->host round trip with a fixed
    cost; a Q1-shaped aggregation has ~16 output leaves.  Packing on device
    (everything concatenated into one f64 vector) pays that cost once, not
    per leaf.  What a round trip costs on the attached chip is not measured.

    Integer leaves travel as two exact f64 halves (value-split hi/lo 32 bits)
    rather than a bitcast: 0 <= half < 2^32 is always exactly representable
    in f64, and no 64-bit bitcast-convert is needed (assumed unsafe under
    the TPU's x64 emulation; not re-tested on the attached chip).

    `name` names the jitted callable (see `_program_name`, and
    `mpp.engine.program_name` for the MPP callers); `merge`, where
    given, is applied to the unpacked pytree inside the `copr.unpack` span
    (the caller's per-shard merge is part of getting from the packed
    buffer to what the caller receives); `join` counts the buffer's
    bytes under JOIN_READBACK.
    """
    meta = {}

    def packed(*args):
        out = fn(*args)
        leaves, treedef = jax.tree_util.tree_flatten(out)
        specs, flat = [], []
        for leaf in leaves:
            dt = np.dtype(str(leaf.dtype))
            specs.append((leaf.shape, dt))
            if jnp.issubdtype(leaf.dtype, jnp.floating):
                flat.append(leaf.reshape(-1).astype(jnp.float64))
            else:  # bool / int32 / int64 — all exact through the split
                x = leaf.reshape(-1).astype(jnp.int64)
                hi = (x >> 32).astype(jnp.float64)        # arithmetic shift
                lo = (x & 0xFFFFFFFF).astype(jnp.float64)  # in [0, 2^32)
                flat.append(hi)
                flat.append(lo)
        # trace-time capture: jit traces synchronously before the first
        # execution returns, so `meta` is populated before any unpack
        meta["treedef"] = treedef
        meta["specs"] = specs
        return jnp.concatenate(flat) if flat else jnp.zeros(0, jnp.float64)

    if name:
        packed.__name__ = packed.__qualname__ = name
    jitted = jax.jit(packed, out_shardings=_readback_sharding(mesh))

    def call(*args):
        from ..trace import span

        buf = _read_back(_launch(jitted, name, args, mesh.devices.size),
                         join)
        with span("copr.unpack", rows=int(buf.size), bytes=buf.nbytes):
            leaves, off = [], 0
            for shape, dt in meta["specs"]:
                n = int(np.prod(shape, dtype=np.int64)) if shape else 1
                if np.issubdtype(dt, np.floating):
                    seg = buf[off: off + n].astype(dt)
                    off += n
                else:
                    hi = buf[off: off + n].astype(np.int64)
                    lo = buf[off + n: off + 2 * n].astype(np.int64)
                    off += 2 * n
                    seg = ((hi << 32) + lo).astype(dt)
                leaves.append(seg.reshape(shape))
            out = jax.tree_util.tree_unflatten(meta["treedef"], leaves)
            return merge(out) if merge is not None else out

    return call


def _mesh_in_specs(an: _Analyzed, hoisted, n_lvals: int = 0):
    """shard_map input specs shared by every fused mesh program: sharded
    column/validity/deletion arrays, the replicated int64 operand vector,
    the replicated layout dictionary-value operands (one per cold
    column), then the variadic parg tail."""
    return (P("dp"), P("dp"), P("dp"), P(),
            tuple(P() for _ in range(n_lvals)),
            ) + _probe_specs(an, hoisted)


def _build_mesh_core(an: _Analyzed, kind: str, col_order: List[int],
                     mesh: Mesh, tiles_per_shard: int,
                     hoisted=None, col_layout=None):
    """The raw shard_map'd whole-fragment program (pre-jit).

    One body per mesh: each shard flattens its local tiles to a
    [Tl*TILE] vector, builds the union row mask over MESH_RANGE_SLOTS
    range slots, and composes the fusion phase emitters
    (copr/fusion.py) — selection, probes/lookups, dense agg or topN —
    so the whole fragment is ONE program with the partial/final agg
    merge on-device (psum over ICI).  Used by `_build_mesh_fn` (which
    jits + packs it) and by kernelcheck's fused-fragment corpus
    (jax.make_jaxpr over a 1-device mesh).

    Signature: core(datas, valids, del_mask, ints, lvals, *pargs)
    where ints is the int64 operand vector from _bounds_args (read by
    _mesh_masks and _split_operands; `hoisted` is the (pi, pf) lengths
    or None) and lvals the cold columns' dictionary-value runtime
    operands (empty tuple for an all-hot fragment — the common case
    compiles the identical program it always did).
    """
    from . import fusion

    S = len(mesh.devices.ravel())
    Tl = tiles_per_shard
    n_local = Tl * je.TILE
    n_global = S * n_local
    n_lvals = sum(1 for c in (col_layout or ()) if c is not None) \
        + _n_remaps(an)

    if kind == "agg" and an.agg_mode == "sort":
        build = (_build_lookup_agg_core if _fd_lookup(an)
                 else _build_sort_agg_core)
        return build(an, col_order, mesh, tiles_per_shard,
                     hoisted=hoisted, col_layout=col_layout)

    view = _RowView(n_local, an, kind, col_layout)

    def region_ctx(datas, valids, del_mask, ints, lvals, pargs):
        counts, pargs, params = _split_operands(an, ints, pargs, hoisted)
        datas, valids, del_mask = view.hold(datas, valids, del_mask)
        cols = _cols_env(an, col_order, datas, valids, view, params,
                         col_layout=col_layout, lvals=lvals)
        gofs, row_mask = _mesh_masks(del_mask, ints, view)
        ctx = fusion.RegionContext(an=an, cols=cols, n=view.shape,
                                   mask=row_mask, axis="dp", gofs=gofs,
                                   n_global=n_global, flat=view.flat)
        fusion.selection_mask(ctx)
        if an.probes or an.lookups:
            ctx.mask = _apply_probes(an, cols, ctx.mask, pargs, counts,
                                     n_local)
        return ctx

    if kind == "agg":
        def shard_fn(datas, valids, del_mask, ints, lvals, *pargs):
            ctx = region_ctx(datas, valids, del_mask, ints, lvals,
                             pargs)
            gidx = fusion.dense_group_codes(ctx)
            gcount, results = fusion.dense_agg_results(ctx, gidx)
            return gcount, tuple(results)

        out_results = []
        for a in an.agg.aggs:
            if a.name == "count":
                out_results.append(P())
            elif a.name in ("sum", "avg"):
                out_results.append((P(), P()))
            elif a.name in ("min", "max"):
                # per-shard partial: min/max merge across shards on the
                # host ([S, G] is tiny) — the reference's partial/final
                # agg split (aggregate.go:101-169) with the final on root.
                # Only Sum all-reduces are used on device (pmin/pmax not
                # re-tested on the attached chip)
                out_results.append((P("dp"), P()))
            else:
                out_results.append(P("dp"))
        out_specs = (P(), tuple(out_results))
    elif kind == "topn":
        from ..serving import topn_budget

        desc = fusion.topn_desc(an)
        k = min(topn_budget(an.topn.limit), n_local)

        def shard_fn(datas, valids, del_mask, ints, lvals, *pargs):
            ctx = region_ctx(datas, valids, del_mask, ints, lvals,
                             pargs)
            key = fusion.topn_key(ctx)
            idx, cnt = ops.masked_top_k(key, ctx.mask, k, desc)
            return ctx.gofs[idx], cnt.reshape(1)

        out_specs = P("dp")
    else:  # filter: the fused selection mask (projection reads it later)
        def shard_fn(datas, valids, del_mask, ints, lvals, *pargs):
            ctx = region_ctx(datas, valids, del_mask, ints, lvals,
                             pargs)
            return ctx.mask

        out_specs = P("dp")

    return _shard_map_norep(shard_fn, mesh,
                            _mesh_in_specs(an, hoisted, n_lvals),
                            out_specs)


def _build_mesh_fn(an: _Analyzed, kind: str, col_order: List[int],
                   mesh: Mesh, tiles_per_shard: int, name: str,
                   hoisted=None, col_layout=None):
    """One jitted shard_map program over the whole fragment.

    Inputs: datas [n_pad, TILE] x cols (cold columns: [n_pad,
    TILE*bits/8] packed bytes), valids likewise, del_mask [n_pad, TILE],
    the int64 operand vector (the range-bound list padded to
    MESH_RANGE_SLOTS, key counts, hoisted int64 parameters), the cold
    columns' dictionary-value operands, then the variadic parg tail
    (probe key sets, lookup payloads, and a non-empty hoisted float64
    parameter vector).  Every range of a
    steady-state fragment runs in this ONE dispatch; intermediates never
    leave HBM.  `name` is the jitted callable's (`_program_name`).
    """
    S = len(mesh.devices.ravel())
    n_local = tiles_per_shard * je.TILE
    core = _build_mesh_core(an, kind, col_order, mesh, tiles_per_shard,
                            hoisted=hoisted, col_layout=col_layout)

    if kind == "agg" and an.agg_mode == "sort":
        if _fd_lookup(an):
            return _wrap_lookup_agg(an, core, mesh, S, name)
        return _wrap_sort_agg(an, core, mesh, S, n_local, name)

    if kind == "agg":
        from . import fusion
        from ..trace import annotate

        agg_ir = an.agg
        G = an.num_groups
        tags = je._agg_tags(agg_ir)
        wide = fusion.wide_sums(an)

        def merge_shards(out):
            gcount, results = out
            # inside `copr.unpack`: how many sums came as limb sums and
            # are put together here as Python integers
            annotate(wide_sums=len(wide))
            if wide:
                REGISTRY.inc(fusion.WIDE_SUM_SLOTS, float(len(wide)))
            merged = []
            for tag, r in zip(tags, results):
                if len(merged) in wide:
                    limbs, cnt = r
                    r = (fusion.recombine_wide(
                        limbs, cnt, wide[len(merged)]), cnt)
                if tag == "minmax":
                    part, cnt = r  # part: [S*G] per-shard partials
                    part = part.reshape(S, G)
                    a = agg_ir.aggs[len(merged)]
                    part = part.min(0) if a.name == "min" else part.max(0)
                    merged.append((tag, (part, cnt)))
                elif tag == "argfirst":
                    merged.append((tag, r.reshape(S, G).min(0)))
                else:
                    merged.append((tag, r))
            return gcount, merged

        packed = _packed_jit(core, mesh, name, merge=merge_shards,
                             join=bool(an.lookups))

        def wrapped(*operands):  # the arguments of _call_args
            return packed(*_call_args(*operands))

        return wrapped

    if kind == "topn":
        from ..serving import topn_budget

        k = min(topn_budget(an.topn.limit), n_local)
        packed = _packed_jit(core, mesh, name)

        def wrapped(*operands):
            gidx, cnt = packed(*_call_args(*operands))
            return gidx, cnt, k
        return wrapped

    # filter (with optional projection evaluated on device).  The mask comes
    # back bit-packed: 1 bit/row instead of 1 byte/row is an 8x smaller
    # device->host readback.
    def packed_mask(*a):
        return jnp.packbits(core(*a).astype(jnp.uint8))

    packed_mask.__name__ = packed_mask.__qualname__ = name
    jitted = jax.jit(packed_mask, out_shardings=_readback_sharding(mesh))

    def wrapped(*operands):
        from ..trace import span

        n_rows = S * n_local
        bits = _read_back(_launch(jitted, name, _call_args(*operands), S))
        with span("copr.unpack", rows=n_rows, bytes=bits.nbytes):
            return np.unpackbits(bits, count=n_rows).astype(np.bool_)
    return wrapped


def _compile_labeled(fn, kind: str, attrs: dict):
    """Wrap a freshly built mesh program so its first dispatch records a
    copr.compile span (cache=miss, and `attrs`); later calls pass
    straight through — _packed_jit's execute/readback spans nest inside
    either way."""
    state = {"first": True}

    def call(*args, **kwargs):
        if state["first"]:
            state["first"] = False
            from ..trace import span

            with span("copr.compile", cache="miss", kind=kind, **attrs):
                return fn(*args, **kwargs)
        return fn(*args, **kwargs)

    return call


class MeshAggOverflow(Exception):
    """Per-shard distinct-group count exceeded the static output budget;
    the caller falls back to the host hash aggregation."""


def _fd_lookup(an: _Analyzed) -> bool:
    """True when the single unique-key lookup FUNCTIONALLY DETERMINES
    every group key (the TPC-H Q3 shape: GROUP BY join_key, payload...)
    and every aggregate is a count or an exact sum over the scanned
    columns: a group then IS a build row, and the aggregate is
    `fusion.lookup_group_sums`'s merge instead of a lookup, a sort by
    every key and a segment reduction."""
    if "fd_lookup" not in an.__dict__:
        an.fd_lookup = _groups_are_build_rows(an)
    return an.fd_lookup


def _groups_are_build_rows(an: _Analyzed) -> bool:
    import json as _json

    from ..expr.expression import ColumnExpr
    from .ir import serialize_expr

    if len(an.lookups) != 1 or an.probes or an.agg is None \
            or an.agg_mode != "sort" or getattr(an, "key_remaps", None):
        return False
    lk = an.lookups[0]
    key_ser = _json.dumps(serialize_expr(lk.key), sort_keys=True)
    width = len(an.scan.columns)
    lo, hi = width, width + len(lk.payload_ftypes)
    for g in an.agg.group_by:
        if isinstance(g, ColumnExpr) and lo <= g.index < hi:
            continue  # payload column: fixed per matched build row
        if _json.dumps(serialize_expr(g), sort_keys=True) == key_ser:
            continue  # the join key itself (unique per build row)
        return False
    for a in an.agg.aggs:
        if a.name not in ("count", "sum", "avg"):
            return False
        if a.name != "count" and \
                a.partial_types()[0].kind == TypeKind.FLOAT:
            return False
        refs: set = set()
        for x in a.args:
            x.collect_columns(refs)
        if any(i >= width for i in refs):
            return False  # an argument from the build side's payload
    return True


def _build_lookup_agg_core(an: _Analyzed, col_order: List[int], mesh: Mesh,
                           tiles_per_shard: int, hoisted=None,
                           col_layout=None):
    """The shard_map'd core of an aggregate whose groups are the build
    rows of its one lookup join (`_fd_lookup`): scan, selection, then
    `fusion.lookup_group_sums` over the shard's rows against the
    replicated build keys.  A shard returns, for each build row, the
    probe rows it matched and every aggregate's state; the host forms
    the partial chunks from the build side it holds
    (`_lookup_agg_chunks`), so no key leaves the device."""
    from . import fusion

    S = len(mesh.devices.ravel())
    n_local = tiles_per_shard * je.TILE
    n_cold = sum(1 for c in (col_layout or ()) if c is not None)
    view = _RowView(n_local)
    lk = an.lookups[0]

    def shard_fn(datas, valids, del_mask, ints, lvals, *pargs):
        _counts, pargs, params = _split_operands(an, ints, pargs, hoisted)
        cols = _cols_env(an, col_order, datas, valids, view, params,
                        col_layout=col_layout, lvals=lvals)
        gofs, m = _mesh_masks(del_mask, ints, view)
        ctx = fusion.RegionContext(an=an, cols=cols, n=n_local, mask=m,
                                   axis="dp", gofs=gofs,
                                   n_global=S * n_local)
        fusion.selection_mask(ctx)
        bkeys = pargs[0]
        dk, vk = compile_expr(lk.key, cols, n_local)
        values = []
        for a in an.agg.aggs:
            if not a.args:
                values.append((None, jnp.ones(n_local, dtype=jnp.bool_)))
                continue
            d, v = compile_expr(a.args[0], cols, n_local)
            if a.name == "count":
                d = None
            else:
                d = _to_state_dtype(d, a.args[0].ftype,
                                    a.partial_types()[0])
            values.append((d, v))
        rows, states = fusion.lookup_group_sums(
            bkeys, dk.astype(bkeys.dtype), ctx.mask & vk, values)
        return rows, tuple(c if t is None else (t, c) for t, c in states)

    return _shard_map_norep(shard_fn, mesh,
                            _mesh_in_specs(an, hoisted, n_cold), P("dp"))


def _wrap_lookup_agg(an: _Analyzed, core, mesh: Mesh, S: int, name: str):
    jitted = _named_jit(core, name, out_shardings=_readback_sharding(mesh))

    def wrapped(*operands):
        rows, states = _read_back_tree(
            _launch(jitted, name, _call_args(*operands), S))
        return {"mode": "lookup", "S": S, "rows": rows, "states": states}

    return wrapped


def _lookup_agg_chunks(out: dict, an: _Analyzed, aux: dict) -> List[Chunk]:
    """A shard's per-build-row states -> one partial chunk [keys...,
    states...] over the build rows that matched any probe row, the key
    columns taken from the build side the host holds."""
    from ..expr.expression import ColumnExpr

    lk = an.lookups[0]
    keys = aux[f"probe_keys_{lk.filter_id}"]
    payload = aux[f"payload_{lk.filter_id}"]
    pvalids = aux.get(f"payload_valid_{lk.filter_id}")
    width = len(an.scan.columns)
    k, K = len(keys), len(out["rows"]) // out["S"]
    chunks: List[Chunk] = []
    for s in range(out["S"]):
        at = slice(s * K, s * K + k)
        sel = np.flatnonzero(out["rows"][at])
        if not len(sel):
            continue
        cols: List[Column] = []
        for g in an.agg.group_by:
            if isinstance(g, ColumnExpr) and g.index >= width:
                j = g.index - width
                pv = pvalids[j] if pvalids is not None else None
                cols.append(Column(g.ftype,
                                   payload[j][sel].astype(g.ftype.np_dtype),
                                   None if pv is None else pv[sel]))
            else:
                cols.append(Column(g.ftype,
                                   keys[sel].astype(g.ftype.np_dtype)))
        for a, st in zip(an.agg.aggs, out["states"]):
            pts = a.partial_types()
            if a.name == "count":
                cols.append(Column(pts[0], st[at][sel].astype(np.int64)))
                continue
            total, c = st[0][at][sel], st[1][at][sel]
            cols.append(Column(pts[0], total.astype(pts[0].np_dtype),
                               c > 0))
            if a.name == "avg":
                cols.append(Column(pts[1], c.astype(np.int64)))
        chunks.append(Chunk(cols))
    return chunks


def _build_sort_agg_core(an: _Analyzed, col_order: List[int], mesh: Mesh,
                         tiles_per_shard: int, hoisted=None,
                         col_layout=None):
    """Sort-based per-shard partial aggregation for arbitrary group keys
    (any NDV, float, NULLable, expression keys) — the shard_map'd core.

    Per shard: lexsort rows by (key bits..., null flags..., selected-last),
    mark group boundaries, segment-reduce into a static OUT-sized budget,
    and emit compacted (keys, partial states).  No collectives: partial
    chunks stream back per shard and the ROOT final HashAgg merges them —
    exactly the reference's coprocessor-partial/root-final split
    (executor/aggregate.go:101-169) mapped onto the mesh.
    """
    import os as _os

    from . import fusion

    S = len(mesh.devices.ravel())
    Tl = tiles_per_shard
    n_local = Tl * je.TILE
    n_global = S * n_local
    OUT = min(int(_os.environ.get("TIDB_TPU_AGG_OUT", 1 << 17)), n_local)
    agg_ir = an.agg
    n_cold = sum(1 for c in (col_layout or ()) if c is not None)
    remaps = getattr(an, "key_remaps", None)
    n_lvals = n_cold + _n_remaps(an)
    view = _RowView(n_local)

    def shard_fn(datas, valids, del_mask, ints, lvals, *pargs):
        counts, pargs, params = _split_operands(an, ints, pargs, hoisted)
        cols = _cols_env(an, col_order, datas, valids, view, params,
                        col_layout=col_layout, lvals=lvals)
        gofs, m = _mesh_masks(del_mask, ints, view)
        ctx = fusion.RegionContext(an=an, cols=cols, n=n_local, mask=m,
                                   axis="dp", gofs=gofs, n_global=n_global)
        fusion.selection_mask(ctx)
        m = _apply_probes(an, cols, ctx.mask, pargs, counts, n_local)
        key_bits, key_flags = [], []
        rslot = 0
        for gi, g in enumerate(agg_ir.group_by):
            rem = remaps[gi] if remaps is not None else None
            if rem is not None:
                # computed string key: code-space gather through the
                # runtime mapping operand (the lvals tail after the cold
                # dictionary operands) — fusion.remap_codes dispatches
                # to the Pallas tier when enabled
                d0, v = cols[rem.src_idx]
                d = fusion.remap_codes(d0, lvals[n_cold + rslot],
                                       n_local)
                rslot += 1
            else:
                d, v = compile_expr(g, cols, n_local)
            # float keys group in VALUE domain (the backend can't lower the
            # f64<->i64 bitcast); -0.0 folds into 0.0, and NULL rows get a
            # fixed key so the validity flag alone separates them
            k = _key_device(d)
            zero = jnp.float64(0.0) if k.dtype == jnp.float64 else jnp.int64(0)
            key_bits.append(jnp.where(v, k, zero))
            key_flags.append(v.astype(jnp.int64))
        order, sm, out_keys, seg, n_uniq = fusion.sort_group_segments(
            key_bits, key_flags, m, OUT)
        out_keys = tuple(out_keys)
        results = fusion.grouped_partial_states(
            agg_ir.aggs, lambda e: compile_expr(e, cols, n_local),
            order, sm, seg, OUT, sgofs=gofs[order], n_global=n_global)
        return n_uniq.reshape(1), out_keys, tuple(results)

    return _shard_map_norep(shard_fn, mesh,
                            _mesh_in_specs(an, hoisted, n_lvals),
                            P("dp"))


def _wrap_sort_agg(an: _Analyzed, core, mesh: Mesh, S: int,
                   n_local: int, name: str):
    import os as _os

    OUT = min(int(_os.environ.get("TIDB_TPU_AGG_OUT", 1 << 17)), n_local)
    tags = je._agg_tags(an.agg)
    packed = _packed_jit(core, mesh, name, join=bool(an.lookups))

    def wrapped(*operands):
        n_uniq, keys, results = packed(*_call_args(*operands))
        return {
            "mode": "sort",
            "S": S, "OUT": OUT,
            "n_uniq": n_uniq,
            "keys": list(keys),
            "results": [(t, r) for t, r in zip(tags, results)],
        }

    return wrapped


def _sort_agg_chunks(out: dict, table, an: _Analyzed) -> List[Chunk]:
    """Per-shard compacted groups -> partial chunks [keys..., states...]
    in the same layout the CPU engine emits (root final agg merges)."""
    from ..types import TypeKind as TK

    S, OUT = out["S"], out["OUT"]
    n_uniq = out["n_uniq"]
    nk = len(an.agg.group_by)
    chunks: List[Chunk] = []
    for s in range(S):
        k_s = int(n_uniq[s])
        if k_s > OUT:
            raise MeshAggOverflow(
                f"shard {s}: {k_s} groups > budget {OUT}"
            )
        if k_s == 0:
            continue
        lo = s * OUT
        cols: List[Column] = []
        for i, g in enumerate(an.agg.group_by):
            bits = out["keys"][i][lo: lo + k_s]
            flags = out["keys"][nk + i][lo: lo + k_s].astype(np.bool_)
            ft = g.ftype
            rem = (an.key_remaps[i]
                   if getattr(an, "key_remaps", None) else None)
            if rem is not None and rem.out_dict is not None:
                # computed-key codes decode through the remap's OUTPUT
                # dictionary (sorted, so code order == string order);
                # INT-valued remaps (out_dict None) carry the computed
                # values in the key bits directly
                from ..store.blockstore import _decode_dict

                data = _decode_dict(bits.astype(np.int64), rem.out_dict)
            elif ft.kind == TK.FLOAT:
                # value-domain keys; already host numpy (packed readback)
                data = bits.astype(np.float64, copy=False)
            elif ft.kind == TK.STRING:
                from ..store.blockstore import _decode_dict

                store_ci = an.scan.columns[g.index]
                data = _decode_dict(
                    bits.astype(np.int64), table.cols[store_ci].dictionary
                )
            else:
                data = bits.astype(ft.np_dtype)
            cols.append(Column(ft, data, flags if not flags.all() else None))
        for a, (tag, r) in zip(an.agg.aggs, out["results"]):
            pts = a.partial_types()
            if tag == "count":
                cols.append(
                    Column(pts[0], r[lo: lo + k_s].astype(np.int64))
                )
            elif tag == "sumcount":
                sm_, c = r[0][lo: lo + k_s], r[1][lo: lo + k_s]
                sum_col = Column(pts[0], sm_.astype(pts[0].np_dtype), c > 0)
                cols.append(sum_col)
                if a.name == "avg":
                    cols.append(Column(pts[1], c.astype(np.int64)))
            elif tag == "minmax":
                v, c = r[0][lo: lo + k_s], r[1][lo: lo + k_s]
                arg_ft = a.args[0].ftype
                if arg_ft.kind == TK.STRING:
                    from ..store.blockstore import _decode_dict

                    store_ci = an.scan.columns[a.args[0].index]
                    obj = _decode_dict(
                        v.astype(np.int64),
                        table.cols[store_ci].dictionary,
                    )
                    cols.append(Column(pts[0], obj, c > 0))
                else:
                    cols.append(Column(pts[0], v.astype(pts[0].np_dtype), c > 0))
            elif tag == "argfirst":
                idx = r[lo: lo + k_s]
                vals, valid = _gather_first_values(
                    table, an, a.args[0], idx, k_s
                )
                cols.append(Column(pts[0], vals, valid))
        chunks.append(Chunk(cols))
    return chunks


def _peel_agg_rerun(storage, req, tid: int, dag: DAG, reason: str):
    """MeshAggOverflow fallback rung: re-run the SAME fragment with the
    fused region cut just before the aggregation — the scan+selection
    head streams from the mesh and the agg runs as a host tail over the
    still-partial chunks (ROADMAP fusion follow-up (c)).  Returns the
    filter-stream generator, or None when no device head remains (the
    caller then demotes to the host hash agg as before)."""
    from .ir import AggregationIR

    cut = next((i for i, x in enumerate(dag.executors)
                if isinstance(x, AggregationIR)), 0)
    if cut <= 1:
        return None  # scan-only head: a device pass reduces nothing
    from ..metrics import REGISTRY
    from ..trace import annotate

    REGISTRY.inc("mesh_agg_peel_total")
    annotate(mesh_agg_peel=reason[:80])
    # the forced cut analyzes cleanly (no JaxUnsupported), so the split
    # label must be supplied: this is a data-dependent budget overflow,
    # not an unsupported operator
    return _run_mesh_once(storage, req, tid, max_cut=cut,
                          forced_label="agg-overflow")


# ---------------------------------------------------------------------------
# entry: run a CopRequest's base scan over the mesh
# ---------------------------------------------------------------------------


def _mesh_over_partitions(storage, req: CopRequest, tids):
    """One mesh program per partition store; empty/stale partitions
    contribute nothing; any ineligible non-empty partition rejects the
    whole request (the fan-out path then covers every partition)."""
    import dataclasses
    import itertools

    from ..lifecycle import scope_check

    outs = []
    for tid in tids:
        scope_check()  # between per-partition mesh programs
        sub = dataclasses.replace(
            req, ranges=[kr for kr in req.ranges if kr.table_id == tid])
        table = storage.table(tid)
        if table.base_rows == 0 and not table.delta:
            continue
        out = try_run_mesh(storage, sub, table_id=tid)
        if out is None:
            req.mesh_reject_reason = (
                f"partition {tid}: "
                f"{getattr(sub, 'mesh_reject_reason', 'ineligible')}")
            return None
        outs.append(out)
    return itertools.chain.from_iterable(outs)


# initial run + up to two failover retries per request: the first retry
# covers the common one-dead-chip case, the second a cascading failure;
# beyond that the request leaves the mesh path (per-region fan-out rung)
MAX_MESH_ATTEMPTS = 3


def _handle_mesh_failure(req: CopRequest, exc: BaseException,
                         attempts: int) -> bool:
    """Consume one mesh runtime failure; True when the request may retry
    on a (possibly rebuilt) mesh.

    Device-attributed errors trip the chip's breaker and evict every
    cached array placed on a mesh containing it; HBM OOM additionally
    evicts the tile caches wholesale (device memory is a cache over host
    blocks).  Unclassifiable errors are NOT consumed — the caller keeps
    the existing whole-query fallback semantics."""
    from ..metrics import REGISTRY

    kind = classify_failure(exc)
    if kind is None:
        return False
    # trip/evict side effects run EVEN on the final attempt: a device
    # implicated in the last failure must still be quarantined (and its
    # poisoned sharded arrays dropped) for the NEXT query, which would
    # otherwise re-run over the dead chip before its breaker ever trips
    from ..layout import coldtier

    dead = attribute_devices(exc)
    for did in dead:
        DEVICE_HEALTH.record_error(did, exc)
        MESH_CACHE.evict_device(did)
        coldtier.evict_device(did)  # packed blocks die with their mesh
        if _ONES_CACHE is not None:
            _ONES_CACHE.evict_if(lambda k, d=did: d in k[0])
    if kind == "oom":
        REGISTRY.inc("mesh_hbm_oom_total")
        MESH_CACHE.clear()
        coldtier.clear()
        je.DEVICE_CACHE.clear()
        if _ONES_CACHE is not None:
            _ONES_CACHE.clear()
    if attempts + 1 >= MAX_MESH_ATTEMPTS:
        return False
    REGISTRY.inc("mesh_failover_retries_total")
    import logging

    logging.getLogger("tidb_tpu.copr").warning(
        "mesh %s failure (devices %s): retrying over surviving device "
        "set: %s", kind, list(dead) or "unattributed", exc)
    return True


def try_run_mesh(storage, req: CopRequest, table_id=None):
    """Run the whole request across the device mesh with device failover;
    None if ineligible (the caller falls back to the per-region fan-out).

    Failover ladder (README "Fault-tolerance model"): a runtime device
    failure trips the chip's circuit breaker, evicts sharded arrays keyed
    to the dead device set, REBUILDS the mesh over the survivors and
    retries the same shard_map program — one sick chip degrades the mesh,
    it does not demote the whole query to the per-region path.

    Returns an ITERABLE of chunks: a list for agg/topn, a ONE-SHOT lazy
    generator for filters (streamed gathers — iterate exactly once; a
    device error before the first chunk retries on the rebuilt mesh,
    after rows were emitted it surfaces to the consumer)."""
    # the scan's table id as the request's dictionary has it: the one
    # parse of the DAG is `_run_mesh_once`'s, under `mesh.analyze`
    tid = (table_id if table_id is not None
           else req.dag["executors"][0]["table_id"])
    range_tids = sorted({kr.table_id for kr in req.ranges})
    if range_tids and (len(range_tids) > 1 or range_tids[0] != tid):
        # partitioned table: ranges address partition stores, not the
        # logical id in the DAG — run one mesh program per partition and
        # chain results (each sub-request re-enters this wrapper, so
        # failover applies per partition)
        return _mesh_over_partitions(storage, req, range_tids)
    if _no_eligible_devices():
        # every breaker open and no probe due: step down the ladder
        req.mesh_reject_reason = "all device breakers open"
        return None
    attempts = 0
    while True:
        try:
            out = _run_mesh_once(storage, req, tid)
        except CoordEpochMismatch:
            # membership moved between mesh build and dispatch (a member
            # lost, rejoined, or health-shrunk on some host): rebuild
            # from the new broadcast and retry — typed and retriable by
            # design, no breaker trips, never a collective desync
            if attempts + 1 >= MAX_MESH_ATTEMPTS:
                raise
            attempts += 1
            continue
        except BaseException as e:
            if not _handle_mesh_failure(req, e, attempts):
                raise
            if _no_eligible_devices():
                # the failure just tripped the LAST breaker: don't burn
                # the remaining attempts rebuilding over known-dead chips
                # (_eligible_devices' all-tripped fallback) — step down
                req.mesh_reject_reason = "all device breakers open"
                return None
            attempts += 1
            continue
        if out is not None and not isinstance(out, list):
            # lazy filter stream: iteration gets the same failover loop
            return _guarded_stream(storage, req, tid, out, attempts)
        return out


def _guarded_stream(storage, req: CopRequest, tid: int, gen, attempts: int):
    """Wrap a one-shot filter stream in the failover loop: a device
    failure BEFORE the first chunk rebuilds the mesh and restarts the
    stream from scratch; after rows were emitted a retry would duplicate
    them, so the error surfaces (distsql applies the same pre-first-chunk
    rule to its own fallback)."""
    while True:
        emitted = False
        try:
            if gen is None:
                # retry setup runs INSIDE the failover loop: a failure
                # while rebuilding (e.g. OOM re-sharding onto fewer
                # chips) gets the same classify/trip/retry treatment
                gen = _run_mesh_once(storage, req, tid)
                if gen is None or isinstance(gen, list):
                    # re-analysis on the rebuilt mesh declined the
                    # request (data changed under us): surface as a
                    # pre-first-chunk device failure — the retry follows
                    # one — so distsql steps down to the per-region path
                    raise DeviceFailure(
                        "mesh retry declined: "
                        f"{getattr(req, 'mesh_reject_reason', 'ineligible')}")
            for c in gen:
                emitted = True
                yield c
            return
        except CoordEpochMismatch:
            # pre-first-chunk membership move: restart the stream on the
            # rebuilt mesh (same rule as device failures — after rows
            # were emitted a retry would duplicate them)
            if emitted or attempts + 1 >= MAX_MESH_ATTEMPTS:
                raise
            attempts += 1
            gen = None
            continue
        except BaseException as e:
            # trip/evict side effects run even when the error must
            # surface (mid-stream failures after emitted rows): the NEXT
            # query needs the dead chip quarantined either way
            handled = _handle_mesh_failure(req, e, attempts)
            if emitted or not handled:
                raise
            if _no_eligible_devices():
                # last breaker just tripped: surface pre-first-chunk so
                # distsql steps down to the per-region rung
                raise
            attempts += 1
            gen = None


def _observe_fragment(table, an: _Analyzed):
    """Feed the fragment's column USAGE to the layout autotuner: which
    store columns serve as filter inputs, group keys, aggregate
    arguments and probe keys (the agg-vs-probe signal the residency
    priority weighs)."""
    from ..layout import LAYOUT, layout_enabled

    if not layout_enabled():
        return
    width = len(an.scan.columns)

    def obs(exprs, kind):
        refs: set = set()
        for e in exprs:
            e.collect_columns(refs)
        for i in refs:
            if i < width:
                LAYOUT.observe(table, an.scan.columns[i], kind)

    obs(an.conds, "filter")
    obs([p.key for p in an.probes] + [lk.key for lk in an.lookups],
        "probe_key")
    if an.agg is not None:
        obs(an.agg.group_by, "agg_key")
        obs([x for a in an.agg.aggs for x in a.args], "agg_arg")


def _run_mesh_once(storage, req: CopRequest, tid: int,
                   max_cut: Optional[int] = None,
                   forced_label: Optional[str] = None):
    """One attempt at running the request over the current mesh; None if
    ineligible.  Raises on runtime failures — try_run_mesh owns failover.

    `max_cut` caps the fused region at an executor boundary — the
    MeshAggOverflow peel re-enters here with the cut placed just before
    the aggregation, so the scan+selection head stays on device and only
    the blown-budget agg moves to the host tail.  `forced_label` names
    the split reason for such forced cuts (the region analyzes cleanly,
    so plan_regions cannot classify them itself)."""
    from ..trace import annotate, span

    with span("mesh.analyze") as asp:
        dag = DAG.from_dict(req.dag)
        table = storage.table(tid)
        if table.base_rows == 0 or table.base_ts > req.ts:
            req.mesh_reject_reason = "empty table or stale snapshot"
            return None
        if len(req.ranges) > MESH_RANGE_SLOTS:
            req.mesh_reject_reason = f"{len(req.ranges)} disjoint ranges"
            return None  # many disjoint ranges: per-region fan-out handles it
        from .fusion import fusion_enabled, plan_regions, run_tail

        if not fusion_enabled():
            req.mesh_reject_reason = "whole-fragment fusion disabled"
            return None
        # fusion-region planning (copr/fusion.py): the longest device-
        # compilable executor prefix becomes the fused mesh program; an
        # unfusable suffix runs as a host tail over the region's output
        # instead of rejecting the whole fragment off the mesh path
        try:
            plan = plan_regions(dag, table, max_cut=max_cut)
        except JaxUnsupported as e:
            req.mesh_reject_reason = str(e)
            return None
        if plan.tail and len(plan.dag.executors) == 1:
            req.mesh_reject_reason = (
                plan.split_reason or "fragment not device-eligible")
            return None
        if plan.tail and forced_label and plan.split_reason is None:
            # the forced cut saw no JaxUnsupported (the head analyzes
            # cleanly), so classify_split_reason defaulted — the caller
            # knows the true cause (e.g. a blown agg budget)
            plan.reason_label = forced_label
        an, tail = plan.an, plan.tail
        kind = "agg" if an.agg is not None else (
            "topn" if an.topn is not None else "filter"
        )
        # hoist predicate constants into runtime parameter slots (serving/
        # params.py): the fingerprint serializes slots, so parameter-different
        # queries — a changed date literal, a different point-lookup key —
        # reuse the SAME compiled shard_map program instead of recompiling
        from ..serving import hoist_conds

        hoisted = hoist_conds(an)

        mesh = get_mesh()
        S = len(mesh.devices.ravel())
        n_tiles, n_pad, Tl = _layout(table.base_rows, S, table=table)
        col_order = an.needed_cols()
        _observe_fragment(table, an)

        # runtime join-filter payloads: sorted build keys, padded to a pow2
        # bucket so compiled programs are reused across key-set sizes
        pargs: list = []
        counts: List[int] = []
        kpads: List[int] = []
        for p in an.probes:
            arr = (req.aux or {}).get(f"probe_keys_{p.filter_id}")
            if arr is None:
                from ..errors import ExecutorError

                raise ExecutorError(
                    f"missing runtime probe keys {p.filter_id}")
            if p.key.ftype.kind == TypeKind.FLOAT:
                # aux carries canonical int64 BIT patterns (ir.key_bits_int64);
                # the device compares float keys by VALUE (no 64-bit bitcast on
                # this backend), so translate bits -> values here and re-sort
                # (bit order != value order for negatives)
                vals = np.sort(arr.view(np.float64))
                k = len(vals)
                kpad = 16
                while kpad < k:
                    kpad <<= 1
                padded = np.full(kpad, np.inf, dtype=np.float64)
                padded[:k] = vals
            else:
                k = len(arr)
                kpad = 16
                while kpad < k:
                    kpad <<= 1
                padded = np.full(kpad, np.iinfo(np.int64).max, dtype=np.int64)
                padded[:k] = arr
            pargs.append(jnp.asarray(padded))
            counts.append(k)
            kpads.append(kpad)
        asp.set(kind=kind)

    from ..expr.expression import ColumnExpr

    if an.lookups:
        annotate(join=len(an.lookups))  # on `distsql.fanout`
    for lk in an.lookups:
        arr = (req.aux or {}).get(f"probe_keys_{lk.filter_id}")
        payload = (req.aux or {}).get(f"payload_{lk.filter_id}")
        pvalids = (req.aux or {}).get(f"payload_valid_{lk.filter_id}")
        if arr is None or payload is None:
            from ..errors import ExecutorError

            raise ExecutorError(f"missing join lookup aux {lk.filter_id}")
        if lk.key.ftype.kind == TypeKind.FLOAT:
            req.mesh_reject_reason = "float lookup key"
            return None
        k = len(arr)
        kpad = 16
        while kpad < k:
            kpad <<= 1
        fd = _fd_lookup(an)
        # a merge's sort key in 32 bits where both sides fit: the probe
        # key is a column cached that narrow, the build keys lie inside
        # (the type's maximum stays free for the pad)
        kdt = np.dtype(np.int64)
        if fd and k and isinstance(lk.key, ColumnExpr) \
                and _wire_dtype(table, an.scan.columns[lk.key.index]
                                ).itemsize <= 4 \
                and np.iinfo(np.int32).min <= arr[0] \
                and arr[-1] < np.iinfo(np.int32).max:
            kdt = np.dtype(np.int32)
        with span("join.build", phase="upload", rows=k) as sp:
            padded = np.full(kpad, np.iinfo(kdt).max, dtype=kdt)
            padded[:k] = arr
            sent = [padded]
            for j, ft in enumerate(() if fd else lk.payload_ftypes):
                pl = np.zeros(kpad, dtype=_full_dtype(ft.kind))
                pl[:k] = payload[j]
                pv = np.zeros(kpad, dtype=np.bool_)
                src_v = pvalids[j] if pvalids is not None else None
                pv[:k] = True if src_v is None else src_v
                sent += [pl, pv]
            pargs += [jnp.asarray(a) for a in sent]
            sp.set(bytes=sum(a.nbytes for a in sent))
        counts.append(k)
        kpads.append((kpad, kdt.name) if fd else kpad)

    # column arrays load BEFORE the program lookup: the compiled program
    # is specialized on each column's wire dtype/null pattern AND its
    # layout class (cold columns arrive as packed codes + a dictionary
    # runtime operand — the decode emitter is part of the fragment).
    # Loads run on the transfer pool so host tile builds overlap
    # host-to-device transfers.
    datas, valids, col_layout, lvals, wire_sig = [], [], [], [], []
    entries = load_layout_columns(
        mesh, table, [an.scan.columns[ci] for ci in col_order])
    with span("mesh.program"):
        for tier, entry in entries:
            if tier == "cold":
                datas.append(entry.packed)
                valids.append(None)
                col_layout.append((entry.bits, entry.cap, entry.kind))
                # the decode operand (bias scalar / dictionary vector) is
                # already device-resident and replicated — a cold hit ships
                # NOTHING over the link
                lvals.append(entry.operand)
                wire_sig.append(
                    (f"cold{entry.bits}c{entry.cap}{entry.kind[0]}", True))
            else:
                d, v = entry
                datas.append(d)
                valids.append(v)
                col_layout.append(None)
                wire_sig.append((str(d.dtype), v is None))
        # computed-key remap operands ride the lvals tail AFTER the cold
        # dictionary operands (one ordering contract with _build_sort_agg_core
        # and trace_fused_fragment); mapping CONTENTS are runtime data
        for r in (getattr(an, "key_remaps", None) or ()):
            if r is not None:
                lvals.append(jnp.asarray(r.mapping))
        lvals = tuple(lvals)
        if not any(col_layout):
            col_layout = None

        # device ids in the key: a rebuilt mesh (even same-size, after a
        # breaker trip + probe-restore cycle) must never reuse a program whose
        # closure captured the dead mesh object
        mesh_ids = tuple(d.id for d in mesh.devices.ravel())
        fp = (_fingerprint(an, kind)
              + f"|mesh S={S} Tl={Tl} devs={mesh_ids} cols={col_order} "
              + f"kpads={kpads} wire={wire_sig}"
              + (f"|hp={len(hoisted[0])},{len(hoisted[1])}"
                 if hoisted is not None else ""))
        if kind == "agg" and an.agg_mode == "sort":
            # the static OUT budget shapes the compiled program: a re-tuned
            # TIDB_TPU_AGG_OUT must not reuse a program with the old budget
            import os as _os

            fp += "|aggout=" + _os.environ.get("TIDB_TPU_AGG_OUT", "")
        from .fusion import compile_attrs, note_agg_dispatch

        cattrs = compile_attrs(an, kind)
        fn = _COMPILED.get(fp)
        if fn is None:
            fn = _build_mesh_fn(an, kind, col_order, mesh, Tl,
                                _program_name(kind, fp),
                                hoisted=(None if hoisted is None else
                                         (len(hoisted[0]), len(hoisted[1]))),
                                col_layout=col_layout)
            _COMPILED.put(fp, fn)
            # label this query's FIRST dispatch as the compile: jit compiles
            # lazily, so the program-cache miss pays XLA compilation there
            fn = _compile_labeled(fn, kind, cattrs)
        else:
            with span("copr.compile", cache="hit", kind=kind, **cattrs):
                pass
        pargs = tuple(pargs)
        # the statement's int64 scalars ride the operand vector behind each
        # dispatch's range slots (the shard program reads them back through
        # _split_operands); float64 parameters, where there are any, are the
        # last parg, a host array as hoist_conds made it
        scalars = np.array(counts, dtype=np.int64)
        if hoisted is not None:
            scalars = np.concatenate([scalars, hoisted[0]])
            if len(hoisted[1]):
                pargs = pargs + (hoisted[1],)

    # on `distsql.fanout`
    annotate(device_ids=list(mesh_ids), tiles=n_tiles, tiles_padded=n_pad)

    with span("mesh.delta") as dsp:
        # one delta pass for the whole table
        deleted, inserted = table.delta_overlay(req.ts, 0, 1 << 62)
        if deleted:
            dm = np.ones((n_pad, je.TILE), dtype=np.bool_)
            flat = dm.reshape(-1)
            flat[np.fromiter(sorted(deleted), dtype=np.int64,
                             count=len(deleted))] = False
            del_mask = jax.device_put(dm, NamedSharding(mesh, P("dp")))
        else:
            del_mask = _all_true(mesh, n_pad)

        REGISTRY.inc("mesh_scans_total")
        if cattrs:
            note_agg_dispatch(an)

        # every requested range runs in ONE fused dispatch: clip the bounds
        # host-side and hand them to the program's range slots — no per-range
        # dispatch loop, no host glue between ranges
        bounds = []
        for kr in req.ranges:
            lo, hi = max(kr.start, 0), min(kr.end, table.base_rows)
            if lo < hi:
                bounds.append((lo, hi))
        dsp.set(deleted=len(deleted), inserted=len(inserted))

    if kind == "filter":
        # large filter outputs STREAM: the generator gathers selected rows
        # in STREAM_ROWS slices as the consumer drains the bounded queue,
        # so peak host memory no longer scales with the selected row count
        return _stream_filter(req, table, an, fn, datas, valids, del_mask,
                              inserted, pargs, scalars, mesh_ids=mesh_ids,
                              bounds=bounds, tail=tail, dag=dag,
                              lvals=lvals,
                              split_label=plan.reason_label)

    out = None
    if bounds:
        out = _dispatch_once(kind, fn, datas, valids, del_mask, bounds,
                             lvals, pargs, scalars, mesh_ids)
    try:
        with span("mesh.result") as rsp:
            chunks = _mesh_result(req, dag, table, an, kind, out, inserted,
                                  S)
            # every shard program over every range completed: reset error
            # streaks and close any half-open breaker that just survived
            # its probe
            DEVICE_HEALTH.record_success(mesh_ids)
            rsp.set(chunks=len(chunks))
            return chunks
    except MeshAggOverflow as e:
        # data-dependent, by-design: too many distinct groups per
        # shard.  Re-enter the fused mesh with the AGG PEELED to
        # the host tail (scan+selection stays device-resident and
        # streamed) instead of dropping the whole fragment to the
        # per-tile fan-out rung; fragments with no device-worthy
        # head still take the old host-hash-agg demotion.
        peeled = _peel_agg_rerun(storage, req, tid, dag, str(e))
        if peeled is not None:
            return peeled
        req.mesh_reject_reason = str(e)
        return None


def _mesh_result(req, dag, table, an: _Analyzed, kind: str, out, inserted,
                 S: int) -> List[Chunk]:
    """What `mesh.result` covers: from the dispatch's unpacked result
    (`out`; None where no range held a row) to the chunks the fan-out
    hands on: accumulate, the delta rows' chunk, the TopN merge and the
    host tail.  A sort aggregate past its budget raises MeshAggOverflow."""
    chunks: List[Chunk] = []
    topn_parts: List[Chunk] = []
    if out is None:
        pass
    elif kind == "agg" and an.agg_mode == "sort" \
            and out["mode"] == "lookup":
        chunks.extend(_lookup_agg_chunks(out, an, req.aux))
    elif kind == "agg" and an.agg_mode == "sort":
        chunks.extend(_sort_agg_chunks(out, table, an))
    elif kind == "agg":
        # wrapped() already unpacked to numpy and merged shard partials
        chunks.append(je._device_agg_to_chunk(
            _mesh_agg_accum(*out, table, an), table, an))
    elif kind == "topn":
        gidx, cnts, k = out
        picks = []
        for s in range(S):
            c = int(cnts[s])
            if c:
                picks.append(gidx[s * k: s * k + c])
        if picks:
            topn_parts.append(
                table.gather_chunk(list(an.scan.columns),
                                   np.concatenate(picks)))

    # delta rows (committed inserts/updates) go through the CPU engine
    res = _delta_chunk(req, dag, an, inserted)
    if res is not None:
        if kind == "topn":
            topn_parts.append(res)
        else:
            chunks.append(res)

    if topn_parts:
        from .cpu_engine import run_topn

        merged = topn_parts[0]
        for p in topn_parts[1:]:
            merged = merged.append(p)
        chunks = [run_topn(an.topn.order_by, an.topn.limit, merged)]

    from .engine import _merge_tail

    return [c for c in _merge_tail(dag, chunks) if c.num_rows > 0]


def _dispatch_once(kind, fn, datas, valids, del_mask, bounds, lvals, pargs,
                   scalars, mesh_ids):
    """The ONE dispatch of a mesh statement: the compiled program over
    all of the statement's range slots, between two cancellation seams.
    A dispatch in flight runs to completion and costs one pass over the
    resident table whatever its bounds (the program masks rows outside
    them, it does not skip them), so KILL / timeout / a depleted
    resource group land before it or after it."""
    from ..lifecycle import dispatch_admission, scope_check
    from ..trace import span

    scope_check()
    # deterministic mid-scan fault injection: the chaos harness kills
    # virtual device k / exhausts HBM exactly here, pre-dispatch
    start, end = bounds[0][0], bounds[-1][1]
    FAILPOINTS.hit("mesh/device_error", kind=kind, device_ids=mesh_ids,
                   start=start, end=end)
    FAILPOINTS.hit("mesh/hbm_oom", kind=kind, start=start, end=end)
    _check_membership_epoch()
    FAILPOINTS.hit("copr/chunk_dispatch", kind=kind, chunk=0, total=1,
                   start=start, end=end)
    with span("copr.chunk", kind=kind, devices=len(mesh_ids),
              rows=sum(hi - lo for lo, hi in bounds)):
        # resource-group admission per dispatch: a depleted group
        # waits here, and the dispatch's device time is its charge
        with dispatch_admission(DISPATCH_LOCK):
            out = fn(datas, valids, del_mask, bounds, lvals, pargs, scalars)
    scope_check()  # post-dispatch seam: expired statements stop here
    return out


def _stream_filter(req, table, an, fn, datas, valids, del_mask, inserted,
                   pargs=(), scalars=(), mesh_ids=(), bounds=(), tail=None,
                   dag=None, lvals=(), split_label=None):
    """Generator over a mesh filter's result chunks: ONE fused bit-packed
    mask dispatch covering every range, then STREAM_ROWS-sized host
    gathers on demand (distsql/stream.go:33-124; kv.Request.Streaming
    kv/kv.go:270).  When the fusion splitter peeled a host tail off the
    fragment, each streamed scan-layout chunk runs the tail through the
    CPU interpreter before it is yielded (copr/fusion.py ladder)."""
    from ..lifecycle import scope_check
    from ..metrics import REGISTRY
    from ..trace import span
    from .fusion import run_tail

    if bounds:
        if tail:
            from .fusion import note_split

            note_split(split_label, type(tail[0]).__name__)
        mask = _dispatch_once("filter", fn, datas, valids, del_mask, bounds,
                              lvals, pargs, scalars, mesh_ids)
        # the host's finishing of the dispatch, each step under a span
        # of its own (none of them wraps a yield: the consumer's time
        # between two slices is the queue's, not this thread's work)
        with span("copr.select", rows_in=int(mask.size)) as sp:
            handles = np.flatnonzero(mask)
            if an.limit is not None:
                handles = handles[:an.limit]
            sp.set(rows=len(handles))
        for off in range(0, len(handles), STREAM_ROWS):
            scope_check()  # between streamed host gathers
            hsub = handles[off: off + STREAM_ROWS]
            with span("copr.gather", rows=len(hsub)) as sp:
                chunk = table.gather_chunk(list(an.scan.columns), hsub)
                if an.proj_exprs is not None:
                    # dict-rewritten exprs expect coded strings; gather
                    # decodes, so project from the original projection
                    # IR
                    chunk = Chunk([
                        _eval_to_column(p, chunk)
                        for p in an.projection.exprs
                    ])
                # the arrays' own bytes (an object column counts its
                # pointers: no walk over the values)
                sp.set(bytes=sum(c.data.nbytes for c in chunk.columns))
            if tail:
                with span("copr.tail", rows_in=chunk.num_rows) as sp:
                    tcs = run_tail(dag, tail, [chunk], req.aux)
                    sp.set(rows=sum(tc.num_rows for tc in tcs))
                for tc in tcs:
                    REGISTRY.inc("mesh_stream_chunks_total")
                    yield tc
                continue
            REGISTRY.inc("mesh_stream_chunks_total")
            yield chunk
    DEVICE_HEALTH.record_success(mesh_ids)
    res = _delta_chunk(req, None, an, inserted)
    if res is not None:
        yield res


def _delta_chunk(req, dag, an, inserted) -> Optional[Chunk]:
    """Committed delta rows in range, run through the CPU engine's DAG
    interpreter (shared by the materialized and streaming paths)."""
    if not inserted:
        return None
    in_range = {
        h: v for h, v in inserted.items()
        if any(kr.start <= h < kr.end for kr in req.ranges)
    }
    if not in_range:
        return None
    from .cpu_engine import run_dag_on_chunk

    if dag is None:
        dag = DAG.from_dict(req.dag)
    hs = sorted(in_range)
    cols = []
    for out_i, store_ci in enumerate(an.scan.columns):
        ft = an.scan.ftypes[out_i]
        vals = [in_range[h][store_ci] for h in hs]
        cols.append(Column.from_values(ft, vals))
    res = run_dag_on_chunk(dag, Chunk(cols), req.aux)
    return res if res.num_rows else None


def _eval_to_column(expr, chunk: Chunk) -> Column:
    v = expr.eval(chunk)
    return Column(expr.ftype, v.data, v.validity())


def _mesh_agg_accum(gcount: np.ndarray, results, table, an: _Analyzed):
    """One mesh dispatch's final arrays in the accum layout
    `je._device_agg_to_chunk` expects."""
    states = []
    for a, (tag, r) in zip(an.agg.aggs, results):
        if tag == "count":
            states.append([tag, r, None])
        elif tag == "argfirst":
            # r: per-group min global row index (sentinel >= base_rows
            # when the group is empty)
            states.append([tag, *_gather_first_values(
                table, an, a.args[0], r, an.num_groups)])
        else:  # sumcount / minmax: (values, counts)
            states.append([tag, *r])
    return {"gcount": gcount, "states": states}


def _gather_first_values(table, an: _Analyzed, arg, idx: np.ndarray, G: int):
    """(values[G], valid[G]) for first_row partials: gather only the store
    columns the argument reads, not the whole scan width."""
    from ..expr.expression import ColumnExpr

    have = idx < table.base_rows
    sel = np.flatnonzero(have)
    st = arg.ftype
    if st.kind == TypeKind.STRING:
        vals = np.empty(G, dtype=object)
        vals[:] = ""
    else:
        vals = np.zeros(G, dtype=st.np_dtype)
    valid = np.zeros(G, dtype=np.bool_)
    if len(sel):
        if isinstance(arg, ColumnExpr):
            rows = table.gather_chunk(
                [an.scan.columns[arg.index]], idx[sel]
            )
            col = rows.col(0)
            vals[sel] = col.data
            valid[sel] = col.validity()
        else:
            rows = table.gather_chunk(list(an.scan.columns), idx[sel])
            v = arg.eval(rows)
            vals[sel] = v.data
            valid[sel] = v.validity()
    return vals, valid
