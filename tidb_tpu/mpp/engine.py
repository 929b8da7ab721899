"""run_mpp_join: the device-resident partitioned shuffle join engine.

Reference: TiFlash's MPP task graph — ExchangeSender hash-partitions each
plan fragment's rows, ExchangeReceiver reassembles partitions per node,
and a per-node hash join runs on co-partitioned inputs.  Mapped onto the
mesh: both sides' base tables are already sharded over the device mesh
(`copr.parallel.MESH_CACHE`), so the "fragments" are shard_map shards,
the sender/receiver pair is one `jax.lax.all_to_all` per column, and the
co-partitioned local join is argsort + searchsorted — one compiled XLA
program from scan to joined rows (or scalar partials).

Join-strategy ladder (README "MPP exchange engine"):

1. shuffle    — both sides hash-partitioned by join key and exchanged;
                per-(src,dst) buckets have static capacity, so skew
                overflows are detected on device and demote to
2. broadcast  — the build side is replicated to every shard via
                all_gather (no probe exchange, immune to probe skew);
                build sides above DEVICE_JOIN_BUILD_MAX skip to
3. host       — MPPIneligible is raised and the caller (MPPReaderExec)
                runs the root HashJoinExec.

Device failures ride the copr.device_health ladder: a classified error
trips the chip's breaker, evicts poisoned sharded arrays, REBUILDS the
mesh and retries; exhausted retries or an all-open breaker set demote to
the host rung instead of failing the query.
"""

from __future__ import annotations

from dataclasses import dataclass, field
from typing import Dict, List, Optional, Tuple

import numpy as np

from .. import ops  # noqa: F401  (configures x64)
import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from jax import shard_map

from ..chunk import Chunk, Column
from ..copr import jax_engine as je
from ..copr.device_health import classify_failure
from ..copr.jax_engine import _Analyzed, _fingerprint, _to_state_dtype
from ..copr.jax_eval import JaxUnsupported, compile_expr
from ..coord import CoordEpochMismatch
from ..copr.parallel import (
    MAX_MESH_ATTEMPTS,
    MESH_RANGE_SLOTS,
    _all_true,
    _bounds_args,
    _check_membership_epoch,
    _RowView,
    _cols_env,
    _handle_mesh_failure,
    _layout,
    _named_jit,
    _no_eligible_devices,
    _packed_jit,
    get_mesh,
)
from ..copr.ir import DAG
from ..metrics import REGISTRY
from ..store.fault import FAILPOINTS
from ..store.kv import KeyRange
from ..types import TypeKind
from . import exchange as ex

# broadcast rung ceiling: replicating the build side to every shard costs
# S * build bytes of HBM; above this the only safe rung is the host join
# (same constant the planner's broadcast lookup join gates on)
from ..planner.physical import DEVICE_JOIN_BUILD_MAX  # noqa: E402


class MPPIneligible(Exception):
    """The MPP engine declines this join; the caller takes the host
    rung.  Message = reason string (surfaced in EXPLAIN ANALYZE)."""


class MPPPartitionOverflow(Exception):
    """A (source, destination) exchange bucket — or the two-pass join's
    emission buffer — exceeded its static capacity: the compiled program
    dropped rows, so the result is incomplete and the run must step down
    the ladder."""


class MPPGroupedAggOverflow(Exception):
    """The per-shard or merged distinct-group count exceeded the runtime
    group budget: the compacted group slots hold merged garbage beyond
    the cap, so the grouped pushdown is invalid for this data.  The run
    retries with the AGG PEELED to a host tail over the still-device-
    resident join output (not a full host-join demotion)."""


@dataclass
class MPPJoinSide:
    """One side of the join: a scan[+selection] cop DAG over one table."""

    table_id: int
    dag: dict                   # serialized DAG (TableScanIR + SelectionIR*)
    ranges: List[KeyRange]
    key_pos: List[int]          # scan-output positions of the join key(s)
    out_ftypes: list = field(default_factory=list)  # schema ftypes by pos


@dataclass
class MPPJoinSpec:
    probe: MPPJoinSide
    build: MPPJoinSide
    kind: str                   # "inner" | "left_outer"
    probe_is_left: bool
    ts: int = 0
    # partial-agg pushdown: AggDescs over the JOINED layout (probe scan
    # positions, then build positions at probe_width+j); only set for
    # inner joins with probe_is_left
    aggs: Optional[list] = None
    # grouped partial-agg pushdown: GROUP BY expressions over the joined
    # layout; None = scalar aggregation (G=1) when aggs is set
    group_by: Optional[list] = None
    # planner's group-cardinality budget: the device detects budget
    # overflow and the run falls back to the agg-peel rung.  The STATIC
    # group capacity pow2-buckets this value; the budget itself rides a
    # runtime scalar slot (never enters the compiled fingerprint)
    group_budget: int = 0
    # co-partitioned elision (PhysMPPJoin.elided): ordinal-aligned
    # (probe partition id, build partition id) pairs — the join runs per
    # pair with NO exchange between partitions (inner joins only)
    copartitions: Optional[List[Tuple[int, int]]] = None


from ..copr.cache import ProgramCache

_COMPILED = ProgramCache("mpp")

OUT_CHUNK_ROWS = 1 << 16


def _pow2ceil(n: int) -> int:
    c = 16
    while c < n:
        c <<= 1
    return c


def _slack() -> float:
    import os

    return float(os.environ.get("TIDB_TPU_MPP_SLACK", "2.0"))


class _SideState:
    """Everything one side contributes to the program: analysis, layout,
    device arrays, range bounds."""

    def __init__(self, storage, side: MPPJoinSide, ts: int, mesh):
        self.side = side
        self.table = storage.table(side.table_id)
        t = self.table
        if t.base_rows == 0:
            raise MPPIneligible(f"table {side.table_id} empty")
        if t.base_ts > ts:
            raise MPPIneligible("stale snapshot")
        deleted, inserted = t.delta_overlay(ts, 0, 1 << 62)
        if inserted:
            # committed delta rows live host-side; joining them against
            # device-resident rows needs the host join
            raise MPPIneligible("delta rows present")
        self.deleted = deleted
        if any(kr.table_id != side.table_id for kr in side.ranges):
            raise MPPIneligible("partitioned ranges")
        if len(side.ranges) > MESH_RANGE_SLOTS:
            raise MPPIneligible(f"{len(side.ranges)} disjoint ranges")
        dag = DAG.from_dict(side.dag)
        try:
            self.an = _Analyzed(dag, t)
        except JaxUnsupported as e:
            raise MPPIneligible(str(e))
        an = self.an
        if an.agg or an.topn or an.probes or an.lookups or an.projection:
            raise MPPIneligible("side DAG is not scan+selection")
        for kp in side.key_pos:
            kft = an.scan.ftypes[kp]
            if kft.kind in (TypeKind.FLOAT, TypeKind.STRING):
                raise MPPIneligible(f"non-int join key {kft.kind.name}")
        for ft in an.scan.ftypes:
            if ft.kind == TypeKind.DECIMAL and ft.is_wide_decimal:
                raise MPPIneligible("wide-decimal column")
        S = len(mesh.devices.ravel())
        self.n_tiles, self.n_pad, self.Tl = _layout(t.base_rows, S,
                                                    table=t)
        self.n_local = self.Tl * je.TILE
        self.col_order = list(range(len(an.scan.columns)))
        self.bounds = [(max(kr.start, 0), min(kr.end, t.base_rows))
                       for kr in side.ranges]

    def load(self, mesh):
        """Device arrays: cached sharded columns + deletion mask."""
        from ..copr.parallel import load_columns

        datas, valids = [], []
        for d, v in load_columns(
                mesh, self.table,
                [self.an.scan.columns[ci] for ci in self.col_order]):
            datas.append(d)
            valids.append(v)
        self.datas, self.valids = datas, valids
        self.wire_sig = [(str(d.dtype), v is None)
                         for d, v in zip(datas, valids)]
        if self.deleted:
            dm = np.ones((self.n_pad, je.TILE), dtype=np.bool_)
            flat = dm.reshape(-1)
            flat[np.fromiter(sorted(self.deleted), dtype=np.int64,
                             count=len(self.deleted))] = False
            self.del_mask = jax.device_put(
                dm, NamedSharding(mesh, P("dp")))
        else:
            self.del_mask = _all_true(mesh, self.n_pad)

    def exchange_cols(self):
        """(scan position, env dtype itemsize) for every exchanged
        column — the bytes-metric accounting."""
        from ..copr.parallel import _full_dtype

        return [(ci, _full_dtype(self.an.scan.ftypes[ci].kind).itemsize)
                for ci in self.col_order]


def _shift_expr(e, delta: int):
    """Clone an expression with every column index shifted by `delta`
    (joined-layout indices -> one side's scan layout)."""
    from ..copr.ir import deserialize_expr, serialize_expr
    from ..expr.expression import ColumnExpr, ScalarFunc

    e2 = deserialize_expr(serialize_expr(e))

    def walk(x):
        if isinstance(x, ColumnExpr):
            x.index += delta
        elif isinstance(x, ScalarFunc):
            for a in x.args:
                walk(a)

    walk(e2)
    return e2


def _mpp_key_remaps(spec: MPPJoinSpec, ps: "_SideState", bs: "_SideState"):
    """Dict-code remaps for computed STRING group keys over the JOINED
    layout (MPP follow-up (d)): each key's single source column resolves
    to its OWNING side's store and the remap builds there; the device
    then re-maps codes post-join, inside the same exchange program.
    Raises MPPIneligible (host rung) when a computed key is not
    remappable."""
    from ..copr import fusion
    from ..expr.expression import ColumnExpr

    if spec.aggs is None or spec.group_by is None:
        return None
    from ..copr.jax_engine import _string_leaf

    wp = len(ps.col_order)
    remaps = []
    for g in spec.group_by:
        if isinstance(g, ColumnExpr) or not (
                g.ftype.kind == TypeKind.STRING or _string_leaf(g)):
            remaps.append(None)
            continue
        # JOINED-layout POSITIONS (collect_columns would return planner
        # uids here — these exprs still carry them; the engine works in
        # index space)
        refs: set = set()

        def walk(x):
            if isinstance(x, ColumnExpr):
                refs.add(x.index)
            for c in getattr(x, "args", ()) or ():
                walk(c)

        walk(g)
        if refs and all(i < wp for i in refs):
            st, shift = ps, 0
        elif refs and all(i >= wp for i in refs):
            st, shift = bs, wp
        else:
            raise MPPIneligible(
                f"computed group key spans both join sides: {g}")
        try:
            rm = fusion.build_key_remap(
                st.table, st.an.scan, _shift_expr(g, -shift))
        except JaxUnsupported as e:
            raise MPPIneligible(str(e))
        remaps.append(fusion.KeyRemap(
            rm.src_idx + shift, rm.mapping, rm.cap, rm.out_dict))
    return remaps if any(r is not None for r in remaps) else None


def _compound_pack(ps: "_SideState", bs: "_SideState"):
    """(los, cards) for exact multi-column key packing, or None when the
    packed space overflows int64 (the mix-hash ladder then remains)."""
    if len(ps.side.key_pos) <= 1:
        return None

    def stats(st, kp):
        lo, hi, _null = st.table.column_stats(st.an.scan.columns[kp])
        return (lo, hi)

    pairs = [(stats(ps, kp), stats(bs, kb))
             for kp, kb in zip(ps.side.key_pos, bs.side.key_pos)]
    return ex.compound_pack_spec(pairs)


def _shard_side(an: _Analyzed, col_order, n_local: int, n_ranges: int):
    """Returns fn(datas, valids, del_mask, bounds) -> (cols env, selected
    row mask) for one side, evaluated per shard pre-exchange."""

    def prep(datas, valids, del_mask, bounds):
        cols = _cols_env(an, col_order, datas, valids, _RowView(n_local))
        shard = jax.lax.axis_index("dp").astype(jnp.int64)
        gofs = shard * n_local + jnp.arange(n_local, dtype=jnp.int64)
        m = jnp.zeros(n_local, dtype=jnp.bool_)
        for r in range(n_ranges):
            m = m | ((gofs >= bounds[2 * r]) & (gofs < bounds[2 * r + 1]))
        m = m & del_mask.reshape(n_local)
        for c in an.conds:
            d, v = compile_expr(c, cols, n_local)
            m = m & v & (d != 0)
        return cols, m

    return prep


def _agg_specs(spec: MPPJoinSpec) -> tuple:
    """shard_map out_specs of what `_agg_tail` returns."""
    if spec.group_by is not None:
        states = tuple(P("dp") if a.name == "count" else (P("dp"), P("dp"))
                       for a in spec.aggs)
        return (P(), P("dp"), P("dp"),
                tuple(P("dp") for _ in range(2 * len(spec.group_by))),
                states)
    states = tuple(P() if a.name == "count"
                   else ((P(), P()) if a.name in ("sum", "avg")
                         else (P("dp"), P()))
                   for a in spec.aggs)
    return (states,)


def _agg_tail(spec: MPPJoinSpec, p_order, b_order, probe_out, build_out,
              row_mask, cap_out: int, cap_g: int, S: int, remaps, extra):
    """Partial aggregation over the joined rows of one shard (inner
    join): `probe_out` / `build_out` are the (data, valid) pairs of the
    joined layout over `cap_out` slots, `row_mask` the slots that hold a
    joined row.  Scalar: psum'd states.  Grouped: per-shard sort-group
    into the static `cap_g` budget, then the cross-shard merge ON
    DEVICE, so only O(G) group rows leave; `extra` is the runtime group
    budget, then the remap mapping operands."""
    from ..copr import fusion
    from ..copr.fusion import (grouped_partial_states,
                               merge_grouped_partials,
                               sort_group_segments)
    from ..copr.parallel import _key_device

    aggs, group_by = spec.aggs, spec.group_by
    grouped = group_by is not None
    nk = len(group_by) if grouped else 0
    gchunk = cap_g // S if grouped else 0
    gbudget = extra[0] if grouped else None
    rvals = extra[1:] if grouped else ()
    wp = len(p_order)
    env = {ci: probe_out[j] for j, ci in enumerate(p_order)}
    for j in range(len(b_order)):
        env[wp + j] = build_out[j]

    if grouped:
        # -- grouped partial aggregation below the exchange --------
        # per-shard sort-group into the static cap_g budget, then
        # merge partials ACROSS shards on device: all_gather the
        # compacted (key, state) rows, second sort-merge (identical
        # on every shard), and each shard emits its 1/S slice — the
        # readback is O(cap_g), never O(joined rows)
        key_bits, key_flags = [], []
        rslot = 0
        for gi, g in enumerate(group_by):
            rem = remaps[gi] if remaps is not None else None
            if rem is not None:
                # computed string key: post-join code-space gather
                # through the runtime mapping operand
                d0, v = env[rem.src_idx]
                d = fusion.remap_codes(d0, rvals[rslot], cap_out)
                rslot += 1
            else:
                d, v = compile_expr(g, env, cap_out)
            k = _key_device(d)
            zero = (jnp.float64(0.0) if k.dtype == jnp.float64
                    else jnp.int64(0))
            key_bits.append(jnp.where(v, k, zero))
            key_flags.append(v.astype(jnp.int64))
        order, sm, out_keys, seg, n_uniq = sort_group_segments(
            key_bits, key_flags, row_mask, cap_g)
        states = grouped_partial_states(
            aggs, lambda e: compile_expr(e, env, cap_out),
            order, sm, seg, cap_g)
        # the BUDGET is a runtime scalar slot: overflow is detected
        # on device against it, but only the pow2 capacity shapes
        # the compiled program
        over_l = jax.lax.psum(
            jnp.maximum(n_uniq - gbudget, 0), "dp")
        slot_ok = jnp.arange(cap_g, dtype=jnp.int64) \
            < jnp.minimum(n_uniq, cap_g)
        g_keys = [ex.replicate(k) for k in out_keys]
        g_ok = ex.replicate(slot_ok)
        g_states = jax.tree_util.tree_map(ex.replicate, states)
        mn_uniq, m_keys, m_states = merge_grouped_partials(
            aggs, g_keys[:nk], g_keys[nk:], g_ok, g_states, cap_g)
        over_m = jnp.maximum(mn_uniq - gbudget, 0)
        shard = jax.lax.axis_index("dp")

        def slc(y):
            return jax.lax.dynamic_slice(y, (shard * gchunk,),
                                         (gchunk,))

        return (over_l, over_m.reshape(1),
                mn_uniq.reshape(1), tuple(slc(k) for k in m_keys),
                tuple(jax.tree_util.tree_map(slc, m_states)))

    # -- scalar partial aggregation --------------------------------
    states = []
    for a in aggs:
        if a.name == "count":
            if a.args:
                d, v = compile_expr(a.args[0], env, cap_out)
                states.append(jax.lax.psum(
                    (row_mask & v).sum().astype(jnp.int64), "dp"))
            else:
                states.append(jax.lax.psum(
                    row_mask.sum().astype(jnp.int64), "dp"))
            continue
        d, v = compile_expr(a.args[0], env, cap_out)
        mv = row_mask & v
        if a.name in ("sum", "avg"):
            st = a.partial_types()[0]
            dd = _to_state_dtype(d, a.args[0].ftype, st)
            states.append((
                jax.lax.psum(jnp.where(mv, dd, 0).sum(), "dp"),
                jax.lax.psum(mv.sum().astype(jnp.int64), "dp"),
            ))
        else:  # min / max: per-shard partial, host merges (only
            # Sum all-reduces are used on device)
            if a.name == "min":
                sent = (jnp.inf if jnp.issubdtype(d.dtype, jnp.floating)
                        else ex.I64_MAX)
                part = jnp.where(mv, d, sent).min()
            else:
                sent = (-jnp.inf if jnp.issubdtype(d.dtype, jnp.floating)
                        else -ex.I64_MAX - 1)
                part = jnp.where(mv, d, sent).max()
            states.append((
                part.reshape(1),
                jax.lax.psum(mv.sum().astype(jnp.int64), "dp"),
            ))
    return (tuple(states),)


def program_name(kind: str, fp: str) -> str:
    """`mpp_<kind>_<crc32 of the program-cache fingerprint>`: the name of
    an MPP program's jitted callable (its XLA module is `jit_<name>`) and
    of its `copr.device.execute` span, as `parallel._program_name` names
    the mesh programs."""
    import zlib

    return f"mpp_{kind}_{zlib.crc32(fp.encode()) & 0xFFFFFFFF:08x}"


def _build_mpp_fn(spec: MPPJoinSpec, ps: _SideState, bs: _SideState,
                  mode: str, mesh, cap_p: int, cap_b: int, cap_out: int,
                  cap_g: int, pack=None, remaps=None, name=None):
    """One shard_map program: per-shard scan+filter on both sides,
    partition exchange (or build broadcast), two-pass count+emit local
    join (non-unique and multi-column keys), then row emission, scalar
    partial aggregation, or grouped partial aggregation with the
    cross-shard merge ON DEVICE (all_gather of compacted (key, state)
    rows + a second sort-merge), so only O(G) group rows leave.

    `pack` = (los, cards) composes multi-column keys EXACTLY (stride
    packing over the union of both sides' column stats): no collision
    re-verify, and left-outer multi-key joins become sound on device.
    `remaps` carries per-group-key dict-code remaps (computed string
    keys); their mapping operands ride trailing runtime args."""
    S = len(mesh.devices.ravel())
    p_an, b_an = ps.an, bs.an
    # capture ONLY scalars/analysis objects in the shard closure: the
    # compiled program lives in _COMPILED for the process lifetime, and
    # closing over the _SideState objects would pin both sides' sharded
    # device arrays (and their table stores) against any cache eviction
    p_order, b_order = list(ps.col_order), list(bs.col_order)
    p_key_pos = list(ps.side.key_pos)
    b_key_pos = list(bs.side.key_pos)
    # range bounds ride in MESH_RANGE_SLOTS runtime scalar slots per
    # side (pad slots are empty ranges), so the range COUNT never enters
    # the fused program's fingerprint — same policy as the mesh scan
    p_prep = _shard_side(p_an, p_order, ps.n_local, MESH_RANGE_SLOTS)
    b_prep = _shard_side(b_an, b_order, bs.n_local, MESH_RANGE_SLOTS)
    louter = spec.kind == "left_outer"
    aggs = spec.aggs
    group_by = spec.group_by
    grouped = aggs is not None and group_by is not None

    def mk_keys(cols_env, key_pos):
        """(join key, partition key): the join key is the EXACT packed
        composition when `pack` is set (mix-hash otherwise); the
        partition key is ALWAYS the mix-hash — its 64-bit avalanche
        spreads clustered key spaces across the static bucket capacity
        better than the dense packed values, and both sides agree on it
        either way."""
        keys = [cols_env[kp][0].astype(jnp.int64) for kp in key_pos]
        mix = ex.combine_keys(keys)
        if pack is not None:
            return ex.pack_keys_exact(keys, pack[0], pack[1]), mix
        return mix, mix

    def shard_fn(p_datas, p_valids, p_del, p_bounds,
                 b_datas, b_valids, b_del, b_bounds, *extra):
        # ---- build side: filter, partition, exchange ------------------
        b_cols, bm = b_prep(b_datas, b_valids, b_del, b_bounds)
        bk, bmix = mk_keys(b_cols, b_key_pos)
        bk_v = b_cols[b_key_pos[0]][1]
        for kp in b_key_pos[1:]:
            bk_v = bk_v & b_cols[kp][1]
        bsel = bm & bk_v  # NULL build keys never match: drop pre-exchange
        b_arrays = [bk]
        for ci in b_order:
            d, v = b_cols[ci]
            b_arrays.append(d)
            b_arrays.append(v)
        if mode == "shuffle":
            bpid = ex.partition_ids(bmix, S)
            bucketed, bval, b_over = ex.pack_buckets(
                bpid, bsel, S, cap_b, b_arrays)
            recv_b = [ex.exchange(a) for a in bucketed]
            b_ok = ex.exchange(bval)
        else:  # broadcast: replicate the whole filtered build side
            recv_b = [ex.replicate(a) for a in b_arrays]
            b_ok = ex.replicate(bsel)
            b_over = jnp.int64(0)
        rbk = recv_b[0]
        sbk, bord, nb = ex.sorted_build(rbk, b_ok)

        # ---- probe side ----------------------------------------------
        p_cols, pm = p_prep(p_datas, p_valids, p_del, p_bounds)
        pk, pmix = mk_keys(p_cols, p_key_pos)
        pk_v = p_cols[p_key_pos[0]][1]
        for kp in p_key_pos[1:]:
            pk_v = pk_v & p_cols[kp][1]
        # left outer keeps NULL-key probe rows (they emit with NULL build
        # cols); inner drops them pre-exchange
        psel = pm & (pk_v if not louter else jnp.bool_(True))
        p_arrays = [jnp.where(pk_v, pk, 0), pk_v]
        for ci in p_order:
            d, v = p_cols[ci]
            p_arrays.append(d)
            p_arrays.append(v)
        if mode == "shuffle":
            ppid = ex.partition_ids(jnp.where(pk_v, pmix, 0), S)
            bucketed, pval, p_over = ex.pack_buckets(
                ppid, psel, S, cap_p, p_arrays)
            recv_p = [ex.exchange(a) for a in bucketed]
            p_ok = ex.exchange(pval)
        else:  # probe rows stay local on the broadcast rung
            recv_p = p_arrays
            p_ok = psel
            p_over = jnp.int64(0)
        rpk, rpk_v = recv_p[0], recv_p[1]

        # ---- two-pass count+emit local join --------------------------
        src, bidx, out_valid, matched, j_over = ex.expand_matches(
            sbk, bord, nb, rpk, p_ok, rpk_v & p_ok, cap_out, louter)
        overflow = jax.lax.psum(b_over + p_over, "dp")
        jover = jax.lax.psum(j_over, "dp")

        probe_out = []
        for j, ci in enumerate(p_order):
            probe_out.append(
                (recv_p[2 + 2 * j][src], recv_p[3 + 2 * j][src]))
        hit = matched
        if len(p_key_pos) > 1 and pack is None:
            # multi-column keys exchange/sort on a MIX-HASH: candidate
            # spans can hold colliding unequal keys, so re-verify TRUE
            # per-column equality on device before any row counts
            # (stride-packed keys are exact — no re-verify needed)
            for kp, kb in zip(p_key_pos, b_key_pos):
                jp = p_order.index(kp)
                jb = b_order.index(kb)
                hit = hit & (
                    probe_out[jp][0].astype(jnp.int64)
                    == recv_b[1 + 2 * jb][bidx].astype(jnp.int64))
        build_out = []
        for j, ci in enumerate(b_order):
            d = recv_b[1 + 2 * j][bidx]
            v = hit & recv_b[2 + 2 * j][bidx]
            build_out.append((d, v))

        if aggs is None:
            keep = out_valid if louter else out_valid & hit
            flat = []
            for d, v in probe_out + build_out:
                flat.append(d)
                flat.append(v)
            return (overflow, jover, keep, tuple(flat))

        # ---- partial aggregation (inner join only) -------------------
        return (overflow, jover) + _agg_tail(
            spec, p_order, b_order, probe_out, build_out, out_valid & hit,
            cap_out, cap_g, S, remaps, extra)

    if aggs is None:
        out_specs = (P(), P(), P("dp"), tuple(
            P("dp") for _ in range(2 * (len(p_order) + len(b_order)))))
    else:
        out_specs = (P(), P()) + _agg_specs(spec)

    # each side's range slots are one replicated int64 vector
    # (parallel._bounds_args)
    in_specs = (P("dp"), P("dp"), P("dp"), P(),
                P("dp"), P("dp"), P("dp"), P())
    if grouped:
        in_specs = in_specs + (P(),)  # the runtime group-budget slot
        # replicated remap-mapping operands (computed string keys)
        in_specs = in_specs + tuple(
            P() for r in (remaps or ()) if r is not None)
    fn = shard_map(shard_fn, mesh=mesh, in_specs=in_specs,
                   out_specs=out_specs, check_vma=False)
    return _packed_jit(fn, mesh, name, join=True)


# ---------------------------------------------------------------------------
# one shard: the exchange is the identity, and the join a directory lookup
# ---------------------------------------------------------------------------

#: widest key range (hi - lo + 1 of the column statistics) a one-shard
#: join addresses directly: an int32 directory of at most this many slots
DIRECTORY_MAX = 1 << 26

#: (table id, store column, base version) of the key columns in which a
#: directory found two selected rows of one key: statements after the
#: first go straight to the sorted two-pass join
_NOT_UNIQUE: set = set()


def _directory_plan(spec: MPPJoinSpec, ps: _SideState, bs: _SideState):
    """How a join of one shard with itself can skip partition, exchange
    and sort: (the directory side is the probe side, lo, hi, static
    directory capacity, `_NOT_UNIQUE` key), or None.  The directory is
    the smaller side whose single key column the column statistics bound
    to at most DIRECTORY_MAX values and which may hold one row a key
    (the build side only for a left outer join, whose probe rows all
    emit); that it does hold one is checked on the device each run."""
    if len(spec.probe.key_pos) != 1:
        return None
    sides = [(False, bs)] + ([(True, ps)] if spec.kind == "inner" else [])
    for probe_is_dir, st in sorted(sides, key=lambda x: x[1].table.base_rows):
        store_ci = st.an.scan.columns[st.side.key_pos[0]]
        lo, hi, _null = st.table.column_stats(store_ci)
        key = (st.side.table_id, store_ci, st.table.base_version)
        span = int(hi) - int(lo) + 1
        if not (st.table.base_rows <= span <= DIRECTORY_MAX) \
                or key in _NOT_UNIQUE:
            continue
        return probe_is_dir, int(lo), int(hi), _pow2ceil(span), key
    return None


def _build_directory_probe(ps: _SideState, bs: _SideState,
                           probe_is_dir: bool, louter: bool, mesh,
                           capacity: int, name: str):
    """First program of the one-shard join: both sides' scan + filter,
    the directory, and every row of the other side's match in it.
    Returns ([unsound, rows to emit], matched directory row a row, the
    rows to emit); the counts are what the host sizes the second program
    from, the two row vectors stay on the device."""
    ds, ss = (ps, bs) if probe_is_dir else (bs, ps)
    d_prep = _shard_side(ds.an, list(ds.col_order), ds.n_local,
                         MESH_RANGE_SLOTS)
    s_prep = _shard_side(ss.an, list(ss.col_order), ss.n_local,
                         MESH_RANGE_SLOTS)
    d_kp, s_kp = ds.side.key_pos[0], ss.side.key_pos[0]

    def shard_fn(d_datas, d_valids, d_del, d_bounds,
                 s_datas, s_valids, s_del, s_bounds):
        d_cols, dm = d_prep(d_datas, d_valids, d_del, d_bounds)
        s_cols, sm = s_prep(s_datas, s_valids, s_del, s_bounds)
        dk, dk_v = d_cols[d_kp]
        sk, sk_v = s_cols[s_kp]
        # the key bounds ride behind the directory side's range slots
        lo, hi = d_bounds[2 * MESH_RANGE_SLOTS: 2 * MESH_RANGE_SLOTS + 2]
        j, unsound = ex.directory_join(dk, dm & dk_v, sk, sm, sk_v,
                                       lo, hi, capacity)
        # left outer: every selected probe row emits, NULL keys too
        emit = sm if louter else j >= 0
        counts = jnp.stack([unsound, emit.sum().astype(jnp.int64)])
        return jax.lax.psum(counts, "dp"), j, emit

    side = (P("dp"), P("dp"), P("dp"), P())
    fn = shard_map(shard_fn, mesh=mesh, in_specs=side + side,
                   out_specs=(P(), P("dp"), P("dp")), check_vma=False)
    return _named_jit(fn, name)


def _build_directory_emit(spec: MPPJoinSpec, ps: _SideState,
                          bs: _SideState, probe_is_dir: bool, mesh,
                          cap_out: int, cap_g: int, remaps, name: str):
    """Second program of the one-shard join: the rows to emit compacted
    into `cap_out` slots (the power of two over the count the first
    program read back), each side's columns gathered there from the
    arrays as they are cached, then the joined rows themselves in those
    narrow types, validity only where a column has any, or the partial
    aggregation of `_agg_tail`."""
    from ..copr.parallel import _full_dtype

    louter = spec.kind == "left_outer"
    p_order, b_order = list(ps.col_order), list(bs.col_order)
    p_n, b_n = ps.n_local, bs.n_local
    p_full = [_full_dtype(ps.an.scan.ftypes[ci].kind) for ci in p_order]
    b_full = [_full_dtype(bs.an.scan.ftypes[ci].kind) for ci in b_order]

    def shard_fn(p_datas, p_valids, b_datas, b_valids, j, emit, *extra):
        # the caller sized cap_out from the count the first program
        # read back, so no emitted row is dropped
        out_row = ops.first_marked(emit, cap_out, 0)
        live = jnp.arange(cap_out, dtype=jnp.int32) \
            < emit.sum().astype(jnp.int32)
        jj = j[out_row]
        matched = live & (jj >= 0)
        d_row = jnp.maximum(jj, 0)
        p_row, b_row = (d_row, out_row) if probe_is_dir \
            else (out_row, d_row)

        def take(datas, valids, n_local, rows):
            return [(d.reshape(n_local)[rows],
                     None if v is None else v.reshape(n_local)[rows])
                    for d, v in zip(datas, valids)]

        probe_out = take(p_datas, p_valids, p_n, p_row)
        build_out = take(b_datas, b_valids, b_n, b_row)
        if spec.aggs is None:
            cols = tuple(x for d, v in probe_out + build_out
                         for x in ((d,) if v is None else (d, v)))
            return ((matched,) if louter else ()) + cols

        def widen(pairs, full, ok):
            return [(d.astype(t), ok if v is None else ok & v)
                    for (d, v), t in zip(pairs, full)]

        return _agg_tail(spec, p_order, b_order,
                         widen(probe_out, p_full, live),
                         widen(build_out, b_full, matched), matched,
                         cap_out, cap_g, 1, remaps, extra)

    rows = (P("dp"),) * 6
    if spec.aggs is None:
        n_out = int(louter) + sum(
            1 + (v is not None) for v in list(ps.valids) + list(bs.valids))
        fn = shard_map(shard_fn, mesh=mesh, in_specs=rows,
                       out_specs=(P("dp"),) * n_out, check_vma=False)
        return _named_jit(fn, name)
    extra = ()
    if spec.group_by is not None:
        extra = (P(),) * (1 + sum(1 for r in (remaps or ())
                                  if r is not None))
    fn = shard_map(shard_fn, mesh=mesh, in_specs=rows + extra,
                   out_specs=_agg_specs(spec), check_vma=False)
    return _packed_jit(fn, mesh, name, join=True)


def _run_directory(spec: MPPJoinSpec, ps: _SideState, bs: _SideState,
                   plan, mesh, base_fp: str, mode: str, cap_g: int,
                   extra: tuple, remaps):
    """The one-shard join by directory: two programs with one small
    readback between them (is the directory sound; how many rows emit),
    so the second is compiled for the size of the result and nothing can
    overflow.  Returns (what the emit program returned, rows emitted,
    cap_out), or None when the directory met a second row of one key:
    the caller then runs the sorted two-pass join."""
    from ..copr.parallel import (DISPATCH_LOCK, _launch, _read_back,
                                 _read_back_tree)
    from ..lifecycle import dispatch_admission

    probe_is_dir, lo, hi, capacity, key = plan
    louter = spec.kind == "left_outer"
    fp = f"{base_fp}|directory={'probe' if probe_is_dir else 'build'}" \
        f",{capacity}"
    name = program_name(mode, fp)
    probe = _COMPILED.get(fp)
    if probe is None:
        probe = _build_directory_probe(ps, bs, probe_is_dir, louter, mesh,
                                       capacity, name)
        _COMPILED.put(fp, probe)
    ds, ss = (ps, bs) if probe_is_dir else (bs, ps)
    _check_membership_epoch()
    with dispatch_admission(DISPATCH_LOCK):
        counts, j, emit = _launch(probe, name, (
            tuple(ds.datas), tuple(ds.valids), ds.del_mask,
            _bounds_args(ds.bounds, (lo, hi)),
            tuple(ss.datas), tuple(ss.valids), ss.del_mask,
            _bounds_args(ss.bounds)))
        unsound, total = (int(x) for x in _read_back(counts, join=True))
        if unsound:
            _NOT_UNIQUE.add(key)
            return None
        cap_out = _pow2ceil(total)
        fp = f"{fp}|emit={cap_out}"
        name = program_name(mode, fp)
        fn = _COMPILED.get(fp)
        if fn is None:
            fn = _build_directory_emit(spec, ps, bs, probe_is_dir, mesh,
                                       cap_out, cap_g, remaps, name)
            _COMPILED.put(fp, fn)
        args = (tuple(ps.datas), tuple(ps.valids),
                tuple(bs.datas), tuple(bs.valids), j, emit) + extra
        if spec.aggs is not None:
            return fn(*args), total, cap_out
        return _read_back_tree(_launch(fn, name, args)), total, cap_out


# ---------------------------------------------------------------------------
# host-side assembly
# ---------------------------------------------------------------------------


def _to_column(table, an: _Analyzed, pos: int, ft, data: np.ndarray,
               valid: np.ndarray) -> Column:
    """Device env array (widened dtype) -> host Column of `ft`, decoding
    dictionary codes for STRING columns through the side's own store."""
    if ft.kind == TypeKind.STRING:
        from ..store.blockstore import _decode_dict

        store_ci = an.scan.columns[pos]
        obj = _decode_dict(data.astype(np.int64),
                           table.cols[store_ci].dictionary)
        return Column(ft, obj, valid)
    return Column(ft, data.astype(ft.np_dtype), valid)


def _assemble_rows(spec: MPPJoinSpec, ps: _SideState, bs: _SideState,
                   keep, flat) -> List[Chunk]:
    louter = spec.kind == "left_outer"
    # `keep` None: the rows come compacted, every one of them live
    sel = slice(None) if keep is None else np.flatnonzero(keep)
    wp = len(ps.col_order)
    probe_cols, build_cols = [], []
    for j, ci in enumerate(ps.col_order):
        d, v = flat[2 * j], flat[2 * j + 1]
        ft = spec.probe.out_ftypes[ci]
        probe_cols.append(_to_column(
            ps.table, ps.an, ci, ft, d[sel], v[sel].astype(np.bool_)))
    for j, ci in enumerate(bs.col_order):
        d, v = flat[2 * wp + 2 * j], flat[2 * wp + 2 * j + 1]
        ft = spec.build.out_ftypes[ci]
        if louter:
            ft = ft.with_nullable(True)
        build_cols.append(_to_column(
            bs.table, bs.an, ci, ft, d[sel], v[sel].astype(np.bool_)))
    cols = (probe_cols + build_cols if spec.probe_is_left
            else build_cols + probe_cols)
    big = Chunk(cols)
    return [c for c in big.split(OUT_CHUNK_ROWS) if c.num_rows]


def _directory_columns(spec: MPPJoinSpec, ps: _SideState, bs: _SideState,
                       leaves, total: int) -> list:
    """What the directory's emit program handed back (matched mask for
    a left outer join; then each column's data, and its validity where
    the column has any) as `_assemble_rows`'s flat (data, valid) pairs
    over the `total` live rows."""
    leaves = [x[:total] for x in leaves]  # host arrays already
    live = np.ones(total, dtype=np.bool_)
    matched = leaves.pop(0) if spec.kind == "left_outer" else live
    flat = []
    for st, ok in ((ps, live), (bs, matched)):
        for v in st.valids:
            flat.append(leaves.pop(0))
            flat.append(ok if v is None else ok & leaves.pop(0))
    return flat


def _assemble_partials(spec: MPPJoinSpec, states, S: int) -> List[Chunk]:
    """Per-agg partial states -> ONE partial row in the same
    [states...] layout the cop partial-agg paths emit (the root final
    HashAgg merges it)."""
    cols: List[Column] = []
    for a, st in zip(spec.aggs, states):
        pts = a.partial_types()
        if a.name == "count":
            cols.append(Column(pts[0], np.array([int(st)], np.int64)))
        elif a.name in ("sum", "avg"):
            sm, c = st
            c = int(c)
            sum_col = Column(pts[0],
                             np.array([sm]).astype(pts[0].np_dtype),
                             np.array([c > 0]))
            cols.append(sum_col)
            if a.name == "avg":
                cols.append(Column(pts[1], np.array([c], np.int64)))
        else:  # min / max: merge the S per-shard partials host-side
            part, c = st
            c = int(c)
            v = part.min() if a.name == "min" else part.max()
            cols.append(Column(pts[0],
                               np.array([v]).astype(pts[0].np_dtype),
                               np.array([c > 0])))
    return [Chunk(cols)]


def _assemble_grouped(spec: MPPJoinSpec, ps: _SideState, bs: _SideState,
                      n_uniq, keys, states, remaps=None) -> List[Chunk]:
    """Device-merged grouped partials -> ONE partial chunk in the
    [keys..., states...] layout the root final HashAgg merges.  String
    group keys come back as dictionary codes and decode through the
    OWNING side's store (probe scan positions < probe width, build
    positions above)."""
    from ..types import TypeKind as TK

    nk = len(spec.group_by)
    k = int(n_uniq[0])
    wp = len(ps.col_order)
    cols: List[Column] = []
    for i, g in enumerate(spec.group_by):
        bits = keys[i][:k]
        flags = keys[nk + i][:k].astype(np.bool_)
        ft = g.ftype
        rem = remaps[i] if remaps is not None else None
        if rem is not None and rem.out_dict is not None:
            # computed-key codes decode through the remap's OUTPUT
            # dictionary, not any store column's (INT-valued remaps
            # carry the computed values in the key bits directly)
            from ..store.blockstore import _decode_dict

            data = _decode_dict(bits.astype(np.int64), rem.out_dict)
        elif ft.kind == TK.FLOAT:
            data = bits.astype(np.float64, copy=False)
        elif ft.kind == TK.STRING:
            from ..store.blockstore import _decode_dict

            st, ci = (ps, g.index) if g.index < wp else (bs, g.index - wp)
            store_ci = st.an.scan.columns[ci]
            data = _decode_dict(bits.astype(np.int64),
                                st.table.cols[store_ci].dictionary)
        else:
            data = bits.astype(ft.np_dtype)
        cols.append(Column(ft, data, flags if not flags.all() else None))
    for a, st in zip(spec.aggs, states):
        pts = a.partial_types()
        if a.name == "count":
            cols.append(Column(pts[0], st[:k].astype(np.int64)))
        elif a.name in ("sum", "avg"):
            s, c = st[0][:k], st[1][:k]
            cols.append(Column(pts[0], s.astype(pts[0].np_dtype), c > 0))
            if a.name == "avg":
                cols.append(Column(pts[1], c.astype(np.int64)))
        else:  # min / max (value, count) — already merged across shards
            v, c = st[0][:k], st[1][:k]
            cols.append(Column(pts[0], v.astype(pts[0].np_dtype), c > 0))
    chunk = Chunk(cols)
    return [chunk] if chunk.num_rows else []


def grouped_pushdown_enabled() -> bool:
    """The one home of the TIDB_TPU_MPP_GROUPED knob (the planner's
    pushdown gate and the engine's force-peel comparator both read it):
    default on, "0" disables."""
    import os

    return os.environ.get("TIDB_TPU_MPP_GROUPED", "1") != "0"


def _host_grouped_partials(spec: MPPJoinSpec,
                           chunks: List[Chunk]) -> List[Chunk]:
    """The agg-peel rung's host tail: grouped PARTIAL aggregation over
    the device-joined row chunks (the join stayed on device; only the
    blown-budget agg moved to the host).  Per-chunk partials are fine —
    the parent is a FINAL HashAgg and merges groups across chunks."""
    from ..copr.cpu_engine import grouped_partial_chunks

    return grouped_partial_chunks(spec.group_by, spec.aggs, chunks)


# ---------------------------------------------------------------------------
# entry
# ---------------------------------------------------------------------------


def _run_once(storage, spec: MPPJoinSpec, mode: str) -> List[Chunk]:
    mesh = get_mesh()
    S = len(mesh.devices.ravel())
    mesh_ids = tuple(d.id for d in mesh.devices.ravel())
    ps = _SideState(storage, spec.probe, spec.ts, mesh)
    bs = _SideState(storage, spec.build, spec.ts, mesh)
    if mode == "broadcast" and bs.table.base_rows > DEVICE_JOIN_BUILD_MAX:
        raise MPPIneligible(
            f"build side {bs.table.base_rows} rows exceeds broadcast cap")
    slack = _slack()
    cap_p = min(_pow2ceil(int(slack * ps.n_local / S) + 1), ps.n_local)
    cap_b = min(_pow2ceil(int(slack * bs.n_local / S) + 1), bs.n_local)
    # two-pass join emission buffer: sized to the received probe rows
    # times TIDB_TPU_MPP_JOIN_SLACK (>1 buys headroom for duplicate-key
    # expansion; emission overflow steps down the ladder)
    import os as _os

    n_recv = S * cap_p if mode == "shuffle" else ps.n_local
    cap_out = max(
        int(float(_os.environ.get("TIDB_TPU_MPP_JOIN_SLACK", "1.0"))
            * n_recv), 16)
    grouped = spec.aggs is not None and spec.group_by is not None
    budget, cap_g = 0, 0
    if grouped:
        budget = (int(_os.environ.get("TIDB_TPU_MPP_GROUP_BUDGET", "0"))
                  or spec.group_budget or 4096)
        # pow2-bucketed STATIC capacity, padded to a multiple of S so
        # every shard emits an equal slice of the merged groups; the
        # budget itself stays a runtime scalar slot
        cap_g0 = _pow2ceil(budget)
        cap_g = S * (-(-cap_g0 // S))

    # exact compound-key packing for multi-column keys (ISSUE 11): the
    # union of both sides' column stats strides every key into ONE int64,
    # so equality is exact and LEFT-OUTER multi-key joins are sound on
    # device; an overflowing key space keeps the mix-hash (inner-only —
    # left-outer then takes the host rung)
    pack = _compound_pack(ps, bs)
    if (spec.kind == "left_outer" and len(spec.probe.key_pos) > 1
            and pack is None):
        raise MPPIneligible(
            "multi-key left-outer join needs exact compound ordering "
            "(packed key space exceeds int64)")
    # computed STRING group keys -> per-side dict-code remaps (runtime
    # mapping operands; MPPIneligible when not remappable)
    remaps = _mpp_key_remaps(spec, ps, bs)

    # column arrays load before the program lookup (compiled programs are
    # specialized on wire dtypes / null patterns, like the mesh scan)
    ps.load(mesh)
    bs.load(mesh)

    import json as _json

    from ..copr.ir import serialize_expr

    agg_sig = ""
    if spec.aggs is not None:
        agg_sig = _json.dumps(
            [[a.name] + [serialize_expr(x) for x in a.args]
             for a in spec.aggs], sort_keys=True)
    group_sig = ""
    if grouped:
        group_sig = _json.dumps(
            [serialize_expr(g) for g in spec.group_by], sort_keys=True)
    base_fp = (f"mpp|{mode}|{spec.kind}|pil={spec.probe_is_left}"
               f"|S={S} devs={mesh_ids}"
               f"|p:{_fingerprint(ps.an, 'filter')}|Tl={ps.Tl}"
               f"|k={spec.probe.key_pos}|wire={ps.wire_sig}"
               f"|b:{_fingerprint(bs.an, 'filter')}|Tl={bs.Tl}"
               f"|k={spec.build.key_pos}|wire={bs.wire_sig}"
               f"|aggs={agg_sig}|gb={group_sig}|capg={cap_g}"
               f"|pack={pack}"
               + (f"|rcaps={[r.cap if r else None for r in remaps]}"
                  if remaps else ""))

    # deterministic mid-shuffle fault injection (chaos harness): fires
    # after both sides are device-resident, before the exchange program
    FAILPOINTS.hit("mpp/exchange", mode=mode, device_ids=mesh_ids,
                   kind=spec.kind)
    if grouped:
        # chaos site for the grouped-agg overflow rung: an armed action
        # raises MPPGroupedAggOverflow, driving the same agg-peel path a
        # genuine on-device budget overflow takes
        FAILPOINTS.hit("mpp/grouped_agg_overflow", mode=mode,
                       budget=budget, cap_g=cap_g)
    # the grouped tail's runtime operands: the group budget, then the
    # remap mappings
    extra = ()
    if grouped:
        extra = (jnp.int64(budget),) + tuple(
            jnp.asarray(r.mapping) for r in (remaps or ())
            if r is not None)

    # one shard exchanges with nobody: where the statistics bound a key
    # column the join is a directory lookup, sized by what it finds
    plan = _directory_plan(spec, ps, bs) if S == 1 else None
    found = plan and _run_directory(spec, ps, bs, plan, mesh, base_fp,
                                    mode, cap_g, extra, remaps)
    if found:
        out, rows_out, cap_out = found
        return _finish(spec, ps, bs, mesh_ids, S, out, budget, remaps,
                       nbytes=0, rows_out=rows_out, cap_out=cap_out)

    fp = f"{base_fp}|caps={cap_p},{cap_b},{cap_out}"
    fn = _COMPILED.get(fp)
    if fn is None:
        fn = _build_mpp_fn(spec, ps, bs, mode, mesh, cap_p, cap_b,
                           cap_out, cap_g, pack=pack, remaps=remaps,
                           name=program_name(mode, fp))
        _COMPILED.put(fp, fn)

    from ..copr.parallel import DISPATCH_LOCK
    from ..lifecycle import dispatch_admission

    # each side's range slots: the mesh scan's slot padding, verbatim
    args = (tuple(ps.datas), tuple(ps.valids), ps.del_mask,
            _bounds_args(ps.bounds),
            tuple(bs.datas), tuple(bs.valids), bs.del_mask,
            _bounds_args(bs.bounds)) + extra
    # dispatch-time membership guard (coordination follow-up (a)): a
    # cross-host membership move between mesh build and this exchange
    # program raises the typed retriable CoordEpochMismatch — the rung
    # loop rebuilds from the new broadcast instead of launching into an
    # XLA collective whose participant set no longer matches other hosts
    _check_membership_epoch()
    with dispatch_admission(DISPATCH_LOCK):
        # collective programs serialize per process (see parallel.py:
        # concurrent shard_map launches deadlock at the rendezvous);
        # admission charges the exchange's device time to the
        # statement's resource group
        out = fn(*args)
    overflow, jover = int(out[0]), int(out[1])
    if overflow:
        raise MPPPartitionOverflow(
            f"{overflow} rows over per-partition capacity "
            f"(cap_p={cap_p}, cap_b={cap_b}, mode={mode})")
    if jover:
        raise MPPPartitionOverflow(
            f"{jover} joined rows over the emission buffer "
            f"(cap_out={cap_out}, mode={mode}): duplicate-key expansion "
            "outgrew the two-pass emit budget")
    # exchange traffic accounting (static shapes: what the program moved)
    if mode == "shuffle":
        per_pair = 8 + 1  # key + bucket validity
        for _ci, isz in ps.exchange_cols():
            per_pair += isz + 1
        nbytes = S * S * cap_p * per_pair + S * S * cap_p  # + key-valid
        per_pair_b = 8 + 1
        for _ci, isz in bs.exchange_cols():
            per_pair_b += isz + 1
        nbytes += S * S * cap_b * per_pair_b
    else:
        per_row = 8 + 1
        for _ci, isz in bs.exchange_cols():
            per_row += isz + 1
        nbytes = S * S * bs.n_local * per_row
    return _finish(spec, ps, bs, mesh_ids, S, out[2:], budget, remaps,
                   nbytes=nbytes, rows_out=None, cap_out=cap_out)


def _finish(spec: MPPJoinSpec, ps: _SideState, bs: _SideState, mesh_ids,
            S: int, out, budget: int, remaps, nbytes: int, rows_out,
            cap_out: int) -> List[Chunk]:
    """Account for one finished exchange program on its `mpp.exchange`
    span and turn what it returned into chunks.  `out` is the program's
    output past the two exchange-overflow scalars; `rows_out` is None
    where the rows come with a keep mask (the exchanged join) and the
    count of leading rows where they come compacted (the directory)."""
    from ..copr.device_health import DEVICE_HEALTH
    from ..trace import annotate

    grouped = spec.aggs is not None and spec.group_by is not None
    if grouped:
        over_l, over_m, n_uniq, keys, states = out
        if int(over_l) or int(np.max(over_m)):
            raise MPPGroupedAggOverflow(
                f"distinct groups over budget {budget} "
                f"(per-shard over {int(over_l)}, merged over "
                f"{int(np.max(over_m))})")
    REGISTRY.inc("mpp_exchange_bytes_total", float(nbytes))
    DEVICE_HEALTH.record_success(mesh_ids)
    if grouped:
        REGISTRY.inc("mpp_grouped_agg_pushed_total")
        annotate(groups=int(n_uniq[0]), group_budget=budget)
        chunks = _assemble_grouped(spec, ps, bs, n_uniq, keys, states,
                                   remaps=remaps)
    elif spec.aggs is not None:
        chunks = _assemble_partials(spec, out[0], S)
    elif rows_out is None:
        chunks = _assemble_rows(spec, ps, bs, out[0], out[1])
    else:
        chunks = _assemble_rows(spec, ps, bs, None,
                                _directory_columns(spec, ps, bs, out,
                                                   rows_out))
    if rows_out is None:
        rows_out = sum(c.num_rows for c in chunks)
    # what the program moved between shards (static shapes), and the
    # rows it handed out against the slots it was compiled for
    annotate(bytes=nbytes, device_ids=list(mesh_ids), rows_out=rows_out,
             cap_out=cap_out)
    return chunks


def run_mpp_join(storage, spec: MPPJoinSpec) -> Tuple[List[Chunk], str]:
    """Run the join over the mesh; (chunks, mode) on success, raises
    MPPIneligible when the host rung must serve it.  Overflow and device
    failures step down the ladder internally.

    Grouped pushdown has its own fallback rung: a group-budget overflow
    retries the SAME join rung with the aggregation PEELED to a host
    tail over the device-joined rows (mode suffix "+agg-peel"); a
    successful grouped pushdown reports mode suffix "+grouped"."""
    import dataclasses

    from ..trace import span

    mode = "shuffle"
    attempts = 0
    # TIDB_TPU_MPP_GROUPED=0 forces the agg-peel rung from the start:
    # the join still runs on device, every joined row ships to the host
    # and aggregates there — the bench's host-merge comparator
    peel = (spec.group_by is not None and spec.aggs is not None
            and not grouped_pushdown_enabled())
    while True:
        # cancellation seam at every rung transition/retry: a cancelled
        # statement must not start the next exchange program (the typed
        # termination error is a TiDBTPUError, so the handler below
        # surfaces it instead of stepping down the ladder)
        from ..lifecycle import current_scope

        FAILPOINTS.hit("exec/cancel", site="mpp", scope=current_scope())
        current_scope().check()
        if _no_eligible_devices():
            raise MPPIneligible("all device breakers open")
        run_spec = spec
        if peel:
            # the join stays on device; only the agg leaves for the host
            run_spec = dataclasses.replace(spec, aggs=None, group_by=None)
        try:
            with span("mpp.exchange", rung=mode, kind=spec.kind,
                      grouped=bool(spec.group_by), peel=peel):
                chunks = _run_once(storage, run_spec, mode)
            if peel:
                with span("mpp.agg_peel", rung=mode):
                    chunks = _host_grouped_partials(spec, chunks)
                mode = mode + "+agg-peel"
            elif spec.group_by is not None and spec.aggs is not None:
                mode = mode + "+grouped"
            REGISTRY.inc("mpp_joins_total")
            # rung suffixes use '+'/'-' for human surfaces (EXPLAIN
            # ANALYZE); metric names must stay in the Prometheus
            # grammar [a-zA-Z0-9_:] or the whole /metrics scrape fails
            REGISTRY.inc("mpp_joins_"
                         + mode.replace("+", "_").replace("-", "_")
                         + "_total")
            return chunks, mode
        except CoordEpochMismatch:
            # membership moved mid-rung (member lost/rejoined, breaker
            # trip on another host): rebuild from the new broadcast and
            # re-run the SAME rung — typed and retriable, never a
            # collective desync; flapping exhausts the mesh attempt
            # budget and demotes to the host rung like any device fault
            attempts += 1
            if attempts >= MAX_MESH_ATTEMPTS:
                raise MPPIneligible(
                    "membership epoch flapping exhausted mesh attempts")
            continue
        except MPPGroupedAggOverflow as e:
            REGISTRY.inc("mpp_grouped_agg_overflow_total")
            REGISTRY.inc("mpp_grouped_agg_fallback_total")
            from ..trace import annotate

            annotate(grouped_agg_overflow=str(e)[:120])
            peel = True
            continue
        except MPPPartitionOverflow as e:
            REGISTRY.inc("mpp_partition_overflow_total")
            if mode == "shuffle":
                mode = "broadcast"  # immune to probe-side skew
                continue
            raise MPPIneligible(f"partition overflow: {e}")
        except (MPPIneligible, KeyboardInterrupt, SystemExit,
                GeneratorExit):
            raise
        except BaseException as e:
            from ..errors import TiDBTPUError

            if isinstance(e, TiDBTPUError):
                # semantic errors (kill/quota/lock) keep their meaning;
                # they are never device-health events
                raise
            if not _handle_mesh_failure(None, e, attempts):
                if classify_failure(e) is not None:
                    # classified device failure, retries exhausted:
                    # step down to the host rung instead of failing
                    raise MPPIneligible(f"device failure: {e}")
                raise
            attempts += 1
