"""Multi-way device-resident join pipelines: the rung-ladder engine.

ISSUE 12's tentpole, the execution half.  The planner's join-tree
compiler (planner/jointree.py) orders an n-way equi-join graph and emits
an `MPPJoinTreeSpec`: a base side plus a ladder of RUNGS, each joining
the current intermediate result against one more scan side.  This
module runs that ladder on the mesh:

- every rung is ONE shard_map program (partition/exchange the
  intermediate by the rung's key, filter+partition the build side,
  two-pass count+emit local join — the PR 8 exchange/local-join
  emitters, verbatim);
- the intermediate result BETWEEN rungs is a set of sharded device
  arrays (one (data, validity) pair per joined column plus a live-row
  mask): it never leaves HBM, so a k-way join is k dispatches with ZERO
  host transfers between them (trace-asserted: no `copr.transfer`
  spans between `mpp.rung` spans on a warm cache);
- semi / anti-semi rungs (decorrelated EXISTS/IN subqueries) filter the
  intermediate in place — a single searchsorted span-count when the key
  is single-column and unconditioned, the full pair expansion when
  correlated other-conds must evaluate per candidate pair;
- the final phase either reads the joined rows back, or runs the
  scalar/grouped partial aggregation ON DEVICE (the PR 8 sort-group +
  cross-shard merge emitters) so only O(G) rows leave.

Per-rung overflow steps down the existing ladder: a blown exchange
bucket or emission buffer retries THAT RUNG on the broadcast strategy
(build side replicated, intermediate stays local); a second overflow —
or any structural ineligibility — raises MPPIneligible and the caller
(MPPTreeReaderExec, mpp/reader.py) runs the same ladder as chained host
hash joins.  Grouped-aggregation budget overflow peels the agg to a
host tail over the still-device-resident joined rows, exactly like the
two-table engine's agg-peel rung.
"""

from __future__ import annotations

from dataclasses import dataclass, field
from typing import List, Optional, Tuple

import numpy as np

from .. import ops  # noqa: F401  (configures x64)
import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from jax import shard_map

from ..chunk import Chunk, Column
from ..copr.device_health import classify_failure
from ..copr.jax_engine import (_fingerprint, _reindex_expr, _to_state_dtype,
                               rewrite_for_dict_resolved)
from ..copr.jax_eval import JaxUnsupported, compile_expr
from ..coord import CoordEpochMismatch
from ..copr.parallel import (
    DISPATCH_LOCK,
    MAX_MESH_ATTEMPTS,
    MESH_RANGE_SLOTS,
    _bounds_args,
    _check_membership_epoch,
    _handle_mesh_failure,
    _named_jit,
    _no_eligible_devices,
    _packed_jit,
    get_mesh,
)
from ..copr.ir import deserialize_expr, serialize_expr
from ..metrics import REGISTRY
from ..store.fault import FAILPOINTS
from ..types import TypeKind
from . import exchange as ex
from .engine import (
    _COMPILED,
    MPPGroupedAggOverflow,
    MPPIneligible,
    MPPJoinSide,
    OUT_CHUNK_ROWS,
    _pow2ceil,
    program_name,
    _shard_side,
    _SideState,
    _slack,
    grouped_pushdown_enabled,
)

#: chaos site: fires before each rung's exchange program (armed actions
#: inject device failures / overflow mid-ladder)
TREE_FAILPOINT = "mpp/tree_rung"


class MPPTreeOverflow(Exception):
    """One rung's exchange bucket or emission buffer blew its static
    capacity; carries the rung index and which capacity blew so the
    ladder can step down THAT rung (partition overflow -> broadcast,
    emission overflow -> boosted buffer)."""

    def __init__(self, rung: int, what: str, msg: str):
        super().__init__(msg)
        self.rung = rung
        self.what = what  # "partition" | "emit"


#: emission-buffer boost ceiling: a rung's cap_out may grow this many
#: times (×4 per overflow) before the ladder gives up on the device
MAX_EMIT_BOOST = 64


@dataclass
class TreeRung:
    """One ladder step: join the current intermediate against a side."""

    side: int                 # ordinal into MPPJoinTreeSpec.sides
    kind: str                 # inner | left_outer | semi | anti_semi
    left_slots: List[int]     # intermediate slot indices of the join keys
    build_key_pos: List[int]  # scan positions of the build-side keys
    # extra join conditions over the PAIR layout [slots..., build cols at
    # n_slots+j]; evaluated per candidate pair on device
    other_conds: List = field(default_factory=list)
    est_rows: float = 0.0     # planner estimate (EXPLAIN + budget sizing)


@dataclass
class MPPJoinTreeSpec:
    sides: List[MPPJoinSide]       # join order; side 0 is the base
    rungs: List[TreeRung]          # rung k joins sides[rungs[k].side]
    # final intermediate layout: per slot the (side ordinal, scan pos)
    # that produced it — slots appear in join order, semi/anti sides
    # contribute none
    slot_src: List[Tuple[int, int]]
    out_slots: List[int]           # rows mode: slots in output order
    out_ftypes: list               # ftypes aligned with out_slots
    ts: int = 0
    # final partial aggregation over the slot layout (positions = slots)
    aggs: Optional[list] = None
    group_by: Optional[list] = None
    group_budget: int = 0


# ---------------------------------------------------------------------------
# slot bookkeeping
# ---------------------------------------------------------------------------


def _slots_of_prefix(spec: MPPJoinTreeSpec, upto_rung: int) -> int:
    """Slot count available BEFORE rung `upto_rung` runs."""
    n = len(spec.sides[0].out_ftypes)
    for r in range(upto_rung):
        rung = spec.rungs[r]
        if rung.kind in ("inner", "left_outer"):
            n += len(spec.sides[rung.side].out_ftypes)
    return n


def _slot_resolver(spec: MPPJoinTreeSpec, states, n_slots: int,
                   build_state=None):
    """Pair-layout column resolver for rewrite_for_dict_resolved: slots
    resolve through slot_src to their owning side's (table, scan); the
    tail past n_slots is the active rung's build side."""

    def resolve(idx: int):
        if 0 <= idx < n_slots:
            side, sp = spec.slot_src[idx]
            st = states[side]
            return st.table, st.an.scan, sp
        if build_state is not None:
            sp = idx - n_slots
            if 0 <= sp < len(build_state.an.scan.columns):
                return build_state.table, build_state.an.scan, sp
        return None

    return resolve


# ---------------------------------------------------------------------------
# per-rung program
# ---------------------------------------------------------------------------


def _build_rung_fn(spec: MPPJoinTreeSpec, r: int, states, mesh, mode: str,
                   n_in: int, cap_p: int, cap_b: int, cap_out: int,
                   conds_rw, elide_probe: bool = False, name=None):
    """One rung's shard_map program.  Inputs: the intermediate arrays
    (rung 0 builds them inline from side 0's scan) + the build side's
    cached scan columns.  Outputs: the NEXT intermediate (still sharded,
    still on device) + overflow scalars.

    `elide_probe` (ISSUE 18 jointree (e)): the caller proved the
    intermediate is ALREADY hash-partitioned by this rung's key slots
    (the previous shuffle rung used the same ones), so the probe side
    skips pack+all-to-all and only the build side exchanges."""
    rung = spec.rungs[r]
    S = len(mesh.devices.ravel())
    bs = states[rung.side]
    first = r == 0
    n_slots = _slots_of_prefix(spec, r)
    kind = rung.kind
    emits = kind in ("inner", "left_outer")
    louter = kind == "left_outer"
    b_order = list(bs.col_order)
    b_key_pos = list(rung.build_key_pos)
    left_slots = list(rung.left_slots)
    multi = len(left_slots) > 1
    b_prep = _shard_side(bs.an, b_order, bs.n_local, MESH_RANGE_SLOTS)
    p_prep = (_shard_side(states[0].an, states[0].col_order,
                          states[0].n_local, MESH_RANGE_SLOTS)
              if first else None)
    # the fast span-count path: single-column key (exact equality after
    # combine_keys' identity) and no pair-level conditions to evaluate —
    # semi/anti rungs then never touch the emission buffer at all
    fast_filter = (kind in ("semi", "anti_semi") and not multi
                   and not conds_rw)

    def shard_fn(*args):
        off = 0
        if first:
            st0 = states[0]
            n0 = 4
            p_datas, p_valids, p_del, p_bounds = args[:n0]
            off = n0
            cols0, m0 = p_prep(p_datas, p_valids, p_del, p_bounds)
            slots = [cols0[ci] for ci in st0.col_order]
            mask = m0
        else:
            slots = []
            for _s in range(n_slots):
                slots.append((args[off], args[off + 1]))
                off += 2
            mask = args[off]
            off += 1
        b_datas, b_valids, b_del, b_bounds = args[off:off + 4]

        # ---- probe (intermediate) side -------------------------------
        keys = [slots[s][0].astype(jnp.int64) for s in left_slots]
        kv = slots[left_slots[0]][1]
        for s in left_slots[1:]:
            kv = kv & slots[s][1]
        mix = ex.combine_keys(keys)
        jk = mix
        if kind in ("inner", "semi"):
            psel = mask & kv
        else:  # left_outer / anti_semi keep NULL-key rows (unmatched)
            psel = mask
        p_arrays = [jnp.where(kv, jk, 0), kv]
        for d, v in slots:
            p_arrays.append(d)
            p_arrays.append(v)
        if mode == "shuffle" and not elide_probe:
            ppid = ex.partition_ids(jnp.where(kv, mix, 0), S)
            bucketed, pval, p_over = ex.pack_buckets(
                ppid, psel, S, cap_p, p_arrays)
            recv = [ex.exchange(a) for a in bucketed]
            p_ok = ex.exchange(pval)
        else:  # broadcast rung, or residency-elided re-shuffle: the
            # intermediate stays local (for elision the build side
            # below still exchanges — equal keys already co-reside)
            recv = p_arrays
            p_ok = psel
            p_over = jnp.int64(0)
        rpk, rkv = recv[0], recv[1]
        n_recv = rpk.shape[0]

        # ---- build side ----------------------------------------------
        b_cols, bm = b_prep(b_datas, b_valids, b_del, b_bounds)
        bkeys = [b_cols[kp][0].astype(jnp.int64) for kp in b_key_pos]
        bmix = ex.combine_keys(bkeys)
        bk_v = b_cols[b_key_pos[0]][1]
        for kp in b_key_pos[1:]:
            bk_v = bk_v & b_cols[kp][1]
        bsel = bm & bk_v  # NULL build keys never match
        b_arrays = [bmix]
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
        else:
            recv_b = [ex.replicate(a) for a in b_arrays]
            b_ok = ex.replicate(bsel)
            b_over = jnp.int64(0)
        sbk, bord, nb = ex.sorted_build(recv_b[0], b_ok)
        overflow = jax.lax.psum(p_over + b_over, "dp")

        # ---- fast span-count semi/anti (no expansion) ----------------
        if fast_filter:
            lo = jnp.searchsorted(sbk, rpk, side="left")
            hi = jnp.minimum(jnp.searchsorted(sbk, rpk, side="right"), nb)
            matched = (p_ok & rkv) & (hi > lo)
            keep = p_ok & (matched if kind == "semi" else ~matched)
            out_slots = []
            for s in range(n_slots):
                out_slots.append(recv[2 + 2 * s])
                out_slots.append(recv[3 + 2 * s])
            return overflow, jnp.int64(0), tuple(out_slots), keep

        # ---- two-pass count+emit expansion ---------------------------
        src, bidx, out_valid, matched, j_over = ex.expand_matches(
            sbk, bord, nb, rpk, p_ok, rkv & p_ok, cap_out, louter)
        jover = jax.lax.psum(j_over, "dp")
        hit = matched
        if multi:
            # mix-hash candidates: re-verify TRUE per-column equality
            for s, kp in zip(left_slots, b_key_pos):
                jb = b_order.index(kp)
                hit = hit & (
                    recv[2 + 2 * s][src].astype(jnp.int64)
                    == recv_b[1 + 2 * jb][bidx].astype(jnp.int64))
        if conds_rw:
            env = {}
            for s in range(n_slots):
                env[s] = (recv[2 + 2 * s][src], recv[3 + 2 * s][src])
            for j, ci in enumerate(b_order):
                env[n_slots + ci] = (recv_b[1 + 2 * j][bidx],
                                     hit & recv_b[2 + 2 * j][bidx])
            for c in conds_rw:
                d, v = compile_expr(c, env, cap_out)
                hit = hit & v & (d != 0)

        if kind in ("semi", "anti_semi"):
            counts = jnp.zeros(n_recv, dtype=jnp.int32).at[src].add(
                (hit & out_valid).astype(jnp.int32))
            matched_any = counts > 0
            keep = p_ok & (matched_any if kind == "semi"
                           else ~matched_any)
            out_slots = []
            for s in range(n_slots):
                out_slots.append(recv[2 + 2 * s])
                out_slots.append(recv[3 + 2 * s])
            return overflow, jover, tuple(out_slots), keep

        # inner / left_outer emission: gather probe slots, append build
        out_slots = []
        for s in range(n_slots):
            out_slots.append(recv[2 + 2 * s][src])
            out_slots.append(recv[3 + 2 * s][src])
        for j, _ci in enumerate(b_order):
            out_slots.append(recv_b[1 + 2 * j][bidx])
            out_slots.append(hit & recv_b[2 + 2 * j][bidx])
        keep = out_valid if louter else out_valid & hit
        return overflow, jover, tuple(out_slots), keep

    n_out_slots = n_slots + (len(b_order) if emits else 0)
    out_specs = (P(), P(), tuple(P("dp") for _ in range(2 * n_out_slots)),
                 P("dp"))
    # a scanned side's range slots are one replicated int64 vector
    # (parallel._bounds_args)
    if first:
        in_specs = (P("dp"), P("dp"), P("dp"), P())
    else:
        in_specs = tuple(P("dp") for _ in range(2 * n_slots)) + (P("dp"),)
    full_in = tuple(in_specs) + (P("dp"), P("dp"), P("dp"), P())
    fn = shard_map(shard_fn, mesh=mesh, in_specs=full_in,
                   out_specs=out_specs, check_vma=False)
    return _named_jit(fn, name)


# ---------------------------------------------------------------------------
# final phase: rows readback or partial aggregation
# ---------------------------------------------------------------------------


def _tree_key_remaps(spec: MPPJoinTreeSpec, states):
    """Per-group-key dict-code remaps over the SLOT layout: computed
    keys reading a string column resolve to their owning side's store
    and the remap builds there (the tree analog of engine._mpp_key_remaps)."""
    from ..copr import fusion
    from ..copr.jax_engine import _string_leaf
    from ..expr.expression import ColumnExpr

    if spec.aggs is None or spec.group_by is None:
        return None
    remaps = []
    for g in spec.group_by:
        if isinstance(g, ColumnExpr) or not (
                g.ftype.kind == TypeKind.STRING or _string_leaf(g)):
            remaps.append(None)
            continue
        refs: set = set()

        def walk(x):
            if isinstance(x, ColumnExpr):
                refs.add(x.index)
            for c in getattr(x, "args", ()) or ():
                walk(c)

        walk(g)
        srcs = {spec.slot_src[i] for i in refs}
        if len(srcs) != 1:
            raise MPPIneligible(
                f"computed group key spans join sides: {g}")
        side, sp = next(iter(srcs))
        st = states[side]
        slot = next(iter(refs))
        try:
            rm = fusion.build_key_remap(
                st.table, st.an.scan, _reindex_expr(g, lambda _i: sp))
        except JaxUnsupported as e:
            raise MPPIneligible(str(e))
        remaps.append(fusion.KeyRemap(slot, rm.mapping, rm.cap,
                                      rm.out_dict))
    return remaps if any(r is not None for r in remaps) else None


def _build_final_fn(spec: MPPJoinTreeSpec, states, mesh, n_in: int,
                    cap_g: int, aggs_rw, group_rw, remaps, name=None):
    """The final partial-aggregation program over the finished
    intermediate (scalar psum or grouped sort-group + on-device merge —
    the PR 8 emitters over the slot layout)."""
    from ..copr import fusion
    from ..copr.fusion import (grouped_partial_states,
                               merge_grouped_partials, sort_group_segments)
    from ..copr.parallel import _key_device

    S = len(mesh.devices.ravel())
    n_slots = len(spec.slot_src)
    grouped = group_rw is not None
    nk = len(group_rw) if grouped else 0
    gchunk = cap_g // S if grouped else 0

    def shard_fn(*args):
        slots = []
        off = 0
        for _s in range(n_slots):
            slots.append((args[off], args[off + 1]))
            off += 2
        mask = args[off]
        off += 1
        env = {i: slots[i] for i in range(n_slots)}
        if grouped:
            gbudget = args[off]
            off += 1
            rvals = args[off:]
            key_bits, key_flags = [], []
            rslot = 0
            for gi, g in enumerate(group_rw):
                rem = remaps[gi] if remaps is not None else None
                if rem is not None:
                    d0, v = env[rem.src_idx]
                    d = fusion.remap_codes(d0, rvals[rslot], n_in)
                    rslot += 1
                else:
                    d, v = compile_expr(g, env, n_in)
                k = _key_device(d)
                zero = (jnp.float64(0.0) if k.dtype == jnp.float64
                        else jnp.int64(0))
                key_bits.append(jnp.where(v, k, zero))
                key_flags.append(v.astype(jnp.int64))
            order, sm, out_keys, seg, n_uniq = sort_group_segments(
                key_bits, key_flags, mask, cap_g)
            states_ = grouped_partial_states(
                aggs_rw, lambda e: compile_expr(e, env, n_in),
                order, sm, seg, cap_g)
            over_l = jax.lax.psum(jnp.maximum(n_uniq - gbudget, 0), "dp")
            slot_ok = jnp.arange(cap_g, dtype=jnp.int64) \
                < jnp.minimum(n_uniq, cap_g)
            g_keys = [ex.replicate(k) for k in out_keys]
            g_ok = ex.replicate(slot_ok)
            g_states = jax.tree_util.tree_map(ex.replicate, states_)
            mn_uniq, m_keys, m_states = merge_grouped_partials(
                aggs_rw, g_keys[:nk], g_keys[nk:], g_ok, g_states, cap_g)
            over_m = jnp.maximum(mn_uniq - gbudget, 0)
            shard = jax.lax.axis_index("dp")

            def slc(y):
                return jax.lax.dynamic_slice(y, (shard * gchunk,),
                                             (gchunk,))

            return (over_l, over_m.reshape(1), mn_uniq.reshape(1),
                    tuple(slc(k) for k in m_keys),
                    tuple(jax.tree_util.tree_map(slc, m_states)))

        # scalar partial aggregation
        states_ = []
        for a in aggs_rw:
            if a.name == "count":
                if a.args:
                    d, v = compile_expr(a.args[0], env, n_in)
                    states_.append(jax.lax.psum(
                        (mask & v).sum().astype(jnp.int64), "dp"))
                else:
                    states_.append(jax.lax.psum(
                        mask.sum().astype(jnp.int64), "dp"))
                continue
            d, v = compile_expr(a.args[0], env, n_in)
            mv = mask & v
            if a.name in ("sum", "avg"):
                st = a.partial_types()[0]
                dd = _to_state_dtype(d, a.args[0].ftype, st)
                states_.append((
                    jax.lax.psum(jnp.where(mv, dd, 0).sum(), "dp"),
                    jax.lax.psum(mv.sum().astype(jnp.int64), "dp"),
                ))
            else:  # min / max: per-shard partial, host merges
                if a.name == "min":
                    sent = (jnp.inf if jnp.issubdtype(d.dtype, jnp.floating)
                            else ex.I64_MAX)
                    part = jnp.where(mv, d, sent).min()
                else:
                    sent = (-jnp.inf if jnp.issubdtype(d.dtype,
                                                       jnp.floating)
                            else -ex.I64_MAX - 1)
                    part = jnp.where(mv, d, sent).max()
                states_.append((
                    part.reshape(1),
                    jax.lax.psum(mv.sum().astype(jnp.int64), "dp"),
                ))
        return (tuple(states_),)

    if grouped:
        out_states = []
        for a in aggs_rw:
            if a.name == "count":
                out_states.append(P("dp"))
            else:
                out_states.append((P("dp"), P("dp")))
        out_specs = (P(), P("dp"), P("dp"),
                     tuple(P("dp") for _ in range(2 * nk)),
                     tuple(out_states))
    else:
        out_states = []
        for a in aggs_rw:
            if a.name == "count":
                out_states.append(P())
            elif a.name in ("sum", "avg"):
                out_states.append((P(), P()))
            else:
                out_states.append((P("dp"), P()))
        out_specs = (tuple(out_states),)
    in_specs = tuple(P("dp") for _ in range(2 * n_slots)) + (P("dp"),)
    if grouped:
        in_specs = in_specs + (P(),)
        in_specs = in_specs + tuple(
            P() for r in (remaps or ()) if r is not None)
    fn = shard_map(shard_fn, mesh=mesh, in_specs=in_specs,
                   out_specs=out_specs, check_vma=False)
    return _packed_jit(fn, mesh, name, join=True)


# ---------------------------------------------------------------------------
# host-side assembly
# ---------------------------------------------------------------------------


def _decode_slot(spec, states, slot: int, ft, data: np.ndarray,
                 valid: np.ndarray) -> Column:
    if ft.kind == TypeKind.STRING:
        from ..store.blockstore import _decode_dict

        side, sp = spec.slot_src[slot]
        st = states[side]
        store_ci = st.an.scan.columns[sp]
        obj = _decode_dict(data.astype(np.int64),
                           st.table.cols[store_ci].dictionary)
        return Column(ft, obj, valid)
    return Column(ft, data.astype(ft.np_dtype), valid)


def _assemble_tree_rows(spec, states, mask, flats) -> List[Chunk]:
    from ..copr.jax_engine import _np_tree

    sel = np.flatnonzero(mask)
    cols = []
    for ft, slot in zip(spec.out_ftypes, spec.out_slots):
        d, v = _np_tree((flats[2 * slot], flats[2 * slot + 1]))
        cols.append(_decode_slot(spec, states, slot, ft, d[sel],
                                 v[sel].astype(np.bool_)))
    big = Chunk(cols)
    return [c for c in big.split(OUT_CHUNK_ROWS) if c.num_rows]


def _assemble_tree_grouped(spec, states, n_uniq, keys, sts,
                           remaps=None) -> List[Chunk]:
    nk = len(spec.group_by)
    k = int(n_uniq[0])
    cols: List[Column] = []
    for i, g in enumerate(spec.group_by):
        bits = keys[i][:k]
        flags = keys[nk + i][:k].astype(np.bool_)
        ft = g.ftype
        rem = remaps[i] if remaps is not None else None
        if rem is not None and rem.out_dict is not None:
            from ..store.blockstore import _decode_dict

            data = _decode_dict(bits.astype(np.int64), rem.out_dict)
        elif ft.kind == TypeKind.FLOAT:
            data = bits.astype(np.float64, copy=False)
        elif ft.kind == TypeKind.STRING:
            from ..store.blockstore import _decode_dict

            side, sp = spec.slot_src[g.index]
            st = states[side]
            store_ci = st.an.scan.columns[sp]
            data = _decode_dict(bits.astype(np.int64),
                                st.table.cols[store_ci].dictionary)
        else:
            data = bits.astype(ft.np_dtype)
        cols.append(Column(ft, data, flags if not flags.all() else None))
    for a, st in zip(spec.aggs, sts):
        pts = a.partial_types()
        if a.name == "count":
            cols.append(Column(pts[0], st[:k].astype(np.int64)))
        elif a.name in ("sum", "avg"):
            s, c = st[0][:k], st[1][:k]
            cols.append(Column(pts[0], s.astype(pts[0].np_dtype), c > 0))
            if a.name == "avg":
                cols.append(Column(pts[1], c.astype(np.int64)))
        else:
            v, c = st[0][:k], st[1][:k]
            cols.append(Column(pts[0], v.astype(pts[0].np_dtype), c > 0))
    chunk = Chunk(cols)
    return [chunk] if chunk.num_rows else []


def _assemble_tree_partials(spec, sts, S: int) -> List[Chunk]:
    cols: List[Column] = []
    for a, st in zip(spec.aggs, sts):
        pts = a.partial_types()
        if a.name == "count":
            cols.append(Column(pts[0], np.array([int(st)], np.int64)))
        elif a.name in ("sum", "avg"):
            sm, c = st
            c = int(c)
            cols.append(Column(pts[0],
                               np.array([sm]).astype(pts[0].np_dtype),
                               np.array([c > 0])))
            if a.name == "avg":
                cols.append(Column(pts[1], np.array([c], np.int64)))
        else:
            part, c = st
            c = int(c)
            v = part.min() if a.name == "min" else part.max()
            cols.append(Column(pts[0],
                               np.array([v]).astype(pts[0].np_dtype),
                               np.array([c > 0])))
    return [Chunk(cols)]


# ---------------------------------------------------------------------------
# entry
# ---------------------------------------------------------------------------


def _clone_expr(e):
    return deserialize_expr(serialize_expr(e))


def _run_tree_once(storage, spec: MPPJoinTreeSpec, modes: List[str],
                   boosts: List[int]) -> List[Chunk]:
    import os as _os

    from ..lifecycle import dispatch_admission, scope_check
    from ..trace import annotate, span

    mesh = get_mesh()
    S = len(mesh.devices.ravel())
    mesh_ids = tuple(d.id for d in mesh.devices.ravel())
    states = [_SideState(storage, s, spec.ts, mesh) for s in spec.sides]
    for st in states:
        st.load(mesh)

    slack = _slack()
    join_slack = float(_os.environ.get("TIDB_TPU_MPP_JOIN_SLACK", "1.0"))
    grouped = spec.aggs is not None and spec.group_by is not None
    budget, cap_g = 0, 0
    if grouped:
        budget = (int(_os.environ.get("TIDB_TPU_MPP_GROUP_BUDGET", "0"))
                  or spec.group_budget or 4096)
        cap_g0 = _pow2ceil(budget)
        cap_g = S * (-(-cap_g0 // S))
    remaps = _tree_key_remaps(spec, states) if grouped else None

    # dict-rewrite the per-rung other conds and final agg exprs against
    # each column's OWNING side (string constants -> codes, LIKE /
    # computed predicates -> code sets); rewritten trees enter the
    # program fingerprints, so dictionary changes recompile correctly
    rung_conds = []
    for r, rung in enumerate(spec.rungs):
        if rung.kind == "left_outer" and rung.other_conds:
            # a probe row whose every candidate pair fails the ON conds
            # must still NULL-extend; the emission path cannot express
            # that — the planner pushes build-side-only ON conds into
            # the scan instead, anything else stays host
            raise MPPIneligible("left-outer rung with pair conditions")
        if rung.kind == "left_outer" and len(rung.left_slots) > 1:
            # defense in depth behind the planner gate: multi-key
            # louter candidates are mix-hash (collision-prone), and a
            # dropped collision pair would still emit a spurious
            # NULL-extended row (keep=out_valid)
            raise MPPIneligible("multi-key left-outer rung")
        n_slots = _slots_of_prefix(spec, r)
        resolver = _slot_resolver(spec, states, n_slots,
                                  states[rung.side])
        try:
            rung_conds.append([
                rewrite_for_dict_resolved(_clone_expr(c), resolver)
                for c in rung.other_conds])
        except JaxUnsupported as e:
            raise MPPIneligible(f"rung {r} condition: {e}")
    aggs_rw = group_rw = None
    if spec.aggs is not None:
        from ..expr.aggregation import AggDesc

        resolver = _slot_resolver(spec, states, len(spec.slot_src))
        try:
            aggs_rw = [AggDesc(a.name,
                               [rewrite_for_dict_resolved(_clone_expr(x),
                                                          resolver)
                                for x in a.args],
                               a.distinct, a.ftype)
                       for a in spec.aggs]
            if grouped:
                group_rw = [
                    g if (remaps is not None
                          and remaps[i] is not None) else
                    rewrite_for_dict_resolved(_clone_expr(g), resolver)
                    for i, g in enumerate(spec.group_by)]
        except JaxUnsupported as e:
            raise MPPIneligible(f"final agg: {e}")

    # ---- run the ladder ---------------------------------------------
    import json as _json

    inter = None     # flat (data, valid) arrays per slot
    mask = None
    n_in = states[0].n_local
    # key-slot tuples the intermediate is hash-partitioned by (empty
    # until the first shuffle rung: rung 0's input is range-partitioned)
    residency: set = set()
    base_fp = (f"mpptree|S={S} devs={mesh_ids}"
               f"|base:{_fingerprint(states[0].an, 'filter')}"
               f"|Tl={states[0].Tl}|wire={states[0].wire_sig}")
    for r, rung in enumerate(spec.rungs):
        bs = states[rung.side]
        mode = modes[r]
        # residency elision (ISSUE 18 jointree (e)): a shuffle rung
        # whose key slots match the partitioning the PREVIOUS shuffle
        # rung left behind skips the probe-side exchange entirely —
        # equal keys (and bucket-0 NULL keys) already co-reside, so
        # only the build side moves
        elide = (mode == "shuffle" and inter is not None
                 and tuple(rung.left_slots) in residency)
        cap_p = min(_pow2ceil(int(slack * n_in / S) + 1), max(n_in, 16))
        cap_b = min(_pow2ceil(int(slack * bs.n_local / S) + 1),
                    bs.n_local)
        n_recv = (S * cap_p if mode == "shuffle" and not elide
                  else n_in)
        # emission buffer sized by the planner's rung estimate (whole
        # result could land on ONE shard when the base side is a single
        # tile), then boosted ×4 per runtime overflow
        est_cap = _pow2ceil(int(2 * max(rung.est_rows, 1)))
        cap_out = max(int(join_slack * n_recv), est_cap, 16) * boosts[r]
        conds_sig = _json.dumps(
            [serialize_expr(c) for c in rung_conds[r]], sort_keys=True)
        fp = (base_fp
              + f"|r{r}|{mode}|{rung.kind}|n_in={n_in}"
              f"|caps={cap_p},{cap_b},{cap_out}"
              f"|lk={rung.left_slots}|el={int(elide)}"
              f"|b:{_fingerprint(bs.an, 'filter')}|Tl={bs.Tl}"
              f"|k={rung.build_key_pos}|wire={bs.wire_sig}"
              f"|oc={conds_sig}")
        # the next rung's input is this rung's output, whose slots every
        # earlier rung's build side shaped: each fingerprint carries the
        # whole ladder up to it (two trees that differ only in an
        # earlier build side's columns once shared a later rung's program
        # and failed on its operand count)
        base_fp = fp
        fn = _COMPILED.get(fp)
        if fn is None:
            fn = _build_rung_fn(spec, r, states, mesh, mode, n_in,
                                cap_p, cap_b, cap_out, rung_conds[r],
                                elide_probe=elide,
                                name=program_name("tree", fp))
            _COMPILED.put(fp, fn)
        FAILPOINTS.hit(TREE_FAILPOINT, rung=r, mode=mode,
                       kind=rung.kind, device_ids=mesh_ids)
        # the rung ladder IS the chunk sequence on the MPP path: each
        # rung re-checks scope and resource-group admission, so KILL of
        # a deep join tree lands between rungs (ISSUE 17)
        FAILPOINTS.hit("copr/chunk_dispatch", kind="mpp", chunk=r,
                       total=len(spec.rungs), start=0, end=0)
        if inter is None:
            args = (tuple(states[0].datas), tuple(states[0].valids),
                    states[0].del_mask, _bounds_args(states[0].bounds))
        else:
            args = tuple(inter) + (mask,)
        args = args + (tuple(bs.datas), tuple(bs.valids), bs.del_mask,
                       _bounds_args(bs.bounds))
        _check_membership_epoch()
        scope_check()
        with span("mpp.rung", idx=r, rung=mode, kind=rung.kind,
                  elided=int(elide), build_table=bs.side.table_id):
            with dispatch_admission(DISPATCH_LOCK):
                overflow, jover, out_slots, keep = fn(*args)
            overflow, jover = int(overflow), int(jover)
        if overflow:
            raise MPPTreeOverflow(
                r, "partition",
                f"rung {r}: {overflow} rows over partition capacity "
                f"(cap_p={cap_p}, cap_b={cap_b}, mode={mode})")
        if jover:
            raise MPPTreeOverflow(
                r, "emit",
                f"rung {r}: {jover} joined rows over the emission "
                f"buffer (cap_out={cap_out}, mode={mode})")
        inter = list(out_slots)
        mask = keep
        n_in = (n_recv if rung.kind in ("semi", "anti_semi")
                else cap_out)
        REGISTRY.inc("mpp_tree_rungs_total")
        if elide:
            REGISTRY.inc("mpp_tree_reshuffle_elided_total")
        if mode == "shuffle":
            # rows now co-reside hashed by this rung's key; for inner
            # rungs the appended build key columns carry the SAME
            # values (the planner canonicalizes later rungs onto any
            # member of the equality class), so they name the layout
            # too.  NOT valid for left_outer — unmatched rows carry
            # NULL build keys that a real shuffle would send to bucket
            # 0.  Broadcast rungs never move the probe side, so any
            # earlier residency still holds.
            residency = {tuple(rung.left_slots)}
            if rung.kind == "inner":
                base = _slots_of_prefix(spec, r)
                order = list(bs.col_order)
                residency.add(tuple(base + order.index(kp)
                                    for kp in rung.build_key_pos))

    from ..copr.device_health import DEVICE_HEALTH

    DEVICE_HEALTH.record_success(mesh_ids)

    # ---- final phase -------------------------------------------------
    if spec.aggs is None:
        from ..copr.jax_engine import _np_tree

        with span("mpp.tree.readback"):
            m = _np_tree(mask)
            return _assemble_tree_rows(spec, states, m, inter)
    fin_sig = _json.dumps(
        [[a.name] + [serialize_expr(x) for x in a.args]
         for a in aggs_rw]
        + ([serialize_expr(g) for g in group_rw] if grouped else []),
        sort_keys=True)
    fp = (base_fp + f"|final|n_in={n_in}|capg={cap_g}|agg={fin_sig}"
          + (f"|rcaps={[r.cap if r else None for r in remaps]}"
             if remaps else ""))
    fn = _COMPILED.get(fp)
    if fn is None:
        fn = _build_final_fn(spec, states, mesh, n_in, cap_g, aggs_rw,
                             group_rw, remaps,
                             name=program_name("tree", fp))
        _COMPILED.put(fp, fn)
    args = tuple(inter) + (mask,)
    if grouped:
        args = args + (jnp.int64(budget),)
        for rm in (remaps or ()):
            if rm is not None:
                args = args + (jnp.asarray(rm.mapping),)
    _check_membership_epoch()
    scope_check()
    with span("mpp.tree.final", grouped=grouped):
        with dispatch_admission(DISPATCH_LOCK):
            out = fn(*args)
    if grouped:
        over_l, over_m = int(out[0]), int(np.max(out[1]))
        if over_l or over_m:
            raise MPPGroupedAggOverflow(
                f"tree: distinct groups over budget {budget} "
                f"(per-shard over {over_l}, merged over {over_m})")
        annotate(groups=int(out[2][0]), group_budget=budget)
        return _assemble_tree_grouped(spec, states, out[2], out[3],
                                      out[4], remaps=remaps)
    return _assemble_tree_partials(spec, out[0], S)


def run_mpp_jointree(storage,
                     spec: MPPJoinTreeSpec) -> Tuple[List[Chunk], str]:
    """Run the rung ladder over the mesh; (chunks, mode) on success,
    raises MPPIneligible when the host chain must serve it.  Per-rung
    overflow steps that rung down to broadcast; grouped-budget overflow
    peels the agg to a host tail over device-joined rows."""
    import dataclasses

    from ..trace import annotate, span

    modes = ["shuffle"] * len(spec.rungs)
    boosts = [1] * len(spec.rungs)
    attempts = 0
    peel = (spec.group_by is not None and spec.aggs is not None
            and not grouped_pushdown_enabled())
    while True:
        from ..lifecycle import current_scope

        FAILPOINTS.hit("exec/cancel", site="mpp", scope=current_scope())
        current_scope().check()
        if _no_eligible_devices():
            raise MPPIneligible("all device breakers open")
        run_spec = spec
        if peel:
            run_spec = dataclasses.replace(spec, aggs=None, group_by=None)
        try:
            with span("mpp.tree", rungs=len(spec.rungs),
                      grouped=bool(spec.group_by), peel=peel):
                chunks = _run_tree_once(storage, run_spec, modes, boosts)
            mode = "tree[" + ",".join(m[0] for m in modes) + "]"
            if peel:
                if spec.aggs is not None and spec.group_by is not None:
                    from .engine import _host_grouped_partials

                    with span("mpp.agg_peel", rung=mode):
                        chunks = _host_grouped_partials(spec, chunks)
                mode += "+agg-peel"
            elif spec.group_by is not None and spec.aggs is not None:
                mode += "+grouped"
            REGISTRY.inc("mpp_tree_joins_total")
            return chunks, mode
        except CoordEpochMismatch:
            attempts += 1
            if attempts >= MAX_MESH_ATTEMPTS:
                raise MPPIneligible(
                    "membership epoch flapping exhausted mesh attempts")
            continue
        except MPPGroupedAggOverflow as e:
            REGISTRY.inc("mpp_grouped_agg_overflow_total")
            REGISTRY.inc("mpp_grouped_agg_fallback_total")
            annotate(grouped_agg_overflow=str(e)[:120])
            peel = True
            continue
        except MPPTreeOverflow as e:
            if e.what == "emit":
                REGISTRY.inc("mpp_tree_emit_overflow_total")
                if boosts[e.rung] < MAX_EMIT_BOOST:
                    # genuine join fan-out: grow THIS rung's emission
                    # buffer and retry (duplicate keys expand the
                    # output past the received-row estimate)
                    boosts[e.rung] *= 4
                    continue
            if e.what == "partition":
                REGISTRY.inc("mpp_partition_overflow_total")
                if modes[e.rung] == "shuffle":
                    modes[e.rung] = "broadcast"  # immune to probe skew
                    continue
            raise MPPIneligible(f"tree rung overflow: {e}")
        except JaxUnsupported as e:
            # a rung/final program failed to compile (planner gates are
            # structural, not exhaustive): the host chain owns it
            raise MPPIneligible(str(e))
        except (MPPIneligible, KeyboardInterrupt, SystemExit,
                GeneratorExit):
            raise
        except BaseException as e:
            from ..errors import TiDBTPUError

            if isinstance(e, TiDBTPUError):
                raise
            if not _handle_mesh_failure(None, e, attempts):
                if classify_failure(e) is not None:
                    raise MPPIneligible(f"device failure: {e}")
                raise
            attempts += 1
