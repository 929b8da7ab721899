"""Device-side exchange primitives for the MPP shuffle join.

The partition/exchange shape follows TQP's relational-algebra-on-tensors
mapping (PAPERS.md): a hash shuffle is a static-shape bucket pack + one
`all_to_all` per column, and the local join is argsort + searchsorted —
all fixed-shape XLA ops, so the whole exchange compiles into the same
shard_map program as the scans feeding it.

Static capacities: each (source shard -> destination shard) bucket holds
at most `cap` rows.  Data-dependent overflow cannot resize a compiled
program, so it is *counted* on device and surfaced as a scalar the host
checks — the MeshAggOverflow contract (copr/parallel.py) applied to
exchanges; the caller then steps down the join-strategy ladder.

Backend notes (mirrors copr/parallel.py): no 64-bit bitcasts (assumed
unsafe under the TPU's x64 emulation; not re-tested on the attached
chip), so the partition hash stays in
int64 value arithmetic (wrapping multiply + arithmetic-shift xor), and
all_to_all payloads keep their widened column dtypes.
"""

from __future__ import annotations

from typing import List, Sequence, Tuple

import numpy as np

from .. import ops  # noqa: F401  (configures x64)
import jax
import jax.numpy as jnp

# splitmix64's multiplicative constants, wrapped into int64 — spreads
# clustered keys (sequential order keys, FK ranges) across partitions so
# the static bucket capacity sees near-uniform load
_MIX = np.int64(np.uint64(0x9E3779B97F4A7C15).astype(np.int64))
_MIX2 = np.int64(np.uint64(0xBF58476D1CE4E5B9).astype(np.int64))

I64_MAX = np.iinfo(np.int64).max


def partition_ids(key, n_parts: int):
    """[0, n_parts) partition id per int64 key, identical on both join
    sides (the ExchangeSender hash of tipb.ExchangeType_Hash).

    Two mixing rounds (splitmix64's finalizer shape, value arithmetic
    only — no 64-bit bitcasts): the single-round mix left small
    sequential key domains (dimension-table primary keys) piled onto
    half the buckets, overflowing static capacities and demoting joins
    to the broadcast rung for no reason (ISSUE 12)."""
    h = key * _MIX
    h = h ^ (h >> 31)  # arithmetic shift: sign bits only perturb, not bias
    h = h * _MIX2
    h = h ^ (h >> 29)
    return jnp.mod(h, n_parts)


def pack_buckets(pid, pack_mask, n_parts: int, cap: int,
                 arrays: Sequence) -> Tuple[List, object, object]:
    """Scatter local rows into [n_parts, cap] destination buckets.

    One argsort on partition id groups each destination's rows
    contiguously; bucket d then gathers rows [offset_d, offset_d+cap).
    Returns (bucketed arrays, bucket validity [n_parts, cap], overflow =
    max rows any bucket wanted minus cap, clamped at 0).  Rows beyond a
    bucket's capacity are DROPPED on device — the overflow scalar is how
    the host learns the result is incomplete and must fall back.
    """
    n = pid.shape[0]
    # unselected rows sort last (pid n_parts), never land in a bucket
    skey = jnp.where(pack_mask, pid, n_parts)
    order = jnp.argsort(skey)
    ssorted = skey[order]
    offsets = jnp.searchsorted(ssorted, jnp.arange(n_parts + 1))
    counts = offsets[1:] - offsets[:-1]
    overflow = jnp.maximum(counts.max() - cap, 0)
    slot = jnp.arange(cap)
    idx = offsets[:-1][:, None] + slot[None, :]          # [n_parts, cap]
    bucket_valid = slot[None, :] < counts[:, None]
    rows = order[jnp.clip(idx, 0, n - 1)]
    out = [a[rows] for a in arrays]
    return out, bucket_valid, overflow


def exchange(bucketed, axis_name: str = "dp"):
    """all_to_all one [S, cap] bucketed array: row d of the input is this
    shard's partition destined for shard d; row j of the output is the
    partition shard j sent here.  Flattened to [S*cap] local rows."""
    out = jax.lax.all_to_all(bucketed, axis_name, split_axis=0,
                             concat_axis=0, tiled=True)
    return out.reshape(-1)


def replicate(local, axis_name: str = "dp"):
    """all_gather a per-shard array to every shard (the broadcast-join
    rung: the build side is replicated instead of partitioned)."""
    return jax.lax.all_gather(local, axis_name).reshape(-1)


def combine_keys(keys):
    """Fold multiple int64 join-key columns into ONE int64 sort/partition
    key (identity for a single column, so single-key joins keep exact
    equality).  Multi-column combination is a mix-hash: colliding unequal
    keys land in the same sorted span, so callers must re-verify TRUE
    per-column equality on candidate matches (expand_matches emits the
    candidates; the engine filters)."""
    h = keys[0]
    for k in keys[1:]:
        h = (h * _MIX) ^ k ^ ((h >> 29) & 0x7FFFFFFF)
    return h


def pack_keys_exact(keys, los, cards):
    """EXACT compound-key composition (ISSUE 11): stats-bounded key
    columns pack into ONE int64 by stride multiplication — equal packed
    keys iff every column is equal, so no collision re-verify is needed
    and dropping candidates is sound for LEFT-OUTER joins (the mix-hash
    cannot promise that).  Callers guarantee prod(cards) <= 2**62 and
    that `los`/`cards` cover BOTH sides' value ranges (the union of
    per-side column stats)."""
    h = jnp.zeros_like(keys[0])
    for k, lo, card in zip(keys, los, cards):
        h = h * card + jnp.clip(k - lo, 0, card - 1)
    return h


def compound_pack_spec(stat_pairs, max_bits: int = 62):
    """(los, cards) for pack_keys_exact from per-key ((lo,hi), (lo,hi))
    stat pairs (probe side, build side), or None when the packed space
    exceeds 2**max_bits — callers then keep the mix-hash ladder."""
    los, cards = [], []
    total = 1
    for (p_lo, p_hi), (b_lo, b_hi) in stat_pairs:
        lo = min(p_lo, b_lo)
        hi = max(p_hi, b_hi)
        if hi < lo:
            lo, hi = 0, 0
        card = hi - lo + 1
        total *= card
        if total > (1 << max_bits):
            return None
        los.append(int(lo))
        cards.append(int(card))
    return los, cards


def sorted_build(keys, valid):
    """(sorted keys with invalid rows pushed to +inf, source order,
    valid count) — the device hash table: searchsorted probes against
    the sorted build keys (duplicates stay adjacent)."""
    sortk = jnp.where(valid, keys, I64_MAX)
    order = jnp.argsort(sortk)
    return sortk[order], order, valid.sum()


def expand_matches(sbk, bord, nb, probe_keys, probe_emit, probe_match_ok,
                   cap_out: int, louter: bool):
    """Two-pass count+emit join expansion over NON-UNIQUE build keys.

    Pass 1 (count): each probe row's match span in the sorted build keys
    is [lo, hi) via two searchsorteds; cnt = hi - lo candidate matches.
    Pass 2 (emit): output slot t maps back to its source probe row via
    searchsorted on the exclusive prefix sums — every (probe row, match
    ordinal) pair lands in one of `cap_out` static output slots.

    Left-outer probe rows with no match still emit ONE row (`matched`
    False there — the engine NULL-extends the build columns).  Total
    emissions beyond cap_out are DROPPED on device; the returned
    overflow scalar is how the host learns the result is incomplete.

    Returns (src, bidx, out_valid, matched, overflow): per-slot source
    probe row, matched build source row, slot-live mask, true-match-span
    mask, and the clamped overflow count.
    """
    n = probe_keys.shape[0]
    lo = jnp.searchsorted(sbk, probe_keys, side="left")
    hi = jnp.minimum(jnp.searchsorted(sbk, probe_keys, side="right"), nb)
    cnt = jnp.where(probe_match_ok, jnp.maximum(hi - lo, 0), 0)
    emit_cnt = (jnp.where(probe_emit, jnp.maximum(cnt, 1), 0)
                if louter else cnt)
    total = emit_cnt.sum().astype(jnp.int64)
    overflow = jnp.maximum(total - cap_out, 0)
    starts = jnp.cumsum(emit_cnt) - emit_cnt
    t = jnp.arange(cap_out, dtype=starts.dtype)
    src = jnp.clip(jnp.searchsorted(starts, t, side="right") - 1, 0, n - 1)
    j = t - starts[src]
    matched = j < cnt[src]
    bpos = jnp.clip(lo[src] + j, 0, sbk.shape[0] - 1)
    out_valid = t < total
    return src, bord[bpos], out_valid, matched & out_valid, overflow


# ---------------------------------------------------------------------------
# the one-shard join: nothing to exchange, keys the statistics bound
# ---------------------------------------------------------------------------

def directory_join(dir_keys, dir_sel, keys, sel, key_ok, lo, hi,
                   capacity: int):
    """The local join of one shard with itself, for a key column whose
    values the column statistics bound to [lo, hi] (`capacity` >= hi -
    lo + 1, static): no partition, no exchange, no sort.  The directory
    side's selected rows are written into a table addressed by key - lo;
    every row of the other side reads its match there, one gather.

    A directory holds ONE row a key: `unsound` counts the selected
    directory rows that do not find themselves in it (a duplicate key,
    or a key outside the statistics' bounds); the caller must then take
    the sorted two-pass join.  Returns (j, unsound): the matched
    directory row of each row of the other side, -1 where `sel & key_ok`
    is false or no directory row has the key."""
    n_dir = dir_keys.shape[0]
    rows = jnp.arange(n_dir, dtype=jnp.int32)
    inside = (dir_keys >= lo) & (dir_keys <= hi)
    slot = jnp.where(dir_sel & inside, dir_keys - lo, capacity) \
        .astype(jnp.int32)
    table = jnp.full(capacity, -1, dtype=jnp.int32) \
        .at[slot].set(rows, mode="drop")
    back = table[jnp.minimum(slot, capacity - 1)]
    unsound = (dir_sel & (back != rows)).sum().astype(jnp.int64)
    ok = sel & key_ok & (keys >= lo) & (keys <= hi)
    at = jnp.where(ok, keys - lo, 0).astype(jnp.int32)
    return jnp.where(ok, table[at], -1), unsound


# ---------------------------------------------------------------------------
# kernelcheck registration: abstract-trace the exchange + partitioned join
# ---------------------------------------------------------------------------


def _canonical_join_fn(S: int, cap: int, n_local: int, mode: str):
    """The canonical partition -> exchange -> local-join program shape
    the lint kernelcheck traces (no tables, no engine state): one int64
    key + one f64 payload per side, inner-join semantics with the
    production two-pass count+emit expansion (non-unique build keys)."""
    cap_out = S * cap if mode == "shuffle" else n_local

    def shard_fn(pk, pm, bk, bm, pv):
        if mode == "shuffle":
            bpid = partition_ids(bk, S)
            (bkb, bvb), bval, b_over = pack_buckets(
                bpid, bm, S, cap, (bk, pv))
            rbk = exchange(bkb)
            rbv = exchange(bvb)
            b_ok = exchange(bval)
            ppid = partition_ids(pk, S)
            (pkb,), pval, p_over = pack_buckets(ppid, pm, S, cap, (pk,))
            rpk = exchange(pkb)
            p_ok = exchange(pval)
        else:  # broadcast
            rbk = replicate(jnp.where(bm, bk, I64_MAX))
            rbv = replicate(pv)
            b_ok = replicate(bm)
            rpk, p_ok = pk, pm
            b_over = p_over = jnp.int64(0)
        sbk, bord, nb = sorted_build(rbk, b_ok)
        src, bidx, out_valid, matched, j_over = expand_matches(
            sbk, bord, nb, rpk, p_ok, p_ok, cap_out, False)
        payload = jnp.where(matched, rbv[bidx], 0.0)
        overflow = jax.lax.psum(b_over + p_over, "dp")
        jover = jax.lax.psum(j_over, "dp")
        return overflow, jover, matched, payload

    return shard_fn


def _canonical_directory_fn(capacity: int, cap_out: int):
    """The canonical one-shard join (mpp/engine.py's two programs as
    one): the directory over the build keys, every probe row's match in
    it, the matched rows compacted into `cap_out` slots and the build
    payload gathered there."""
    from .. import ops

    def shard_fn(pk, pm, bk, bm, pv):
        j, unsound = directory_join(bk, bm, pk, pm, pm, jnp.int64(0),
                                    jnp.int64(capacity - 1), capacity)
        emit = j >= 0
        rows = ops.first_marked(emit, cap_out, 0)
        live = jnp.arange(cap_out, dtype=jnp.int32) \
            < emit.sum().astype(jnp.int32)
        payload = jnp.where(live, pv[jnp.maximum(j[rows], 0)], 0.0)
        return jax.lax.psum(unsound, "dp"), jnp.int64(0), live, payload

    return shard_fn


def trace_exchange_kernel(mode: str = "shuffle"):
    """make_jaxpr stats for the canonical exchange join over a 1-device
    mesh (deterministic across environments regardless of how many
    virtual devices the harness exposes); used by lint.kernelcheck.
    `mode` "directory" is the one-shard join, which exchanges nothing."""
    from jax import shard_map
    from jax.sharding import Mesh, PartitionSpec as P

    S, cap, n_local = 1, 64, 256
    mesh = Mesh(np.array(jax.devices()[:1]), ("dp",))
    fn = shard_map(
        _canonical_directory_fn(n_local, n_local) if mode == "directory"
        else _canonical_join_fn(S, cap, n_local, mode), mesh=mesh,
        in_specs=(P("dp"),) * 5,
        out_specs=(P(), P(), P("dp"), P("dp")),
    )
    args = (
        jnp.zeros(n_local, jnp.int64), jnp.ones(n_local, jnp.bool_),
        jnp.zeros(n_local, jnp.int64), jnp.ones(n_local, jnp.bool_),
        jnp.zeros(n_local, jnp.float64),
    )
    return jax.make_jaxpr(fn)(*args)


def _canonical_tree_fn(S: int, cap: int, n_local: int, cap_out: int):
    """The canonical 3-way rung-ladder program shape (ISSUE 12,
    mpp/jointree.py): rung 0 joins base(key a, payload) against side B
    (key a -> key b mapping), rung 1 joins the DEVICE-RESIDENT
    intermediate against side C (key b, measure) — both rungs inside
    ONE traced program so kernelcheck guards the whole ladder's int64
    census.  Operand SHIFTS (the caller adds a constant to every key
    column) must trace to the IDENTICAL jaxpr: key values are runtime
    data, never compiled constants."""

    def one_rung(pk, pm, slots, bk, bm, b_payload):
        bpid = partition_ids(bk, S)
        packed, bval, b_over = pack_buckets(
            bpid, bm, S, cap, (bk, b_payload))
        rbk = exchange(packed[0])
        rbv = exchange(packed[1])
        b_ok = exchange(bval)
        ppid = partition_ids(pk, S)
        parrs = [pk] + [a for pair in slots for a in pair]
        packed_p, pval, p_over = pack_buckets(ppid, pm, S, cap, parrs)
        recv = [exchange(a) for a in packed_p]
        p_ok = exchange(pval)
        sbk, bord, nb = sorted_build(rbk, b_ok)
        src, bidx, out_valid, matched, j_over = expand_matches(
            sbk, bord, nb, recv[0], p_ok, p_ok, cap_out, False)
        out_slots = [(recv[1 + 2 * i][src], recv[2 + 2 * i][src])
                     for i in range(len(slots))]
        out_slots.append((rbv[bidx], matched))
        keep = out_valid & matched
        over = jax.lax.psum(p_over + b_over, "dp")
        jover = jax.lax.psum(j_over, "dp")
        return out_slots, keep, over, jover

    def shard_fn(ak, av, bk_a, bk_b, bm, ck, cv, cm):
        # rung 0: base(a_key, a_payload) ⋈ B(a_key -> b_key)
        slots0, keep0, ov0, jo0 = one_rung(
            ak, jnp.ones_like(ak, dtype=jnp.bool_),
            [(av, jnp.ones_like(ak, dtype=jnp.bool_))], bk_a, bm, bk_b)
        # rung 1: intermediate(b_key) ⋈ C(b_key, measure) — the
        # intermediate arrays feed straight in, no host boundary
        bkey = slots0[1][0].astype(jnp.int64)
        slots1, keep1, ov1, jo1 = one_rung(
            bkey, keep0 & slots0[1][1], slots0, ck, cm, cv)
        payload = jnp.where(keep1, slots1[0][0], 0.0)
        measure = jnp.where(keep1, slots1[-1][0], 0.0)
        total = jax.lax.psum((payload * measure).sum(), "dp")
        return ov0 + ov1, jo0 + jo1, keep1, total

    return shard_fn


#: canonical tree-kernel shape (S, cap, n_local, cap_out) — one source
#: for the shard_map builder AND the numpy oracle's input size, so a
#: retune can never make executed-parity compare different row counts
_TREE_KERNEL_SHAPE = (1, 256, 64, 1024)


def _tree_kernel_fn():
    """The canonical 3-way ladder wrapped in its 1-device shard_map —
    shared by trace_tree_join_kernel and run_tree_join_kernel so the
    traced jaxpr and the executed result can never diverge on mesh or
    spec constants.  Returns (fn, n_local)."""
    from jax import shard_map
    from jax.sharding import Mesh, PartitionSpec as P

    S, cap, n_local, cap_out = _TREE_KERNEL_SHAPE
    mesh = Mesh(np.array(jax.devices()[:1]), ("dp",))
    fn = shard_map(
        _canonical_tree_fn(S, cap, n_local, cap_out), mesh=mesh,
        in_specs=(P("dp"),) * 8,
        out_specs=(P(), P(), P("dp"), P()),
    )
    return fn, n_local


def trace_tree_join_kernel(shift: int = 0):
    """make_jaxpr stats for the canonical 3-way ladder over a 1-device
    mesh; `shift` offsets every key operand — lint.kernelcheck traces
    two shifts and requires identical jaxprs (key VALUES must never
    shape the compiled ladder)."""
    fn, n_local = _tree_kernel_fn()
    args = _tree_kernel_args(n_local, shift)
    return jax.make_jaxpr(fn)(*args)


def _tree_kernel_args(n_local: int, shift: int = 0):
    rng = np.random.default_rng(5)
    ak = rng.integers(0, 16, n_local).astype(np.int64) + shift
    av = rng.uniform(0, 1, n_local)
    bk_a = rng.integers(0, 16, n_local).astype(np.int64) + shift
    bk_b = rng.integers(0, 8, n_local).astype(np.int64) + shift
    bm = rng.random(n_local) < 0.5
    ck = rng.integers(0, 8, n_local).astype(np.int64) + shift
    cv = rng.uniform(0, 1, n_local)
    cm = rng.random(n_local) < 0.8
    # host numpy: trace/run callers device_put, the oracle reads direct
    return (ak, av, bk_a, bk_b, bm, ck, cv, cm)


def run_tree_join_kernel(shift: int = 0):
    """Execute the canonical ladder concretely (1 device) and return the
    scalar result — kernelcheck compares it against the numpy oracle
    (`tree_join_oracle`) for executed parity."""
    fn, n_local = _tree_kernel_fn()
    over, jover, _keep, total = fn(*_tree_kernel_args(n_local, shift))
    return int(over), int(jover), float(total)


def tree_join_oracle(shift: int = 0) -> float:
    """Numpy reference for run_tree_join_kernel: the same 3-way join
    evaluated row-at-a-time on the host."""
    n_local = _TREE_KERNEL_SHAPE[2]
    ak, av, bk_a, bk_b, bm, ck, cv, cm = _tree_kernel_args(n_local, shift)
    total = 0.0
    for i in range(n_local):
        for j in range(n_local):
            if not bm[j] or bk_a[j] != ak[i]:
                continue
            for k in range(n_local):
                if cm[k] and ck[k] == np.int64(bk_b[j]):
                    total += av[i] * cv[k]
    return float(total)


def _canonical_grouped_fn(S: int, cap_out: int, cap_g: int):
    """Canonical grouped-partial + on-device-merge program: one int64
    group key + one f64 measure over cap_out joined rows — per-shard
    sort-group into cap_g slots, all_gather of the compacted
    (key, state) rows, second sort-merge, per-shard slice emission.
    The group BUDGET is the runtime scalar argument: kernelcheck
    asserts the traced jaxpr is IDENTICAL across budget values."""
    from ..copr.fusion import (grouped_partial_states,
                               merge_grouped_partials,
                               sort_group_segments)
    from ..expr.aggregation import AggDesc
    from ..types import FieldType, TypeKind

    f64 = FieldType(TypeKind.FLOAT)
    aggs = [AggDesc("count", [], False, FieldType(TypeKind.INT)),
            AggDesc("sum", [_CanonArg(f64)], False, f64)]
    gchunk = cap_g // S

    def shard_fn(gk, gv, meas, mm, gbudget):
        key_bits = [jnp.where(gv, gk, 0)]
        key_flags = [gv.astype(jnp.int64)]
        order, sm, out_keys, seg, n_uniq = sort_group_segments(
            key_bits, key_flags, mm, cap_g)
        states = grouped_partial_states(
            aggs, lambda e: (meas, mm), order, sm, seg, cap_g)
        over_l = jax.lax.psum(jnp.maximum(n_uniq - gbudget, 0), "dp")
        slot_ok = jnp.arange(cap_g, dtype=jnp.int64) \
            < jnp.minimum(n_uniq, cap_g)
        g_keys = [replicate(k) for k in out_keys]
        g_ok = replicate(slot_ok)
        g_states = jax.tree_util.tree_map(replicate, states)
        mn_uniq, m_keys, m_states = merge_grouped_partials(
            aggs, g_keys[:1], g_keys[1:], g_ok, g_states, cap_g)
        over_m = jnp.maximum(mn_uniq - gbudget, 0)
        shard = jax.lax.axis_index("dp")

        def slc(y):
            return jax.lax.dynamic_slice(y, (shard * gchunk,), (gchunk,))

        return (over_l, over_m.reshape(1), mn_uniq.reshape(1),
                tuple(slc(k) for k in m_keys),
                tuple(jax.tree_util.tree_map(slc, m_states)))

    return shard_fn


class _CanonArg:
    """Minimal expression stand-in for the canonical grouped kernel:
    grouped_partial_states only reads `.args[0].ftype` and calls the
    arg_fn closure, which ignores the expression object."""

    def __init__(self, ftype):
        self.ftype = ftype


def trace_grouped_agg_kernel(budget: int = 7):
    """make_jaxpr stats for the canonical grouped-partial + merge
    program over a 1-device mesh; `budget` rides the runtime scalar
    slot — lint.kernelcheck traces two budgets and requires identical
    jaxprs (the budget must never become a compiled constant)."""
    from jax import shard_map
    from jax.sharding import Mesh, PartitionSpec as P

    S, cap_out, cap_g = 1, 256, 32
    mesh = Mesh(np.array(jax.devices()[:1]), ("dp",))
    fn = shard_map(
        _canonical_grouped_fn(S, cap_out, cap_g), mesh=mesh,
        in_specs=(P("dp"), P("dp"), P("dp"), P("dp"), P()),
        out_specs=(P(), P("dp"), P("dp"), (P("dp"),) * 2,
                   (P("dp"), (P("dp"), P("dp")))),
    )
    args = (
        jnp.zeros(cap_out, jnp.int64), jnp.ones(cap_out, jnp.bool_),
        jnp.zeros(cap_out, jnp.float64), jnp.ones(cap_out, jnp.bool_),
        jnp.int64(budget),
    )
    return jax.make_jaxpr(fn)(*args)
