"""Whole-fragment kernel fusion: one XLA launch per pushed-down fragment.

Flare (PAPERS.md) showed the order-of-magnitude wins come from compiling
an ENTIRE stage natively instead of operator-at-a-time; "Query Processing
on Tensor Computation Runtimes" maps full relational fragments onto
single tensor programs.  This module is that idea applied to the copr
engines:

- **Phase emitters** (`selection_mask`, `dense_group_codes`,
  `dense_agg_results`, `topn_key`, `projection_outputs`): each pushed
  phase of a fragment — filter, project, group-code, aggregate, topN —
  emits jax ops into a shared tracing context instead of owning its own
  device dispatch.  Both engines' program builders
  (`jax_engine._tile_core` per tile, `parallel._build_mesh_fn` per mesh
  shard) compose these emitters, so scan→filter→project→agg→topN lowers
  into ONE jitted/shard_map program: intermediates never leave HBM and a
  steady-state fragment is exactly one `copr.device.execute` span per
  mesh dispatch.  The collective axis rides in the context (`axis="dp"`
  under shard_map, None per tile) so the same emitter serves both.

- **Fusion regions + fallback ladder** (`plan_regions`,
  `run_fragment`): a fragment containing one unfusable operator no
  longer demotes the WHOLE fragment to the CPU interpreter.  The
  splitter finds the longest device-compilable executor prefix (the
  fused region) and peels the remainder into a host tail evaluated by
  the CPU engine over the region's output chunks — split the region at
  the unfusable boundary, never fail the query.  The chaos site
  `copr/fusion_split` forces splits at arbitrary boundaries so parity
  under every split point is test-asserted.

Compiled fused programs key on the existing DAG fingerprint compile
cache (`copr/cache.py` ProgramCache) and compose with the serving
layer's ParamConst slots and pow2 shape buckets: parameter-different
literals, growing tables, and (on the mesh) any range count up to
`parallel.MESH_RANGE_SLOTS` all share one compiled program.
"""

from __future__ import annotations

import os
from dataclasses import dataclass, field
from typing import List, Optional

import numpy as np

from .. import ops  # noqa: F401  (configures x64)
import jax
import jax.numpy as jnp

from ..store.fault import FAILPOINTS
from .ir import DAG
from .jax_eval import JaxUnsupported, compile_expr

#: chaos site: an armed action may raise JaxUnsupported to force the
#: splitter to cut the fused region at an arbitrary executor boundary
SPLIT_FAILPOINT = "copr/fusion_split"

#: the measured split-reason inventory (ISSUE 11): every host-tail split
#: carries one of these labels on `fusion_splits_reason_*_total`, /status
#: and INFORMATION_SCHEMA.TIDB_TPU_FUSION_SPLITS, so fusion-coverage
#: regressions are visible per cause, not as one opaque counter
SPLIT_REASONS = ("unsupported-op", "computed-key", "compound-order",
                 "head-shape", "agg-overflow")


def classify_split_reason(msg: Optional[str]) -> str:
    """Map a JaxUnsupported message onto the split-reason inventory."""
    m = (msg or "").lower()
    if "group key" in m and ("string" in m or "computed" in m
                             or "remap" in m):
        return "computed-key"
    if "sort key" in m or "compound order" in m or "order key" in m:
        return "compound-order"
    return "unsupported-op"


def note_split(label: Optional[str], boundary: str):
    """Count one region split under its reason label (the labelled
    fusion_splits_total of ISSUE 11) and annotate the active trace."""
    from ..metrics import REGISTRY
    from ..trace import annotate

    label = label if label in SPLIT_REASONS else "unsupported-op"
    REGISTRY.inc("fusion_splits_total")
    REGISTRY.inc("fusion_splits_reason_"
                 + label.replace("-", "_") + "_total")
    annotate(fusion_split=boundary, fusion_split_reason=label)


def fusion_enabled() -> bool:
    """Whole-fragment fusion switch (TIDB_TPU_FUSION=0 restores the
    per-tile dispatch loop — the bench's unfused comparator)."""
    return os.environ.get("TIDB_TPU_FUSION", "1") != "0"


# ---------------------------------------------------------------------------
# phase emitters: fragment phases emit into a shared tracing context
# ---------------------------------------------------------------------------


@dataclass
class RegionContext:
    """The shared tracing context one fused program body emits into.

    Phase emitters read/extend `cols` (the column environment) and AND
    into `mask` (live-row mask); nothing dispatches — the caller jits the
    composed body once per fragment shape class.
    """

    an: object                  # jax_engine._Analyzed of the fused region
    cols: dict                  # col index -> (data, valid) device arrays
    n: object                   # the rows' shape: TILE, the shard-local
    #                             row count, or a shard's row view (a tuple)
    mask: object                # live-row bool array of that shape
    axis: Optional[str] = None  # collective axis under shard_map, else None
    gofs: object = None         # global row offsets (mesh), else None
    n_global: int = 0           # total rows across shards (argfirst sentinel)
    flat: object = None         # a row-shaped array in row order, 1-D

    def psum(self, x):
        return jax.lax.psum(x, self.axis) if self.axis is not None else x

    def in_row_order(self, x):
        return x.reshape(-1) if self.flat is None else self.flat(x)


def selection_mask(ctx: RegionContext):
    """Emit the fused selection: AND every pushed condition into the
    live-row mask (one fused elementwise program, no dispatch)."""
    m = ctx.mask
    for c in ctx.an.conds:
        d, v = compile_expr(c, ctx.cols, ctx.n)
        m = m & v & (d != 0)
    ctx.mask = m
    return m


def dense_group_codes(ctx: RegionContext):
    """Emit mixed-radix dense group codes; NULL key rows drop from the
    mask (NULL keys are excluded by _Analyzed's dense-mode gate).  The
    codes are int32 (the code space is capped at MAX_GROUPS), taken
    straight from the column's wire array where its statistics allow."""
    an = ctx.an
    wire = ctx.cols.get("__wire__", {})
    gidx = jnp.zeros(ctx.n, dtype=jnp.int32)
    stride = 1
    m = ctx.mask
    for kcol, (klo, card) in zip(an.group_cols, an.group_card):
        d, v = ctx.cols[kcol]
        w = wire.get(kcol)
        if w is not None and max(abs(klo), abs(klo + card)) < 1 << 30:
            code = jnp.clip(w.astype(jnp.int32) - klo, 0, card - 1)
        else:
            code = jnp.clip(d.astype(jnp.int64) - klo, 0,
                            card - 1).astype(jnp.int32)
        gidx = gidx + code * stride
        m = m & v
        stride *= card
    ctx.mask = m
    return gidx


#: rows in one block of a dense aggregate's first level.  A limb is an
#: int32 within +-AGG_LIMB, so a block's sum cannot pass int32.
AGG_BLOCK = 1 << 14
AGG_LIMB = ((1 << 31) - 1) // AGG_BLOCK
#: operands of one variadic reduce.  The v5e compiler keeps TPC-H Q1's 60
#: one fusion that reads the columns once; given 220 it cuts the fusion
#: itself and writes operands out in full (the program no longer fits the
#: chip).  Past the cap an aggregate is a few reduces: a pass each over
#: the columns its operands read, not over all.
AGG_REDUCE_OPERANDS = 64


def _agg_wire(an, arrays=None) -> dict:
    """What `jax_eval.bounded_int` reads: scan column -> (wire array, lo,
    hi, nullable) over `an.agg_bounds`; without `arrays`, the dry run."""
    from .jax_eval import DRY

    return {ci: (DRY if arrays is None else arrays[ci], lo, hi, nullable)
            for ci, (lo, hi, nullable) in an.agg_bounds.items()
            if arrays is None or ci in arrays}


def _int_state(a) -> bool:
    from ..types import TypeKind

    return (a.name in ("sum", "avg")
            and a.partial_types()[0].kind != TypeKind.FLOAT)


def agg_lanes(an) -> str:
    """How a dense aggregate's integer sums ride the first level, one
    entry a distinct argument in the order the statement names them:
    `i32:<limbs>` where the column statistics bound the argument (int32
    arithmetic from the wire arrays), `i64:4` where they do not (int64
    arithmetic, four 16-bit limbs).  Part of the program's fingerprint;
    'scatter' where the group space is past `ops.UNROLL_G` and the sums
    stay `jax.ops.segment_sum`.  Where sums can pass int64 over the
    table's rows (`wide_sums`), `;wide=<their aggregates>` follows."""
    from .ir import serialize_expr
    from .jax_eval import bounded_int, lane_limbs

    if an.agg_lanes is not None:
        return an.agg_lanes
    if an.num_groups > ops.UNROLL_G:
        out = "scatter"
    else:
        wire, seen = _agg_wire(an), {}
        for a in an.agg.aggs:
            if not _int_state(a):
                continue
            key = str(serialize_expr(a.args[0]))
            if key not in seen:
                v = bounded_int(a.args[0], wire)
                seen[key] = ("i64:4" if v is None else
                             f"i32:{len(lane_limbs(v, AGG_LIMB))}")
        out = ",".join(seen.values())
        wide = wide_sums(an)
        if wide:
            out += ";wide=" + ",".join(str(i) for i in sorted(wide))
    an.agg_lanes = out
    return out


#: sums that left the device as limb sums and were put together on the
#: host as Python integers, one a slot a dispatch
WIDE_SUM_SLOTS = "agg_wide_sum_slots_total"


def _state_rescale(expr, state_ft) -> int:
    """What a sum's argument is multiplied by to stand in its state's
    scale."""
    from ..types import TypeKind

    ft = expr.ftype
    return 10 ** (state_ft.scale
                  - (ft.scale if ft.kind == TypeKind.DECIMAL else 0))


def _passes_int64(an, expr, mul: int) -> bool:
    """Whether the sum of `expr * mul` over the table's base rows can
    pass int64 while no row's value does.  Reckoned from the statistics'
    own minima and maxima, not from the powers of two the lanes are cut
    by: TPC-H Q1's sum_charge at SF10 fits by the first and not by the
    second."""
    from .jax_eval import int_bounds

    own = int_bounds(expr, an.agg_stats)
    top = 0 if own is None else max(abs(own[0]), abs(own[1])) * mul
    return top < 1 << 63 <= top * an.agg_rows


#: the limbs of a sum the statistics do not bound: an int64 in 16-bit
#: pieces, the last one signed
I64_SHIFTS = (0, 16, 32, 48)


def wide_sums(an) -> dict:
    """{aggregate: (limb shifts, constant, rescale)} for the sums of a
    small-G dense aggregate that can pass int64: under a mesh such a sum
    leaves the device as its limb sums (each far inside int64: a limb is
    within +-AGG_LIMB) and `recombine_wide` puts it together on the host
    as Python integers, which the DECIMAL(38, s) state holds as they
    are.  Empty for every other aggregate."""
    from .jax_eval import bounded_int, lane_limbs

    if an.agg_wide is None:
        an.agg_wide = {}
        if an.agg_mode == "dense" and an.num_groups <= ops.UNROLL_G:
            for i, a in enumerate(an.agg.aggs):
                if not _int_state(a):
                    continue
                mul = _state_rescale(a.args[0], a.partial_types()[0])
                if not _passes_int64(an, a.args[0], mul):
                    continue
                v = bounded_int(a.args[0], _agg_wire(an))
                an.agg_wide[i] = (I64_SHIFTS, 0, mul) if v is None else (
                    [s for _x, s in lane_limbs(v, AGG_LIMB)], v.const, mul)
    return an.agg_wide


def recombine_wide(limbs: np.ndarray, counts: np.ndarray, plan) -> np.ndarray:
    """A wide sum's limb sums [limbs, G] and its counts [G] as the exact
    sums, an object array of Python integers."""
    shifts, const, mul = plan
    out = np.empty(limbs.shape[1], dtype=object)
    for g in range(limbs.shape[1]):
        out[g] = mul * (sum(int(x) << s for x, s in zip(limbs[:, g], shifts))
                        + const * int(counts[g]))
    return out


def compile_attrs(an, kind: str) -> dict:
    """What a `copr.compile` span says of the program beyond its kind:
    a dense aggregate's lanes."""
    if kind == "agg" and an.agg_mode == "dense":
        return {"agg_lanes": agg_lanes(an)}
    return {}


def note_agg_dispatch(an):
    """Count one dispatched dense aggregate by whether every summed
    argument was bounded."""
    from ..metrics import REGISTRY

    lanes = agg_lanes(an)
    if "i64" in lanes or lanes == "scatter":
        REGISTRY.inc("copr_agg_wide_total")
    else:
        REGISTRY.inc("copr_agg_narrow_total")


def _add_lanes(a, b):
    """The variadic reduce's computation (module-level: its name is in
    the jaxpr's text, which kernelcheck compares between traces)."""
    return tuple(x + y for x, y in zip(a, b))


class _BlockSums:
    """The integer sums and counts of one small-G dense aggregate as ONE
    two-level reduction.  First level: every limb of every distinct
    (argument, validity), masked to each group, in one variadic
    `jax.lax.reduce` over blocks of at most AGG_BLOCK rows with int32
    accumulators, so the columns are read once.  Second level: the block
    partials as one stacked array, widened to int64, summed, the limbs
    recombined by their weights, one psum.  Under a mesh a sum that can
    pass int64 (`wide_sums`) is not recombined: its limb sums ride the
    same psum as they are and its slot reads [limbs, G].

    An operand of a variadic reduce is merged with no other by anyone,
    so equal requests share a slot here: `sum(x)` and `avg(x)`, and every
    count over rows that cannot be NULL."""

    def __init__(self, ctx: RegionContext, gidx):
        self.ctx, self.gidx = ctx, gidx
        self.wire = _agg_wire(ctx.an, ctx.cols.get("__wire__", {}))
        self.valids = {frozenset(): None}   # validity key -> array
        self.counts = {}                    # validity key -> slot
        self.sums = {}                      # (argument, scale) -> slot
        self.slots = []                     # (limbs, const, mul, vkey)
        self.wide = set()                   # slots that stay limb sums
        self.out = None
        self.count(frozenset())

    def _vkey(self, vcols):
        """Validity of a bounded value: the AND of its NULLable columns'."""
        if vcols not in self.valids:
            v = None
            for ci in sorted(vcols):
                cv = self.ctx.cols[ci][1]
                v = cv if v is None else v & cv
            self.valids[vcols] = v
        return vcols

    def valid_of(self, expr, v):
        """Key of the validity `v` that compile_expr gave `expr`: a plain
        column that cannot be NULL shares the row count."""
        from ..expr.expression import ColumnExpr
        from .ir import serialize_expr

        if isinstance(expr, ColumnExpr) and expr.index in self.wire:
            return self._vkey(frozenset([expr.index])
                              if self.wire[expr.index][3] else frozenset())
        key = str(serialize_expr(expr))
        self.valids.setdefault(key, v)
        return key

    def count(self, vkey) -> int:
        if vkey not in self.counts:
            self.counts[vkey] = len(self.slots)
            self.slots.append(([(1, 0)], 0, 1, vkey))
        return self.counts[vkey]

    def sum(self, expr, state_ft):
        """Slots of (sum of `expr` in the state's scale, its count)."""
        from .ir import serialize_expr
        from .jax_eval import bounded_int, lane_limbs

        mul = _state_rescale(expr, state_ft)
        key = (str(serialize_expr(expr)), mul)
        if key not in self.sums:
            v = bounded_int(expr, self.wire)
            if v is not None:
                vkey = self._vkey(v.vcols)
                limbs, const = lane_limbs(v, AGG_LIMB), v.const
            else:
                d, valid = compile_expr(expr, self.ctx.cols, self.ctx.n)
                d = d.astype(jnp.int64)
                vkey = self.valid_of(expr, valid)
                limbs = [(((d >> s) & 0xFFFF if s < 48 else d >> s)
                          .astype(jnp.int32), s) for s in I64_SHIFTS]
                const = 0
            cnt = self.count(vkey)   # may take the next slot itself
            self.sums[key] = (len(self.slots), cnt)
            if self.ctx.axis is not None \
                    and _passes_int64(self.ctx.an, expr, mul):
                self.wide.add(len(self.slots))
            self.slots.append((limbs, const, mul, vkey))
        return self.sums[key]

    def run(self):
        ctx, G = self.ctx, self.ctx.an.num_groups
        sel = {}
        for vkey, v in self.valids.items():
            mv = ctx.mask if v is None else ctx.mask & v
            sel[vkey] = [mv if G == 1 else mv & (self.gidx == g)
                         for g in range(G)]
        operands = []
        for limbs, _c, _m, vkey in self.slots:
            for x, _s in limbs:
                for g in range(G):
                    s = sel[vkey][g]
                    operands.append(_blocks(
                        s.astype(jnp.int32) if isinstance(x, int)
                        else jnp.where(s, x, 0)))
        parts = []
        for i in range(0, len(operands), AGG_REDUCE_OPERANDS):
            some = tuple(operands[i: i + AGG_REDUCE_OPERANDS])
            parts += jax.lax.reduce(some, (jnp.int32(0),) * len(some),
                                    _add_lanes, [some[0].ndim - 1])
        # second level, tiny: [limbs * G, blocks] -> [limbs, G], then every
        # slot at once as weights @ sums, the weights being each limb's
        # 2**shift, the slot's constant on its count's limb, and the
        # state's rescale (int64 wraps as the sums it replaces did)
        sums = jnp.stack([p.reshape(-1) for p in parts]) \
            .reshape(-1, G, parts[0].size).astype(jnp.int64).sum(axis=2)
        weights = [[0] * sums.shape[0] for _ in self.slots]
        first, at = [], 0
        for i, (row, (limbs, const, mul, vkey)) in enumerate(
                zip(weights, self.slots)):
            first.append(at)
            at += len(limbs)
            if i in self.wide:
                continue
            for k, (_x, s) in enumerate(limbs):
                row[first[i] + k] = mul << s
            row[first[self.counts[vkey]]] += mul * const
        weights = np.array(
            [[(w + (1 << 63)) % (1 << 64) - (1 << 63) for w in row]
             for row in weights], dtype=np.int64)
        weights = np.broadcast_to(weights[:, :, None], weights.shape + (G,))
        out = (weights * sums[None]).sum(axis=1)
        n = len(self.slots)
        if not self.wide:
            self.out = [o.reshape(G) for o in jnp.split(ctx.psum(out), n)]
            return
        kept = [sums[first[i]: first[i] + len(self.slots[i][0])]
                for i in sorted(self.wide)]
        out = ctx.psum(jnp.concatenate([out, *kept]))
        self.out = [o.reshape(G) for o in jnp.split(out[:n], n)]
        for i, limbs in zip(sorted(self.wide), kept):
            self.out[i] = out[n: n + len(limbs)]
            n += len(limbs)

    def __getitem__(self, slot):
        return self.out[slot]


def _blocks(x):
    """Rows as [..., blocks, rows of a block]: the last axis is cut where
    it is longer than AGG_BLOCK (a 1-D tile, or a shard's rows that the
    engine did not view as blocks itself)."""
    import math

    last = x.shape[-1]
    if last <= AGG_BLOCK:
        return x if x.ndim > 1 else x.reshape(1, last)
    return x.reshape(x.shape[:-1] + (-1, math.gcd(last, AGG_BLOCK)))


def dense_agg_results(ctx: RegionContext, gidx):
    """Emit the dense segment reductions for every aggregate in the
    region.  Under a mesh (`ctx.axis`) sum/count partials merge across
    shards ON DEVICE via psum; min/max stay per-shard partials (only Sum
    all-reduces are used; pmin/pmax not re-tested on the attached chip)
    and first_row emits global
    row indices.  Per tile (`axis=None`) psum is the identity and
    first_row emits tile-local argfirst indices — the exact layouts each
    engine's host merge consumes.

    With at most `ops.UNROLL_G` groups every count and every sum of an
    integer or decimal state is a slot of ONE `_BlockSums` reduction;
    past that they are `jax.ops.segment_sum` scatters, one each.  Float
    sums, min, max and first_row are reductions of their own either way.
    """
    from ..types import TypeKind
    from .jax_engine import _to_state_dtype

    an = ctx.an
    agg_ir = an.agg
    G = an.num_groups
    m = ctx.mask
    block = _BlockSums(ctx, gidx) if G <= ops.UNROLL_G else None

    def count_of(expr, v):
        """A thunk of the count of rows where `expr` is not NULL."""
        if block is not None:
            slot = block.count(block.valid_of(expr, v))
            return lambda: block[slot]
        c = ctx.psum(ops.masked_segment_count(gidx, m & v, G))
        return lambda: c

    if block is None:
        gcount = ctx.psum(ops.masked_segment_count(gidx, m, G))
    thunks = []
    for a in agg_ir.aggs:
        if a.name == "count" and not a.args:
            thunks.append(None)
            continue
        st = a.partial_types()[0]
        if block is not None and _int_state(a):
            si, ci = block.sum(a.args[0], st)
            thunks.append(lambda si=si, ci=ci: (block[si], block[ci]))
            continue
        d, v = compile_expr(a.args[0], ctx.cols, ctx.n)
        mv = m & v
        if a.name != "first_row":
            cnt = count_of(a.args[0], v)
        if a.name == "count":
            thunks.append(cnt)
        elif a.name in ("sum", "avg"):
            dd = _to_state_dtype(d, a.args[0].ftype, st)
            if st.kind == TypeKind.FLOAT:
                # a float sum keeps its order of additions: row order
                dd, gi, mv = (ctx.in_row_order(x) for x in (dd, gidx, mv))
            else:
                gi = gidx
            s = ctx.psum(ops.masked_segment_sum(dd, gi, mv, G))
            thunks.append(lambda s=s, cnt=cnt: (s, cnt()))
        elif a.name in ("min", "max"):
            red = (ops.masked_segment_min if a.name == "min"
                   else ops.masked_segment_max)
            part = red(d, gidx, mv, G)
            thunks.append(lambda part=part, cnt=cnt: (part, cnt()))
        elif a.name == "first_row":
            if ctx.gofs is not None:
                # per-shard first GLOBAL row index (sentinel n_global when
                # the shard has none); host takes the min across shards
                contrib = jnp.where(mv, ctx.gofs, ctx.n_global)
                r = ops.segment_min(contrib, gidx, G)
            else:
                r = ops.masked_segment_argfirst(gidx, mv, G)
            thunks.append(lambda r=r: r)
    if block is not None:
        block.run()
        gcount = block[0]
    return gcount, [gcount if t is None else t() for t in thunks]


def topn_key(ctx: RegionContext):
    """Emit the TopN sort key with MySQL NULL ordering: first ascending,
    last descending.  The sentinel stays distinguishable from masked-out
    rows (masked_top_k uses -inf for those), so NULLs get a finite
    extreme: -MAX asc (sorts first), -MAX desc (sorts last but still
    beats masked rows).

    Multi-column orderings with a packed compound spec (`an.topn_pack`,
    built by _Analyzed from column stats) emit ONE lexicographically
    exact integer key instead: per-key ranks (NULL slot included,
    desc keys rank-flipped) compose by stride multiplication, so the
    device's single top_k IS the exact compound ordering — the
    "stable key-composition over packed integer and dict-code columns"
    emitter of ISSUE 11.  Callers sort the packed key ASCENDING."""
    pack = getattr(ctx.an, "topn_pack", None)
    if pack is not None:
        return compound_topn_key(ctx)
    key_expr, _desc = ctx.an.topn.order_by[0]
    d, v = compile_expr(key_expr, ctx.cols, ctx.n)
    key = d.astype(jnp.float64)
    return jnp.where(v, key, -1.7e308)


def compound_topn_key(ctx: RegionContext):
    """The packed lexicographic key over `an.topn_pack` specs: per key
    (col_idx, lo, hi, slots, desc, has_null), rank ascending-first-wins,
    strides most-significant-first; the product of slots is capped at
    2**52 by the analyzer so the f64 top_k stays exact."""
    key = jnp.zeros(ctx.n, dtype=jnp.int64)
    for col_idx, lo, hi, slots, desc, has_null in ctx.an.topn_pack:
        d, v = ctx.cols[col_idx]
        d = d.astype(jnp.int64)
        if desc:
            # largest value first; NULLs last (MySQL desc ordering)
            rank = jnp.clip(hi - d, 0, slots - 1)
            if has_null:
                rank = jnp.where(v, rank, slots - 1)
        else:
            # NULLs first ascending: slot 0 reserved when nullable
            if has_null:
                rank = jnp.where(v, jnp.clip(d - lo, 0, slots - 2) + 1, 0)
            else:
                rank = jnp.clip(d - lo, 0, slots - 1)
        key = key * slots + rank
    return key.astype(jnp.float64)


def projection_outputs(ctx: RegionContext):
    """Emit the fused projection expressions (device-evaluated outputs)."""
    return [compile_expr(p, ctx.cols, ctx.n) for p in ctx.an.proj_exprs]


def topn_desc(an) -> bool:
    """The descending flag the device top_k runs with: packed compound
    keys already fold per-key direction into the rank, so they always
    sort ASCENDING; single keys keep their own flag."""
    if getattr(an, "topn_pack", None) is not None:
        return False
    return an.topn.order_by[0][1]


# ---------------------------------------------------------------------------
# computed string group keys: device-side dictionary-code re-mapping
# ---------------------------------------------------------------------------

#: the one home of the dictionary-computable function set is the
#: (jax-free) pushdown module — the planner gate and the engine's remap
#: builder must agree exactly on it
from ..expr.pushdown import DICT_COMPUTABLE_FUNCS  # noqa: E402


class KeyRemap:
    """One computed group key lowered to a code-space gather.

    `mapping` (pow2-padded to `cap`) rides as a RUNTIME operand of the
    fused program: row code -> computed-key output.  STRING keys map
    code -> output-dictionary code (int32) and `out_dict` (sorted so
    code order == string order) decodes the compacted group keys
    host-side after readback; INT-valued computed keys (LENGTH/ASCII —
    ISSUE 12 satellite (a)) map code -> the computed VALUE directly
    (int64, `out_dict` None)."""

    __slots__ = ("src_idx", "mapping", "cap", "out_dict")

    def __init__(self, src_idx: int, mapping: np.ndarray, cap: int,
                 out_dict: List[str]):
        self.src_idx = src_idx
        self.mapping = mapping
        self.cap = cap
        self.out_dict = out_dict


def _single_dict_column(expr, scan, table, cols=None):
    """The ONE dict-encoded string column a remappable expression reads,
    or None.  The structural walk is the SHARED
    `pushdown.dict_computable_columns` /
    `pushdown._computed_dict_tree_columns` (one source of truth with the
    planner gate and plancheck); this adds the engine-side identity
    check: a single scan index whose store column is dict-encoded."""
    from ..expr.pushdown import (_computed_dict_tree_columns,
                                 dict_computable_columns)

    if cols is None:
        cols = dict_computable_columns(expr)
        if cols is None:
            cols = _computed_dict_tree_columns(expr)
    if cols is None:
        return None
    idxs = {c.index for c in cols}
    if len(idxs) != 1:
        return None
    idx = next(iter(idxs))
    if not (0 <= idx < len(scan.columns)):
        return None  # join payload column: no store dictionary
    store_ci = scan.columns[idx]
    if store_ci not in table.dict_encoded_cols():
        return None
    return idx


import threading as _threading_mod

_REMAP_MU = _threading_mod.Lock()
#: (store_uid, base_version, expr json) -> KeyRemap; the host pays the
#: per-dictionary evaluation ONCE per base version, not once per query.
#: Bounded: superseded base versions purge per store, and the whole map
#: caps at _REMAP_CACHE_MAX entries (FIFO) so long-lived servers with
#: heavy table churn never grow it without bound.
_REMAP_CACHE: dict = {}
_REMAP_CACHE_MAX = 256


def build_key_remap(table, scan, expr) -> KeyRemap:
    """Lower a computed STRING group key over a dict-encoded column to a
    code-space re-mapping: evaluate the expression once per DICTIONARY
    entry on the host (|dict| rows, not |table| rows), sort-unique the
    outputs into a new dictionary, and hand the code->code mapping to the
    device as a runtime gather operand.  Raises JaxUnsupported with a
    'computed group key' message (the computed-key split reason) when the
    expression is not remappable."""
    import json as _json

    from .ir import serialize_expr

    ck = (table.store_uid, table.base_version,
          _json.dumps(serialize_expr(expr), sort_keys=True))
    with _REMAP_MU:
        hit = _REMAP_CACHE.get(ck)
        if hit is not None:
            return hit
        # drop remaps of superseded base versions for this store
        for k in [k for k in _REMAP_CACHE
                  if k[0] == ck[0] and k[1] != ck[1]]:
            del _REMAP_CACHE[k]
    rm = _build_key_remap_uncached(table, scan, expr)
    with _REMAP_MU:
        while len(_REMAP_CACHE) >= _REMAP_CACHE_MAX:
            _REMAP_CACHE.pop(next(iter(_REMAP_CACHE)))  # FIFO victim
        _REMAP_CACHE[ck] = rm
    return rm


def _eval_over_dictionary(table, scan, expr, idx):
    """Evaluate `expr` once per DICTIONARY entry of scan column `idx`
    (the shared recipe of the key-remap and predicate-code lowerings):
    a chunk wide enough for the source index, every other slot a zero
    placeholder — only the source column is ever read (checked by
    _single_dict_column)."""
    from ..chunk import Chunk, Column
    from ..types import ty_string

    store_ci = scan.columns[idx]
    dictionary = table.cols[store_ci].dictionary or []
    if not dictionary:
        raise JaxUnsupported("computed dict expression over empty "
                             "dictionary")
    nd = len(dictionary)
    vals = np.empty(nd, dtype=object)
    vals[:] = [str(s) for s in dictionary]
    cols = []
    for j in range(idx + 1):
        if j == idx:
            cols.append(Column(ty_string(False), vals))
        else:
            cols.append(Column(scan.ftypes[j],
                               np.zeros(nd, dtype=np.int64)))
    return expr.eval(Chunk(cols)), nd


def _build_key_remap_uncached(table, scan, expr) -> KeyRemap:
    from ..types import TypeKind

    if expr.ftype.kind not in (TypeKind.STRING, TypeKind.INT,
                               TypeKind.UINT):
        raise JaxUnsupported(
            f"computed group key not dict-remappable: {expr}")
    idx = _single_dict_column(expr, scan, table)
    if idx is None:
        raise JaxUnsupported(
            f"computed string group key not dict-remappable: {expr}")
    out, nd = _eval_over_dictionary(table, scan, expr, idx)
    if not np.all(out.validity()):
        raise JaxUnsupported(
            f"computed group key maps entries to NULL: {expr}")
    cap = 2
    while cap < nd:
        cap <<= 1
    if expr.ftype.kind != TypeKind.STRING:
        # INT-valued computed key (LENGTH/ASCII, ISSUE 12 satellite (a)):
        # the mapping carries the computed VALUE per code — no output
        # dictionary, the key bits ARE the values
        mapping = np.zeros(cap, dtype=np.int64)
        mapping[:nd] = [int(x) for x in out.data]
        return KeyRemap(idx, mapping, cap, None)
    outs = [str(x) for x in out.data]
    out_dict = sorted(set(outs))
    rank = {s: i for i, s in enumerate(out_dict)}
    mapping = np.zeros(cap, dtype=np.int32)
    mapping[:nd] = [rank[s] for s in outs]
    return KeyRemap(idx, mapping, cap, out_dict)


def dict_pred_codes(table, scan, expr):
    """Lower a computed predicate over ONE dict-encoded column to its
    matching CODE SET (ISSUE 12: LIKE / SUBSTR / LENGTH predicates on
    the device probe path): evaluate the whole predicate once per
    dictionary entry on the host (NULL -> no match, SQL filter
    semantics) and return (src_idx, sorted matching codes ndarray,
    dictionary size).  Raises JaxUnsupported when not loweable.
    Cached per (store, base_version, expr) alongside the key remaps."""
    import json as _json

    from ..expr.pushdown import dict_pred_source
    from .ir import serialize_expr

    cols = dict_pred_source(expr)
    idx = (_single_dict_column(expr, scan, table, cols=cols)
           if cols is not None else None)
    if idx is None:
        raise JaxUnsupported(
            f"predicate not dict-code-loweable: {expr}")
    ck = (table.store_uid, table.base_version,
          "pred:" + _json.dumps(serialize_expr(expr), sort_keys=True))
    with _REMAP_MU:
        hit = _REMAP_CACHE.get(ck)
    if hit is not None:
        return hit
    out, nd = _eval_over_dictionary(table, scan, expr, idx)
    truth = np.zeros(nd, dtype=np.bool_)
    valid = out.validity()
    for i, v in enumerate(out.data):
        if valid[i] and v:
            truth[i] = True  # NULL predicate results drop the row
    codes = np.flatnonzero(truth).astype(np.int64)
    res = (idx, codes, nd)
    with _REMAP_MU:
        while len(_REMAP_CACHE) >= _REMAP_CACHE_MAX:
            _REMAP_CACHE.pop(next(iter(_REMAP_CACHE)))
        _REMAP_CACHE[ck] = res
    return res


def remap_codes(ctx_or_codes, mapping, n: int):
    """Code-space gather emitter: dictionary codes -> computed-key codes
    through a runtime mapping operand.  Dispatches to the Pallas tier
    (copr/pallas) when enabled; the jnp take is the TIDB_TPU_PALLAS=0
    comparator — parity is test-asserted both ways."""
    from . import pallas as pk

    return pk.remap_codes(ctx_or_codes, mapping, n)


def decode_packed(packed, dict_arg, bits: int, n: int,
                  kind: str = "unique"):
    """Decode emitter for COLD-TIER columns (tidb_tpu/layout): bit-packed
    dictionary codes -> the column's value vector, in-register inside the
    same fused program as every other phase — a cold column costs a few
    extra VPU ops, never a second dispatch or a host transfer.

    `packed` is the shard-local packed byte vector (n // (8//bits)
    bytes).  The unpack is GATHER-FREE: bytes broadcast against the
    per-slot shift vector and reshape back to rows, so it lowers to pure
    elementwise VPU work.  `dict_arg` is a RUNTIME operand (layout
    VALUES never enter the fingerprint, kernelcheck-guarded): for
    'range' dictionaries it is the scalar bias (decode = code + lo, no
    dictionary at all); for 'unique' (float) dictionaries it is the
    value vector indexed by code.  Code arithmetic stays int32: no
    int64 emulation chain enters the kernel census."""
    from . import pallas as pk

    vpb = 8 // bits
    p = packed.reshape(-1)
    if vpb == 1:
        code = p
    elif pk.pallas_enabled():
        # the Pallas tier's hand-written unpack kernel (copr/pallas):
        # one strided shift/mask store per slot, uint8 end to end
        code = pk.unpack_codes(p, bits, n)
    else:
        # stay in uint8 through the unpack: measured ~1.7x cheaper than
        # int32 shift chains on the CPU harness (narrower VPU lanes)
        shifts = jnp.arange(vpb, dtype=jnp.uint8) * jnp.uint8(bits)
        code = ((p[:, None] >> shifts[None, :])
                & jnp.uint8((1 << bits) - 1)).reshape(n)
    if kind == "range":
        return code.astype(dict_arg.dtype) + dict_arg
    return dict_arg[code.astype(jnp.int32)]


# ---------------------------------------------------------------------------
# grouped sort-agg emitters: shared by the mesh sort-agg program
# (parallel._build_sort_agg_core) and the MPP grouped partial-agg phase
# (mpp/engine.py) — the "partial partial aggregates" machinery
# ---------------------------------------------------------------------------


def sort_group_segments(key_bits, key_flags, mask, cap):
    """Sort-based grouping into a static `cap`-slot budget.

    lexsorts rows by (key bits..., null flags..., selected-last), marks
    group boundaries, and clips segment ids to [0, cap).

    Returns (order, sm, out_keys, seg, n_uniq): the sort permutation,
    sorted selection mask, every key array at the first row of each
    group ([cap] each; slots past the groups hold the last row's), per-row
    segment ids, and the TRUE distinct-group count — n_uniq > cap means
    the budget blew and slots past cap-1 hold merged garbage; the caller
    must treat the result as overflowed.
    """
    n = mask.shape[0]
    keys = key_bits + key_flags
    # lexsort: LAST key is primary -> selected rows first, grouped by key
    order = jnp.lexsort(tuple(keys + [(~mask).astype(jnp.int64)]))
    sm = mask[order]
    skeys = [k[order] for k in keys]
    diff = jnp.arange(n, dtype=jnp.int64) == 0
    for k in skeys:
        diff = diff | (k != jnp.roll(k, 1))
    boundary = sm & diff
    n_uniq = boundary.sum().astype(jnp.int64)
    counts = ops.prefix_counts(boundary)
    seg = jnp.clip(counts - 1, 0, cap - 1)
    pos = ops.first_marked(boundary, cap, n - 1, counts)
    return order, sm, [k[pos] for k in skeys], seg, n_uniq


def lookup_group_sums(bkeys, keys, live, values):
    """Counts and exact sums of the probe rows by the build row they
    join to, for a lookup join whose build keys are unique and sorted
    (`bkeys`, padded with the type's maximum): the aggregate of a shape
    in which every group key is fixed by the build row, so a group IS a
    build row.  A merge, because this chip sorts a row in a few
    nanoseconds and gathers or scatters one in thirty: build and probe
    keys ride ONE stable sort with the values as payload, so each build
    row lands in front of the probe rows of its key; a probe row joins
    where the nearest build key before it is its own; running sums read
    at the build rows' places (a second, two-operand sort finds those)
    and differenced give each build row's totals.  Nothing of the probe
    side's length is gathered or scattered.

    `values` is one (data or None for count(*), valid) pair an
    aggregate; sums must be integers (a difference of running sums is
    exact modulo 2^64, and would cancel catastrophically in floats).
    Returns (matched probe rows a build row, [(sum or None, count) an
    aggregate]), each of `bkeys`' length.
    """
    K, n = bkeys.shape[0], keys.shape[0]
    big = jnp.iinfo(bkeys.dtype).max
    # tag: 0 a build row; a probe row has bit 0 set, and bit a + 1 where
    # aggregate a's argument is not NULL
    tag = jnp.ones(n, dtype=jnp.int32)
    for a, (_d, v) in enumerate(values):
        tag = tag | (v.astype(jnp.int32) << (a + 1))
    operands = [jnp.concatenate([bkeys, jnp.where(live, keys, big)]),
                jnp.concatenate([jnp.zeros(K, jnp.int32), tag])]
    summed = [a for a, (d, _v) in enumerate(values) if d is not None]
    for a in summed:
        d = values[a][0]
        operands.append(jnp.concatenate([jnp.zeros(K, d.dtype), d]))
    skey, stag, *svals = jax.lax.sort(operands, num_keys=1)
    is_build = stag == 0
    nearest = ops.prefix_max(jnp.where(is_build, skey,
                                       jnp.iinfo(skey.dtype).min))
    match = ~is_build & (skey == nearest) & (skey != big)
    _, places = jax.lax.sort(
        ((~is_build).astype(jnp.int32),
         jnp.arange(K + n, dtype=jnp.int32)), num_keys=1)
    places = places[:K]

    def by_build_row(contrib):
        run = ops.prefix_sums(contrib)
        at = run[places]
        return jnp.concatenate([at[1:], run[-1:]]) - at

    rows = by_build_row(match.astype(jnp.int32))
    out = []
    for a, (d, _v) in enumerate(values):
        ok = match & ((stag >> (a + 1)) & 1 == 1)
        total = None
        if d is not None:
            sv = svals[summed.index(a)]
            total = by_build_row(jnp.where(ok, sv, jnp.zeros((), sv.dtype)))
        out.append((total, by_build_row(ok.astype(jnp.int32))))
    return rows, out


def _equal_keys(keys, probe):
    """[K, rows...]: build key k equals the row's key.  The keys lie on
    the major axis and the rows keep their own layout on the minor ones,
    so a reduction over axis 0 is one fused loop that reads each row's
    key once and never writes this operand."""
    return keys.reshape(keys.shape + (1,) * probe.ndim) == probe[None]


def compare_lookup(keys, codes, probe, valid):
    """A lookup into a small build side by comparing each probe row's key
    with every build key, in place of a gather or a binary search.  On a
    TPU v5e a gather from more than 32 entries reads a 4 KB tile a row
    whatever the table's size (34.5 GB at 2^23 rows by the compiler's
    count), and a binary search is one such gather a level; this is one
    fused reduction over the keys, a few lane operations a key and row
    (`parallel.COMPARE_LOOKUP_MAX_KEYS` says where it stops paying).

    `keys` [K]: the side's keys, a pad repeating a real key and its code
    (so no sentinel can equal a probe value); `codes` [K] non-negative
    integers or None; `probe` the rows' keys in the keys' dtype, `valid`
    their validity (a NULL key never matches).  Returns (hit, code): the
    code of the build row whose key the row's equals, -1 on a miss, or
    None where `codes` is None."""
    eq = _equal_keys(keys, probe)
    if codes is None:
        # the validity inside the reduction: outside it, the compiler
        # reads the probe keys a second time
        return jnp.any(eq & valid[None], axis=0), None
    fill = jnp.asarray(-1, codes.dtype)
    code = jnp.max(jnp.where(eq, codes.reshape(eq.shape[:1]
                                               + (1,) * probe.ndim), fill),
                   axis=0)
    return valid & (code >= 0), code


def compare_take(keys, values, probe):
    """A payload column of a compared lookup (`compare_lookup`): each row's
    value of the build row whose key equals its key, by the same fused
    reduction (a max, the dtype's least value where no key equals; an
    `any` for a boolean column).  The caller's hit masks a miss."""
    eq = _equal_keys(keys, probe)
    vals = values.reshape(eq.shape[:1] + (1,) * probe.ndim)
    if values.dtype == jnp.bool_:
        return jnp.any(eq & vals, axis=0)
    least = (-jnp.inf if jnp.issubdtype(values.dtype, jnp.floating)
             else jnp.iinfo(values.dtype).min)
    return jnp.max(jnp.where(eq, vals, jnp.asarray(least, values.dtype)),
                   axis=0)


#: a star's group count up to which `star_group_sums` compares each row's
#: group id with every group, and the count past 2^26 rows a shard; past
#: it, one sort of the ids.  On a v5e the compare takes about 0.6 ns a row
#: and 5.5 ps a row and group (at 2^27.2 rows 0.101 s for 16 groups, 0.280
#: for 256 and 0.471 for 512; at 2^23 rows 13.8 ms for 256 and 24.4 for
#: 512) and compiles in a second; the sort about 4.6 ns a row at 2^23 rows
#: and 8.3 at 2^27.2 (38.5 ms and 1.26 s) and compiles in a minute.  A
#: scatter-add takes 90 ns a row.
STAR_COMPARE_GROUPS = 512
STAR_COMPARE_GROUPS_LARGE = 1024
STAR_COMPARE_LARGE_ROWS = 1 << 26
#: rows one step of the compare takes (a whole number of them a shard)
STAR_COMPARE_BLOCK = 1 << 20


def star_compares(G: int, n: int) -> bool:
    """Whether `star_group_sums` over `n` rows a shard and `G` groups
    compares (else it sorts): where the compare is the faster."""
    return G <= (STAR_COMPARE_GROUPS_LARGE if n >= STAR_COMPARE_LARGE_ROWS
                 else STAR_COMPARE_GROUPS)


def star_group_sums(gid, live, values, G: int, nulls: bool):
    """Counts and exact sums of the live rows by group, for group ids in
    [0, G) (a star's: each row's codes from its dimensions, combined
    mixed-radix).  Where `star_compares`, a compare of every row's id with
    every group (`_compared_group_sums`); else one sort of
    the ids with the summed values riding it, then running sums read at
    the groups' boundaries, which a binary search of the G + 1 ids in the
    sorted ids finds: nothing of the rows' length is gathered or
    scattered.

    `values` is one (data or None for count(*), valid) pair an
    aggregate; sums must be integers (a difference of running sums is
    exact modulo 2^64).  With `nulls` False no argument can be NULL and
    every count is the group's rows; else the arguments' validity rides
    the sort as bits of one int32.  Returns (rows a group, [(sum or
    None, count) an aggregate]), each [G]."""
    if star_compares(G, gid.shape[0]):
        return _compared_group_sums(gid, live, values, G, nulls)
    key = jnp.where(live, gid, G).astype(jnp.int32)
    summed = [a for a, (d, _v) in enumerate(values) if d is not None]
    operands = [key] + [jnp.where(live & values[a][1], values[a][0],
                                  jnp.zeros((), values[a][0].dtype))
                        for a in summed]
    if nulls:
        tag = jnp.zeros(key.shape, dtype=jnp.int32)
        for a, (_d, v) in enumerate(values):
            tag = tag | ((live & v).astype(jnp.int32) << a)
        operands.append(tag)
    skey, *sorted_ = jax.lax.sort(operands, num_keys=1)
    pos = jnp.searchsorted(skey, jnp.arange(G + 1, dtype=jnp.int32))

    def by_group(x):
        run = jnp.concatenate([jnp.zeros(1, x.dtype), jnp.cumsum(x)])
        return run[pos[1:]] - run[pos[:-1]]

    rows = (pos[1:] - pos[:-1]).astype(jnp.int64)
    out = []
    for a in range(len(values)):
        cnt = by_group((sorted_[-1] >> a) & 1).astype(jnp.int64) \
            if nulls else rows
        total = (by_group(sorted_[summed.index(a)]) if a in summed
                 else None)
        out.append((total, cnt))
    return rows, out


def _compared_group_sums(gid, live, values, G: int, nulls: bool):
    """`star_group_sums` by comparing: the rows in blocks of
    STAR_COMPARE_BLOCK, a loop over the blocks, and in each one fused
    reduction over the block's rows for all G groups at once: a group's
    rows, each summed integer's low 32 bits (unsigned, summed in 64) and
    high 32 bits, and with `nulls` each argument's valid rows.  A block's
    sums add up exactly modulo 2^64 and come in the argument's dtype, as
    the sort path's running sums do."""
    n = gid.shape[0]
    B = int(np.gcd(n, STAR_COMPARE_BLOCK))

    def blocks(x):
        return x.reshape(n // B, B)

    key = blocks(jnp.where(live, gid, G).astype(jnp.int32))
    summed = [a for a, (d, _v) in enumerate(values) if d is not None]
    halves = []
    for a in summed:
        d, v = values[a]
        d = jnp.where(live & v, d.astype(jnp.int64), jnp.int64(0))
        halves += [blocks((d & 0xFFFFFFFF).astype(jnp.uint32)),
                   blocks((d >> 32).astype(jnp.int32))]
    valids = [blocks(live & v) for _d, v in values] if nulls else []
    groups = jnp.arange(G, dtype=jnp.int32)[:, None]

    def step(acc, xs):
        k, hs, vs = xs
        eq = k[None, :] == groups
        rows = jnp.sum(eq, axis=1, dtype=jnp.int32).astype(jnp.int64)
        sums = []
        for lo, hi in zip(hs[0::2], hs[1::2]):
            s_lo = jnp.sum(jnp.where(eq, lo[None, :], jnp.uint32(0)),
                           axis=1, dtype=jnp.uint64)
            s_hi = jnp.sum(jnp.where(eq, hi[None, :], jnp.int32(0)),
                           axis=1, dtype=jnp.int64)
            sums.append(s_lo.astype(jnp.int64) + (s_hi << 32))
        cnts = [jnp.sum(eq & v[None, :], axis=1,
                        dtype=jnp.int32).astype(jnp.int64) for v in vs]
        return tuple(x + y for x, y in zip(acc, [rows] + sums + cnts)), None

    zero = jnp.zeros(G, dtype=jnp.int64)
    acc, _ = jax.lax.scan(step, (zero,) * (1 + len(summed) + len(valids)),
                          (key, tuple(halves), tuple(valids)))
    rows, sums, cnts = acc[0], acc[1:1 + len(summed)], acc[1 + len(summed):]
    return rows, [(sums[summed.index(a)].astype(values[a][0].dtype)
                    if a in summed else None, cnts[a] if nulls else rows)
                  for a in range(len(values))]


def grouped_partial_states(aggs, arg_fn, order, sm, seg, cap,
                           sgofs=None, n_global=0):
    """Segment-reduce per-group partial states for every aggregate over
    sort-grouped rows (the layouts `_agg_tags` names: count -> [cap],
    sum/avg/min/max -> ([cap], [cap]) value+count, first_row -> [cap]
    global row indices when `sgofs` is given).

    `arg_fn(expr)` evaluates an aggregate argument in the UNSORTED row
    layout; this emitter applies the sort permutation.
    """
    from .jax_engine import _to_state_dtype

    results = []
    for a in aggs:
        if a.name == "count":
            if a.args:
                d, v = arg_fn(a.args[0])
                results.append(
                    ops.masked_segment_count(seg, sm & v[order], cap))
            else:
                results.append(ops.masked_segment_count(seg, sm, cap))
            continue
        d, v = arg_fn(a.args[0])
        d, mv = d[order], sm & v[order]
        if a.name in ("sum", "avg"):
            st = a.partial_types()[0]
            dd = _to_state_dtype(d, a.args[0].ftype, st)
            results.append((
                ops.masked_segment_sum(dd, seg, mv, cap),
                ops.masked_segment_count(seg, mv, cap),
            ))
        elif a.name == "min":
            results.append((
                ops.masked_segment_min(d, seg, mv, cap),
                ops.masked_segment_count(seg, mv, cap),
            ))
        elif a.name == "max":
            results.append((
                ops.masked_segment_max(d, seg, mv, cap),
                ops.masked_segment_count(seg, mv, cap),
            ))
        elif a.name == "first_row":
            contrib = jnp.where(mv, sgofs, jnp.int64(n_global))
            results.append(
                jax.ops.segment_min(contrib, seg, num_segments=cap)
            )
    return results


def merge_grouped_partials(aggs, key_bits, key_flags, row_valid, states,
                           cap):
    """Merge compacted (key, partial-state) rows — e.g. the all_gathered
    per-shard groups of an MPP grouped aggregation — into <= cap merged
    groups: a second sort-group over the partial rows, then state-MERGE
    reductions (counts/sums add, min/min max/max, first_row keeps the
    global minimum row index).

    `states` uses grouped_partial_states' layout per agg.  Returns
    (n_uniq, out_keys, merged_states); n_uniq > cap means the merged
    group count blew the budget.
    """
    order, sm, out_keys, seg, n_uniq = sort_group_segments(
        key_bits, key_flags, row_valid, cap)
    merged = []
    for a, st in zip(aggs, states):
        if a.name == "count":
            merged.append(
                ops.masked_segment_sum(st[order], seg, sm, cap))
        elif a.name in ("sum", "avg"):
            s, c = st
            merged.append((
                ops.masked_segment_sum(s[order], seg, sm, cap),
                ops.masked_segment_sum(c[order], seg, sm, cap),
            ))
        elif a.name in ("min", "max"):
            v, c = st
            mv = sm & (c[order] > 0)  # empty partials carry sentinels
            red = (ops.masked_segment_min if a.name == "min"
                   else ops.masked_segment_max)
            merged.append((
                red(v[order], seg, mv, cap),
                ops.masked_segment_sum(c[order], seg, sm, cap),
            ))
        else:  # first_row: the smallest global row index wins
            merged.append(
                ops.masked_segment_min(st[order], seg, sm, cap))
    out_keys = tuple(out_keys)
    return n_uniq, out_keys, merged


# ---------------------------------------------------------------------------
# fusion regions: split a fragment at unfusable boundaries
# ---------------------------------------------------------------------------


@dataclass
class FusionPlan:
    """One fragment's fused region plus its host tail."""

    dag: DAG                      # scan + the fused executor prefix
    an: object                    # its _Analyzed
    tail: List = field(default_factory=list)  # host-run executor suffix
    split_reason: Optional[str] = None        # why the region was cut
    reason_label: Optional[str] = None        # SPLIT_REASONS inventory


def plan_regions(dag: DAG, table, max_cut: Optional[int] = None
                 ) -> FusionPlan:
    """Longest device-compilable executor prefix → fused region; the
    suffix becomes the host tail (the per-phase fallback ladder).
    Raises JaxUnsupported (with the first rejection's reason) when not
    even the bare scan analyzes — the CPU interpreter owns those
    fragments outright.

    HYBRID device-partial/host-final regions (ISSUE 11): a region whose
    head ends in a device PROJECTION may still carry a host tail — the
    tail's executor indices address the projection's OUTPUT layout,
    which the region hands across the boundary (run_tail interprets over
    the head's output chunks, whatever their layout).  Partial-agg and
    topN heads still refuse tails: a Limit over whole-table partials
    would drop groups, so those peel to the deepest safe boundary and
    the split is labelled 'head-shape'."""
    from .jax_engine import _Analyzed

    execs = dag.executors
    hi = len(execs) if max_cut is None else min(max_cut, len(execs))
    reason: Optional[str] = None
    guard_cut: Optional[int] = None
    for cut in range(hi, 0, -1):
        head, tail = execs[:cut], list(execs[cut:])
        try:
            if cut > 1:
                # chaos: an armed action raises JaxUnsupported to force
                # the split one boundary earlier
                FAILPOINTS.hit(SPLIT_FAILPOINT, cut=cut,
                               boundary=type(head[-1]).__name__)
            sub = DAG(list(head))
            an = _Analyzed(sub, table)
        except JaxUnsupported as e:
            if reason is None:
                reason = str(e)
            continue
        if tail and (an.agg is not None or an.topn is not None):
            # partial agg / topn outputs must not feed tail executors (a
            # Limit over whole-table partials would drop groups) — keep
            # peeling; projection heads ARE hybrid-eligible (the tail
            # reads the projected layout)
            if guard_cut is None:
                guard_cut = cut
            continue
        label = None
        if tail:
            label = ("head-shape"
                     if guard_cut is not None and cut < guard_cut
                     else classify_split_reason(reason))
        return FusionPlan(sub, an, tail,
                          split_reason=reason if tail else None,
                          reason_label=label)
    raise JaxUnsupported(reason or "no device-eligible fused region")


def run_tail(dag: DAG, tail: List, chunks, aux=None):
    """Interpret a host tail over the fused region's output chunks (the
    CPU engine is the tail's executor).  Partial-agg tails stay partial —
    the root executor merges, exactly as for an all-host region."""
    from .cpu_engine import run_dag_on_chunk

    if not tail:
        return chunks
    tail_dag = DAG([dag.scan] + list(tail))
    out = []
    for c in chunks:
        r = run_dag_on_chunk(tail_dag, c, aux)
        if r.num_rows:
            out.append(r)
    return out


def run_fragment(table, dag: DAG, start: int, end: int, deleted,
                 aux=None):
    """Per-region fused execution with the fallback ladder: run the
    largest region the per-tile engine accepts, stepping the split point
    down one boundary per runtime JaxUnsupported; the host tail runs over
    the region's output.  Raises JaxUnsupported only when no region
    beyond the bare scan is device-eligible (the caller's CPU
    interpreter is then strictly cheaper than a device scan-only pass).
    """
    from .jax_engine import run_base_jax

    cut: Optional[int] = None
    while True:
        plan = plan_regions(dag, table, max_cut=cut)
        if plan.tail and len(plan.dag.executors) == 1:
            # a device scan-only region reduces nothing; the CPU
            # interpreter over host blocks is strictly cheaper
            raise JaxUnsupported(
                plan.split_reason or "no device-eligible fused region")
        try:
            chunks = run_base_jax(table, plan.dag, start, end, deleted,
                                  aux=aux, an=plan.an)
            break
        except JaxUnsupported:
            if len(plan.dag.executors) == 1:
                raise
            cut = len(plan.dag.executors) - 1
    if plan.tail:
        note_split(plan.reason_label, type(plan.tail[0]).__name__)
        chunks = run_tail(dag, plan.tail, chunks, aux)
    return chunks


# ---------------------------------------------------------------------------
# kernelcheck registration: abstract-trace fused mesh fragments
# ---------------------------------------------------------------------------


def trace_fused_fragment(table, dag, n_ranges: int = 1, cold: bool = False,
                         dict_shift: int = 0):
    """make_jaxpr for the whole-fragment MESH program over a 1-device
    mesh (deterministic regardless of how many virtual devices the
    harness exposes) — the fused-fragment corpus of lint.kernelcheck.
    Raises JaxUnsupported when the fragment has no fused mesh form.

    `cold=True` traces the cold-tier layout class: every packable scan
    column rides as bit-packed dictionary codes with its decode emitter
    fused in, the dictionary-value operands shifted by `dict_shift` —
    two shifts must trace to the IDENTICAL jaxpr (layout values are
    runtime slots, never compiled constants)."""
    import numpy as np
    from jax.sharding import Mesh

    from . import jax_engine as je
    from . import parallel as par

    dag = DAG.from_dict(dag.to_dict())
    an = je._Analyzed(dag, table)
    kind = "agg" if an.agg is not None else (
        "topn" if an.topn is not None else "filter")
    col_order = an.needed_cols()
    mesh = Mesh(np.array(jax.devices()[:1]), ("dp",))
    tile = je.TILE
    datas, valids, col_layout, lvals = [], [], [], []
    from .jax_eval import _np_dtype_for

    for ci in col_order:
        store_ci = an.scan.columns[ci]
        meta = table.cols[store_ci]
        info = None
        if cold:
            from ..layout.coldtier import dict_values, pack_info

            info = pack_info(table, store_ci)
        if info is not None:
            vpb = 8 // info.bits
            datas.append(np.zeros((1, tile // vpb), dtype=np.uint8))
            valids.append(np.ones((1, tile), dtype=np.bool_))
            col_layout.append((info.bits, info.cap, info.kind))
            dv = dict_values(table, store_ci, info)
            if info.kind == "range":
                lvals.append(dv.dtype.type(info.lo + dict_shift))
            else:
                lvals.append(dv + dv.dtype.type(dict_shift))
        else:
            # the engine's own dtype mapping (raises JaxUnsupported for
            # host-only columns), so the traced corpus can never
            # green-light a shape class the production engine rejects
            dt = np.dtype(_np_dtype_for(meta.ftype))
            datas.append(np.zeros((1, tile), dtype=dt))
            valids.append(np.ones((1, tile), dtype=np.bool_))
            col_layout.append(None)
    if cold and not any(col_layout):
        raise JaxUnsupported("no cold-packable column in fragment")
    # computed-key remap operands ride the lvals tail AFTER the cold
    # dictionary operands (same ordering contract as _run_mesh_once)
    for r in (getattr(an, "key_remaps", None) or ()):
        if r is not None:
            lvals.append(r.mapping)
    core = par._build_mesh_core(an, kind, col_order, mesh,
                                tiles_per_shard=1,
                                col_layout=col_layout if cold else None)
    del_mask = np.ones((1, tile), dtype=np.bool_)
    bounds = par._bounds_args(
        [(r * 8, r * 8 + 8) for r in range(n_ranges)])
    return jax.make_jaxpr(core)(
        tuple(datas), tuple(valids), del_mask, bounds, tuple(lvals))
