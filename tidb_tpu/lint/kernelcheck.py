"""Kernel-contract checker: abstract-trace every registered copr kernel.

DrJAX's observation (PAPERS.md) applies directly: shape/dtype/sharding
contracts of jitted programs are verifiable by abstract tracing, no TPU
required.  For every canonical device-DAG shape this repro registers
(dense agg / scalar agg / filter+projection / topn — the per-tile kernels
`jax_engine._build_tile_fn` compiles), this pass:

1. traces the kernel with `jax.make_jaxpr` on canonical TILE-shaped
   inputs (the exact dtypes `_gather_tile` feeds it) — any shape or
   dtype inconsistency fails the trace and fails the lint;
2. counts jaxpr equations whose outputs are int64 — growth vs the
   checked-in baseline means an int64-emulation chain crept back into a
   kernel (VERDICT.md names the int64-emulated VPU sum chain as the Q1
   bottleneck: TPUs have no native int64, XLA emulates it pairwise);
3. runs the canonical query corpus end-to-end twice through the real
   engines and fails on distinct-jit-signature growth between the runs —
   the recompile-bomb guard (a query re-run must never compile anything
   new), plus a cap on the corpus' total signature count vs baseline.

Everything runs under JAX_PLATFORMS=cpu and needs no chip.
"""

from __future__ import annotations

from typing import Dict, List, Optional, Tuple

import numpy as np

from . import Finding

#: queries whose cop DAGs define the registered kernel corpus; keep shapes
#: covering every `_build_tile_fn` kind plus the mesh lookup-join program.
CANONICAL_KERNEL_QUERIES = [
    ("q1-dense-agg",
     "select l_returnflag, l_linestatus, sum(l_quantity),"
     " sum(l_extendedprice * (1 - l_discount)), avg(l_discount), count(*)"
     " from lineitem where l_shipdate <= '1998-09-02'"
     " group by l_returnflag, l_linestatus"),
    ("q6-scalar-agg",
     "select sum(l_extendedprice * l_discount) from lineitem"
     " where l_discount between 0.05 and 0.07 and l_quantity < 24"),
    ("filter-project",
     "select l_orderkey, l_extendedprice * (1 - l_discount) from lineitem"
     " where l_quantity < 10"),
    ("topn",
     "select l_orderkey from lineitem order by l_extendedprice desc"
     " limit 5"),
    ("minmax-agg",
     "select l_returnflag, min(l_quantity), max(l_extendedprice)"
     " from lineitem group by l_returnflag"),
]

#: MPP exchange kernels (mpp/exchange.py): traced over a 1-device mesh so
#: the jaxpr stats are deterministic regardless of how many virtual
#: devices the harness exposes; covers the partition/all_to_all shuffle
#: and the all_gather broadcast rung of the partitioned join (both with
#: the two-pass count+emit expansion).
MPP_EXCHANGE_KERNELS = ("mpp-shuffle-join", "mpp-broadcast-join",
                        "mpp-directory-join")

#: the grouped-partial + on-device-merge kernel (mpp/exchange.py
#: trace_grouped_agg_kernel): per-shard sort-group, all_gather of
#: compacted (key, state) rows, second sort-merge, sliced emission.  The
#: group BUDGET rides a runtime scalar slot; the checker traces two
#: budget values and fails on any jaxpr divergence.
MPP_GROUPED_KERNEL = "mpp-grouped-agg-merge"

#: the 3-way join-tree rung-ladder kernel (ISSUE 12, mpp/jointree.py's
#: canonical shape in mpp/exchange.trace_tree_join_kernel): two
#: exchange/local-join rungs chained inside ONE traced program with the
#: intermediate staying in registers — jaxpr-identical across key
#: operand shifts, and EXECUTED against the row-at-a-time CPU oracle.
TREE_JOIN_KERNEL = "mpp-tree-3way-join"

#: the micro-batcher's vmapped padded-batch kernel (serving/batcher.py):
#: the q6-scalar-agg shape with predicate constants hoisted to parameter
#: slots, vmapped over a pow2-padded batch of parameter vectors.
VMAP_BATCH_KERNEL = "serving-vmapped-batch"
VMAP_BATCH_B = 4

#: whole-fragment fused MESH programs (copr/fusion.py emitters composed
#: by parallel._build_mesh_core, traced over a 1-device mesh): one entry
#: per fused shape class.  Each traces the ENTIRE fragment — scan masks
#: over the range slots, fused selection, dense/sort agg or topN — as
#: ONE program, guarding int64-emulation chains per shape class.
#: the cold-tier decode-emitter fused kernel (tidb_tpu/layout +
#: fusion.decode_packed): the q6 scalar-agg fragment with every packable
#: column riding as bit-packed dictionary codes.  The checker asserts
#: the dictionary VALUES are runtime operands — tracing under shifted
#: contents must yield the identical jaxpr (a builder that closed over
#: the values would bake them as constants and recompile per re-tune).
COLD_FRAGMENT_KERNEL = "fused-mesh-cold-agg"

FUSED_FRAGMENT_KERNELS = [
    ("fused-mesh-dense-agg",
     "select l_returnflag, l_linestatus, sum(l_quantity),"
     " sum(l_extendedprice * (1 - l_discount)), avg(l_discount), count(*)"
     " from lineitem where l_shipdate <= '1998-09-02'"
     " group by l_returnflag, l_linestatus"),
    ("fused-mesh-scalar-agg",
     "select sum(l_extendedprice * l_discount) from lineitem"
     " where l_discount between 0.05 and 0.07 and l_quantity < 24"),
    ("fused-mesh-sort-agg",
     "select l_discount, count(*), sum(l_quantity) from lineitem"
     " group by l_discount"),
    ("fused-mesh-filter",
     "select l_orderkey, l_quantity from lineitem where l_quantity < 10"),
    ("fused-mesh-topn",
     "select l_orderkey from lineitem order by l_extendedprice desc"
     " limit 5"),
    # ISSUE 11 zero-host-tail shapes: a computed STRING group key lowered
    # to a device dict-code re-map, and a packed-compound multi-column
    # TopN ordering — both must trace as ONE fused mesh program
    ("fused-mesh-computed-key-agg",
     "select substr(l_returnflag, 1, 1), count(*), sum(l_quantity)"
     " from lineitem group by substr(l_returnflag, 1, 1)"),
    ("fused-mesh-compound-topn",
     "select l_orderkey from lineitem"
     " order by l_returnflag desc, l_shipdate, l_orderkey limit 5"),
]

#: the Pallas kernel tier (copr/pallas): hand-written cores below the
#: fusion emitters.  Each traces on a canonical shape, guards the
#: operand-value rule (shifted mapping contents -> identical jaxpr), and
#: EXECUTES against the TIDB_TPU_PALLAS=0 jnp reference for parity.
PALLAS_KERNELS = ("pallas-remap-codes", "pallas-unpack-codes")


def _iter_eqns(jaxpr):
    """All equations including nested call/pjit sub-jaxprs.  shard_map
    stores its body as a raw Jaxpr (no .jaxpr attribute), so anything
    with .eqns descends too — the exchange kernels live in there."""
    for eqn in jaxpr.eqns:
        yield eqn
        for v in eqn.params.values():
            sub = getattr(v, "jaxpr", None)
            if sub is None and hasattr(v, "eqns"):
                sub = v
            if sub is not None:
                yield from _iter_eqns(sub)


def _jaxpr_stats(closed) -> Dict[str, int]:
    eqns = list(_iter_eqns(closed.jaxpr))
    i64 = 0
    for e in eqns:
        for ov in e.outvars:
            if getattr(getattr(ov, "aval", None), "dtype", None) is not None \
                    and str(ov.aval.dtype) == "int64":
                i64 += 1
                break
    return {"eqns": len(eqns), "i64_eqns": i64}


def _reader_dags(phys):
    """Every cop DAG reachable from a physical plan (readers may hide
    under DeviceJoinReader/DML wrappers)."""
    out = []
    seen = set()

    def walk(p):
        if id(p) in seen or p is None:
            return
        seen.add(id(p))
        dag = getattr(p, "dag", None)
        if dag is not None:
            out.append((p, dag))
        for c in getattr(p, "children", ()) or ():
            walk(c)
        for attr in ("reader", "build_plan", "select_phys"):
            walk(getattr(p, attr, None))

    walk(phys)
    return out


def canonical_inputs(table, an, col_order):
    """TILE-shaped inputs with the exact dtypes `_gather_tile` feeds the
    kernel (DATE/STRING as int32 codes, FLOAT as f64, else i64)."""
    from ..copr.jax_engine import TILE
    from ..types import TypeKind

    datas, valids = [], []
    for ci in col_order:
        meta = table.cols[an.scan.columns[ci]]
        k = meta.ftype.kind
        dt = np.int32 if k in (TypeKind.DATE, TypeKind.STRING) else (
            np.float64 if k == TypeKind.FLOAT else np.int64)
        datas.append(np.zeros(TILE, dtype=dt))
        valids.append(np.ones(TILE, dtype=np.bool_))
    del_mask = np.ones(TILE, dtype=np.bool_)
    return datas, valids, np.int64(0), np.int64(TILE), del_mask


def trace_kernel(table, dag) -> Dict[str, int]:
    """Abstract-trace one registered kernel; raises on contract breaks
    (bad shapes/dtypes, out-of-range refs, non-compilable exprs)."""
    import jax

    from ..copr.ir import DAG
    from ..copr.jax_engine import _Analyzed, _build_tile_fn

    # trace the WIRE format: the engine only ever sees DAGs that crossed
    # the distsql codec (which strips planner unique_ids); tracing the
    # in-memory plan object would check a shape production never runs
    dag = DAG.from_dict(dag.to_dict())
    an = _Analyzed(dag, table)
    kind = "agg" if an.agg is not None else (
        "topn" if an.topn is not None else "filter")
    col_order = an.needed_cols()
    fn = _build_tile_fn(an, kind, col_order)
    args = canonical_inputs(table, an, col_order)
    if kind == "agg":
        # the agg wrapper pairs each result with a static string tag for
        # the host merge; strip tags so the output pytree is all-array
        def traced(*a):
            gcount, results = fn(*a)
            return gcount, [v for _t, v in results]

        closed = jax.make_jaxpr(traced)(*args)
    else:
        closed = jax.make_jaxpr(fn)(*args)
    return _jaxpr_stats(closed)


def trace_batch_kernel(table, dag, B: int = VMAP_BATCH_B,
                       masked: bool = False):
    """Abstract-trace the micro-batcher's vmapped padded-batch kernel.

    `masked=True` traces with a partially-false deletion mask, a clipped
    [lo, hi) and shifted parameter values: bucket members differ only in
    DATA, so the jaxpr must be identical either way — any divergence
    means value-dependent tracing crept into the batch path (a program
    whose arity changes with bucket fill would defeat batching)."""
    import jax

    from ..copr.ir import DAG
    from ..copr.jax_engine import TILE, _Analyzed, _tile_core
    from ..serving import shape_bucket
    from ..serving.params import hoist_conds

    dag = DAG.from_dict(dag.to_dict())
    an = _Analyzed(dag, table)
    kind = "agg" if an.agg is not None else (
        "topn" if an.topn is not None else "filter")
    col_order = an.needed_cols()
    hoisted = hoist_conds(an)
    pi, pf = hoisted if hoisted is not None else (
        np.zeros(0, dtype=np.int64), np.zeros(0, dtype=np.float64))
    b_pad = shape_bucket(B)
    PI = np.stack([pi] * b_pad)
    PF = np.stack([pf] * b_pad)
    datas, valids, lo, hi, del_mask = canonical_inputs(table, an, col_order)
    if masked:
        del_mask = del_mask.copy()
        del_mask[::7] = False
        lo, hi = np.int64(3), np.int64(TILE - 5)
        if PI.size:
            PI = PI + np.arange(b_pad, dtype=np.int64).reshape(-1, 1)
        if PF.size:
            PF = PF * 0.5
    core = _tile_core(an, kind, col_order, with_params=True)
    vfn = jax.vmap(core, in_axes=(None, None, None, None, None, 0, 0))
    return jax.make_jaxpr(vfn)(datas, valids, lo, hi, del_mask, PI, PF)


def _signature_census() -> Tuple[set, set]:
    from ..copr import jax_engine as je
    from ..copr import parallel as par

    return set(je._COMPILED), set(par._COMPILED)


def lint_kernels(baseline_kernels: Optional[Dict[str, dict]] = None,
                 collect_stats: Optional[Dict[str, dict]] = None
                 ) -> List[Finding]:
    """Trace the kernel corpus; returns findings for contract breaks,
    int64-chain growth vs baseline, and jit-signature growth.

    baseline_kernels: {kernel: {"i64_eqns": n}, "__signatures__": {...}}
    (defaults to the checked-in baseline.json).  collect_stats, when a
    dict, receives measured per-kernel stats (the --update-baseline path).
    """
    from ..parser import parse_one
    from .baseline import load_baseline
    from .plancheck import _canonical_session

    if baseline_kernels is None:
        baseline_kernels = load_baseline().get("kernels", {})
    findings: List[Finding] = []

    def emit(kernel: str, msg: str):
        findings.append(Finding(
            rule="kernel-contract", path="tidb_tpu/copr", line=0,
            scope=kernel, token="trace", message=msg))

    s = _canonical_session()
    dom = s.domain
    table = dom.storage.table(
        dom.catalog.info_schema().table("test", "lineitem").id)

    # -- per-kernel abstract traces -------------------------------------
    from ..copr.jax_eval import JaxUnsupported

    for name, sql in CANONICAL_KERNEL_QUERIES:
        dags = []
        try:
            phys = s._plan(parse_one(sql))
            dags = [d for _p, d in _reader_dags(phys)]
            if not dags:
                emit(name, "canonical query produced no cop DAG — the "
                           "pushdown rewrite regressed")
                continue
            stats = None
            for dag in dags:
                try:
                    stats = trace_kernel(table, dag)
                    break
                except JaxUnsupported:
                    continue  # e.g. mesh-only shapes; try the next DAG
            if stats is None:
                emit(name, "no device-eligible kernel for canonical query "
                           "(JaxUnsupported on every cop DAG) — device "
                           "coverage regressed")
                continue
        except Exception as e:  # noqa: BLE001 — contract break
            emit(name, f"kernel trace failed: {type(e).__name__}: {e}")
            continue
        if collect_stats is not None:
            # collect mode refreshes the baseline, so comparing against
            # the one being replaced is meaningless — contract breaks
            # (trace failures, lost DAGs) are still emitted above
            collect_stats[name] = stats
            continue
        base = baseline_kernels.get(name)
        if base is None:
            emit(name, f"kernel not in baseline (measured {stats}); run "
                       "python -m tidb_tpu.lint --update-baseline")
        elif stats["i64_eqns"] > int(base.get("i64_eqns", 0)):
            emit(name,
                 f"int64 equation count grew {base.get('i64_eqns')} -> "
                 f"{stats['i64_eqns']}: an int64-emulation chain was "
                 "reintroduced (TPUs emulate i64 pairwise; VERDICT.md "
                 "names this the Q1 VPU bottleneck)")

    # -- MPP exchange / partitioned-join kernels ------------------------
    for name in MPP_EXCHANGE_KERNELS:
        mode = name.split("-")[1]
        try:
            from ..mpp.exchange import trace_exchange_kernel

            stats = _jaxpr_stats(trace_exchange_kernel(mode))
        except Exception as e:  # noqa: BLE001 — contract break
            emit(name, f"exchange kernel trace failed: "
                       f"{type(e).__name__}: {e}")
            continue
        if collect_stats is not None:
            collect_stats[name] = stats
            continue
        base = baseline_kernels.get(name)
        if base is None:
            emit(name, f"kernel not in baseline (measured {stats}); run "
                       "python -m tidb_tpu.lint --update-baseline")
        elif stats["i64_eqns"] > int(base.get("i64_eqns", 0)):
            emit(name,
                 f"int64 equation count grew {base.get('i64_eqns')} -> "
                 f"{stats['i64_eqns']}: an int64-emulation chain was "
                 "reintroduced into the exchange program")

    # -- 3-way join-tree rung-ladder kernel (ISSUE 12) ------------------
    name = TREE_JOIN_KERNEL
    try:
        from ..mpp.exchange import (run_tree_join_kernel,
                                    trace_tree_join_kernel,
                                    tree_join_oracle)

        closed = trace_tree_join_kernel(0)
        stats = _jaxpr_stats(closed)
        # key operands are runtime data: tracing under SHIFTED key
        # values must produce the identical ladder program
        other = trace_tree_join_kernel(3)
        if str(closed) != str(other):
            emit(name,
                 "shifted key operands changed the 3-way ladder's jaxpr "
                 "— key values must never become compiled constants")
        else:
            over, jover, total = run_tree_join_kernel(0)
            want = tree_join_oracle(0)
            if over or jover:
                emit(name, f"canonical ladder overflowed (partition "
                           f"{over}, emit {jover}) — capacities no "
                           "longer fit the canonical shape")
            elif abs(total - want) > 1e-6 * max(abs(want), 1.0):
                emit(name,
                     f"executed 3-way ladder disagrees with the CPU "
                     f"oracle: {total} != {want}")
            elif collect_stats is not None:
                collect_stats[name] = stats
            else:
                base = baseline_kernels.get(name)
                if base is None:
                    emit(name, f"kernel not in baseline (measured "
                               f"{stats}); run python -m tidb_tpu.lint "
                               "--update-baseline")
                elif stats["i64_eqns"] > int(base.get("i64_eqns", 0)):
                    emit(name,
                         f"int64 equation count grew "
                         f"{base.get('i64_eqns')} -> {stats['i64_eqns']}"
                         ": an int64-emulation chain was reintroduced "
                         "into the rung ladder")
    except Exception as e:  # noqa: BLE001 — contract break
        emit(name, f"tree join kernel trace failed: "
                   f"{type(e).__name__}: {e}")

    # -- MPP grouped-partial + on-device-merge kernel -------------------
    name = MPP_GROUPED_KERNEL
    try:
        from ..mpp.exchange import trace_grouped_agg_kernel

        closed = trace_grouped_agg_kernel(budget=5)
        stats = _jaxpr_stats(closed)
        # the budget is a runtime slot: tracing under a DIFFERENT budget
        # must produce the identical program (a budget baked into the
        # jaxpr would recompile per budget value — the range-slot rule
        # applied to the group capacity)
        other = trace_grouped_agg_kernel(budget=9)
        if str(closed) != str(other):
            emit(name,
                 "group-budget value changed the grouped kernel's jaxpr "
                 "— the budget must stay a runtime scalar slot, never a "
                 "compiled constant")
        elif collect_stats is not None:
            collect_stats[name] = stats
        else:
            base = baseline_kernels.get(name)
            if base is None:
                emit(name, f"kernel not in baseline (measured {stats}); "
                           "run python -m tidb_tpu.lint --update-baseline")
            elif stats["i64_eqns"] > int(base.get("i64_eqns", 0)):
                emit(name,
                     f"int64 equation count grew {base.get('i64_eqns')} "
                     f"-> {stats['i64_eqns']}: an int64-emulation chain "
                     "was reintroduced into the grouped merge kernel")
    except Exception as e:  # noqa: BLE001 — contract break
        emit(name, f"grouped agg kernel trace failed: "
                   f"{type(e).__name__}: {e}")

    # -- whole-fragment fused mesh programs -----------------------------
    from ..copr.fusion import trace_fused_fragment

    for name, sql in FUSED_FRAGMENT_KERNELS:
        try:
            phys = s._plan(parse_one(sql))
            stats = None
            for _p, dag in _reader_dags(phys):
                try:
                    stats = _jaxpr_stats(trace_fused_fragment(table, dag))
                except JaxUnsupported:
                    continue
                if name == "fused-mesh-scalar-agg":
                    # region-boundary signature guard: the range-bound
                    # SLOTS are runtime scalars, so a 3-range fragment
                    # must trace to the identical program as a 1-range
                    # one — any divergence means range layout leaked
                    # into the compiled shape (a recompile per range set)
                    multi = _jaxpr_stats(
                        trace_fused_fragment(table, dag, n_ranges=3))
                    if multi != stats:
                        emit(name,
                             f"range count changed the fused program's "
                             f"jaxpr ({stats} vs {multi}) — range bounds "
                             "must stay runtime data, not program shape")
                    # membership-epoch guard (coord plane): the epoch is
                    # host-side control state — re-tracing after a bump
                    # must yield the identical program.  An epoch baked
                    # into the jaxpr would recompile on every failover
                    # AND desync SPMD processes tracing at different
                    # epochs.
                    from ..coord import get_plane

                    get_plane().bump("kernelcheck-epoch-guard")
                    ep_stats = _jaxpr_stats(
                        trace_fused_fragment(table, dag))
                    if ep_stats != stats:
                        emit(name,
                             f"membership epoch bump changed the fused "
                             f"program's jaxpr ({stats} vs {ep_stats}) — "
                             "the epoch must stay host-side control "
                             "state, never a compiled constant")
                break
            if stats is None:
                emit(name, "no fused mesh form for canonical fragment — "
                           "whole-fragment fusion coverage regressed")
                continue
        except Exception as e:  # noqa: BLE001 — contract break
            emit(name, f"fused fragment trace failed: "
                       f"{type(e).__name__}: {e}")
            continue
        if collect_stats is not None:
            collect_stats[name] = stats
            continue
        base = baseline_kernels.get(name)
        if base is None:
            emit(name, f"kernel not in baseline (measured {stats}); run "
                       "python -m tidb_tpu.lint --update-baseline")
        elif stats["i64_eqns"] > int(base.get("i64_eqns", 0)):
            emit(name,
                 f"int64 equation count grew {base.get('i64_eqns')} -> "
                 f"{stats['i64_eqns']}: an int64-emulation chain was "
                 "reintroduced into the fused fragment program")

    # -- cold-tier decode-emitter fused kernel --------------------------
    name = COLD_FRAGMENT_KERNEL
    try:
        sql = dict(CANONICAL_KERNEL_QUERIES)["q6-scalar-agg"]
        phys = s._plan(parse_one(sql))
        stats = None
        diverged = False
        for _p, dag in _reader_dags(phys):
            try:
                closed = trace_fused_fragment(table, dag, cold=True)
            except JaxUnsupported:
                continue
            stats = _jaxpr_stats(closed)
            # layout runtime-slot guard: dictionary values are dispatch
            # operands — different contents, identical program
            shifted = trace_fused_fragment(table, dag, cold=True,
                                           dict_shift=3)
            if str(closed) != str(shifted):
                emit(name,
                     "dictionary contents changed the cold kernel's "
                     "jaxpr — layout VALUES must ride runtime operands, "
                     "never compiled constants")
                diverged = True
                break
            break
        if diverged:
            pass  # divergence already emitted above
        elif stats is None:
            emit(name, "no cold-packable fused form for the canonical "
                       "fragment — cold-tier decode coverage regressed")
        elif collect_stats is not None:
            collect_stats[name] = stats
        else:
            base = baseline_kernels.get(name)
            if base is None:
                emit(name, f"kernel not in baseline (measured {stats}); "
                           "run python -m tidb_tpu.lint --update-baseline")
            elif stats["i64_eqns"] > int(base.get("i64_eqns", 0)):
                emit(name,
                     f"int64 equation count grew {base.get('i64_eqns')} "
                     f"-> {stats['i64_eqns']}: an int64-emulation chain "
                     "was reintroduced into the cold decode kernel")
    except Exception as e:  # noqa: BLE001 — contract break
        emit(name, f"cold fragment trace failed: "
                   f"{type(e).__name__}: {e}")

    # -- Pallas kernel tier (copr/pallas) -------------------------------
    for name in PALLAS_KERNELS:
        try:
            import os as _os2

            from ..copr.pallas import (trace_remap_kernel,
                                       trace_unpack_kernel)

            if name == "pallas-remap-codes":
                closed = trace_remap_kernel(shift=0)
                other = trace_remap_kernel(shift=5)
                if str(closed) != str(other):
                    emit(name,
                         "mapping contents changed the remap kernel's "
                         "jaxpr — the mapping must stay a runtime "
                         "operand, never a compiled constant")
                    continue
                # executed parity vs the TIDB_TPU_PALLAS=0 jnp reference
                from ..copr.pallas import remap_codes

                codes = (np.arange(257, dtype=np.int32) * 7) % 16
                mapping = (np.arange(16, dtype=np.int32) * 3 + 1)
                got = np.asarray(remap_codes(codes, mapping, 257))
                prior = _os2.environ.get("TIDB_TPU_PALLAS")
                _os2.environ["TIDB_TPU_PALLAS"] = "0"
                try:
                    ref = np.asarray(remap_codes(codes, mapping, 257))
                finally:
                    if prior is None:
                        _os2.environ.pop("TIDB_TPU_PALLAS", None)
                    else:
                        _os2.environ["TIDB_TPU_PALLAS"] = prior
                if not np.array_equal(got, ref):
                    emit(name, "pallas remap disagrees with the jnp "
                               "reference path")
                    continue
                stats = _jaxpr_stats(closed)
            else:
                from ..copr.pallas import unpack_codes
                from ..layout.coldtier import pack_codes

                closed = trace_unpack_kernel(bits=4)
                stats = _jaxpr_stats(closed)
                raw = (np.arange(512) % 16).astype(np.uint8)
                packed = pack_codes(raw, 4)
                got = np.asarray(unpack_codes(packed, 4, 512))
                if not np.array_equal(got, raw):
                    emit(name, "pallas unpack disagrees with "
                               "pack_codes round-trip")
                    continue
        except Exception as e:  # noqa: BLE001 — contract break
            emit(name, f"pallas kernel trace failed: "
                       f"{type(e).__name__}: {e}")
            continue
        if collect_stats is not None:
            collect_stats[name] = stats
            continue
        base = baseline_kernels.get(name)
        if base is None:
            emit(name, f"kernel not in baseline (measured {stats}); run "
                       "python -m tidb_tpu.lint --update-baseline")
        elif stats["i64_eqns"] > int(base.get("i64_eqns", 0)):
            emit(name,
                 f"int64 equation count grew {base.get('i64_eqns')} -> "
                 f"{stats['i64_eqns']}: an int64-emulation chain was "
                 "reintroduced into the pallas kernel")

    # -- micro-batch vmapped padded-batch kernel ------------------------
    name = VMAP_BATCH_KERNEL
    try:
        sql = dict(CANONICAL_KERNEL_QUERIES)["q6-scalar-agg"]
        phys = s._plan(parse_one(sql))
        stats = mstats = None
        for _p, dag in _reader_dags(phys):
            try:
                stats = _jaxpr_stats(trace_batch_kernel(table, dag))
                mstats = _jaxpr_stats(
                    trace_batch_kernel(table, dag, masked=True))
                break
            except JaxUnsupported:
                continue
        if stats is None:
            emit(name, "no device-eligible DAG for the vmapped batch "
                       "kernel — micro-batch coverage regressed")
        elif stats != mstats:
            emit(name,
                 f"padding mask / bucket-fill values changed the vmapped "
                 f"batch kernel's jaxpr ({stats} vs {mstats}) — batch "
                 "members must share one program regardless of fill")
        elif collect_stats is not None:
            collect_stats[name] = stats
        else:
            base = baseline_kernels.get(name)
            if base is None:
                emit(name, f"kernel not in baseline (measured {stats}); "
                           "run python -m tidb_tpu.lint --update-baseline")
            elif stats["i64_eqns"] > int(base.get("i64_eqns", 0)):
                emit(name,
                     f"int64 equation count grew {base.get('i64_eqns')} "
                     f"-> {stats['i64_eqns']}: an int64-emulation chain "
                     "was reintroduced into the batch kernel")
    except Exception as e:  # noqa: BLE001 — contract break
        emit(name, f"vmapped batch kernel trace failed: "
                   f"{type(e).__name__}: {e}")

    # -- context-capture guards (trace spans + lifecycle scope) ---------
    # span hooks AND lifecycle scope checks live strictly OUTSIDE
    # compiled code: re-tracing the kernels while (a) a query trace is
    # ACTIVE and (b) a QueryScope with an ACTIVE DEADLINE is current
    # must produce byte-identical jaxpr stats.  Any trace/scope state
    # captured into a jitted function would change the equation census —
    # and make compiled programs trace- or deadline-dependent.
    import contextlib

    from ..lifecycle import QueryScope, activate_scope, deactivate_scope
    from ..trace import finish_trace, start_trace

    @contextlib.contextmanager
    def active_trace():
        tr, token = start_trace("kernelcheck-instrumented", 0)
        try:
            yield
        finally:
            finish_trace(tr, token)

    @contextlib.contextmanager
    def active_deadline():
        token = activate_scope(QueryScope(timeout_s=3600.0))
        try:
            yield
        finally:
            deactivate_scope(token)

    guards = (
        ("instrumented", active_trace,
         "span hooks leaked into the compiled program", "query trace"),
        ("scoped", active_deadline,
         "lifecycle scope leaked into the compiled program", "deadline"),
    )
    # the context-free baseline (plan + jaxpr trace, the costly part)
    # is computed ONCE per query; each guard pays only its own re-trace
    for name, sql in CANONICAL_KERNEL_QUERIES:
        if name not in ("q1-dense-agg", "filter-project"):
            continue
        try:
            phys = s._plan(parse_one(sql))
            base_dag = base_stats = None
            for _p, dag in _reader_dags(phys):
                try:
                    base_stats = trace_kernel(table, dag)
                except JaxUnsupported:
                    continue
                base_dag = dag
                break
        except Exception as e:  # noqa: BLE001 — contract break
            for suffix, _c, _m, _n in guards:
                emit(f"{name}-{suffix}",
                     f"baseline kernel trace failed: "
                     f"{type(e).__name__}: {e}")
            continue
        if base_dag is None:
            continue
        for suffix, ctx, leak_msg, ctx_name in guards:
            try:
                with ctx():
                    ctx_stats = trace_kernel(table, base_dag)
            except Exception as e:  # noqa: BLE001 — contract break
                emit(f"{name}-{suffix}",
                     f"{suffix} kernel trace failed: "
                     f"{type(e).__name__}: {e}")
                continue
            if ctx_stats != base_stats:
                emit(f"{name}-{suffix}",
                     f"{leak_msg}: jaxpr stats changed {base_stats} -> "
                     f"{ctx_stats} under an active {ctx_name}")

    # -- recompile-bomb guard -------------------------------------------
    # count only signatures the corpus itself compiles: the engine caches
    # are process-global, and other passes (or the bootstrap INSERT/
    # ANALYZE statements) legitimately add their own entries
    queries = [sql for _n, sql in CANONICAL_KERNEL_QUERIES]
    je0, par0 = _signature_census()
    for q in queries:
        s.query(q)
    je1, par1 = _signature_census()
    for q in queries:
        s.query(q)
    je2, par2 = _signature_census()
    grew = (je2 - je1) | (par2 - par1)
    if grew:
        emit("signature-growth",
             f"re-running the canonical corpus compiled {len(grew)} NEW "
             "jit signature(s) — a recompile bomb (fingerprint must be "
             "stable across identical queries)")
    # running the same corpus under an ACTIVE trace must not compile
    # anything either: program fingerprints carry no trace state, so a
    # new signature here means a span hook captured tracer-varying
    # state into a compiled program
    tr, token = start_trace("kernelcheck-traced-corpus", 0)
    try:
        for q in queries:
            s.query(q)
    finally:
        finish_trace(tr, token)
    je3, par3 = _signature_census()
    grew_traced = (je3 - je2) | (par3 - par2)
    if grew_traced:
        emit("trace-capture",
             f"running the corpus under an active query trace compiled "
             f"{len(grew_traced)} NEW jit signature(s) — span hooks must "
             "stay outside compiled code")
    n_sigs = len((je2 - je0)) + len((par2 - par0))
    base_sigs = baseline_kernels.get("__signatures__", {}).get("max")
    if collect_stats is not None:
        # refreshing: the cap comparison targets the new stats
        collect_stats["__signatures__"] = {"max": n_sigs}
    elif base_sigs is not None and n_sigs > int(base_sigs):
        emit("signature-growth",
             f"canonical corpus now compiles {n_sigs} distinct jit "
             f"signatures (baseline {base_sigs}) — new recompiles on the "
             "hot path; justify and refresh the baseline if intended")
    elif base_sigs is None and collect_stats is None:
        emit("signature-growth",
             "no __signatures__ entry in baseline; run "
             "python -m tidb_tpu.lint --update-baseline")
    return findings
