"""Ask the chip's compiler, without the chip.

The TPU compiler is installed here and compiles for a chip that is described,
not attached (on-chip-measurement guide, section 2).  These tests compile the
programs of the served TPC-H path for a described v5e at the PRODUCTION tile
(2^20 rows; the rest of the suite runs at 1,024) and real row counts, so a
program the chip's compiler refuses is found at no chip time.  Nothing runs:
a compile that passes says nothing about results or times.

The topology is described inside a module-scoped fixture and every compile
happens in this process — only one process may hold the TPU library.
TopN is left out: its fused program takes about a minute to compile.
"""

import numpy as np
import pytest

PROD_TILE = 1 << 20
SF10_TILES = 64            # 67,108,864 lineitem rows, the smoke's scale
Q3_TILES = 16              # 16,777,216 lineitem rows
Q3_BUILD_PAD = 1 << 22     # pow2 pad of the ~2.1M orders that pass the filter


@pytest.fixture(scope="module")
def topo():
    import os

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies

    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def meshes(topo):
    from jax.sharding import Mesh

    return {n: Mesh(np.array(topo.devices[:n]), ("dp",)) for n in (1, 4)}


@pytest.fixture(autouse=True)
def _production_tile_no_cache(monkeypatch):
    """The production tile for this file's tests only; the persistent cache
    off around them (a compile for a described chip is written to the cache
    but cannot be read back without one)."""
    import jax
    from jax.experimental.compilation_cache import compilation_cache as cc

    from tidb_tpu.copr import jax_engine as je

    monkeypatch.setattr(je, "TILE", PROD_TILE)
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", True)
    cc.reset_cache()


@pytest.fixture(scope="module")
def lineitem():
    """(session, table) over tpch_data's lineitem schema — a few rows are
    enough: only dtypes and shapes reach the compiler."""
    from tidb_tpu.tpch_data import build_lineitem

    s = build_lineitem(2048, regions=2)
    t = s.domain.catalog.info_schema().table("test", "lineitem")
    return s, s.domain.storage.table(t.id)


@pytest.fixture(scope="module")
def q3_pair():
    from tidb_tpu.tpch_data import build_q3_tables

    s = build_q3_tables(2048, 512, regions=2)
    t = s.domain.catalog.info_schema().table("test", "lineitem")
    return s, s.domain.storage.table(t.id)


def _fragment_args(sess, table, sql, mesh, n_tiles, build_pad=16,
                   wire=None, rows=None):
    """(core, abstract args) of the fused mesh program for `sql`'s cop DAG,
    built the way fusion.trace_fused_fragment builds it, but over `mesh`
    and with ShapeDtypeStructs carrying NamedShardings: columns in their
    production wire dtypes, NULL-free columns without a validity array.
    `rows` stands in for the table's base rows where they decide the
    program (a sum that can pass int64 over them)."""
    import jax
    from jax.sharding import NamedSharding, PartitionSpec as P

    from tidb_tpu.copr import jax_engine as je
    from tidb_tpu.copr import parallel as par
    from tidb_tpu.copr.ir import DAG
    from tidb_tpu.copr.jax_eval import JaxUnsupported
    from tidb_tpu.lint.kernelcheck import _reader_dags
    from tidb_tpu.parser import parse_one
    from tidb_tpu.serving import hoist_conds

    S = len(mesh.devices.ravel())
    sharded = NamedSharding(mesh, P("dp"))
    repl = NamedSharding(mesh, P())

    def sds(shape, dtype, sharding=repl):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)

    an = None
    for _p, dag in _reader_dags(sess._plan(parse_one(sql))):
        try:
            cand = je._Analyzed(DAG.from_dict(dag.to_dict()), table)
        except JaxUnsupported:
            continue
        if an is None or cand.lookups:  # prefer the DAG that holds the join
            an = cand
    assert an is not None, f"no device-eligible cop DAG for {sql!r}"
    kind = "agg" if an.agg is not None else (
        "topn" if an.topn is not None else "filter")
    if rows is not None:
        an.agg_rows = rows
    hoisted = hoist_conds(an)
    col_order = an.needed_cols()
    datas, valids = [], []
    for ci in col_order:
        store_ci = an.scan.columns[ci]
        dt = par._wire_dtype(table, store_ci)
        if wire is not None:
            dt = np.dtype(wire[table.cols[store_ci].name])
        elif table.cols[store_ci].name == "l_orderkey":
            dt = np.dtype(np.int32)  # SF10's key range; 2,048 rows fit int16
        datas.append(sds((n_tiles, PROD_TILE), dt, sharded))
        valids.append(None)
    pargs = []
    for lk in an.lookups:
        # where the groups are the build rows the keys alone are sent,
        # in 32 bits beside a probe key cached that narrow
        fd = par._fd_lookup(an)
        pargs.append(sds((build_pad,),
                         np.int32 if fd and wire is not None else np.int64))
        for ft in () if fd else lk.payload_ftypes:
            pargs += [sds((build_pad,), par._full_dtype(ft.kind)),
                      sds((build_pad,), np.bool_)]
    # the operand vector as _run_mesh_once fills it: range slots, one key
    # count a lookup, the hoisted int64 parameters; a non-empty float64
    # parameter vector is the last parg
    scalars = [0] * len(an.lookups)
    if hoisted is not None:
        scalars += list(hoisted[0])
        if len(hoisted[1]):
            pargs.append(sds(hoisted[1].shape, np.float64))
    ints = par._bounds_args([], scalars)
    core = par._build_mesh_core(
        an, kind, col_order, mesh, tiles_per_shard=n_tiles // S,
        hoisted=hoisted and (len(hoisted[0]), len(hoisted[1])))
    args = (tuple(datas), tuple(valids),
            sds((n_tiles, PROD_TILE), np.bool_, sharded),
            sds(ints.shape, ints.dtype), ()) + tuple(pargs)
    return core, args


def _compile(fn, args):
    import jax

    return jax.jit(fn).lower(*args).compile()


@pytest.mark.parametrize("name", ["q1-dense-agg", "q6-scalar-agg",
                                  "filter-project", "minmax-agg"])
def test_fused_scan_programs_compile_for_one_v5e_chip(name, meshes, lineitem):
    from tidb_tpu.lint.kernelcheck import CANONICAL_KERNEL_QUERIES

    sess, table = lineitem
    core, args = _fragment_args(sess, table,
                                dict(CANONICAL_KERNEL_QUERIES)[name],
                                meshes[1], SF10_TILES)
    compiled = _compile(core, args)
    mem = compiled.memory_analysis()
    # 64 tiles of the wire columns and temporaries fit the chip's 16 GB
    assert mem.argument_size_in_bytes + mem.temp_size_in_bytes < 16e9


def _benchmark_sql(name, param):
    """The benchmark's own statement text (`benchmarks/queries/<name>.json`)
    with one of its parameter tuples."""
    import json
    import os

    here = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    with open(os.path.join(here, "benchmarks", "queries", name + ".json")) as f:
        q = json.load(f)
    return q["sql"].format(**q["params"][param])


def _full_length_copies(compiled):
    """`copy` operations of the entry computation over row-sized arrays:
    each is a pass over a column or mask (the flatten's transposes)."""
    import re

    text = compiled.as_text()
    entry = text[text.index("ENTRY"):]
    out = []
    for shape in re.findall(r"= \w+\[([\d,]*)\]\S* copy\(", entry):
        n = 1
        for d in shape.split(","):
            n *= int(d or 1)
        if n >= PROD_TILE:
            out.append(shape)
    return out


@pytest.mark.parametrize("name,param,gb,seconds", [("q1", 2, 10.0, 20.0),
                                                   ("q6", 1, 2.5, 20.0)])
def test_dense_aggregate_reads_its_columns_once(name, param, gb, seconds,
                                                meshes, lineitem):
    """The guard that needs no chip (ISSUE 35): while the program is
    bound by bandwidth, the compiler's `bytes accessed` over 819 GB/s is
    its device time (41.5 GB, 50.7 ms predicted and 50.8 measured for the
    Q1 program whose every sum was a pass of its own).  An emitter change
    that brings full-length temporaries back fails here; so does one that
    takes the cold compile past 20 s."""
    import time

    sess, table = lineitem
    core, args = _fragment_args(sess, table, _benchmark_sql(name, param),
                                meshes[1], SF10_TILES)
    t0 = time.perf_counter()
    compiled = _compile(core, args)
    took = time.perf_counter() - t0
    cost = compiled.cost_analysis()
    cost = cost[0] if isinstance(cost, (list, tuple)) else cost
    assert cost["bytes accessed"] < gb * 1e9
    assert not _full_length_copies(compiled)
    assert took < seconds, f"{name} compiled in {took:.1f} s"


def test_q3_device_join_program_compiles_for_one_v5e_chip(meshes, q3_pair):
    from tidb_tpu.tpch_data import Q3_SQL

    sess, table = q3_pair
    core, args = _fragment_args(sess, table, Q3_SQL, meshes[1], Q3_TILES,
                                build_pad=Q3_BUILD_PAD)
    assert len(args) > 5, "the join's build-side operands are missing"
    _compile(core, args)


def test_q1_compiles_for_four_chips_with_its_psum(meshes, lineitem):
    from tidb_tpu.lint.kernelcheck import CANONICAL_KERNEL_QUERIES

    sess, table = lineitem
    core, args = _fragment_args(sess, table,
                                dict(CANONICAL_KERNEL_QUERIES)["q1-dense-agg"],
                                meshes[4], SF10_TILES)
    assert "all-reduce" in _compile(core, args).as_text()


def test_sf100_q1_compiles_for_four_chips_with_its_wide_sum(meshes, lineitem):
    """The program of the benchmark's cell `sf100-q1-4chip`: 144 tiles a
    shard (600 M rows are 573 tiles, a shard's 144 a class of its own in
    steps of an eighth), sum_charge leaving the device as three limb sums
    beside the recombined slots, one all-reduce for all of them, no
    full-length temporary, and a shard's share of the columns inside a
    chip."""
    import jax

    from tidb_tpu.copr import fusion
    from tidb_tpu.copr import parallel as par

    sess, table = lineitem
    tiles, sf100_tiles, per_shard = par._layout(600_000_000, 4)
    assert (tiles, sf100_tiles, per_shard) == (573, 576, 144)
    core, args = _fragment_args(sess, table, _benchmark_sql("q1w", 2),
                                meshes[4], sf100_tiles, rows=600_000_000)
    compiled = _compile(core, args)
    text = compiled.as_text()
    assert text.count(" all-reduce(") + text.count(" all-reduce-start(") == 1
    assert not _full_length_copies(compiled)
    mem = compiled.memory_analysis()
    # 144 tiles of 2^20 rows, 12 bytes of columns and one of mask a row
    assert mem.argument_size_in_bytes + mem.temp_size_in_bytes < 2.2e9
    # the same statement over SF10's rows keeps every slot on the device
    core10, args10 = _fragment_args(sess, table, _benchmark_sql("q1w", 2),
                                    meshes[1], SF10_TILES, rows=60_000_000)
    out = jax.eval_shape(core, *args)
    out10 = jax.eval_shape(core10, *args10)
    assert [x[0].shape for x in out[1][:4]] == [(6,), (6,), (6,), (3, 6)]
    assert [x[0].shape for x in out10[1][:4]] == [(6,)] * 4
    assert fusion.AGG_LIMB * 600_000_000 < 1 << 61


def test_mpp_shuffle_join_compiles_for_four_chips_with_its_all_to_all(meshes):
    """The canonical partition -> all_to_all -> local join program of
    mpp/exchange.py at the smoke's 8M x 2M shape: 2^21 probe rows a shard."""
    import jax
    from jax import shard_map
    from jax.sharding import NamedSharding, PartitionSpec as P

    from tidb_tpu.mpp.exchange import _canonical_join_fn

    mesh = meshes[4]
    S, n_local = 4, 1 << 21
    fn = shard_map(
        _canonical_join_fn(S, 2 * n_local // S, n_local, "shuffle"),
        mesh=mesh, in_specs=(P("dp"),) * 5,
        out_specs=(P(), P(), P("dp"), P("dp")))
    sharded = NamedSharding(mesh, P("dp"))
    args = [jax.ShapeDtypeStruct((S * n_local,), dt, sharding=sharded)
            for dt in (np.int64, np.bool_, np.int64, np.bool_, np.float64)]
    assert "all-to-all" in _compile(fn, args).as_text()


@pytest.mark.parametrize("kernel", ["remap_codes", "unpack_codes-1bit",
                                    "unpack_codes-2bit", "unpack_codes-4bit"])
def test_pallas_kernels_compile_to_mosaic_at_the_production_tile(
        kernel, topo, monkeypatch):
    """Compiled, not interpreted: on a tpu backend `_interpret()` is False;
    here the backend is the CPU, so the test says so itself."""
    import jax
    from jax.sharding import SingleDeviceSharding

    from tidb_tpu.copr.pallas import kernels

    monkeypatch.setattr(kernels, "_interpret", lambda: False)
    one_chip = SingleDeviceSharding(topo.devices[0])

    def sds(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    n = SF10_TILES * PROD_TILE
    if kernel == "remap_codes":
        fn = lambda c, m: kernels.remap_codes(c, m, n)  # noqa: E731
        args = (sds((n,), np.int32), sds((kernels.REMAP_MAX_CAP,), np.int32))
    else:
        bits = int(kernel.split("-")[1][0])
        fn = lambda p: kernels.unpack_codes(p, bits, n)  # noqa: E731
        args = (sds((n * bits // 8,), np.uint8),)
    assert "tpu_custom_call" in _compile(fn, args).as_text()


# ---------------------------------------------------------------------------
# the one-chip Q3 of the benchmark's cell `sf1-q3`
# ---------------------------------------------------------------------------

#: SF1's shapes: tiles of 2^20 rows a table (padded to a power of two),
#: the wire dtypes its column statistics give, the directory over
#: c_custkey's 150,000 values, 2^18 slots for the ~147 k joined rows
SF1_TILES = {"customer": 1, "orders": 2, "lineitem": 8}
SF1_WIRE = {"c_custkey": np.int32, "c_mktsegment": np.int8,
            "o_orderkey": np.int32, "o_custkey": np.int32,
            "o_orderdate": np.int16, "o_shippriority": np.int8,
            "l_orderkey": np.int32, "l_extendedprice": np.int32,
            "l_discount": np.int8, "l_shipdate": np.int16}
SF1_DIRECTORY = 1 << 18
SF1_JOINED = 1 << 18


@pytest.fixture(scope="module")
def q3_bench():
    """(session, Q3's text, its MPP join's spec, storage) over the
    benchmark's own generator at SF 0.02, analysed: the plan of the cell."""
    import json
    import os
    import sys

    bench = os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), "benchmarks")
    sys.path.insert(0, bench)
    try:
        from harness import serve, traffic

        config = json.load(open(os.path.join(
            bench, "configs", "tpch-sf1-q3.json")))
        query = json.load(open(os.path.join(bench, "queries", "q3.json")))
        domain, _, _ = serve.load(config, 7, 0.02, lambda _n: None)
        return domain.new_session(), traffic.render(query, 0), domain
    finally:
        sys.path.remove(bench)


def _sf1_side(state, table_name, mesh):
    """A `_SideState` of the small table given SF1's shapes, and its
    abstract (datas, valids, del_mask, bounds) operands."""
    import jax
    from jax.sharding import NamedSharding, PartitionSpec as P

    from tidb_tpu.copr import parallel as par

    tiles = SF1_TILES[table_name]
    state.Tl = state.n_tiles = state.n_pad = tiles
    state.n_local = tiles * PROD_TILE
    sharded = NamedSharding(mesh, P("dp"))
    names = [state.table.cols[state.an.scan.columns[ci]].name
             for ci in state.col_order]
    state.datas = [jax.ShapeDtypeStruct((tiles, PROD_TILE), SF1_WIRE[n],
                                        sharding=sharded) for n in names]
    state.valids = [None] * len(names)
    state.del_mask = jax.ShapeDtypeStruct((tiles, PROD_TILE), np.bool_,
                                          sharding=sharded)
    bounds = par._bounds_args([], (0, 0))
    return (tuple(state.datas), tuple(state.valids), state.del_mask,
            jax.ShapeDtypeStruct(bounds.shape, bounds.dtype,
                                 sharding=NamedSharding(mesh, P())))


def _timed_compile(fn, args):
    import time

    t0 = time.perf_counter()
    compiled = fn.lower(*args).compile()
    took = time.perf_counter() - t0
    cost = compiled.cost_analysis()
    cost = cost[0] if isinstance(cost, (list, tuple)) else cost
    return compiled, took, cost["bytes accessed"]


def test_one_chip_q3_join_programs_at_sf1_shapes(meshes, q3_bench):
    """The three device programs a Q3 statement of `sf1-q3` runs on one
    chip (the directory join's probe and emit, the lineitem aggregate
    with its lookup join), compiled for the described v5e at SF1's
    shapes: no sort and no one-level prefix sum over a table's length in
    the customer-orders join (a full-length int64 `argsort` alone
    compiles for over a minute), and the whole of a statement's cold
    compile bounded."""
    import jax
    from jax.sharding import NamedSharding, PartitionSpec as P

    from tidb_tpu.mpp import engine as mpp
    from tidb_tpu.parser import parse_one
    from tidb_tpu.planner.physical import PhysMPPJoin

    sess, sql, domain = q3_bench
    mesh = meshes[1]
    def walk(p):
        yield p
        for c in list(getattr(p, "children", ()) or ()) + [
                getattr(p, a, None) for a in ("reader", "build_plan")]:
            if c is not None:
                yield from walk(c)

    join = next(p for p in walk(sess._plan(parse_one(sql)))
                if isinstance(p, PhysMPPJoin))
    spec = join.build(None).spec
    names = {}
    for side in (spec.probe, spec.build):
        names[side.table_id] = next(
            n for n in SF1_TILES if domain.catalog.info_schema()
            .table("test", n).id == side.table_id)
    ps = mpp._SideState(domain.storage, spec.probe, 1 << 62, mesh)
    bs = mpp._SideState(domain.storage, spec.build, 1 << 62, mesh)
    p_args = _sf1_side(ps, names[spec.probe.table_id], mesh)
    b_args = _sf1_side(bs, names[spec.build.table_id], mesh)
    plan = mpp._directory_plan(spec, ps, bs)
    assert plan is not None and plan[0], "customer is the directory"
    ds, ss, d_args, s_args = (ps, bs, p_args, b_args)

    probe = mpp._build_directory_probe(ps, bs, True, False, mesh,
                                       SF1_DIRECTORY, "mpp_shuffle_probe")
    compiled, took_a, bytes_a = _timed_compile(probe, d_args + s_args)
    text = compiled.as_text()
    assert " sort(" not in text and "all-to-all" not in text

    sharded = NamedSharding(mesh, P("dp"))
    emit = mpp._build_directory_emit(spec, ps, bs, True, mesh, SF1_JOINED,
                                     0, None, "mpp_shuffle_emit")
    rows = (jax.ShapeDtypeStruct((ss.n_local,), np.int32, sharding=sharded),
            jax.ShapeDtypeStruct((ss.n_local,), np.bool_, sharding=sharded))
    compiled, took_b, bytes_b = _timed_compile(
        emit, (p_args[0], p_args[1], b_args[0], b_args[1]) + rows)
    assert " sort(" not in compiled.as_text()

    table = domain.storage.table(
        domain.catalog.info_schema().table("test", "lineitem").id)
    core, args = _fragment_args(sess, table, sql, mesh,
                                SF1_TILES["lineitem"],
                                build_pad=SF1_JOINED, wire=SF1_WIRE)
    assert len(args) > 5, "the join's build-side operand is missing"
    compiled, took_c, bytes_c = _timed_compile(jax.jit(core), args)
    text = compiled.as_text()
    # a merge: its two sorts, and no binary search (a `while` of 18
    # rounds of gathers took 4.9 s a statement on the chip)
    assert text.count(" sort(") == 2 and " while(" not in text
    # readings on this sandbox (PR 36): 4.3 s / 13.4 GB, 2.2 s / 5.8 GB,
    # 56.7 s / 9.8 GB (the compiler counts a gather's whole operand)
    print(f"probe {took_a:.1f}s {bytes_a / 1e9:.3f}GB, emit {took_b:.1f}s "
          f"{bytes_b / 1e9:.3f}GB, lineitem {took_c:.1f}s "
          f"{bytes_c / 1e9:.3f}GB")
    assert took_a < 20 and bytes_a < 20e9
    assert took_b < 15 and bytes_b < 9e9
    assert took_c < 170 and bytes_c < 15e9
