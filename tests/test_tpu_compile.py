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


def _fragment_args(sess, table, sql, mesh, n_tiles, build_pad=16):
    """(core, abstract args) of the fused mesh program for `sql`'s cop DAG,
    built the way fusion.trace_fused_fragment builds it, but over `mesh`
    and with ShapeDtypeStructs carrying NamedShardings: columns in their
    production wire dtypes, NULL-free columns without a validity array."""
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
    hoisted = hoist_conds(an)
    col_order = an.needed_cols()
    datas, valids = [], []
    for ci in col_order:
        store_ci = an.scan.columns[ci]
        dt = par._wire_dtype(table, store_ci)
        if table.cols[store_ci].name == "l_orderkey":
            dt = np.dtype(np.int32)  # SF10's key range; 2,048 rows fit int16
        datas.append(sds((n_tiles, PROD_TILE), dt, sharded))
        valids.append(None)
    pargs = []
    for lk in an.lookups:
        pargs.append(sds((build_pad,), np.int64))
        for ft in lk.payload_ftypes:
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
