"""The dense aggregate's block sums are exact (ISSUE 35).

`copr/fusion.py::_BlockSums` emits every count and every integer or
decimal sum of a small-G aggregate as one two-level reduction on int32
lanes: arguments the column statistics bound are computed in int32 from
the wire arrays (`jax_eval.bounded_int`), split into limbs a block of
`AGG_BLOCK` rows cannot overflow, reduced per block in one variadic
reduce and recombined in int64.  These tests hold that to Python
integers and to the CPU oracle (`SET tidb_use_tpu = 0`) on the mesh and
on the per-tile path, at the edges the limbs have.
"""

import decimal

import numpy as np
import pytest

from tidb_tpu.metrics import REGISTRY
from tidb_tpu.session import Domain

DDL = ("create table t (g bigint, a bigint, b bigint,"
       " d decimal(15,2), f double)")
RNG_SEED = 35


def _load(store, domain, n, a, b=None, g=None, d=None, f=None, a_valid=None,
          seed=RNG_SEED):
    rng = np.random.default_rng(seed)
    cols = [
        np.zeros(n, np.int64) if g is None else np.asarray(g, np.int64),
        np.asarray(a, np.int64),
        np.ones(n, np.int64) if b is None else np.asarray(b, np.int64),
        rng.integers(-99_999, 100_000, n) if d is None
        else np.asarray(d, np.int64),
        rng.normal(size=n) if f is None else np.asarray(f, np.float64),
    ]
    valids = None
    if a_valid is not None:
        valids = [None, np.asarray(a_valid, np.bool_), None, None, None]
    store.bulk_load_arrays(cols, valids=valids,
                           ts=domain.storage.current_ts())
    return cols


def _fresh(regions=2):
    domain = Domain()
    s = domain.new_session()
    s.execute(DDL)
    t = domain.catalog.info_schema().table("test", "t")
    return domain, s, t, domain.storage.table(t.id)


def _split(domain, t, store, regions=2):
    domain.storage.regions.split_even(t.id, regions, store.base_rows)


@pytest.fixture
def tile_path(monkeypatch):
    from tidb_tpu.copr import parallel

    monkeypatch.setattr(parallel, "try_run_mesh", lambda *a, **k: None)


@pytest.fixture(params=["mesh", "tile"])
def path(request):
    """Both engines that compose `dense_agg_results`."""
    if request.param == "tile":
        request.getfixturevalue("tile_path")
    return request.param


def _both(s, sql):
    s.execute("set tidb_use_tpu = 1")
    dev = s.query(sql)
    s.execute("set tidb_use_tpu = 0")
    cpu = s.query(sql)
    s.execute("set tidb_use_tpu = 1")
    return dev, cpu


def _ints(rows):
    return [tuple(None if x is None else int(x) for x in r) for r in rows]


def _group_sums(g, *values, mask=None):
    """Python-integer sums by group, rows in group order."""
    out = {}
    for i, k in enumerate(g):
        if mask is not None and not mask[i]:
            continue
        acc = out.setdefault(int(k), [0] * len(values))
        for j, v in enumerate(values):
            acc[j] += int(v[i])
    return [(k, *out[k]) for k in sorted(out)]


N = 3000  # under one block, and with TILE 1,024 not a whole number of tiles

#: name -> (a, b): values at each limb's edge, negative values, sums that
#: cross zero, products past 2^31 and past 2^62
def _edge_cases(rng):
    edge = np.array([0, 1, 65535, 65536, 65537, 131071, 131072, 1 << 20])
    huge = np.zeros(N, np.int64)
    huge[:3] = [(1 << 31) - 1, -(1 << 31) + 1, (1 << 31) - 7]
    return {
        "limb-edges": (rng.choice(edge, N), None),
        "one-limb-hi": (np.full(N, 131071), None),
        "two-limbs-lo": (np.full(N, 131072), None),
        "own-lo-hi": (rng.choice([-70001, 90001], N), None),
        "cross-zero": (rng.integers(-70000, 70001, N), None),
        "all-negative": (-rng.integers(1, 1 << 18, N), None),
        "product-past-2^31": (rng.integers(0, 1 << 20, N),
                              rng.integers(-(1 << 14), 1 << 14, N)),
        "product-past-2^62": (huge, np.where(huge != 0, 1 << 30, 5)),
        "wide-column": (rng.integers(-(1 << 40), 1 << 40, N), None),
    }


@pytest.mark.parametrize("case", list(_edge_cases(np.random.default_rng(0))))
@pytest.mark.parametrize("groups", [1, 6, 32])
def test_sums_equal_python_integers(case, groups, path):
    rng = np.random.default_rng(RNG_SEED)
    a, b = _edge_cases(rng)[case]
    domain, s, t, store = _fresh()
    g = rng.integers(0, groups, N)
    g[:groups] = np.arange(groups)
    cols = _load(store, domain, N, a, b=b, g=g)
    _split(domain, t, store)
    if groups == 1:
        sql = "select 0, sum(a), sum(a * b), count(*), sum(d) from t"
    else:
        sql = ("select g, sum(a), sum(a * b), count(*), sum(d) from t"
               " group by g order by g")
    dev, cpu = _both(s, sql)
    want = _group_sums(cols[0], cols[1], cols[1] * cols[2].astype(object),
                       np.ones(N, np.int64), cols[3])
    got = [(r[0], int(r[1]), int(r[2]), int(r[3]),
            int(decimal.Decimal(str(r[4])) * 100)) for r in dev]
    assert got == want
    assert dev == cpu


@pytest.mark.parametrize("groups", [1, 6, 32, 33])
def test_nullable_argument_masked_group_and_shared_limbs(groups, path):
    """A NULLable argument counts its own rows; a group the selection
    masks out entirely is no row; `sum` and `avg` of one argument agree
    (one reduction serves both); 33 groups take the scatter path."""
    rng = np.random.default_rng(RNG_SEED + groups)
    domain, s, t, store = _fresh()
    g = rng.integers(0, groups, N)
    g[:groups] = np.arange(groups)
    a = rng.integers(-(1 << 17), 1 << 17, N)
    a[g == groups - 1] = -5          # the last group fails `a >= 0`...
    valid = rng.random(N) < 0.7
    cols = _load(store, domain, N, a, g=g, a_valid=valid)
    _split(domain, t, store)
    where = " where a >= -4 or a is null" if groups > 1 else ""
    sql = ("select g, sum(a), count(a), count(*), avg(a), sum(a + 1),"
           " min(a), max(a) from t" + where + " group by g order by g")
    dev, cpu = _both(s, sql)
    assert dev == cpu
    keep = (valid & (a >= -4)) | ~valid if groups > 1 else np.ones(N, bool)
    sums = dict((k, (sa, ca)) for k, sa, ca in _group_sums(
        g, a, np.ones(N, np.int64), mask=keep & valid))
    rows = dict((k, c) for k, c in _group_sums(
        g, np.ones(N, np.int64), mask=keep))
    assert [r[0] for r in dev] == sorted(rows)
    if groups > 1:
        # ...so it is there only through its NULL rows, with NULL sums
        last = [r for r in dev if r[0] == groups - 1]
        assert not last or last[0][1] is None
    for r in dev:
        sa, ca = sums.get(r[0], (None, 0))
        assert (None if r[1] is None else int(r[1])) == sa
        assert (int(r[2]), int(r[3])) == (ca, rows[r[0]])
        if ca:
            assert int(r[5]) == sa + ca
            assert abs(float(r[4]) - sa / ca) < 1e-3


@pytest.mark.parametrize("rows,engine", [
    (700, "mesh"), (3000, "mesh"), (8 * 8 * 1024 + 77, "mesh"),
    (700, "tile"), (3000, "tile")])
def test_any_shard_length(rows, engine, request):
    """Shards shorter than a block, not a whole number of tiles, and (the
    largest) whole groups of eight tiles a shard, where the mesh program
    sees its rows as [tile groups, blocks, 8, rows of a block]."""
    if engine == "tile":
        request.getfixturevalue("tile_path")
    rng = np.random.default_rng(rows)
    domain, s, t, store = _fresh()
    g = rng.integers(0, 6, rows)
    a = rng.integers(-(1 << 20), 1 << 20, rows)
    b = rng.integers(0, 1 << 12, rows)
    cols = _load(store, domain, rows, a, b=b, g=g)
    _split(domain, t, store, regions=4)
    sql = ("select g, sum(a), sum(a * b), count(*), sum(d), sum(f), min(a)"
           " from t where b < 4000 group by g order by g")
    dev, cpu = _both(s, sql)
    keep = b < 4000
    want = _group_sums(g, a, a * b, np.ones(rows, np.int64), cols[3],
                       mask=keep)
    got = [(r[0], int(r[1]), int(r[2]), int(r[3]),
            int(decimal.Decimal(str(r[4])) * 100)) for r in dev]
    assert got == want
    assert [r[:5] + r[6:] for r in dev] == [r[:5] + r[6:] for r in cpu]
    for x, y in zip(dev, cpu):
        assert abs(x[5] - y[5]) <= 1e-9 * max(1.0, abs(y[5]))


def _analyzed(s, store, sql):
    from tidb_tpu.copr import jax_engine as je
    from tidb_tpu.copr.ir import DAG
    from tidb_tpu.lint.kernelcheck import _reader_dags
    from tidb_tpu.parser import parse_one

    (_p, dag), = _reader_dags(s._plan(parse_one(sql)))
    return je._Analyzed(DAG.from_dict(dag.to_dict()), store)


def _compile_lanes(s):
    """`agg_lanes` of the statement's last `copr.compile` span."""
    spans = []

    def walk(sp):
        spans.append(sp)
        for c in sp.children:
            walk(c)

    walk(s.last_trace.root)
    return [sp.attrs.get("agg_lanes") for sp in spans
            if sp.name == "copr.compile"][-1]


def test_second_load_widens_a_column_and_the_program(path):
    """A load that carries a column past a limb compiles a new program
    (the bounds are in the fingerprint) and stays exact."""
    from tidb_tpu.copr import fusion
    from tidb_tpu.copr.jax_engine import _fingerprint

    rng = np.random.default_rng(RNG_SEED)
    domain, s, t, store = _fresh()
    sql = "select sum(a), count(*) from t"
    a1 = rng.integers(0, 1 << 15, N)
    _load(store, domain, N, a1)
    _split(domain, t, store)
    an1 = _analyzed(s, store, sql)
    assert fusion.agg_lanes(an1) == "i32:1"
    assert _ints(_both(s, sql)[0]) == [(int(a1.sum()), N)]
    a2 = rng.integers(0, 1 << 20, N)
    _load(store, domain, N, a2)
    _split(domain, t, store)
    an2 = _analyzed(s, store, sql)
    assert fusion.agg_lanes(an2) == "i32:2"
    assert _fingerprint(an1, "agg") != _fingerprint(an2, "agg")
    dev, cpu = _both(s, sql)
    assert _ints(dev) == [(int(a1.sum() + a2.sum()), 2 * N)] and dev == cpu
    # a load inside the same power of two keeps the program
    _load(store, domain, N, a2 // 2)
    assert _fingerprint(_analyzed(s, store, sql), "agg") \
        == _fingerprint(an2, "agg")


def test_agg_lanes_attribute_and_counters(path):
    rng = np.random.default_rng(RNG_SEED)
    domain, s, t, store = _fresh()
    _load(store, domain, N, rng.integers(-(1 << 20), 1 << 20, N),
          b=rng.integers(-(1 << 40), 1 << 40, N), g=rng.integers(0, 4, N))
    _split(domain, t, store)
    narrow0 = REGISTRY.get("copr_agg_narrow_total")
    wide0 = REGISTRY.get("copr_agg_wide_total")
    s.execute("trace select g, sum(a), avg(a), sum(a * 3), sum(d), count(*)"
              " from t group by g")
    # sum(a) and avg(a) are one argument; d is a decimal(15,2) under 2^17
    assert _compile_lanes(s) == "i32:2,i32:2,i32:2"
    assert REGISTRY.get("copr_agg_narrow_total") > narrow0
    assert REGISTRY.get("copr_agg_wide_total") == wide0
    s.execute("trace select g, sum(a), sum(b), sum(a * b) from t group by g")
    # a * b is within 2^60 a row and can pass int64 over the rows: on
    # the mesh its limb sums leave the device (fusion.wide_sums)
    assert _compile_lanes(s) == "i32:2,i64:4,i64:4;wide=2"
    assert REGISTRY.get("copr_agg_wide_total") > wide0


def test_benchmark_q1_rides_int32_lanes():
    """TPC-H Q1 as the benchmark writes it over dbgen's value ranges: all
    five summed arguments bounded, the charge (under 2^38) from 16-bit
    halves of the discounted price in three limbs."""
    import json
    import os

    from tidb_tpu.copr import fusion
    from tidb_tpu.tpch_data import build_lineitem

    here = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    with open(os.path.join(here, "benchmarks", "queries", "q1.json")) as f:
        q = json.load(f)
    s = build_lineitem(2048, regions=2)
    t = s.domain.catalog.info_schema().table("test", "lineitem")
    an = _analyzed(s, s.domain.storage.table(t.id),
                   q["sql"].format(**q["params"][2]))
    assert fusion.agg_lanes(an) == "i32:1,i32:2,i32:2,i32:3,i32:1"


def test_sum_and_avg_share_their_limbs():
    """One variadic reduce a program, and an argument's limbs in it once:
    `sum(a)`, `avg(a)`, `count(a)` and `count(*)` over a NULL-free column
    of two limbs are three operands a group."""
    import jax

    from tidb_tpu.copr.jax_engine import _build_tile_fn
    from tidb_tpu.lint.kernelcheck import _iter_eqns, canonical_inputs

    rng = np.random.default_rng(RNG_SEED)
    domain, s, t, store = _fresh()
    _load(store, domain, N, rng.integers(0, 1 << 20, N),
          g=rng.integers(0, 4, N))
    an = _analyzed(s, store, "select g, sum(a), avg(a), count(a), count(*)"
                             " from t group by g")
    col_order = an.needed_cols()
    fn = _build_tile_fn(an, "agg", col_order)

    def traced(*a):
        gcount, results = fn(*a)
        return gcount, [v for _t, v in results]

    closed = jax.make_jaxpr(traced)(*canonical_inputs(store, an, col_order))
    reduces = [e for e in _iter_eqns(closed.jaxpr)
               if e.primitive.name == "reduce"]
    assert len(reduces) == 1
    assert len(reduces[0].outvars) == 3 * an.num_groups


@pytest.mark.parametrize("tiles", [3, 8])
def test_float_sums_keep_the_parents_bits(tiles):
    """A float sum is the parent's emitter to the bit: one masked full
    reduction a group over the shard's rows in row order, whatever view
    the integer sums beside it take (8 tiles: the blocked view)."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import Mesh

    from tidb_tpu import ops
    from tidb_tpu.copr import jax_engine as je
    from tidb_tpu.copr import parallel as par

    n = tiles * je.TILE
    rng = np.random.default_rng(tiles)
    domain, s, t, store = _fresh()
    g = rng.integers(0, 6, n)
    f = rng.normal(scale=1e6, size=n) * rng.random(n)
    a = rng.integers(0, 1 << 20, n)
    _load(store, domain, n, a, g=g, f=f)
    an = _analyzed(s, store, "select g, sum(f), sum(a), avg(f) from t"
                             " where a > 1000 group by g")
    col_order = an.needed_cols()
    mesh = Mesh(np.array(jax.devices()[:1]), ("dp",))
    core = par._build_mesh_core(an, "agg", col_order, mesh,
                                tiles_per_shard=tiles)
    by_name = {"g": g, "a": a, "f": f}
    datas = tuple(
        by_name[store.cols[an.scan.columns[ci]].name].reshape(
            tiles, je.TILE) for ci in col_order)
    gcount, results = jax.jit(core)(
        datas, tuple(None for _ in datas),
        np.ones((tiles, je.TILE), np.bool_),
        par._bounds_args([(0, n)]), ())
    mask = jnp.asarray(a > 1000)
    parent = ops.masked_segment_sum(jnp.asarray(f), jnp.asarray(g), mask, 6)
    assert np.asarray(results[0][0]).tobytes() == \
        np.asarray(parent).tobytes()
    assert np.asarray(results[2][0]).tobytes() == \
        np.asarray(parent).tobytes()
    want = _group_sums(g, a, np.ones(n, np.int64), mask=a > 1000)
    assert [int(x) for x in np.asarray(results[1][0])] == \
        [w[1] for w in want]
    assert [int(x) for x in np.asarray(gcount)] == [w[2] for w in want]


def test_bounded_int_declines_what_it_cannot_bound():
    """The evaluator's own contract, without a device: bounds by interval
    arithmetic, int32 terms only, None past 2^62 or for an operator it
    does not cover."""
    from tidb_tpu.copr.jax_eval import DRY, bounded_int, lane_limbs
    from tidb_tpu.copr.fusion import AGG_LIMB

    domain, s, t, store = _fresh()
    _load(store, domain, 8, np.arange(8))

    def lanes(expr, **bounds):
        an = _analyzed(s, store, f"select sum({expr}) from t")
        names = [store.cols[c].name for c in an.scan.columns]
        wire = {i: (DRY, *bounds[nm], False)
                for i, nm in enumerate(names) if nm in bounds}
        return bounded_int(an.agg.aggs[0].args[0], wire)

    v = lanes("a * b", a=(0, (1 << 24) - 1), b=(85, 100))
    assert v.bounds() == (0, ((1 << 24) - 1) * 100) and len(v.terms) == 1
    assert len(lane_limbs(v, AGG_LIMB)) == 2
    # past int32: formed from 16-bit halves of the wider factor
    v = lanes("a * b", a=(0, (1 << 31) - 1), b=(100, 115))
    assert len(v.terms) == 2 and v.bounds()[1] == ((1 << 31) - 1) * 115
    assert len(lane_limbs(v, AGG_LIMB)) == 3
    v = lanes("a - 7", a=(-5, 9))
    assert v.const == -7 and v.bounds() == (-12, 2)
    v = lanes("-(a + b)", a=(0, 10), b=(-3, 3))
    assert v.bounds() == (-13, 3)
    assert lanes("a * b", a=(0, 1 << 31), b=(0, 9)) is None   # a past int32
    assert lanes("a * b", a=(0, (1 << 31) - 1),
                 b=(0, (1 << 31) - 1)) is None                # both wide
    assert lanes("a div b", a=(0, 9), b=(1, 9)) is None
    assert lanes("a + f", a=(0, 9)) is None
