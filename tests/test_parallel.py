"""Mesh-parallel scan path (copr/parallel.py): shard_map + collectives.

These tests run on the 8-virtual-CPU-device mesh (conftest) with TILE=1024,
so a 20k-row table spans ~20 tiles across all 8 shards — the cross-tile
merge, cross-shard psum/pmin/pmax, deletion masks beyond tile 0, and the
device cache all execute.  Parity is asserted against the CPU oracle engine.
"""

import numpy as np
import pytest

import jax

from tidb_tpu.metrics import REGISTRY
from tidb_tpu.session import Domain


def _approx_eq(a, b):
    if isinstance(a, float) or isinstance(b, float):
        return a == pytest.approx(b, rel=1e-9, abs=1e-9)
    return a == b


def _parity(sess, sql):
    sess.execute("set tidb_use_tpu = 1")
    tpu = sess.query(sql)
    sess.execute("set tidb_use_tpu = 0")
    cpu = sess.query(sql)
    sess.execute("set tidb_use_tpu = 1")
    assert len(tpu) == len(cpu), (sql, tpu, cpu)
    for ra, rb in zip(tpu, cpu):
        assert all(_approx_eq(x, y) for x, y in zip(ra, rb)), (sql, ra, rb)
    return tpu


@pytest.fixture(scope="module")
def sess():
    d = Domain()
    s = d.new_session()
    s.execute(
        "create table t (k bigint, g bigint, x double, s varchar(10), "
        "d decimal(10,2))"
    )
    t = d.catalog.info_schema().table("test", "t")
    store = d.storage.table(t.id)
    rng = np.random.default_rng(3)
    n = 20_000
    names = np.array(["aa", "bb", "cc"], dtype=object)
    store.bulk_load_arrays(
        [
            np.arange(n, dtype=np.int64),
            rng.integers(0, 7, n, dtype=np.int64),
            rng.uniform(0, 100, n),
            names[rng.integers(0, 3, n)],
            rng.integers(0, 10_000, n, dtype=np.int64),  # scaled .2
        ],
        ts=d.storage.current_ts(),
    )
    d.storage.regions.split_even(t.id, 4, store.base_rows)
    return s


def _mesh_count():
    return REGISTRY.snapshot().get("mesh_scans_total", 0)


def test_mesh_used_and_sharded(sess):
    """The query must go through the mesh program, and the cached tile
    arrays must actually be laid out across every device (not replicated,
    not single-device)."""
    from tidb_tpu.copr.parallel import MESH_CACHE

    # the cache is the process's: an earlier test's entries (another
    # domain's table of the same id, laid out while a breaker was open)
    # must not stand in for this query's
    MESH_CACHE.clear()
    before = _mesh_count()
    sess.execute("set tidb_use_tpu = 1")
    sess.query("select g, count(*) from t group by g")
    assert _mesh_count() > before, "query did not take the mesh path"

    assert MESH_CACHE._cache, "mesh cache empty"
    data, _valid = next(iter(MESH_CACHE._cache.values()))
    used = {s.device for s in data.addressable_shards}
    assert len(used) == len(jax.devices()), (
        f"tiles on {len(used)} devices, expected {len(jax.devices())}"
    )


def test_mesh_agg_parity(sess):
    _parity(
        sess,
        "select g, sum(x), count(*), min(x), max(x), avg(x), sum(d) from t "
        "where k < 15000 and s != 'bb' group by g order by g",
    )


def test_mesh_agg_no_groupby(sess):
    _parity(sess, "select sum(x), count(*), min(k), max(k) from t "
                  "where x between 10 and 60")


def test_mesh_string_group_key(sess):
    _parity(sess, "select s, count(*), avg(x) from t group by s order by s")


def test_mesh_topn_parity(sess):
    _parity(sess, "select k, x from t where s = 'aa' order by x desc limit 9")
    _parity(sess, "select k, x from t order by x limit 5")


def test_mesh_filter_parity(sess):
    r = _parity(sess, "select k from t where x < 0.5 and s != 'cc' order by k")
    assert len(r) > 0


def test_mesh_limit(sess):
    sess.execute("set tidb_use_tpu = 1")
    rows = sess.query("select k from t where x < 50 limit 13")
    assert len(rows) == 13


def test_mesh_with_deletes_and_updates(sess):
    """MVCC delta overlay on the mesh path: deletes mask rows in high tiles,
    updates surface through the CPU delta merge."""
    sess.execute("set tidb_use_tpu = 1")
    sess.execute("delete from t where k >= 18000 and k < 18500")
    sess.execute("update t set x = 1000000.0 where k = 19000")
    _parity(sess, "select g, count(*), sum(x) from t group by g order by g")
    _parity(sess, "select k, x from t order by x desc limit 3")
    rows = sess.query("select max(x) from t")
    assert rows[0][0] == pytest.approx(1000000.0)
    cnt = sess.query("select count(*) from t where k >= 18000 and k < 18500")
    assert cnt == [(0,)]


def test_mesh_first_row_groupkey(sess):
    """first_row partials (SELECT of a group key col) resolve globally."""
    _parity(sess, "select s, min(k) from t group by s order by s")


@pytest.fixture(scope="module")
def ndv_sess():
    """High-NDV / float / NULLable group keys -> the sort-based device agg."""
    d = Domain()
    s = d.new_session()
    s.execute("create table h (k bigint, f double, g bigint, x double)")
    t = d.catalog.info_schema().table("test", "h")
    store = d.storage.table(t.id)
    rng = np.random.default_rng(5)
    n = 30_000
    gv = rng.integers(0, 200_000, n)       # NDV far beyond the 64k dense cap
    gvalid = rng.random(n) > 0.02          # ~2% NULL keys
    fv = np.round(rng.uniform(0, 3, n), 1)
    store.bulk_load_arrays(
        [np.arange(n, dtype=np.int64), fv, gv.astype(np.int64),
         rng.uniform(0, 10, n)],
        valids=[None, None, gvalid, None],
        ts=d.storage.current_ts(),
    )
    d.storage.regions.split_even(t.id, 5, store.base_rows)
    return s


def _sort_parity(sess, sql):
    e0 = REGISTRY.snapshot().get("mesh_scan_errors_total", 0)
    m0 = _mesh_count()
    rows = _parity(sess, sql)
    assert _mesh_count() > m0, f"not on the mesh path: {sql}"
    assert REGISTRY.snapshot().get("mesh_scan_errors_total", 0) == e0
    return rows


def test_sort_agg_high_ndv(ndv_sess):
    rows = _sort_parity(
        ndv_sess,
        "select g, count(*), sum(x), min(x), max(x), avg(x) from h "
        "group by g order by g limit 50",
    )
    assert len(rows) == 50


def test_sort_agg_null_key_group(ndv_sess):
    """NULL is its own group and must survive the device path."""
    rows = _sort_parity(
        ndv_sess, "select count(*) from h where g is null")
    assert rows[0][0] > 0


def test_sort_agg_float_key(ndv_sess):
    rows = _sort_parity(
        ndv_sess, "select f, count(*), sum(x) from h group by f order by f")
    assert len(rows) == 31


def test_sort_agg_multi_key(ndv_sess):
    _sort_parity(
        ndv_sess,
        "select f, g, count(*) from h where g < 1000 "
        "group by f, g order by f, g",
    )


def test_sort_agg_first_row_key(ndv_sess):
    """Selecting a group key column uses first_row partials."""
    _sort_parity(
        ndv_sess,
        "select g, min(k) from h where g < 5000 group by g order by g",
    )


@pytest.fixture(scope="module")
def q3_sess():
    """customer ⋈ orders ⋈ lineitem with the fact scan on the mesh."""
    from tidb_tpu.types.values import parse_date

    d = Domain()
    s = d.new_session()
    rng = np.random.default_rng(2)
    s.execute("create table customer (c_custkey bigint, c_mktsegment varchar(10))")
    s.execute("create table orders (o_orderkey bigint, o_custkey bigint, "
              "o_orderdate date, o_shippriority bigint)")
    s.execute("create table lineitem (l_orderkey bigint, l_extendedprice double, "
              "l_discount double, l_shipdate date)")
    nc, no, nl = 1000, 4000, 20000
    segs = np.array(["BUILDING", "AUTOMOBILE", "MACHINERY"], dtype=object)
    base = parse_date("1995-01-01")
    for name, arrays in (
        ("customer", [np.arange(1, nc + 1, dtype=np.int64),
                      segs[rng.integers(0, 3, nc)]]),
        ("orders", [np.arange(1, no + 1, dtype=np.int64),
                    rng.integers(1, nc + 1, no).astype(np.int64),
                    (base + rng.integers(-200, 200, no)).astype(np.int32),
                    rng.integers(0, 3, no).astype(np.int64)]),
        ("lineitem", [rng.integers(1, no + 1, nl).astype(np.int64),
                      rng.uniform(900, 100000, nl),
                      np.round(rng.uniform(0, 0.1, nl), 2),
                      (base + rng.integers(-200, 200, nl)).astype(np.int32)]),
    ):
        t = d.catalog.info_schema().table("test", name)
        d.storage.table(t.id).bulk_load_arrays(
            arrays, ts=d.storage.current_ts())
    lt = d.catalog.info_schema().table("test", "lineitem")
    d.storage.regions.split_even(lt.id, 6, d.storage.table(lt.id).base_rows)
    return s


Q3 = """
select l_orderkey, sum(l_extendedprice * (1 - l_discount)), o_orderdate,
       o_shippriority
from customer, orders, lineitem
where c_mktsegment = 'BUILDING' and c_custkey = o_custkey
  and l_orderkey = o_orderkey
  and o_orderdate < '1995-03-15' and l_shipdate > '1995-03-15'
group by l_orderkey, o_orderdate, o_shippriority
order by 2 desc, o_orderdate limit 10
"""


def test_q3_plans_runtime_filter(q3_sess):
    """With MPP lanes off, Q3 keeps the host hash-join plan whose build
    side pushes a runtime filter into the probe scan.  (With MPP on the
    join-tree compiler now owns this shape end-to-end — ISSUE 12 — so
    the runtime-filter lane is the fallback under test here.)"""
    q3_sess.execute("set tidb_allow_mpp = 0")
    try:
        rs = q3_sess.execute("explain " + Q3)[0]
    finally:
        q3_sess.execute("set tidb_allow_mpp = 1")
    plan = "\n".join(str(r) for r in rs.rows)
    assert "JoinProbe" in plan, plan
    assert "runtime-filter" in plan, plan


def test_q3_plans_device_join_tree(q3_sess):
    """The default plan for the Q3 shape is now the device rung ladder
    with the chosen join order and per-rung estimates."""
    rs = q3_sess.execute("explain " + Q3)[0]
    plan = "\n".join(str(r) for r in rs.rows)
    assert "MPPJoinTree" in plan, plan
    assert "order: " in plan, plan


def test_q3_parity_with_device_probe(q3_sess):
    e0 = REGISTRY.snapshot().get("mesh_scan_errors_total", 0)
    _parity(q3_sess, Q3)
    assert REGISTRY.snapshot().get("mesh_scan_errors_total", 0) == e0


def test_runtime_filter_semi_join(q3_sess):
    _parity(
        q3_sess,
        "select count(*) from lineitem where l_orderkey in "
        "(select o_orderkey from orders where o_orderdate < '1994-09-01')",
    )


def test_runtime_filter_null_probe_keys():
    """Probe rows with NULL keys never pass the device filter."""
    d = Domain()
    s = d.new_session()
    s.execute("create table bb (k bigint, v bigint)")
    s.execute("create table pp (k bigint, w bigint)")
    s.execute("insert into bb values (1, 1), (2, 2)")
    s.execute("insert into pp values (1, 10), (null, 99), (2, 20)")
    rows = sorted(s.query(
        "select pp.w, bb.v from pp join bb on pp.k = bb.k"))
    assert rows == [(10, 1), (20, 2)]


def test_mesh_multi_range_not_used():
    """>4 disjoint ranges falls back to the per-region path but stays
    correct."""
    d = Domain()
    s = d.new_session()
    s.execute("create table m (a bigint, b bigint)")
    s.execute("insert into m values " + ", ".join(
        f"({i}, {i * 2})" for i in range(100)
    ))
    assert s.query("select sum(b) from m") == [(sum(i * 2 for i in range(100)),)]


def test_dense_first_row_bare_column(sess):
    """A bare non-grouped column becomes a first_row agg: exercises the
    dense-mode per-shard argfirst partial + host min-merge (only Sum
    all-reduces are used on device, so first_row does not pmin)."""
    before = REGISTRY.snapshot()
    _parity(sess, "select g, s, min(k) from t group by g order by g")
    after = REGISTRY.snapshot()
    assert after.get("mesh_scans_total", 0) > before.get("mesh_scans_total", 0)
    assert after.get("mesh_scan_errors_total", 0) == \
        before.get("mesh_scan_errors_total", 0)


def test_dense_minmax_partial_merge(sess):
    """min/max partials are per-shard (host-merged): cover groups that are
    empty on some shards via a selective filter."""
    _parity(sess, "select g, min(d), max(d), min(x), max(x) from t "
                  "where k < 1500 group by g order by g")


def test_filter_results_stream_in_bounded_chunks():
    """Low-selectivity mesh filters gather selected rows in STREAM_ROWS
    slices (distsql/stream.go analog): peak host materialization per step
    is bounded, and LIMIT stops the gather early (VERDICT r2 item 9)."""
    from tidb_tpu.copr import parallel as pp

    d = Domain()
    s = d.new_session()
    s.execute("create table st (a bigint, b bigint)")
    t = d.catalog.info_schema().table("test", "st")
    n = 60_000
    d.storage.table(t.id).bulk_load_arrays(
        [np.arange(n, dtype=np.int64),
         np.arange(n, dtype=np.int64) % 7],
        ts=d.storage.current_ts())
    s.execute("set tidb_use_tpu = 1")
    orig = pp.STREAM_ROWS
    pp.STREAM_ROWS = 4096
    try:
        before = REGISTRY.snapshot().get("mesh_stream_chunks_total", 0)
        rows = s.query("select a from st where b < 6")  # ~86% selectivity
        after = REGISTRY.snapshot().get("mesh_stream_chunks_total", 0)
        assert len(rows) == sum(1 for i in range(n) if i % 7 < 6)
        assert after - before >= len(rows) / 4096  # many bounded chunks
        # LIMIT early-stop: only ~1 slice gathered despite ~51k matches
        before = REGISTRY.snapshot().get("mesh_stream_chunks_total", 0)
        rows = s.query("select a from st where b < 6 limit 10")
        after = REGISTRY.snapshot().get("mesh_stream_chunks_total", 0)
        assert len(rows) == 10
        assert after - before <= 2
    finally:
        pp.STREAM_ROWS = orig


@pytest.fixture(scope="module")
def sess4(sess):
    """A scale-4 decimal column beside `t`, base rows on the mesh."""
    d = sess.domain
    sess.execute("create table t4 (k bigint primary key, c decimal(12,4))")
    t = d.catalog.info_schema().table("test", "t4")
    n = 5_000
    d.storage.table(t.id).bulk_load_arrays(
        [np.arange(n, dtype=np.int64),
         np.arange(n, dtype=np.int64) * 10_000 + 1234],  # k.1234
        ts=d.storage.current_ts())
    sess.execute("analyze table t4")
    return sess


@pytest.mark.parametrize("where, on_device, rows", [
    # folded 0.25 at scale 2, raised to the column's scale 4 on the device
    ("c >= 0.5 * 0.5", True, 4_999),
    ("c between 0.5 * 0.5 and 100.5 + 0.5", True, 100),
    # 10**16 at scale 1 (17 digits): * 1000 would wrap int64 on the device
    # and match no row; the conjunct keeps the exact host path instead
    ("c < 999999999999999.5 + 0.5", False, 5_000),
    ("c between 0.5 * 0.5 and 999999999999999.5 + 0.5", False, 4_999),
    ("c + (999999999999999.5 + 0.5) > 0", False, 5_000),
    ("c in (999999999999999.5 + 0.5, 5.1234)", False, 1),
    ("c < 9999999999999999.5", False, 5_000),
])
def test_folded_decimal_bound_beside_finer_column(sess4, where, on_device,
                                                  rows):
    sql = "select count(*), sum(c) from t4 where " + where
    sess4.execute("set tidb_use_tpu = 1")
    plan = sess4.execute("explain " + sql)[0].rows
    cop = [r[0] for r in plan if r[2] == "cop[tpu]"]
    assert any("Selection" in op for op in cop) is on_device, plan
    assert any("Aggregation" in op for op in cop) is on_device, plan
    before = _mesh_count()
    got = _parity(sess4, sql)
    assert _mesh_count() > before
    assert got[0][0] == rows


# ---------------------------------------------------------------------------
# the operands of a dispatch reach the device inside the call (ISSUE 33)
# ---------------------------------------------------------------------------

def _is_host(a, dtype):
    return isinstance(a, np.ndarray) and a.dtype == dtype


@pytest.mark.parametrize("bounds, scalars", [
    ([], ()), ([(5, 9)], ()), ([(3, 1 << 62), (7, 9)], (4, 1 << 62)),
    ([(0, 1), (2, 3), (4, 5), (6, 1 << 62)], (11,)),
])
def test_operand_vector_is_one_host_int64_array(bounds, scalars):
    """Building the operand vector starts no device work (a `jnp.int64(x)`
    is a transfer and a program of its own), pads the range slots with
    empty ranges, keeps 2^62 exact, and traces as non-weak int64."""
    from tidb_tpu.copr import parallel as pl

    with jax.transfer_guard_host_to_device("disallow"):
        out = pl._bounds_args(bounds, scalars)
    slots = 2 * pl.MESH_RANGE_SLOTS
    assert _is_host(out, np.int64) and out.shape == (slots + len(scalars),)
    flat = [x for lohi in bounds for x in lohi]
    assert out.tolist() == (flat + [0] * (slots - len(flat))
                            + list(scalars))
    aval = jax.typeof(out)
    assert aval.dtype == np.int64 and not aval.weak_type


def test_call_args_hands_over_host_operands_without_device_work():
    from tidb_tpu.copr import parallel as pl
    from tidb_tpu.trace import recorder

    col = jax.numpy.zeros((8, 4), dtype=np.int32)
    keys = jax.numpy.arange(16)
    pf = np.array([0.5], np.float64)
    tr, token = recorder.start_trace("q")
    try:
        with jax.transfer_guard_host_to_device("disallow"):
            args = pl._call_args([col], [None], col, [(1, 5)], (),
                                 (keys, pf), np.array([3, 7, 1 << 62]))
    finally:
        recorder.finish_trace(tr, token)
    assert args[0] == (col,) and args[2] is col and args[4] == ()
    assert args[5] is keys and args[6] is pf and len(args) == 7
    assert _is_host(args[3], np.int64)
    assert args[3].tolist() == [1, 5, 0, 0, 0, 0, 0, 0, 3, 7, 1 << 62]
    (sp,) = [s for s in tr.root.children if s.name == "copr.args"]
    # the operand vector and the float parameters; the resident key set
    # is not counted
    assert sp.attrs == {"operands": 2, "bytes": 11 * 8 + 8}


_OPERAND_SQL = {
    "dense_agg": "select g, count(*), sum(x), min(d), max(x) from t"
                 " where x < 61.5 and k >= 100 group by g",
    "sort_agg": "select x, count(*), sum(d) from t where d > 2.5"
                " group by x",
    "topn": "select k, x from t where x > 3.25 order by x desc limit 7",
    "filter": "select k, d from t where x < 2.5 and d > 10",
}
_OPERAND_RANGES = {
    1: [(0, 20_000)],
    2: [(100, 6_000), (11_000, 19_999)],
    3: [(0, 2_048), (4_097, 9_000), (12_345, 1 << 62)],  # one pad slot
    4: [(0, 3_000), (5_000, 5_001), (7_000, 12_345), (15_000, 1 << 62)],
}


def _run_over_ranges(sess, sql, ranges, use_tpu):
    """The statement's rows with its one table reader held to `ranges`
    (SQL alone only ever asks for the whole table)."""
    from tidb_tpu.executor import collect_all
    from tidb_tpu.parser import parse_one
    from tidb_tpu.planner.physical import PhysTableReader
    from tidb_tpu.store.kv import KeyRange

    sess.execute(f"set tidb_use_tpu = {use_tpu}")
    phys = sess._plan(parse_one(sql))
    readers, todo = [], [phys]
    while todo:
        p = todo.pop()
        todo.extend(p.children)
        if isinstance(p, PhysTableReader):
            readers.append(p)
    (reader,) = readers
    whole = reader.ranges
    reader.ranges = [KeyRange(whole[0].table_id, a, b) for a, b in ranges]
    try:
        chunks = collect_all(phys.build(sess._exec_ctx()))
    finally:
        reader.ranges = whole
    return [r for c in chunks for r in c.to_pylist()]


@pytest.mark.parametrize("hoisted", [True, False],
                         ids=["hoisted", "literal"])
@pytest.mark.parametrize("n_ranges", [1, 2, 3, 4],
                         ids=["1range", "2ranges", "3ranges", "4ranges"])
@pytest.mark.parametrize("kind", sorted(_OPERAND_SQL))
def test_mesh_dispatch_over_ranges_equals_the_oracle(
        sess, monkeypatch, kind, n_ranges, hoisted):
    """Every kind of mesh program, handed its range slots and parameter
    vectors as host values in ONE dispatch, returns what the oracle
    engine returns."""
    from tidb_tpu import serving
    from tidb_tpu.copr import parallel as pl
    from tidb_tpu.trace import recorder

    calls, sorted_aggs = [], []
    real, real_sort = pl._call_args, pl._sort_agg_chunks

    def spy(*a):
        with jax.transfer_guard_host_to_device("disallow"):
            calls.append(real(*a))
        return calls[-1]

    def sort_spy(*a):
        sorted_aggs.append(1)
        return real_sort(*a)

    monkeypatch.setattr(pl, "_call_args", spy)
    monkeypatch.setattr(pl, "_sort_agg_chunks", sort_spy)
    sql, ranges = _OPERAND_SQL[kind], _OPERAND_RANGES[n_ranges]
    serving.configure(shape_buckets=hoisted)
    tr, token = recorder.start_trace(sql)
    try:
        got = _run_over_ranges(sess, sql, ranges, 1)
    finally:
        recorder.finish_trace(tr, token)
        serving.configure(shape_buckets=True)
    assert len(calls) == 1, "not on the mesh path, or not one dispatch"
    passes, todo = [], [tr.root]
    while todo:
        sp = todo.pop()
        todo.extend(sp.children)
        if sp.name == "copr.chunk":
            passes.append(sp)
    assert len(passes) == 1
    assert {sp.attrs["kind"] for sp in passes} == {
        "agg" if kind.endswith("agg") else kind}
    assert bool(sorted_aggs) is (kind == "sort_agg")
    want = _run_over_ranges(sess, sql, ranges, 0)
    assert len(calls) == 1, "the oracle dispatched to the mesh"
    if kind != "topn":  # the aggregates' and the filter's rows are a set
        got, want = sorted(got, key=repr), sorted(want, key=repr)
    assert len(got) == len(want) > 0
    for ra, rb in zip(got, want):
        assert all(_approx_eq(x, y) for x, y in zip(ra, rb)), (ra, rb)
    (args,) = calls
    # one int64 vector (range slots, then the hoisted int64
    # parameters) and at most one float64 parameter vector
    assert _is_host(args[3], np.int64) and len(args) <= 6
    assert all(_is_host(pf, np.float64) and pf.size for pf in args[5:])
    assert (len(args[3]) > 8 or len(args) > 5) is hoisted
    base = 20_000
    assert args[3][:8].tolist() == (
        [min(x, base) for lohi in ranges for x in lohi]
        + [0] * (8 - 2 * len(ranges)))
