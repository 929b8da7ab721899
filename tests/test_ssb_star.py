"""The Star Schema Benchmark's star joins (configuration `ssb-sf1-star`).

- The SSB generator (`benchmarks/generators/ssb.py`) is deterministic by
  seed and makes every column the configuration lists.
- Each of Q2.1-Q4.3 plans as ONE DeviceJoinReader whose cop DAG chains a
  lookup into each of its dimensions (3 or 4), and no join tree.
- Served over the wire, all 13 queries equal the plain reference
  (`benchmarks/queries/_ssb.py`) on both engines, over a mesh of one device
  and of eight.
- A dimension filter that removes every row gives the empty result.
- A star whose aggregate is not a star's (a group key from the fact, a
  `max`) leaves the mesh before a dimension string reaches the device,
  and answers as the host engine.
- `benchmarks/run.py` rehearses `sf1-ssb` correct, and catches the float32
  control; the cell's readers read hand-made runs as they should.
"""

import importlib
import json
import os
import sys

import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BENCH = os.path.join(ROOT, "benchmarks")
QUERIES = ["ssb11", "ssb12", "ssb13", "ssb21", "ssb22", "ssb23", "ssb31",
           "ssb32", "ssb33", "ssb34", "ssb41", "ssb42", "ssb43"]
#: the dimensions each star query joins: one lookup each
LOOKUPS = {"ssb21": 3, "ssb22": 3, "ssb23": 3, "ssb31": 3, "ssb32": 3,
           "ssb33": 3, "ssb34": 3, "ssb41": 4, "ssb42": 4, "ssb43": 4}
SF, SEED = 0.01, 2**31 + 45


@pytest.fixture(scope="module")
def bench():
    """`benchmarks/` and the root on `sys.path`, as `run.py` puts them."""
    added = [p for p in (BENCH, ROOT) if p not in sys.path]
    for p in added:
        sys.path.insert(0, p)
    yield
    for p in added:
        sys.path.remove(p)


def _config():
    with open(os.path.join(BENCH, "configs", "ssb-sf1-star.json")) as f:
        return json.load(f)


def _query(name):
    with open(os.path.join(BENCH, "queries", name + ".json")) as f:
        return json.load(f)


@pytest.fixture(scope="module")
def loaded(bench):
    from harness import serve

    return serve.load(_config(), SEED, SF, lambda _note: None)[:2]


def test_the_generator_is_deterministic_by_seed_and_makes_every_column(
        bench):
    gen = importlib.import_module("generators.ssb")
    want = _config()["tables"]

    def made(seed):
        out = {}
        for table, block in gen.blocks(set(want), SF, seed):
            for c, a in block.items():
                out.setdefault((table, c), []).append(a)
        return {k: np.concatenate(v) for k, v in out.items()}

    a, b, c = made(SEED), made(SEED), made(SEED + 1)
    assert {t for t, _c in a} == set(want)
    for table, spec in want.items():
        for col in spec["columns"]:
            assert (table, col) in a, (table, col)
    assert a.keys() == b.keys()
    assert all(np.array_equal(a[k], b[k]) for k in a)
    assert not np.array_equal(a[("lineorder", "lo_revenue")][:100],
                              c[("lineorder", "lo_revenue")][:100])
    lo = {col: a[("lineorder", col)] for _t, col in a if _t == "lineorder"}
    assert len(a[("date", "d_datekey")]) == 2556
    assert lo["lo_quantity"].min() >= 1 and lo["lo_quantity"].max() <= 50
    assert lo["lo_discount"].min() >= 0 and lo["lo_discount"].max() <= 10
    assert np.array_equal(lo["lo_revenue"].astype(np.int64), (
        lo["lo_extendedprice"].astype(np.int64)
        * (100 - lo["lo_discount"]) // 100))
    assert np.isin(lo["lo_orderdate"], a[("date", "d_datekey")]).all()
    for col, sorted_ in gen.DICTIONARIES.items():
        assert sorted_ == sorted(sorted_), col
    assert "UNITED KI1" in gen.DICTIONARIES["c_city"]
    assert gen.DICTIONARIES["p_brand1"][:2] == ["MFGR#111", "MFGR#1110"]


@pytest.mark.parametrize("name", sorted(LOOKUPS))
def test_a_star_query_is_one_device_join_reader_with_a_lookup_a_dimension(
        loaded, name):
    domain, _tables = loaded
    ops = [r[0].strip().lstrip("└─") for r in domain.new_session().execute(
        "explain " + _query(name)["sql"])[0].rows]
    assert sum(op.startswith("DeviceJoinReader") for op in ops) == 1, ops
    assert ops.count("JoinLookup") == LOOKUPS[name], ops
    assert not any(op.startswith("MPPJoinTree") for op in ops), ops


@pytest.fixture
def mesh_of(request):
    """The mesh the statements run on: one device, or all eight."""
    from tidb_tpu.copr import parallel

    n = request.param
    devs, epoch = parallel._eligible_devices()
    mp = pytest.MonkeyPatch()
    mp.setattr(parallel, "_eligible_devices", lambda: (devs[:n], epoch))
    parallel.MESH_CACHE.clear()
    try:
        yield n
    finally:
        mp.undo()
        parallel.MESH_CACHE.clear()


@pytest.mark.parametrize("mesh_of", [1, 8], indirect=True)
@pytest.mark.parametrize("engine", [1, 0])
def test_served_answers_equal_the_reference(loaded, mesh_of, engine):
    from harness import compare, serve

    from tidb_tpu.metrics import REGISTRY, STAR_LOOKUPS

    domain, tables = loaded
    srv = serve.Served(domain)
    before = REGISTRY.snapshot().get(STAR_LOOKUPS, 0)
    try:
        cli = srv.client()
        try:
            cli.query(f"set tidb_use_tpu = {engine}")
            for name in QUERIES:
                ref = importlib.import_module(f"queries.{name}")
                _types, got = cli.query(_query(name)["sql"])
                want = ref.reference(tables, {})
                assert compare.same_rows(got, want), (name, got[:3],
                                                      want[:3])
        finally:
            cli.close()
    finally:
        srv.stop()
    # on the device every statement is one mesh program with all of its
    # lookups chained in it: 3 x 1 + 7 x 3 + 3 x 4
    lookups = REGISTRY.snapshot().get(STAR_LOOKUPS, 0) - before
    assert lookups == (36 if engine else 0)


def test_join_trees_that_differ_in_an_earlier_build_side_keep_apart(
        loaded):
    """With the exchange engine pinned the stars run as join trees: Q2.1
    and Q2.3 differ only in the columns of their first build side (part),
    so their later rungs' inputs differ in width; each tree's programs
    are its own (a later rung's program was once taken from the other
    tree and failed on its operands)."""
    domain, tables = loaded
    sess = domain.new_session()
    sess.execute("set tidb_enforce_mpp = 1")
    for name in ("ssb21", "ssb23", "ssb21", "ssb22"):
        sql = _query(name)["sql"]
        ops = [r[0] for r in sess.execute("explain " + sql)[0].rows]
        assert any("MPPJoinTree" in op for op in ops), ops
        ref = importlib.import_module(f"queries.{name}")
        got = [tuple(str(v) if isinstance(v, str) else v for v in r)
               for r in sess.query(sql)]
        assert got == ref.reference(tables, {}), name


@pytest.mark.parametrize("engine", [1, 0])
def test_a_dimension_filter_that_removes_every_row_gives_no_rows(
        loaded, engine):
    domain, _tables = loaded
    sess = domain.new_session()
    sess.execute(f"set tidb_use_tpu = {engine}")
    grouped = _query("ssb21")["sql"].replace("'MFGR#12'", "'MFGR#99'")
    assert sess.query(grouped) == []
    scalar = ("select sum(lo_revenue) from lineorder, date, supplier "
              "where lo_orderdate = d_datekey and lo_suppkey = s_suppkey "
              "and s_region = 'ATLANTIS'")
    assert sess.query(scalar) == [(None,)]


@pytest.mark.parametrize("mesh_of", [1, 8], indirect=True)
def test_a_star_with_null_keys_and_values_equals_the_host_engine(mesh_of):
    """SSB has no NULL: here a dimension string that is NULL is a group of
    its own, a NULL summed value counts in `count(*)` and not in
    `count(v)`, `sum` or `avg`, and a dimension filter drops rows; the
    device's star answers as the host engine does."""
    from tidb_tpu.metrics import REGISTRY, STAR_LOOKUPS
    from tidb_tpu.session import Domain

    sess = Domain().new_session()
    sess.execute("create table f (a bigint, b bigint, v bigint)")
    sess.execute("create table d1 (k bigint primary key, s varchar(10))")
    sess.execute("create table d2 (k bigint primary key, t bigint)")
    rng = np.random.default_rng(7)
    n = 3000
    fact = sess.domain.storage.table(
        sess.domain.catalog.info_schema().table("test", "f").id)
    fact.bulk_load_arrays(
        [rng.integers(0, 12, n), rng.integers(1, 8, n),
         rng.integers(-50, 1000, n)],
        valids=[None, None, np.arange(n) % 5 != 0],
        ts=sess.domain.storage.current_ts())
    sess.execute("insert into d1 values " + ",".join(
        f"({k}, {'NULL' if k % 4 == 0 else repr('s' + str(k % 3))})"
        for k in range(1, 11)))
    sess.execute("insert into d2 values " + ",".join(
        f"({k}, {k % 3})" for k in range(1, 7)))
    for t in ("f", "d1", "d2"):
        sess.execute(f"analyze table {t}")
    sql = ("select s, t, sum(v), count(v), count(*), avg(v) from f, d1, d2 "
           "where f.a = d1.k and f.b = d2.k and d2.t < 2 "
           "group by s, t order by s, t")
    ops = [r[0].strip().lstrip("└─") for r in sess.execute(
        "explain " + sql)[0].rows]
    assert ops.count("JoinLookup") == 2, ops
    before = REGISTRY.snapshot().get(STAR_LOOKUPS, 0)
    sess.execute("set tidb_use_tpu = 1")
    got = sess.query(sql)
    assert REGISTRY.snapshot().get(STAR_LOOKUPS, 0) - before == 2
    sess.execute("set tidb_use_tpu = 0")
    want = sess.query(sql)
    assert got == want and len(want) == 8
    assert any(r[0] is None for r in want)


#: a star outside the star aggregate: a group key from the fact table, or
#: an aggregate that is not a count or a sum
OUTSIDE_STAR = [
    "select f.a, s, sum(v) from f, d1, d2 where f.a = d1.k and f.b = d2.k "
    "group by f.a, s order by f.a, s",
    "select s, max(v), min(v) from f, d1, d2 where f.a = d1.k "
    "and f.b = d2.k group by s order by s",
]


@pytest.mark.parametrize("mesh_of", [1, 8], indirect=True)
@pytest.mark.parametrize("sql", OUTSIDE_STAR)
def test_a_dimension_string_outside_the_star_aggregate_equals_the_host(
        mesh_of, sql):
    """A dimension string rides a lookup as a code only in a star's
    aggregate; any other aggregate over the same star leaves the mesh
    before a string reaches the device, and answers as the host engine,
    with no mesh failure counted."""
    from tidb_tpu.metrics import REGISTRY
    from tidb_tpu.session import Domain

    sess = Domain().new_session()
    sess.execute("create table f (a bigint, b bigint, v bigint)")
    sess.execute("create table d1 (k bigint primary key, s varchar(10))")
    sess.execute("create table d2 (k bigint primary key, t bigint)")
    rng = np.random.default_rng(11)
    n = 4000
    store = sess.domain.storage
    info = sess.domain.catalog.info_schema()
    store.table(info.table("test", "f").id).bulk_load_arrays(
        [rng.integers(0, 12, n), rng.integers(1, 8, n),
         rng.integers(-50, 1000, n)], ts=store.current_ts())
    sess.execute("insert into d1 values " + ",".join(
        f"({k}, 's{k % 3}')" for k in range(1, 11)))
    sess.execute("insert into d2 values " + ",".join(
        f"({k}, {k % 3})" for k in range(1, 7)))
    for t in ("f", "d1", "d2"):
        sess.execute(f"analyze table {t}")
    ops = [r[0].strip().lstrip("└─") for r in sess.execute(
        "explain " + sql)[0].rows]
    assert ops.count("JoinLookup") == 2, ops
    errors = REGISTRY.snapshot().get("mesh_scan_errors_total", 0)
    sess.execute("set tidb_use_tpu = 1")
    got = sess.query(sql)
    assert REGISTRY.snapshot().get("mesh_scan_errors_total", 0) == errors
    sess.execute("set tidb_use_tpu = 0")
    want = sess.query(sql)
    assert got == want and want


def test_sf1_ssb_rehearsed_is_correct_and_its_control_is_caught(
        bench, capsys):
    from tidb_tpu.copr import parallel

    run = importlib.import_module("run")
    try:
        rc = run.main(["--workload", "sf1-ssb", "--seed", str(SEED),
                       "--seconds", "3", "--trace", "1", "--sf", str(SF),
                       "--rehearse-cpu", "1", "--control", "1"])
    finally:
        parallel.MESH_CACHE.clear()
    assert rc == 0
    lines = capsys.readouterr().out.strip().splitlines()
    result, notes = json.loads(lines[-1]), [json.loads(x) for x in lines[:-1]]
    assert result["correct"] is True and result["rehearsal"] is True
    # a seed-shuffled cycle of the 13 classes: four statements in a row
    # hold at least one of flights 2-4
    assert result["attempted"] >= 4 and result["failed"] == 0
    assert [n["control"]["caught"] for n in notes if "control" in n] == [
        True]
    got = result["metrics"]
    assert got["device_rung_pct"]["value"] == 100.0
    assert got["compiles_in_window"]["value"] == 0
    # one lookup a statement of flight 1, 3 or 4 of the others
    assert 1 < got["star_lookups_per_stmt"]["value"] <= 4
    assert got["star_build_ms"]["value"] > 0
    # some of the lookups compare (the date side's 2,556 keys among them)
    assert 0 < got["compare_lookup_pct"]["value"] <= 100
    assert "star_roofline" not in got   # no peaks in a rehearsal


# ---- the cell's readers, on hand-made runs ----------------------------------

def _span(name, **attrs):
    return {"name": name, "start_ns": 0, "dur_ns": 1, "depth": 1,
            "attrs": attrs}


def _read(name, run):
    return importlib.import_module(f"metrics.{name}").read(run)


def test_star_roofline_is_the_statements_bytes_over_the_busy_time(bench):
    run = {"device_trace": {"stmt_busy_s": [0.01, 0.03]},
           "peaks": {"hbm_bytes_per_s": 819e9},
           "stmt_bytes": [8.19e9, 8.19e9]}
    # 0.02 s the least the chip could take, over 0.04 s busy
    assert _read("star_roofline", run) == pytest.approx(50.0)
    assert _read("star_roofline", dict(run, peaks=None)) is None


def test_star_build_ms_is_the_median_of_the_summed_build_spans(bench):
    def built(*ms):
        return [dict(_span("star.build", phase="sort"), dur_ns=int(m * 1e6))
                for m in ms]

    spans = [built(1.0, 2.0), built(4.0), built(0.5, 0.5, 1.0),
             [_span("copr.chunk", lookups=1)]]
    # 3.0, 4.0 and 2.0 ms; the statement with no such span is not one
    assert _read("star_build_ms", {"spans": spans}) == pytest.approx(3.0)
    assert _read("star_build_ms", {"spans": [[], spans[3]]}) is None


def test_star_lookups_per_stmt_is_the_mean_of_the_dispatches_lookups(bench):
    star = [_span("copr.chunk", lookups=0), _span("copr.chunk", lookups=3),
            _span("copr.chunk", lookups=0)]
    one = [_span("copr.chunk", lookups=1)]
    assert _read("star_lookups_per_stmt",
                 {"spans": [star, one, one, []]}) == pytest.approx(5 / 3)
    # the parent's `copr.chunk` carries no such attribute: nothing
    bare = [_span("copr.chunk", kind="agg")]
    assert _read("star_lookups_per_stmt", {"spans": [bare, []]}) is None


def test_compare_lookup_pct_is_the_compared_share_of_the_lookups(bench):
    star = [_span("copr.chunk", lookups=0, compares=0),
            _span("copr.chunk", lookups=4, compares=3)]
    one = [_span("copr.chunk", lookups=1, compares=1)]
    gathered = [_span("copr.chunk", lookups=1, compares=0)]
    # 4 of the 6 lookups over the three statements
    assert _read("compare_lookup_pct",
                 {"spans": [star, one, gathered, []]}) == pytest.approx(
                     100 * 4 / 6)
    # a scan cell's programs carry the attribute and no lookup: nothing
    scan = [_span("copr.chunk", lookups=0, compares=0)]
    assert _read("compare_lookup_pct", {"spans": [scan, scan]}) is None
    # the parent's `copr.chunk` carries no such attribute: nothing
    bare = [_span("copr.chunk", lookups=3)]
    assert _read("compare_lookup_pct", {"spans": [bare, []]}) is None
