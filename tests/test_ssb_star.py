"""The Star Schema Benchmark's star joins (configuration `ssb-sf1-star`).

- The SSB generator (`benchmarks/generators/ssb.py`) is deterministic by
  seed and makes every column the configuration lists.
- Each of Q2.1-Q4.3 plans as ONE DeviceJoinReader whose cop DAG chains a
  lookup into each of its dimensions (3 or 4), and no join tree.
- Served over the wire, all 13 queries equal the plain reference
  (`benchmarks/queries/_ssb.py`) on both engines, over a mesh of one device,
  of four and of eight; over four, a star's dispatch names its shards and
  its build sides' uploads the bytes that went to the devices.
- The blocked reference for SF100 (`benchmarks/queries/_ssbw.py`) equals
  `_ssb.py` on every query, blocks smaller than the fact.
- A dimension filter that removes every row gives the empty result.
- A star whose aggregate is not a star's (a group key from the fact, a
  `max`) leaves the mesh before a dimension string reaches the device,
  and answers as the host engine.
- `benchmarks/run.py` rehearses `sf1-ssb` correct, and `sf100-ssb-4chip`
  over four of the eight devices, and catches the float32 control in
  both; the cells' readers read hand-made runs as they should.
"""

import importlib
import json
import os
import sys
from types import SimpleNamespace

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
    """The mesh the statements run on: one device, four (the four chips
    of one host), or all eight."""
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


@pytest.mark.parametrize("mesh_of", [1, 4, 8], indirect=True)
@pytest.mark.parametrize("engine", [1, 0])
def test_served_answers_equal_the_reference(loaded, mesh_of, engine):
    from harness import compare, serve

    from tidb_tpu.copr import jax_engine as je
    from tidb_tpu.metrics import REGISTRY, STAR_LOOKUPS

    domain, tables = loaded
    # the fact is not a whole number of tiles a shard: the last shard's
    # padding is masked
    assert len(tables["lineorder"]["lo_orderdate"]) % (mesh_of * je.TILE)
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


@pytest.mark.parametrize("mesh_of", [4], indirect=True)
def test_a_star_over_four_shards_names_them_and_its_uploads_bytes(
        loaded, mesh_of):
    """Q4.1 over four devices: one dispatch that names its four shards
    and its four lookups, and one upload a dimension whose `bytes` are
    its code table's on each of the four devices."""
    domain, tables = loaded
    sess = domain.new_session()
    sess.execute("set tidb_use_tpu = 1")
    got = sess.execute("trace " + _query("ssb41")["sql"])
    assert got
    spans = []

    def walk(sp):
        spans.append(sp)
        for c in sp.children:
            walk(c)

    walk(sess.last_trace.root)
    chunks = [sp.attrs for sp in spans if sp.name == "copr.chunk"
              and (sp.attrs or {}).get("lookups")]
    assert [(a["lookups"], a["shards"], a["devices"]) for a in chunks] \
        == [(4, 4, 4)]
    ups = [sp.attrs for sp in spans if sp.name == "star.build"
           and sp.attrs.get("phase") == "upload"]
    assert len(ups) == 4
    for a in ups:
        assert a["devices"] == 4
        # a direct table of int32 codes, or a compared side's [2, K] keys
        # and codes in 32 or 64 bits, on each device
        assert a["bytes"] in (4 * 4 * a["rows"], 4 * 2 * 4 * a["rows"],
                              4 * 2 * 8 * a["rows"]), a


def test_the_blocked_reference_equals_the_plain_one(loaded, monkeypatch):
    """`_ssbw.py` answers every query as `_ssb.py` does over blocks that
    cut the fact anywhere; its float32 control has the same groups (its
    sums round block by block, so they are not `_ssb.py`'s control's)."""
    w = importlib.import_module("queries._ssbw")
    _domain, tables = loaded
    monkeypatch.setattr(w, "ROWS", 4093)
    assert len(tables["lineorder"]["lo_orderdate"]) > 10 * w.ROWS
    for name in QUERIES:
        ref = importlib.import_module(f"queries.{name}")
        assert w.reference(tables, ref.Q) == ref.reference(tables, {}), name
        lower = w.control(tables, ref.Q)
        assert len(lower) == len(ref.control(tables, {})), name
    for name in LOOKUPS:
        blocked = importlib.import_module(f"queries.{name}w")
        assert blocked.Q is importlib.import_module(f"queries.{name}").Q
        assert _query(name + "w")["sql"] == _query(name)["sql"]


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


def test_sf100_ssb_4chip_rehearsed_over_four_devices_is_correct(
        bench, capsys, monkeypatch):
    """The four-chip cell at SF 0.01 over four of the eight devices: every
    statement a star over four shards, `correct`, the control caught."""
    from tidb_tpu.copr import parallel

    devs, epoch = parallel._eligible_devices()
    monkeypatch.setattr(parallel, "_eligible_devices",
                        lambda: (devs[:4], epoch))
    run = importlib.import_module("run")
    parallel.MESH_CACHE.clear()
    try:
        rc = run.main(["--workload", "sf100-ssb-4chip", "--seed",
                       str(SEED + 3), "--seconds", "2", "--trace", "1",
                       "--sf", str(SF), "--rehearse-cpu", "1",
                       "--control", "1"])
    finally:
        parallel.MESH_CACHE.clear()
    assert rc == 0
    lines = capsys.readouterr().out.strip().splitlines()
    result, notes = json.loads(lines[-1]), [json.loads(x) for x in lines[:-1]]
    assert result["correct"] is True and result["rehearsal"] is True
    assert result["attempted"] >= 1 and result["failed"] == 0
    assert [n["control"]["caught"] for n in notes if "control" in n] == [
        True]
    got = result["metrics"]
    assert got["device_rung_pct"]["value"] == 100.0
    assert got["compiles_in_window"]["value"] == 0
    assert got["star_upload_mb"]["value"] > 0
    assert 0 < got["host_store_gb"]["value"] < 1
    assert "mesh_star_roofline" not in got   # no peaks in a rehearsal


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


def test_mesh_star_roofline_divides_by_the_traced_devices(bench):
    run = {"device_trace": {"stmt_busy_s": [0.01, 0.03], "devices": 4},
           "peaks": {"hbm_bytes_per_s": 819e9},
           "stmt_bytes": [8.19e9, 8.19e9]}
    # 0.02 s the least one chip could take, a quarter of it over four
    # chips, over 0.04 s busy on the first
    assert _read("mesh_star_roofline", run) == pytest.approx(12.5)
    assert _read("mesh_star_roofline", dict(run, peaks=None)) is None


def test_star_collective_ms_is_the_collectives_a_device_and_statement(
        bench):
    ok = SimpleNamespace(ok=True)
    run = {"statements": [ok, ok, SimpleNamespace(ok=False)],
           "device_trace": {"devices": 4, "op_time": {
               "all-reduce.3": 0.008, "all-reduce-start.1": 0.004,
               "sort.19": 9.0}}}
    # 12 ms of collectives over four devices and two statements completed
    assert _read("star_collective_ms", run) == pytest.approx(1.5)
    assert _read("star_collective_ms", dict(run, device_trace=None)) is None


def test_star_upload_mb_is_the_median_of_the_statements_upload_bytes(bench):
    def up(*sizes, devices=4):
        return [_span("star.build", phase="upload", bytes=b,
                      devices=devices) for b in sizes]

    spans = [up(1e6, 3e6), up(8e6) + [_span("star.build", phase="codes",
                                             bytes=5e6)], up(2e6),
             [_span("copr.chunk", lookups=3)]]
    # 4, 8 and 2 MB; the statement with no upload is not one
    assert _read("star_upload_mb", {"spans": spans}) == pytest.approx(4.0)
    # the parent's upload spans name no devices: nothing
    bare = [[_span("star.build", phase="upload", bytes=1e6)]]
    assert _read("star_upload_mb", {"spans": bare}) is None


def test_host_store_gb_reads_the_gauge_of_the_stores_blocks(bench):
    from tidb_tpu.metrics import REGISTRY, STORE_BASE_BYTES

    before = REGISTRY.get(STORE_BASE_BYTES, None)
    try:
        REGISTRY.set(STORE_BASE_BYTES, 18.5e9)
        assert _read("host_store_gb", {}) == pytest.approx(18.5)
    finally:
        if before is not None:
            REGISTRY.set(STORE_BASE_BYTES, before)


@pytest.mark.parametrize("G", [1, 16, 256])
@pytest.mark.parametrize("nulls", [False, True])
def test_star_group_sums_compare_and_sort_agree(G, nulls, monkeypatch):
    """A star's group sums compare every row's id with every group, block
    by block, where `star_compares` (up to STAR_COMPARE_GROUPS groups, or
    STAR_COMPARE_GROUPS_LARGE past STAR_COMPARE_LARGE_ROWS rows a shard);
    else they sort the ids.
    Both give the same rows, counts and sums, exact modulo 2^64: rows
    that are not live, an argument's NULLs, a count(*) beside two sums,
    values at both ends of int64 that wrap, and a shard of several blocks
    and a part of one."""
    import jax.numpy as jnp

    from tidb_tpu.copr import fusion

    rng = np.random.default_rng(G + 7 * nulls)
    n = 3 * 4096
    monkeypatch.setattr(fusion, "STAR_COMPARE_BLOCK", 4096)
    gid = jnp.asarray(rng.integers(0, G, n, dtype=np.int32))
    live = jnp.asarray(rng.random(n) < 0.7)
    big = rng.integers(-(1 << 62), 1 << 62, n) * 2
    small = rng.integers(-1000, 1000, n).astype(np.int32)
    valid = [jnp.asarray(rng.random(n) < 0.8 if nulls else np.ones(n, bool))
             for _ in range(2)]
    values = [(None, jnp.ones(n, dtype=jnp.bool_)),
              (jnp.asarray(big), valid[0]), (jnp.asarray(small), valid[1])]

    def run(limit):
        monkeypatch.setattr(fusion, "STAR_COMPARE_GROUPS", limit)
        monkeypatch.setattr(fusion, "STAR_COMPARE_GROUPS_LARGE", limit)
        rows, out = fusion.star_group_sums(gid, live, values, G, nulls)
        return [np.asarray(rows)] + [np.asarray(x) for t, c in out
                                     for x in (t, c) if x is not None]

    compared, sorted_ = run(G), run(G - 1)
    assert len(compared) == len(sorted_) == 6
    for a, b in zip(compared, sorted_):
        assert a.dtype == b.dtype
        np.testing.assert_array_equal(a, b)
    assert compared[0].sum() == int(np.asarray(live).sum())


def test_a_star_compares_its_groups_where_that_is_the_faster():
    """The compare up to 512 groups, and up to 1,024 from 2^26 rows a
    shard, where the sort costs more a row; past that the sort."""
    from tidb_tpu.copr.fusion import star_compares

    assert star_compares(512, 1 << 23) and not star_compares(1024, 1 << 23)
    assert star_compares(1024, 144 << 20) and star_compares(1024, 1 << 26)
    assert not star_compares(2048, 144 << 20)
