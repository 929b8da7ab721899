"""Device broadcast lookup join (JoinLookupIR): the inner join + partial
aggregation complete inside the cop task.

Reference role: executor/join.go HashJoinExec (build :232, probe workers
:307-414) — relocated into the coprocessor so join-heavy aggregates return
partials, not probe streams."""

import numpy as np
import pytest

from tidb_tpu.session import Domain
from tidb_tpu.types.values import parse_date


@pytest.fixture()
def d():
    return Domain()


def _load(d, n_o=500, n_l=8000, null_probe_keys=False):
    s = d.new_session()
    s.execute("create table orders (o_orderkey bigint primary key,"
              " o_orderdate date, o_shippriority bigint)")
    s.execute("create table li (l_orderkey bigint,"
              " l_extendedprice decimal(15,2), l_discount decimal(15,2),"
              " l_shipdate date)")
    rng = np.random.default_rng(3)
    base = parse_date("1995-01-01")
    t_o = d.catalog.info_schema().table("test", "orders")
    t_l = d.catalog.info_schema().table("test", "li")
    d.storage.table(t_o.id).bulk_load_arrays([
        np.arange(n_o, dtype=np.int64),
        (base + rng.integers(-200, 200, n_o)).astype(np.int64),
        rng.integers(0, 5, n_o),
    ], ts=d.storage.current_ts())
    lk = rng.integers(0, n_o * 2, n_l)  # half the keys have no match
    lv = None
    if null_probe_keys:
        lv = [np.ones(n_l, np.bool_), None, None, None]
        lv[0][:100] = False
    d.storage.table(t_l.id).bulk_load_arrays([
        lk,
        rng.integers(90_000, 10_500_001, n_l),
        rng.integers(0, 11, n_l),
        (base + rng.integers(-300, 300, n_l)).astype(np.int64),
    ], [lv[i] if lv else None for i in range(4)] if lv else None,
        ts=d.storage.current_ts())
    d.storage.regions.split_even(t_l.id, 8, n_l)
    s.execute("analyze table orders")
    s.execute("analyze table li")
    return s


Q3 = ("select l_orderkey, o_orderdate, o_shippriority,"
      " sum(l_extendedprice * (1 - l_discount)) as rev"
      " from li, orders where l_orderkey = o_orderkey"
      " and o_orderdate < '1995-03-15' and l_shipdate > '1995-03-15'"
      " group by l_orderkey, o_orderdate, o_shippriority"
      " order by rev desc, l_orderkey limit 10")


def _parity(s, q):
    s.execute("set tidb_use_tpu = 1")
    tpu = s.query(q)
    s.execute("set tidb_use_tpu = 0")
    cpu = s.query(q)
    s.execute("set tidb_use_tpu = 1")
    assert tpu == cpu, (tpu[:3], cpu[:3])
    return tpu


def _plan_ops(s, q):
    return [r[0] for r in s.execute("explain " + q)[0].rows]


def test_q3_shape_joins_in_cop_task(d):
    s = _load(d)
    ops = _plan_ops(s, Q3)
    assert any("DeviceJoinReader" in op for op in ops), ops
    assert any("JoinLookup" in op for op in ops), ops
    rows = _parity(s, Q3)
    assert len(rows) == 10


def test_scalar_agg_over_join(d):
    s = _load(d)
    q = ("select count(*), sum(l_extendedprice) from li, orders"
         " where l_orderkey = o_orderkey and o_shippriority < 3")
    assert any("DeviceJoinReader" in op for op in _plan_ops(s, q))
    _parity(s, q)


def test_group_by_payload_column(d):
    s = _load(d)
    q = ("select o_shippriority, count(*), min(l_extendedprice)"
         " from li, orders where l_orderkey = o_orderkey"
         " group by o_shippriority order by o_shippriority")
    assert any("DeviceJoinReader" in op for op in _plan_ops(s, q))
    _parity(s, q)


def test_empty_build_side(d):
    s = _load(d)
    q = ("select count(*) from li, orders where l_orderkey = o_orderkey"
         " and o_orderdate < '1200-01-01'")
    assert _parity(s, q) == [(0,)]


def test_delta_rows_join_through_cpu_engine(d):
    """Committed delta inserts on the probe table flow through the CPU
    engine's JoinLookupIR path and merge with device partials."""
    s = _load(d)
    s.execute("insert into li values (1, 1000.00, 0.00, '1995-06-01'),"
              " (1, 2000.00, 0.00, '1995-06-01')")
    q = ("select count(*), sum(l_extendedprice) from li, orders"
         " where l_orderkey = o_orderkey")
    _parity(s, q)


def test_null_probe_keys_never_match(d):
    s = _load(d, null_probe_keys=True)
    q = ("select count(*) from li, orders where l_orderkey = o_orderkey")
    _parity(s, q)


def test_non_unique_build_key_not_planned_as_device_join(d):
    """No PK/unique index on the build key -> planner keeps the root hash
    join (uniqueness is a hard requirement for the lookup join)."""
    s = _load(d)
    s.execute("create table dup_dim (k bigint, v bigint)")
    s.execute("insert into dup_dim values (1, 10), (1, 20), (2, 30)")
    s.execute("insert into li values (1, 5000.00, 0.00, '1995-06-01')")
    q = ("select count(*), sum(v) from li, dup_dim where l_orderkey = k")
    ops = _plan_ops(s, q)
    assert not any("DeviceJoinReader" in op for op in ops), ops
    _parity(s, q)


def test_merge_join_preference_overrides_device_join(d):
    s = _load(d)
    q = ("select count(*) from li, orders where l_orderkey = o_orderkey")
    s.execute("set tidb_opt_prefer_merge_join = 1")
    try:
        ops = _plan_ops(s, q)
        assert not any("DeviceJoinReader" in op for op in ops), ops
        assert any("MergeJoin" in op for op in ops), ops
        _parity(s, q)
    finally:
        s.execute("set tidb_opt_prefer_merge_join = 0")


def test_uniqueness_through_filtered_build(d):
    """Build side with its own filter keeps key uniqueness (Selection
    preserves it) and still device-joins."""
    s = _load(d)
    q = ("select count(*) from li, orders where l_orderkey = o_orderkey"
         " and o_orderdate >= '1994-06-01' and o_shippriority = 1")
    assert any("DeviceJoinReader" in op for op in _plan_ops(s, q))
    _parity(s, q)


def test_explain_analyze_runs(d):
    s = _load(d)
    rows = s.execute("explain analyze " + Q3)[0].rows
    assert any("DeviceJoinReader" in r[0] for r in rows)


# ---- a lookup into a small side compares keys instead of gathering ---------

I32 = np.iinfo(np.int32)
FACT_ROWS = 12_000


@pytest.fixture
def mesh_of(request):
    """The mesh the statements run on: one device, or all eight."""
    from tidb_tpu.copr import parallel

    devs, epoch = parallel._eligible_devices()
    mp = pytest.MonkeyPatch()
    mp.setattr(parallel, "_eligible_devices",
               lambda: (devs[:request.param], epoch))
    parallel.MESH_CACHE.clear()
    try:
        yield request.param
    finally:
        mp.undo()
        parallel.MESH_CACHE.clear()


@pytest.fixture(scope="module")
def fact():
    """A fact table whose probe columns are cached at three widths: `a`
    in 16 bits, `e` in 32 with values at int32's edges, `b` in 64; `n` a
    key with NULLs; `v` a summed value with NULLs."""
    d = Domain()
    s = d.new_session()
    s.execute("create table f (a bigint, b bigint, e bigint, n bigint,"
              " v bigint)")
    rng = np.random.default_rng(46)
    n = FACT_ROWS
    edges = np.array([I32.min, I32.min + 1, -5, 0, 7, I32.max - 1, I32.max])
    cols = [rng.integers(-50, 5000, n),
            (1 << 40) + rng.integers(0, 400, n),
            np.concatenate([edges, rng.integers(100, 200, n - len(edges))]),
            rng.integers(0, 100, n),
            rng.integers(-1000, 1000, n)]
    valids = [None, None, None, np.arange(n) % 7 != 0, np.arange(n) % 5 != 0]
    t = d.catalog.info_schema().table("test", "f")
    d.storage.table(t.id).bulk_load_arrays(cols, valids,
                                           ts=d.storage.current_ts())
    d.storage.regions.split_even(t.id, 8, n)
    s.execute("analyze table f")
    return d


def _dim(d, name, keys):
    """A dimension `name` over `keys` (k primary key, w bigint with NULLs,
    x double, s a string), loaded and analysed once in `d`."""
    if d.catalog.info_schema().has_table("test", name):
        return
    sess = d.new_session()
    keys = np.asarray(keys, dtype=np.int64)
    k = len(keys)
    sess.execute(f"create table {name} (k bigint primary key, w bigint,"
                 " x double, s varchar(8))")
    t = d.catalog.info_schema().table("test", name)
    d.storage.table(t.id).bulk_load_arrays(
        [keys, np.arange(k) % 11 - 3, keys % 13 * 0.5 - 2,
         np.array([f"s{i % 4}" for i in range(k)], dtype=object)],
        [None, np.arange(k) % 6 != 0, None, None],
        ts=d.storage.current_ts())
    sess.execute(f"analyze table {name}")


#: (case, the dimensions' keys, the statement, whether each of its
#: lookups compares): one-lookup aggregates over a side of 0 to 4,097
#: keys, probe keys cached in 16, 32 and 64 bits, NULL probe keys, a
#: payload, and stars whose sides take both forms
ONE = "select count(*), sum(f.v) from f, {d} where f.{col} = {d}.k{cond}"
LOOKUP_CASES = [
    ("keys-0", {"d0": range(100)}, ONE.format(d="d0", col="a",
                                               cond=" and d0.w > 100"),
     [False]),
    ("keys-1", {"d1": [17]}, ONE.format(d="d1", col="a", cond=""), [False]),
    ("keys-32", {"d32": range(0, 320, 10)},
     ONE.format(d="d32", col="a", cond=""), [False]),
    ("keys-33", {"d33": range(0, 330, 10)},
     ONE.format(d="d33", col="a", cond=""), [True]),
    ("keys-256", {"d256": range(-40, 4800, 19)[:256]},
     ONE.format(d="d256", col="a", cond=""), [True]),
    ("keys-4096", {"d4096": range(4096)},
     ONE.format(d="d4096", col="a", cond=""), [True]),
    ("keys-4097", {"d4097": range(4097)},
     ONE.format(d="d4097", col="a", cond=""), [False]),
    ("int32-edges", {"dedge": [I32.min - 7, I32.min, -5, 7, I32.max,
                               I32.max + 1, 1 << 40] + list(range(100, 160))},
     ONE.format(d="dedge", col="e", cond=""), [True]),
    ("int64-probe", {"dwide": [(1 << 40) + i for i in range(0, 400, 3)]},
     ONE.format(d="dwide", col="b", cond=""), [True]),
    ("null-probe", {"dnull": range(0, 100, 2)},
     ONE.format(d="dnull", col="n", cond=""), [True]),
    ("filtered-to-some", {"dsome": range(3000)},
     ONE.format(d="dsome", col="a", cond=" and dsome.w = 2"), [True]),
    ("payload", {"dpay": range(0, 2000, 7)},
     "select count(*), sum(f.v), sum(dpay.w), count(dpay.w), sum(dpay.x)"
     " from f, dpay where f.a = dpay.k and dpay.w < 5", [True]),
    ("star-mixed", {"dbig": range(0, 3000, 2), "dsmall": range(100, 120)},
     "select dbig.s, dsmall.w, sum(f.v), count(*) from f, dbig, dsmall"
     " where f.a = dbig.k and f.e = dsmall.k and dbig.w < 4"
     " group by dbig.s, dsmall.w order by dbig.s, dsmall.w", [True, False]),
    ("star-wide", {"dwide2": [(1 << 40) + i for i in range(0, 400, 3)],
                   "dnarrow": range(0, 30, 3)},
     "select dwide2.s, dnarrow.s, sum(f.v) from f, dwide2, dnarrow"
     " where f.b = dwide2.k and f.n = dnarrow.k"
     " group by dwide2.s, dnarrow.s order by dwide2.s, dnarrow.s",
     [True, False]),
]


def _chunk_attrs(sess, sql):
    """The `copr.chunk` spans' attributes of one traced run of `sql`."""
    import json

    tr = json.loads(sess.execute("trace format='json' " + sql)[0].rows[0][0])
    found = []

    def walk(node):
        if node["name"] == "copr.chunk":
            found.append(node.get("attrs") or {})
        for c in node.get("children", ()):
            walk(c)

    walk(tr["root"] if "root" in tr else tr)
    return found


def _sides(d, sql):
    """Each lookup's (build keys after its filter, entries its gather
    would read) as the host hands them to the program, in plan order."""
    from tidb_tpu.copr import parallel as par

    seen = []
    real = par._compare_side

    def spy(keys, gathered, narrow):
        seen.append((len(keys), gathered))
        return real(keys, gathered, narrow)

    mp = pytest.MonkeyPatch()
    mp.setattr(par, "_compare_side", spy)
    try:
        d.new_session().query(sql)
    finally:
        mp.undo()
    return seen


@pytest.mark.parametrize("mesh_of", [1, 8], indirect=True)
@pytest.mark.parametrize("case,dims,sql,forms", LOOKUP_CASES,
                         ids=[c[0] for c in LOOKUP_CASES])
def test_a_lookup_compares_or_gathers_and_answers_as_the_host(
        fact, mesh_of, case, dims, sql, forms):
    """Each lookup takes the form the rule gives for its side's size
    (`_compare_keys`: compare where its keys, padded to 256, are at most
    4,096 and the operand a gather reads has more than 32 entries), the
    two forms answer as the host engine, and `compares` on `copr.chunk`
    and `lookup_compares_total` count the compared sides."""
    from tidb_tpu.copr import parallel as par
    from tidb_tpu.metrics import LOOKUP_COMPARES, REGISTRY

    for name, keys in dims.items():
        _dim(fact, name, keys)
    s = fact.new_session()
    ops = [r[0] for r in s.execute("explain " + sql)[0].rows]
    assert sum("DeviceJoinReader" in op for op in ops) == 1, ops
    assert sum("JoinLookup" in op for op in ops) == len(forms), ops
    # the rule, side by side: at this scale the sizes are the case's own
    sides = _sides(fact, sql)
    assert [par._compare_keys(k, g) > 0 for k, g in sides] == forms, sides
    before = REGISTRY.snapshot().get(LOOKUP_COMPARES, 0)
    errors = REGISTRY.snapshot().get("mesh_scan_errors_total", 0)
    s.execute("set tidb_use_tpu = 1")
    got = s.query(sql)
    assert REGISTRY.snapshot().get(LOOKUP_COMPARES, 0) - before == sum(forms)
    assert REGISTRY.snapshot().get("mesh_scan_errors_total", 0) == errors
    # the fact's program; each filtered side's own has no lookup
    chunks = [c for c in _chunk_attrs(s, sql) if c["lookups"]]
    assert [(c["lookups"], c["compares"]) for c in chunks] == [
        (len(forms), sum(forms))]
    s.execute("set tidb_use_tpu = 0")
    want = s.query(sql)
    assert got == want, (got[:3], want[:3])
    if case != "keys-0":
        assert want[0][0], "no probe row joined"


@pytest.mark.parametrize("keys,gathered,pad", [
    (365, 512, 512),          # SSB Q1.1's date side at SF1
    (31, 32, 0),              # Q1.2's one month: a small gather stays
    (7, 16, 0),               # Q1.3's one week
    (2556, 1 << 16, 2560),    # a star's whole date side, its direct table
    (400, 1 << 11, 512),      # a filtered supplier side
    (6000, 1 << 15, 0),       # a filtered customer side: past 4,096
    (80_000, 1 << 18, 0),     # a filtered part side
    (0, 16, 0),               # an empty side: all misses, by the gather
    (3, 1 << 12, 256),        # few keys over a wide range: compared
])
def test_the_lookup_form_follows_the_sides_size(keys, gathered, pad):
    from tidb_tpu.copr import parallel as par

    assert par._compare_keys(keys, gathered) == pad
    if gathered == par._pow2(keys) and keys:
        # a one-lookup side's operand: its length alone says its form
        assert par._compared(pad or gathered) == bool(pad)
