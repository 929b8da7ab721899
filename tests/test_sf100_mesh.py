"""The four-chip deployment `tpch-sf100-lineitem` at a size the CPU holds:
TPC-H Q1 as the benchmark serves it, over the wire, against the
benchmark's own plain reference `benchmarks/queries/q1w.py` (exact at any
scale factor), on the host engine and on a mesh of four virtual devices;
and the sums that pass int64 at scale factor 100.

A sum of a small-G dense aggregate whose bound over the table's rows can
pass int64 leaves the mesh program as its limb sums and is put together on
the host as Python integers (`copr/fusion.py::wide_sums`).  A thousand
rows a group of prices near 2^40 hundredths reach the bound that Q1's sum_charge
reaches with 600 M rows of dbgen's prices; the same statement with the
rule turned off is shown to wrap, so the test fails without the change.
"""

import importlib
import json
import os
import sys
from decimal import Decimal

import numpy as np
import pytest

BENCH = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "benchmarks")
SF = 0.02
SEED = 2**31 + 39


@pytest.fixture(scope="module")
def bench():
    """The benchmark's own modules (harness, queries), importable."""
    sys.path.insert(0, BENCH)
    try:
        yield {
            "serve": importlib.import_module("harness.serve"),
            "traffic": importlib.import_module("harness.traffic"),
            "compare": importlib.import_module("harness.compare"),
            "datagen": importlib.import_module("harness.datagen"),
            "q1": importlib.import_module("queries.q1"),
            "q1w": importlib.import_module("queries.q1w"),
            "query": json.load(open(os.path.join(BENCH, "queries",
                                                 "q1w.json"))),
            "config": json.load(open(os.path.join(
                BENCH, "configs", "tpch-sf100-lineitem.json"))),
        }
    finally:
        sys.path.remove(BENCH)


def _mesh_of(n):
    """Shrink the eligible device set to `n`, as a host of `n` chips has
    it; the mesh rebuilds when the set changes back."""
    from tidb_tpu.copr import parallel

    devs, epoch = parallel._eligible_devices()
    assert len(devs) >= n
    mp = pytest.MonkeyPatch()
    mp.setattr(parallel, "_eligible_devices", lambda: (devs[:n], epoch))
    return mp


@pytest.fixture(scope="module", params=[1, 4], ids=["mesh-of-1", "mesh-of-4"])
def mesh(request):
    from tidb_tpu.copr import parallel

    mp = _mesh_of(request.param)
    try:
        yield request.param
    finally:
        mp.undo()
        parallel.MESH_CACHE.clear()


@pytest.fixture(scope="module")
def served(bench):
    """The configuration's table at SF 0.02, loaded and served as
    `benchmarks/run.py` does it, on a mesh of four."""
    from tidb_tpu.copr import parallel

    mp = _mesh_of(4)
    domain, tables, _ = bench["serve"].load(
        bench["config"], SEED, SF, lambda _note: None)
    srv = bench["serve"].Served(domain)
    try:
        yield srv, tables
    finally:
        srv.stop()
        mp.undo()
        parallel.MESH_CACHE.clear()


@pytest.mark.parametrize("index", range(5))
@pytest.mark.parametrize("engine", ["host", "mesh-of-4"])
def test_q1_over_the_wire_is_q1w_reference(bench, served, engine, index):
    srv, tables = served
    q = bench["query"]
    cli = srv.client()
    try:
        cli.query(f"set tidb_use_tpu = {0 if engine == 'host' else 1}")
        _types, got = cli.query(bench["traffic"].render(q, index))
    finally:
        cli.close()
    want = bench["q1w"].reference(tables, q["params"][index])
    assert len(want) == 4
    assert bench["compare"].same_rows(got, want), (got[:2], want[:2])
    control = [tuple(str(v) for v in r)
               for r in bench["q1w"].control(tables, q["params"][index])]
    assert not bench["compare"].same_rows(control, want)


def test_q1_ran_on_the_mesh_of_four_and_its_sums_stayed_narrow(bench, served):
    """At SF 0.02 no sum can pass int64: the program is the one `sf10-q1`
    compiles (no `;wide=` in its lanes), `wide_sums` reads 0 and the
    counter stays."""
    from tidb_tpu.metrics import REGISTRY

    srv, _tables = served
    sess = srv.srv.domain.new_session()
    sess.execute("set tidb_use_tpu = 1")
    before = REGISTRY.get("agg_wide_sum_slots_total")
    sess.execute("trace " + bench["traffic"].render(bench["query"], 2))
    spans = _spans(sess)
    fan = [sp for sp in spans if sp.name == "distsql.fanout"]
    assert fan and all(sp.attrs["scan_engine"] == "mesh"
                       and len(sp.attrs["device_ids"]) == 4 for sp in fan)
    assert [sp.attrs["devices"] for sp in spans
            if sp.name in ("copr.chunk", "copr.device.execute")] == [4, 4]
    assert [sp.attrs["wide_sums"] for sp in spans
            if sp.name == "copr.unpack"] == [0]
    assert [sp.attrs["agg_lanes"] for sp in spans
            if sp.name == "copr.compile"] == ["i32:1,i32:2,i32:2,i32:3,i32:1"]
    assert REGISTRY.get("agg_wide_sum_slots_total") == before


def _spans(sess):
    out = []

    def walk(sp):
        out.append(sp)
        for c in sp.children:
            walk(c)

    walk(sess.last_trace.root)
    return out


# ---- sums past int64 -------------------------------------------------------

ROWS = 3600
WIDE_SQL = ("select g, sum(p * (1 - d) * (1 + x)), sum(p), count(*) "
            "from t group by g order by g")


def _wide_table():
    """Prices near 2^40 hundredths: a row's charge is under 2^54, a
    group's sum of some 1,200 of them passes 2^63."""
    from tidb_tpu.session import Domain

    domain = Domain()
    s = domain.new_session()
    s.execute("create table t (g varchar(1), p decimal(15,2), "
              "d decimal(15,2), x decimal(15,2))")
    info = domain.catalog.info_schema().table("test", "t")
    store = domain.storage.table(info.id)
    rng = np.random.default_rng(39)
    g = rng.integers(0, 3, ROWS).astype(np.int8)
    p = rng.integers((1 << 40) - 10**6, 1 << 40, ROWS)
    d = rng.integers(0, 11, ROWS)
    x = rng.integers(0, 9, ROWS)
    store.bulk_load_arrays([g, p, d, x], ts=domain.storage.current_ts(),
                           dictionaries={0: ["A", "N", "R"]})
    domain.storage.regions.split_even(info.id, 2, store.base_rows)
    want = []
    for k in range(3):
        m = np.flatnonzero(g == k)
        charge = sum(int(p[i]) * (100 - int(d[i])) * (100 + int(x[i]))
                     for i in m)
        want.append(("ANR"[k], charge, sum(int(p[i]) for i in m), len(m)))
    return s, want


def _as_decimal_text(scaled: int, scale: int) -> str:
    """What MySQL sends for a DECIMAL of `scale` places."""
    return str(Decimal(scaled).scaleb(-scale))


def test_a_sum_past_int64_is_exact_on_the_mesh(bench, mesh):
    from tidb_tpu.metrics import REGISTRY

    s, want = _wide_table()
    assert all(charge > 1 << 63 for _g, charge, _p, _n in want)
    s.execute("set tidb_use_tpu = 1")
    before = REGISTRY.get("agg_wide_sum_slots_total")
    s.execute("trace " + WIDE_SQL)
    got = s.query(WIDE_SQL)
    assert [(r[0], str(r[1]), str(r[2]), int(r[3])) for r in got] == [
        (g, _as_decimal_text(charge, 6), _as_decimal_text(price, 2), n)
        for g, charge, price, n in want]
    spans = _spans(s)
    assert [len(sp.attrs["device_ids"]) for sp in spans
            if sp.name == "distsql.fanout"] == [mesh]
    # the charge alone: 3,600 prices do not pass int64, and keep the
    # device's own recombination
    assert [sp.attrs["wide_sums"] for sp in spans
            if sp.name == "copr.unpack"] == [1]
    assert [sp.attrs["agg_lanes"] for sp in spans
            if sp.name == "copr.compile"] == ["i64:4,i64:4;wide=0"]
    assert REGISTRY.get("agg_wide_sum_slots_total") == before + 2
    # and the host engine says the same
    s.execute("set tidb_use_tpu = 0")
    assert [tuple(map(str, r)) for r in s.query(WIDE_SQL)] \
        == [tuple(map(str, r)) for r in got]
    # over the wire, as the benchmark's comparison reads it
    srv = bench["serve"].Served(s.domain)
    try:
        cli = srv.client()
        _types, rows = cli.query(WIDE_SQL)
        cli.close()
    finally:
        srv.stop()
    assert bench["compare"].same_rows(rows, [
        (g, Decimal(charge).scaleb(-6), Decimal(price).scaleb(-2), n)
        for g, charge, price, n in want])


def test_the_same_sum_wraps_where_the_rule_is_off(mesh, monkeypatch):
    """The parent's path: every slot recombined on the device in int64."""
    from tidb_tpu.copr import fusion, parallel

    monkeypatch.setattr(fusion, "_passes_int64", lambda *a: False)
    parallel._COMPILED.clear()
    try:
        s, want = _wide_table()
        s.execute("set tidb_use_tpu = 1")
        got = s.query(WIDE_SQL)
        wrapped = [(c + (1 << 63)) % (1 << 64) - (1 << 63)
                   for _g, c, _p, _n in want]
        assert [str(r[1]) for r in got] == [
            _as_decimal_text(w, 6) for w in wrapped]
        assert all(w != c for w, (_g, c, _p, _n) in zip(wrapped, want))
    finally:
        parallel._COMPILED.clear()


def test_the_per_tile_rung_adds_its_tiles_as_python_integers(monkeypatch):
    """Off the mesh a tile's partial sum fits int64 (1,024 rows here) and
    the host adds the tiles' partials exactly."""
    from tidb_tpu.copr import parallel

    monkeypatch.setattr(parallel, "try_run_mesh", lambda *a, **k: None)
    s, want = _wide_table()
    s.execute("set tidb_use_tpu = 1")
    assert [(r[0], str(r[1])) for r in s.query(WIDE_SQL)] == [
        (g, _as_decimal_text(charge, 6)) for g, charge, _p, _n in want]


def test_an_average_whose_rescale_passes_int64_is_exact():
    """avg(p) divides sum(p) * 10^4: TPC-H Q1's avg_price at SF100."""
    from tidb_tpu.session import Domain

    domain = Domain()
    s = domain.new_session()
    s.execute("create table t (p decimal(15,2))")
    info = domain.catalog.info_schema().table("test", "t")
    p = np.arange(2000, dtype=np.int64) + (1 << 40)
    domain.storage.table(info.id).bulk_load_arrays(
        [p], ts=domain.storage.current_ts())
    total = sum(int(v) for v in p)
    assert total * 10**4 > 1 << 63
    want = (total * 10**4 + 1000) // 2000
    for engine in (1, 0):
        s.execute(f"set tidb_use_tpu = {engine}")
        (got,), = s.query("select avg(p) from t")
        assert str(got) == _as_decimal_text(want, 6)


# ---- the rule, from the bounds alone ---------------------------------------

def _q1_analyzed(bench, rows):
    """Q1 analysed over dbgen's value ranges, the table said to hold
    `rows` base rows."""
    from tidb_tpu.copr import jax_engine as je
    from tidb_tpu.copr.ir import DAG
    from tidb_tpu.lint.kernelcheck import _reader_dags
    from tidb_tpu.parser import parse_one
    from tidb_tpu.session import Domain

    domain = Domain()
    s = domain.new_session()
    s.execute(bench["config"]["tables"]["lineitem"]["ddl"])
    info = domain.catalog.info_schema().table("test", "lineitem")
    store = domain.storage.table(info.id)
    # the minima and maxima of clause 4.2.3, two rows
    lo = [100, 90_000, 0, 0, 0, 0, 8036]
    hi = [5000, 50 * 209_899, 10, 8, 2, 1, 10561]
    store.bulk_load_arrays(
        [np.array(v) for v in zip(lo, hi)],
        ts=domain.storage.current_ts(),
        dictionaries={4: ["A", "N", "R"], 5: ["F", "O"]})
    sql = bench["traffic"].render(bench["query"], 2)
    (_p, dag), = _reader_dags(s._plan(parse_one(sql)))
    an = je._Analyzed(DAG.from_dict(dag.to_dict()), store)
    an.agg_rows = rows
    return an


@pytest.mark.parametrize("rows, wide", [
    (60_000_000, []), (67_108_864, []),      # sf10-q1, and its padded rows
    (600_000_000, [3]), (180_000_000, [3]),  # SF100 and SF30: sum_charge
])
def test_which_of_q1s_sums_are_wide_follows_from_bounds_and_rows(
        bench, rows, wide):
    from tidb_tpu.copr import fusion

    an = _q1_analyzed(bench, rows)
    assert sorted(fusion.wide_sums(an)) == wide
    lanes = "i32:1,i32:2,i32:2,i32:3,i32:1"
    assert fusion.agg_lanes(an) == lanes + (";wide=3" if wide else "")
    if wide:
        shifts, const, mul = fusion.wide_sums(an)[3]
        assert len(shifts) == 3 and const == 0 and mul == 1


def test_recombine_wide_is_python_integers():
    from tidb_tpu.copr import fusion

    limbs = np.array([[(1 << 46) - 1, -5], [(1 << 46) - 3, 7],
                      [(1 << 45), -(1 << 40)]], dtype=np.int64)
    counts = np.array([3, 4], dtype=np.int64)
    got = fusion.recombine_wide(limbs, counts, ((0, 16, 32), -2, 100))
    want = [100 * (sum(int(limbs[k, g]) << s
                       for k, s in enumerate((0, 16, 32))) - 2 * int(c))
            for g, c in enumerate(counts)]
    assert list(got) == want and got.dtype == object
    assert want[0] > 1 << 63 and want[1] < -(1 << 63)


# ---- the benchmark's own reference -----------------------------------------

def test_q1w_reference_equals_q1_reference_at_a_small_scale(bench, served):
    _srv, tables = served
    for p in bench["query"]["params"]:
        assert bench["q1w"].reference(tables, p) \
            == bench["q1"].reference(tables, p)


def test_q1w_reference_passes_its_own_bound_at_sf100s_day_counts(bench):
    """One ship day of one group at SF100 holds some 250,000 rows; with
    charges near their maximum its sum passes 2^53, where `q1.py` stops
    (its assert) and `q1w.py` goes on exactly."""
    n = 300_000
    rng = np.random.default_rng(5)
    day = bench["q1w"].c.days("1998-06-01")
    li = {
        "l_quantity": np.full(n, 5000, np.int16),
        "l_extendedprice": rng.integers(10_400_000, 10_494_950, n,
                                        dtype=np.int32),
        "l_discount": rng.integers(0, 2, n, dtype=np.int8),
        "l_tax": rng.integers(7, 9, n, dtype=np.int8),
        "l_returnflag": np.ones(n, np.int8),
        "l_linestatus": np.ones(n, np.int8),
        "l_shipdate": np.where(np.arange(n) < n - 10, day, day + 1
                               ).astype(np.int16),
    }
    charge = sum(int(a) * (100 - int(b)) * (100 + int(c)) for a, b, c in zip(
        li["l_extendedprice"], li["l_discount"], li["l_tax"]))
    assert charge > 1 << 53
    (row,), = [bench["q1w"].reference({"lineitem": li}, {"delta": 60})]
    assert row[:2] == ("N", "O") and row[-1] == n
    assert row[5] == Decimal(charge).scaleb(-6)
    with pytest.raises(AssertionError):
        bench["q1"].reference({"lineitem": dict(li)}, {"delta": 60})
