"""TPC-H Q3 as the benchmark serves it, against the benchmark's own plain
reference (`benchmarks/queries/q3.py`: exact integer numpy, neither engine).

The data is the benchmark's generator's (`benchmarks/harness/datagen.py`) at
SF 0.02 on two seeds, loaded and analysed as `benchmarks/run.py` does it; the
five Q3 texts of `benchmarks/queries/q3.json` run through a `Session` on the
host engine, on the mesh of eight virtual devices (the exchanged shuffle
join) and on a mesh of one (the directory join of a single chip), and rows,
order and exact decimals must be the reference's.  The two engines agreeing
with each other is not the test: they shared `sort_indices`, which ranked a
decimal sum as text.
"""

import importlib
import json
import os
import sys

import numpy as np
import pytest

BENCH = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "benchmarks")
SF = 0.02
SEEDS = (7, 2**31 + 3)
TUPLES = range(5)


@pytest.fixture(scope="module")
def bench():
    """The benchmark's own modules (harness, queries), importable."""
    sys.path.insert(0, BENCH)
    try:
        yield {
            "serve": importlib.import_module("harness.serve"),
            "traffic": importlib.import_module("harness.traffic"),
            "compare": importlib.import_module("harness.compare"),
            "q3": importlib.import_module("queries.q3"),
            "query": json.load(open(os.path.join(BENCH, "queries",
                                                 "q3.json"))),
            "config": json.load(open(os.path.join(BENCH, "configs",
                                                  "tpch-sf1-q3.json"))),
        }
    finally:
        sys.path.remove(BENCH)


@pytest.fixture(scope="module")
def loaded(bench):
    """{seed: (domain, the generator's arrays)}, made on first use."""
    made = {}

    def get(seed):
        if seed not in made:
            domain, tables, _ = bench["serve"].load(
                bench["config"], seed, SF, lambda _note: None)
            made[seed] = (domain, tables)
        return made[seed]

    return get


@pytest.fixture(scope="module", params=["host", "mesh-of-8", "mesh-of-1"])
def engine(request):
    """How the statement is served.  `mesh-of-1` shrinks the eligible
    device set to one for the module's cases that ask for it, as a single
    chip has it; the mesh rebuilds when the set changes back."""
    from tidb_tpu.copr import parallel

    if request.param != "mesh-of-1":
        yield request.param
        return
    devs, epoch = parallel._eligible_devices()
    mp = pytest.MonkeyPatch()
    mp.setattr(parallel, "_eligible_devices", lambda: (devs[:1], epoch))
    try:
        yield request.param
    finally:
        mp.undo()
        # no column cached on the mesh of one stays behind for the
        # other tests of this worker
        parallel.MESH_CACHE.clear()


def _as_wire(rows):
    return [tuple(None if v is None else str(v) for v in r) for r in rows]


@pytest.mark.parametrize("index", TUPLES)
@pytest.mark.parametrize("seed", SEEDS)
def test_q3_as_served_is_the_reference(bench, loaded, engine, seed, index):
    domain, tables = loaded(seed)
    sess = domain.new_session()
    sess.execute(f"set tidb_use_tpu = {0 if engine == 'host' else 1}")
    q = bench["query"]
    got = _as_wire(sess.query(bench["traffic"].render(q, index)))
    want = bench["q3"].reference(tables, q["params"][index])
    assert len(want) == 10
    assert bench["compare"].same_rows(got, want), (got[:3], want[:3])
    # and the float32 control is told apart from it
    assert not bench["compare"].same_rows(
        _as_wire(bench["q3"].control(tables, q["params"][index])), want)


def test_no_aggregate_of_q3_walks_rows_in_python(bench, loaded, engine):
    """`agg_rowwise_groups_total` counts rows an aggregate walked one at a
    time in Python; a Q3 statement (partial aggregate, the root's final
    merge over its groups, TopN) leaves it where it was."""
    from tidb_tpu.metrics import REGISTRY

    domain, _ = loaded(SEEDS[0])
    sess = domain.new_session()
    sess.execute(f"set tidb_use_tpu = {0 if engine == 'host' else 1}")
    before = REGISTRY.get("agg_rowwise_groups_total")
    rows = sess.query(bench["traffic"].render(bench["query"], 0))
    assert len(rows) == 10
    assert REGISTRY.get("agg_rowwise_groups_total") == before


def test_one_chip_joins_by_directory_and_names_its_programs(bench, loaded,
                                                           engine):
    """On a mesh of one the customer-orders join exchanges nothing: its
    `mpp.exchange` span carries the rows it emitted against the slots it
    was compiled for (the power of two over them), its programs have
    names, and what the join programs read back is the size of their
    results."""
    if engine != "mesh-of-1":
        pytest.skip("the directory join is the one-shard form")
    domain, _ = loaded(SEEDS[0])
    sess = domain.new_session()
    sql = bench["traffic"].render(bench["query"], 0)
    sess.query(sql)
    tr = json.loads(sess.execute("trace format='json' " + sql)[0].rows[0][0])
    found = []

    def walk(node, under):
        found.append((node["name"], node.get("attrs") or {}, under))
        for c in node.get("children", ()):
            walk(c, under + (node["name"],))

    walk(tr["root"] if "root" in tr else tr, ())
    ex = [a for n, a, _ in found if n == "mpp.exchange"]
    assert len(ex) == 1 and ex[0]["rung"] == "shuffle"
    assert ex[0]["bytes"] == 0
    assert 0 < ex[0]["rows_out"] <= ex[0]["cap_out"] < 2 * ex[0]["rows_out"]
    programs = [a["program"] for n, a, _ in found
                if n == "copr.device.execute"]
    # the directory's probe and emit, then the lineitem merge aggregate
    assert len(programs) == 3
    assert all(p.startswith(("mpp_shuffle_", "mesh_agg_")) for p in programs)
    phases = [a["phase"] for n, a, _ in found if n == "join.build"]
    assert sorted(phases) == ["sort", "upload"]
    fan = [a for n, a, _ in found if n == "distsql.fanout"]
    assert fan and fan[0]["join"] == 1
    # a joined row comes back in its columns' narrow types, and a
    # build row's states as one int64 sum and two int32 counts
    back = [a["bytes"] for n, a, under in found if n == "copr.readback"
            and ("mpp.exchange" in under or "distsql.fanout" in under)]
    assert sum(back) < 64 * ex[0]["cap_out"]


def test_explain_shows_the_join_on_the_device(bench, loaded):
    domain, _ = loaded(SEEDS[0])
    sess = domain.new_session()
    rows = sess.execute("explain " + bench["traffic"].render(
        bench["query"], 0))[0].rows
    ops = [(r[0].strip(" └─"), r[2]) for r in rows]
    names = [name for name, _ in ops]
    assert any(n.startswith(("MPPJoin", "DeviceJoinReader")) for n in names)
    assert not any("HashJoin" in n or "MergeJoin" in n or "IndexJoin" in n
                   for n in names), names
    assert ("JoinLookup", "cop[tpu]") in ops
    assert any(task == "mpp[tpu]" for _, task in ops)


# ---------------------------------------------------------------------------
# sort_indices on object columns: exact integers order by value
# ---------------------------------------------------------------------------

def _decimal_chunk(revenues, dates):
    """A (wide decimal revenue, int date) chunk; None is NULL."""
    from tidb_tpu.chunk import Chunk, Column
    from tidb_tpu.types import ty_decimal, ty_int

    return Chunk([Column.from_values(ty_decimal(38, 4), revenues),
                  Column.from_values(ty_int(), dates)])


def _order(chunk, *keys):
    from tidb_tpu.copr.cpu_engine import sort_indices
    from tidb_tpu.expr.expression import ColumnExpr

    by = [(ColumnExpr(i, chunk.col(i).ftype), desc) for i, desc in keys]
    return sort_indices(by, chunk).tolist()


# the fault's own witness (PERF.md, PR 28): as text these sort
# 99999.8781, 99992.83, 999.9081, 99990.434
REVENUES = [9999081, 999998781, 999904340, 999928300, -55000, 0,
            -99999999999, 10**30, -10**30, 123]

SORT_CASES = {
    "desc": ([(0, True)], sorted(range(10), key=lambda i: -REVENUES[i])),
    "asc": ([(0, False)], sorted(range(10), key=lambda i: REVENUES[i])),
}


@pytest.mark.parametrize("case", sorted(SORT_CASES))
def test_sort_indices_ranks_decimals_by_value(case):
    keys, want = SORT_CASES[case]
    chunk = _decimal_chunk(REVENUES, list(range(10)))
    assert chunk.col(0).data.dtype == object
    assert _order(chunk, *keys) == want


@pytest.mark.parametrize("desc", [False, True])
def test_sort_indices_puts_null_decimals_first_ascending_last_descending(
        desc):
    chunk = _decimal_chunk([500, None, -7, 10**25, None], [1, 2, 3, 4, 5])
    got = _order(chunk, (0, desc))
    nulls, rest = ([1, 4], [2, 0, 3])
    assert got == (rest[::-1] + nulls if desc else nulls + rest)


@pytest.mark.parametrize("desc", [False, True])
def test_sort_indices_breaks_decimal_ties_by_the_next_key(desc):
    chunk = _decimal_chunk([700, 700, 10**20, 700, 10**20],
                           [3, 1, 9, 2, 4])
    got = _order(chunk, (0, True), (1, desc))
    assert got == ([2, 4, 0, 3, 1] if desc else [4, 2, 1, 3, 0])


def test_sort_indices_orders_strings_of_digits_as_text():
    from tidb_tpu.chunk import Chunk, Column
    from tidb_tpu.types import ty_string

    chunk = Chunk([Column.from_values(ty_string(), ["10", "9", "100", "2"])])
    assert _order(chunk, (0, False)) == [0, 2, 3, 1]
    assert _order(chunk, (0, True)) == [1, 3, 2, 0]


def test_sort_indices_ranks_escalated_integer_sums_and_strings_as_before():
    from tidb_tpu.chunk import Chunk, Column
    from tidb_tpu.types import ty_string

    ints = np.empty(4, dtype=object)
    ints[:] = [2**70, -2**70, 5, 2**64]
    strs = Column.from_values(ty_string(), ["b", "B", "aa", "a"])
    chunk = Chunk([Column(strs.ftype.__class__(strs.ftype.kind), ints),
                   strs])
    assert _order(chunk, (0, False)) == [1, 2, 3, 0]
    assert _order(chunk, (1, False)) == [1, 3, 2, 0]
