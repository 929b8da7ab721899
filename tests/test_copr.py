"""Coprocessor engine tests: CPU oracle vs JAX device engine result parity.

This is the framework's north-star test pattern (SURVEY.md §4 carry-over):
the same DAG runs on both engines and must produce identical result sets.
"""

import numpy as np
import pytest

from tidb_tpu.chunk import concat_chunks
from tidb_tpu.copr.aggstate import merge_partials_to_final
from tidb_tpu.copr.ir import (
    DAG,
    AggregationIR,
    LimitIR,
    ProjectionIR,
    SelectionIR,
    TableScanIR,
    TopNIR,
)
from tidb_tpu.expr import ColumnExpr, Constant, ScalarFunc
from tidb_tpu.expr.aggregation import AggDesc
from tidb_tpu.expr.builtins import infer_ftype
from tidb_tpu.store import BlockStorage, CopRequest, KeyRange
from tidb_tpu.types import (
    parse_date,
    ty_date,
    ty_decimal,
    ty_float,
    ty_int,
    ty_string,
)

N = 5000


@pytest.fixture(scope="module")
def storage():
    st = BlockStorage()
    t = st.create_table(
        1,
        [
            ("k", ty_int(False)),
            ("qty", ty_decimal(15, 2)),
            ("price", ty_decimal(15, 2)),
            ("disc", ty_float()),
            ("ship", ty_date()),
            ("flag", ty_string()),
        ],
    )
    rng = np.random.default_rng(7)
    k = np.arange(N, dtype=np.int64)
    qty = rng.integers(100, 5000, N)  # 1.00 .. 50.00
    price = rng.integers(10000, 100000, N)
    disc = np.round(rng.random(N) * 0.1, 2)
    ship = parse_date("1994-01-01") + rng.integers(0, 2000, N).astype(np.int32)
    flag = np.array([["A", "N", "R"][i] for i in rng.integers(0, 3, N)], dtype=object)
    # sprinkle NULLs in disc
    disc_valid = rng.random(N) > 0.05
    t.bulk_load_arrays([k, qty, price, disc, ship, flag],
                       [None, None, None, disc_valid, None, None], ts=0)
    st.regions.split_even(1, 3, N)
    return st


def scan_ir():
    return TableScanIR(
        1, [0, 1, 2, 3, 4, 5],
        [ty_int(False), ty_decimal(15, 2), ty_decimal(15, 2), ty_float(),
         ty_date(), ty_string()],
    )


def col(i, ft):
    return ColumnExpr(i, ft)


def fn(name, *args, meta=None):
    meta = meta or {}
    ft = infer_ftype(name, [a.ftype for a in args], meta)
    return ScalarFunc(name, list(args), ft, meta)


def run_both(storage, dag: DAG, n_keys=None, aggs=None):
    """Run via the pushdown boundary on both engines; return row sets."""
    results = {}
    for engine in ("cpu", "tpu"):
        req = CopRequest(
            dag=dag.to_dict(), ranges=[KeyRange(1, 0, 1 << 62)],
            ts=storage.current_ts(), engine=engine,
        )
        chunks = []
        for resp in storage.get_client().send(req):
            chunks.extend(resp.chunks)
        if aggs is not None:
            final = merge_partials_to_final(n_keys, aggs, chunks)
            rows = final.to_pylist() if final is not None else []
        else:
            whole = concat_chunks(chunks)
            # root-side merge of per-region partial TopN/Limit results
            tail = dag.executors[-1]
            if whole is not None and isinstance(tail, TopNIR):
                from tidb_tpu.copr.cpu_engine import run_topn

                whole = run_topn(tail.order_by, tail.limit, whole)
            elif whole is not None and isinstance(tail, LimitIR):
                whole = whole.slice(0, min(tail.limit, whole.num_rows))
            rows = whole.to_pylist() if whole else []
        results[engine] = rows
    return results["cpu"], results["tpu"]


def test_filter_parity(storage):
    # WHERE qty < 24.00 AND disc BETWEEN 0.05 AND 0.07  (Q6 shape)
    conds = [
        fn("<", col(1, ty_decimal(15, 2)), Constant(2400, ty_decimal(15, 2))),
        fn(">=", col(3, ty_float()), Constant(0.05, ty_float())),
        fn("<=", col(3, ty_float()), Constant(0.07, ty_float())),
    ]
    dag = DAG([scan_ir(), SelectionIR(conds)])
    cpu, tpu = run_both(storage, dag)
    assert len(cpu) > 0
    assert sorted(cpu) == sorted(tpu)


def test_filter_on_dict_string(storage):
    conds = [fn("=", col(5, ty_string()), Constant("R", ty_string()))]
    dag = DAG([scan_ir(), SelectionIR(conds)])
    cpu, tpu = run_both(storage, dag)
    assert len(cpu) > 0 and sorted(cpu) == sorted(tpu)
    # range predicate over sorted dictionary
    conds2 = [fn(">=", col(5, ty_string()), Constant("N", ty_string()))]
    dag2 = DAG([scan_ir(), SelectionIR(conds2)])
    cpu2, tpu2 = run_both(storage, dag2)
    assert sorted(cpu2) == sorted(tpu2)
    assert all(r[5] in ("N", "R") for r in cpu2)


def test_projection_parity(storage):
    # SELECT price * (1 - disc) ... the Q1 revenue expression
    one = Constant(1.0, ty_float())
    rev = fn("*", col(2, ty_decimal(15, 2)), fn("-", one, col(3, ty_float())))
    dag = DAG([scan_ir(),
               SelectionIR([fn("<", col(0, ty_int(False)), Constant(1000, ty_int()))]),
               ProjectionIR([col(0, ty_int(False)), rev])])
    cpu, tpu = run_both(storage, dag)
    assert len(cpu) == 1000
    for (ka, va), (kb, vb) in zip(sorted(cpu), sorted(tpu)):
        assert ka == kb
        if va is None:
            assert vb is None
        else:
            assert va == pytest.approx(vb, rel=1e-12)


def test_scalar_agg_parity(storage):
    aggs = [
        AggDesc("count", []),
        AggDesc("sum", [col(2, ty_decimal(15, 2))]),
        AggDesc("avg", [col(1, ty_decimal(15, 2))]),
        AggDesc("min", [col(4, ty_date())]),
        AggDesc("max", [col(4, ty_date())]),
        AggDesc("sum", [col(3, ty_float())]),
    ]
    dag = DAG([scan_ir(), AggregationIR([], aggs, mode="partial")])
    cpu, tpu = run_both(storage, dag, n_keys=0, aggs=aggs)
    assert len(cpu) == 1 and len(tpu) == 1
    for a, b in zip(cpu[0], tpu[0]):
        if isinstance(a, float):
            assert a == pytest.approx(b, rel=1e-9)
        else:
            assert a == b


def test_group_agg_parity(storage):
    # GROUP BY flag (dict string) — Q1 shape
    aggs = [
        AggDesc("count", []),
        AggDesc("sum", [col(1, ty_decimal(15, 2))]),
        AggDesc("avg", [col(3, ty_float())]),
        AggDesc("min", [col(2, ty_decimal(15, 2))]),
        AggDesc("max", [col(5, ty_string())]),
        AggDesc("first_row", [col(5, ty_string())]),
    ]
    gb = [col(5, ty_string())]
    dag = DAG([scan_ir(), AggregationIR(gb, aggs, mode="partial")])
    cpu, tpu = run_both(storage, dag, n_keys=1, aggs=aggs)
    assert len(cpu) == 3
    key = lambda r: r[0]
    for a, b in zip(sorted(cpu, key=key), sorted(tpu, key=key)):
        for x, y in zip(a, b):
            if isinstance(x, float):
                assert x == pytest.approx(y, rel=1e-9)
            else:
                assert x == y


def test_group_by_int_key_with_filter(storage):
    # GROUP BY year(ship)? — not a bare column; use int key k % small via
    # group on date column year range instead: group by ship (int32 date,
    # card ~2000) with a filter
    aggs = [AggDesc("count", []), AggDesc("sum", [col(2, ty_decimal(15, 2))])]
    gb = [col(4, ty_date())]
    conds = [fn("<", col(0, ty_int(False)), Constant(500, ty_int()))]
    dag = DAG([scan_ir(), SelectionIR(conds),
               AggregationIR(gb, aggs, mode="partial")])
    cpu, tpu = run_both(storage, dag, n_keys=1, aggs=aggs)
    assert sorted(cpu) == sorted(tpu)
    assert sum(r[1] for r in cpu) == 500


# ---------------------------------------------------------------------------
# the grouped merge as array operations, held to a row-at-a-time oracle
# ---------------------------------------------------------------------------

_KEY_TYPES = {"i": ty_int(), "d": ty_date(), "s": ty_string(),
              "f": ty_float()}
# (what the aggregate reads, its AggDesc name): x INT, w DECIMAL(30,4)
# with values near 2^62 so sums pass 2^63, s STRING, y exact quarters
_AGG_SPECS = [("count", None), ("count", "x"), ("sum", "x"), ("sum", "w"),
              ("avg", "x"), ("min", "x"), ("max", "x"), ("min", "s"),
              ("max", "s"), ("first_row", "x"), ("first_row", "s"),
              ("sum", "y"), ("min", "dt"), ("max", "w")]
_VAL_TYPES = {"x": ty_int(), "w": ty_decimal(30, 4), "s": ty_string(),
              "y": ty_float(), "dt": ty_date()}


def _key_values(kind, ids, rng):
    """Key values that are equal exactly where `ids` are (then NULLed)."""
    if kind == "i":
        return (ids * 7919 - 100).tolist()
    if kind == "d":
        return (ids % 2400 + 8000).tolist()
    if kind == "s":
        return ["k%d" % (i * 31) for i in ids.tolist()]
    # floats: id 0 is written as 0.0 and as -0.0, one key
    zero = rng.choice([0.0, -0.0], len(ids))
    return [float(z) if i == 0 else i * 0.5 for i, z in
            zip(ids.tolist(), zero)]


def _raw_rows(keymix, G, n, seed):
    """Raw rows: key columns (NULLs in each) then the value columns."""
    rng = np.random.default_rng(seed)
    ids = rng.integers(0, G, n)
    ids[rng.permutation(n)[:G]] = np.arange(G)  # every id occurs
    cols, ftypes = [], []
    for j, kind in enumerate(keymix):
        vals = _key_values(kind, ids if j == 0 else ids // (j + 1), rng)
        for i in np.flatnonzero(rng.random(n) < 0.08).tolist():
            vals[i] = None
        cols.append(vals)
        ftypes.append(_KEY_TYPES[kind])
    nullify = lambda vals: [None if rng.random() < 0.1 else v for v in vals]
    values = {
        "x": nullify(rng.integers(-1000, 1000, n).tolist()),
        "w": nullify([int(v) + 2**62 for v in rng.integers(0, 10**6, n)]),
        "s": nullify(["s%03d" % v for v in rng.integers(0, 500, n)]),
        "y": nullify((rng.integers(-40, 40, n) * 0.25).tolist()),
        "dt": nullify(rng.integers(9000, 9500, n).tolist()),
    }
    for name in values:
        cols.append(values[name])
        ftypes.append(_VAL_TYPES[name])
    return cols, ftypes


def _oracle(n_keys, cols, aggs_spec, names):
    """One row at a time into a dictionary; rows out in the order the
    groups first appeared, values in their physical representation."""
    groups = {}
    for i in range(len(cols[0])):
        key = tuple(c[i] for c in cols[:n_keys])
        st = groups.setdefault(key, {"n": 0, "seen": {}, "first": {}})
        st["n"] += 1
        for name in names:
            v = cols[n_keys + names.index(name)][i]
            st["first"].setdefault(name, v)
            if v is not None:
                st["seen"].setdefault(name, []).append(v)
    out = []
    for key, st in groups.items():
        row = list(key)
        for fn_name, arg in aggs_spec:
            vals = st["seen"].get(arg, [])
            if fn_name == "count":
                row.append(st["n"] if arg is None else len(vals))
            elif fn_name == "first_row":
                row.append(st["first"][arg])
            elif not vals:
                row.append(None)
            elif fn_name == "sum":
                row.append(sum(vals))
            elif fn_name == "avg":  # INT -> DECIMAL(38,4), half away from 0
                num, cnt = sum(vals) * 10**4, len(vals)
                q = (2 * abs(num) + cnt) // (2 * cnt)
                row.append(q if num >= 0 else -q)
            else:
                row.append(min(vals) if fn_name == "min" else max(vals))
        out.append(tuple(row))
    return out


GROUPED_CASES = (
    [(mix, 37, 3) for mix in ("i", "d", "s", "f", "id", "sf", "ids", "idi",
                              "fds")]
    + [("idi", G, k) for G in (1, 1000, 50_000) for k in (1, 4)]
    + [("s", 50_000, 2), ("f", 1, 1), ("i", 50_000, 1)]
)


@pytest.mark.parametrize("keymix,G,pieces", GROUPED_CASES,
                         ids=["%s-G%d-x%d" % c for c in GROUPED_CASES])
def test_grouped_merge_equals_a_row_at_a_time_oracle(keymix, G, pieces):
    from tidb_tpu.chunk import chunk_from_pylists
    from tidb_tpu.copr.aggstate import group_indices
    from tidb_tpu.copr.cpu_engine import _run_agg

    n = max(2 * G + 11, 60)
    cols, ftypes = _raw_rows(keymix, G, n, seed=G * 10 + pieces)
    n_keys, names = len(keymix), list(_VAL_TYPES)
    raw = chunk_from_pylists(ftypes, cols)
    want = _oracle(n_keys, cols, _AGG_SPECS, names)

    # group ids and first rows, in first-appearance order
    gidx, first, n_groups = group_indices([raw.col(i) for i in range(n_keys)])
    order, opened = {}, []
    for i in range(n):
        key = tuple(c[i] for c in cols[:n_keys])
        if key not in order:
            order[key] = len(order)
            opened.append(i)
    assert n_groups == len(want) and first.tolist() == opened
    assert gidx.tolist() == [order[tuple(c[i] for c in cols[:n_keys])]
                             for i in range(n)]

    key_exprs = [col(i, ftypes[i]) for i in range(n_keys)]
    aggs = [AggDesc(name, [] if arg is None else
                    [col(n_keys + names.index(arg), _VAL_TYPES[arg])])
            for name, arg in _AGG_SPECS]
    # the host engine's complete aggregate over the raw rows
    complete = _run_agg(AggregationIR(key_exprs, aggs, mode="complete"), raw)
    # (0.0 == -0.0 in Python too: values are compared, not spellings)
    assert complete.to_pylist() == want
    # its partial aggregate over pieces of them, merged to the final
    partial_ir = AggregationIR(key_exprs, aggs, mode="partial")
    cuts = np.linspace(0, n, pieces + 1).astype(int)
    partials = [_run_agg(partial_ir, raw.slice(a, b))
                for a, b in zip(cuts[:-1], cuts[1:])]
    final = merge_partials_to_final(n_keys, aggs, partials)
    assert final.to_pylist() == want
    # a wide sum stays exact Python integers
    wide = final.col(n_keys + _AGG_SPECS.index(("sum", "w")))
    assert wide.data.dtype == object
    assert all(type(v) is int for v in wide.data[wide.validity()][:20])
    if G > 2:
        assert max(v for v in wide.to_pylist() if v is not None) > 2**63


def test_group_codes_refactorize_before_they_could_wrap():
    # four key columns each spanning nearly 2^31: the mixed-radix product
    # passes 2^62 at the third, so the prefix is re-numbered first
    from tidb_tpu.chunk import Column
    from tidb_tpu.copr.aggstate import group_indices

    rng = np.random.default_rng(11)
    n = 4000
    picks = [rng.choice([0, 1, 2**31 - 2], n) for _ in range(4)]
    gidx, first, G = group_indices([Column(ty_int(), p) for p in picks])
    order = {}
    want = [order.setdefault(k, len(order)) for k in zip(*map(list, picks))]
    assert G == len(order) == 81 and gidx.tolist() == want


def test_merging_groups_walks_no_rows_in_python(monkeypatch):
    """No clock needed: however many groups a final merge has, it reads
    no value through `Column.get` and builds no column through
    `Column.from_values`, and the row-wise counter stays where it was."""
    from tidb_tpu.chunk import Chunk, Column
    from tidb_tpu.metrics import REGISTRY

    def partial(G):
        ids = np.arange(2 * G) % G
        wide = np.empty(2 * G, dtype=object)
        wide[:] = [2**62 + int(i) for i in ids]
        return Chunk([Column(ty_int(), ids * 3),
                      Column(ty_date(), (ids % 2000).astype(np.int32)),
                      Column(ty_int(), np.zeros(2 * G, dtype=np.int64),
                             ids % 10 != 0),
                      Column(ty_decimal(38, 4), wide),
                      Column(ty_int(False), np.ones(2 * G, dtype=np.int64))])

    aggs = [AggDesc("sum", [col(3, ty_decimal(30, 4))]), AggDesc("count", [])]
    calls = {"get": 0, "from_values": 0}
    real_get, real_from_values = Column.get, Column.from_values

    def counted_get(self, i):
        calls["get"] += 1
        return real_get(self, i)

    def counted_from_values(ftype, values):
        calls["from_values"] += 1
        return real_from_values(ftype, values)

    monkeypatch.setattr(Column, "get", counted_get)
    monkeypatch.setattr(Column, "from_values",
                        staticmethod(counted_from_values))
    rowwise = REGISTRY.get("agg_rowwise_groups_total")
    seen = {}
    for G in (200, 20_000):
        calls.update(get=0, from_values=0)
        final = merge_partials_to_final(3, aggs, list(partial(G).split(1024)))
        assert final.num_rows == G
        assert int(final.col(3).data[G - 1]) == 2 * (2**62 + G - 1)
        seen[G] = dict(calls)
    assert seen[200] == seen[20_000] == {"get": 0, "from_values": 0}
    assert REGISTRY.get("agg_rowwise_groups_total") == rowwise


def test_a_row_at_a_time_aggregate_counts_itself():
    """The paths that still walk rows in Python say so: group_concat, and
    object keys that do not compare."""
    from tidb_tpu.chunk import Chunk, Column
    from tidb_tpu.copr.aggstate import group_indices
    from tidb_tpu.copr.cpu_engine import _run_agg
    from tidb_tpu.metrics import REGISTRY

    keys = Column(ty_int(), np.array([1, 2, 1, 2, 1]))
    strs = Column(ty_string(), np.array(list("abcde"), dtype=object))
    before = REGISTRY.get("agg_rowwise_groups_total")
    out = _run_agg(AggregationIR(
        [col(0, ty_int())], [AggDesc("group_concat", [col(1, ty_string())])],
        mode="complete"), Chunk([keys, strs]))
    assert out.to_pylist() == [(1, "a,c,e"), (2, "b,d")]
    assert REGISTRY.get("agg_rowwise_groups_total") == before + 5
    mixed = np.empty(4, dtype=object)
    mixed[:] = ["a", 1, "a", 1]  # str < int raises: np.unique cannot sort
    gidx, first, G = group_indices([Column(ty_string(), mixed), keys.slice(
        0, 4)])
    assert (gidx.tolist(), first.tolist(), G) == ([0, 1, 0, 1], [0, 1], 2)
    assert REGISTRY.get("agg_rowwise_groups_total") == before + 9


def test_spill_partitions_ignore_what_lies_under_a_null():
    """A taken key column keeps whatever the source held under a NULL;
    the spill's partition hash must send every NULL key to one place."""
    from tidb_tpu.chunk import Chunk, Column
    from tidb_tpu.executor.aggregate import _partition_hash

    valid = np.array([True, False, False, True, False])
    a = Chunk([Column(ty_int(), np.array([5, 17, 99, 6, -3]), valid)])
    b = Chunk([Column(ty_int(), np.array([5, 0, 0, 6, 0]), valid)])
    assert _partition_hash(a, 1).tolist() == _partition_hash(b, 1).tolist()
    assert len(set(_partition_hash(a, 1)[~valid].tolist())) == 1


def test_topn_parity(storage):
    dag = DAG([
        scan_ir(),
        SelectionIR([fn("=", col(5, ty_string()), Constant("A", ty_string()))]),
        TopNIR([(col(2, ty_decimal(15, 2)), True)], 7),
    ])
    cpu, tpu = run_both(storage, dag)
    assert len(cpu) == 7 and len(tpu) == 7
    # same price ordering (ties may reorder other cols; compare sort keys)
    assert [r[2] for r in cpu] == [r[2] for r in tpu]


def test_limit(storage):
    dag = DAG([scan_ir(), LimitIR(13)])
    cpu, tpu = run_both(storage, dag)
    assert len(cpu) == 13 and len(tpu) == 13


def test_region_error_retry(storage):
    from tidb_tpu.errors import RegionError
    from tidb_tpu.store.fault import failpoint, once

    with failpoint("copr/region_error", once(RegionError("injected"))):
        dag = DAG([scan_ir(), LimitIR(5)])
        req = CopRequest(dag=dag.to_dict(), ranges=[KeyRange(1, 0, 100)],
                         ts=storage.current_ts(), engine="cpu")
        chunks = []
        for resp in storage.get_client().send(req):
            chunks.extend(resp.chunks)
        assert concat_chunks(chunks).num_rows == 5


def test_delta_overlay_included(storage):
    # runs last: mutates the module-scoped fixture's data
    txn = storage.begin()
    t = storage.table(1)
    h = t.alloc_handle()
    txn.put(1, h, (999999, 100, 100, 0.5, parse_date("2001-01-01"), "Z"))
    txn.delete(1, 0)
    txn.commit()
    conds = [fn(">=", col(0, ty_int(False)), Constant(0, ty_int()))]
    dag = DAG([scan_ir(), SelectionIR(conds)])
    cpu, tpu = run_both(storage, dag)
    assert sorted(cpu) == sorted(tpu)
    keys = {r[0] for r in cpu}
    assert 999999 in keys  # delta insert visible
    assert len([r for r in cpu if r[0] == 0]) == 0  # base row 0 deleted
