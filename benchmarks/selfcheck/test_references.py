"""Each query's plain reference against the program's oracle engine
(`tidb_use_tpu = 0`) at a small scale: the one place the two may meet."""

import importlib
import json
import os

import pytest

from harness import compare, serve, traffic

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
SF = 0.02


def _load(config_name):
    config = json.load(open(os.path.join(BENCH, "configs",
                                         config_name + ".json")))
    config = dict(config, analyze=[])
    domain, tables, _ = serve.load(config, 2**31 + 3, SF, lambda _n: None)
    sess = domain.new_session()
    sess.execute("set tidb_use_tpu = 0")
    return sess, tables


@pytest.fixture(scope="module")
def lineitem():
    return _load("tpch-sf10-lineitem")


@pytest.fixture(scope="module")
def q3_tables():
    return _load("tpch-sf1-q3")


def _query(name):
    return (json.load(open(os.path.join(BENCH, "queries", name + ".json"))),
            importlib.import_module(f"queries.{name}"))


def _as_wire(rows):
    return [tuple(None if v is None else str(v) for v in r) for r in rows]


@pytest.mark.parametrize("name", ["q1", "q6"])
def test_reference_equals_the_oracle_engine_on_every_tuple(name, lineitem):
    sess, tables = lineitem
    q, ref = _query(name)
    for i, p in enumerate(q["params"]):
        got = _as_wire(sess.query(traffic.render(q, i)))
        want = ref.reference(tables, p)
        assert compare.same_rows(got, want), (p, got[:2], want[:2])
        # and the float32 control is told apart
        assert not compare.same_rows(_as_wire(ref.control(tables, p)), want)


def test_q3_reference_equals_the_programs_groups_sorted_as_numbers(q3_tables):
    """The program's own groups, without its ORDER BY and LIMIT, sorted
    here as numbers, are the reference's ten: the witness that showed a
    decimal sum ranked as text."""
    from decimal import Decimal

    sess, tables = q3_tables
    q, ref = _query("q3")
    for i, p in enumerate(q["params"]):
        sql = traffic.render(q, i)
        groups = sess.query(sql[:sql.index(" order by ")])
        top = sorted(groups, key=lambda r: (-Decimal(str(r[1])), str(r[2]),
                                            r[0]))[:10]
        assert compare.same_rows(_as_wire(top), ref.reference(tables, p)), p


def test_q3_as_served_equals_the_reference(q3_tables):
    sess, tables = q3_tables
    q, ref = _query("q3")
    for i, p in enumerate(q["params"]):
        got = _as_wire(sess.query(traffic.render(q, i)))
        assert compare.same_rows(got, ref.reference(tables, p)), p
