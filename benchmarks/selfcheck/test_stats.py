"""Percentile, rate and byte-count arithmetic on made-up inputs."""

import json
import os

import pytest

from harness import stats

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def test_percentiles_interpolate_between_closest_ranks():
    xs = [40.0, 10.0, 20.0, 30.0]
    assert stats.median(xs) == 25.0
    assert stats.percentile(xs, 0) == 10.0
    assert stats.percentile(xs, 100) == 40.0
    assert stats.percentile(list(range(1, 101)), 95) == pytest.approx(95.05)
    assert stats.percentile([7.0], 95) == 7.0
    with pytest.raises(ValueError):
        stats.percentile([], 50)


def test_rate_is_all_the_work_over_all_the_time():
    assert stats.rate(120, 30.0) == 4.0
    with pytest.raises(ValueError):
        stats.rate(1, 0.0)


def test_statement_bytes_come_from_the_query_and_configuration_files():
    config = json.load(open(os.path.join(
        BENCH, "configs", "tpch-sf10-lineitem.json")))
    rows = {"lineitem": 60_000_000}
    q1 = json.load(open(os.path.join(BENCH, "queries", "q1.json")))
    q6 = json.load(open(os.path.join(BENCH, "queries", "q6.json")))
    # Q1 names seven columns: 2+4+1+1+1+1+2 bytes a row; Q6 four: 2+4+1+2
    assert stats.statement_bytes(q1, config, rows) == 60_000_000 * 12
    assert stats.statement_bytes(q6, config, rows) == 60_000_000 * 9
    assert stats.statement_rows(q1, rows) == 60_000_000
    # every column a query names is a column its configuration loads
    for q in (q1, q6):
        for table, cols in q["columns"].items():
            assert set(cols) <= set(config["tables"][table]["columns"])
