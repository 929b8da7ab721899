"""Parallel root executors consume their concurrency sysvars.

Reference: executor/aggregate.go:101-169 (partial/final worker graph),
executor/join.go:307-414 (probe workers), executor/projection.go:185-217
(parallel projection).  These tests assert (a) the knobs are actually read
— the worker metric moves with the setting — and (b) results are identical
to the serial path (order-preserving pipelines).
"""

import numpy as np
import pytest

from tidb_tpu.metrics import REGISTRY
from tidb_tpu.session import Domain


@pytest.fixture(scope="module")
def sess():
    d = Domain()
    s = d.new_session()
    s.execute("create table p (a bigint, b bigint, g bigint)")
    t = d.catalog.info_schema().table("test", "p")
    store = d.storage.table(t.id)
    rng = np.random.default_rng(7)
    n = 40_000
    store.bulk_load_arrays([
        np.arange(n, dtype=np.int64),
        rng.integers(0, 1000, n, dtype=np.int64),
        rng.integers(0, 12_000, n, dtype=np.int64),  # high NDV for final
    ], ts=d.storage.current_ts())
    d.storage.regions.split_even(t.id, 8, store.base_rows)
    s.execute("create table q (k bigint, v bigint)")
    s.execute("insert into q values " + ",".join(
        f"({i},{i * 10})" for i in range(500)))
    return s


def _workers_used(sess, sql):
    before = REGISTRY.snapshot().get("executor_parallel_workers_total", 0)
    rows = sess.query(sql)
    after = REGISTRY.snapshot().get("executor_parallel_workers_total", 0)
    return rows, after - before


def test_projection_workers_follow_sysvar(sess):
    sess.execute("set tidb_use_tpu = 0")  # fan-out: multi-chunk stream
    sql = "select a + b * 2, b - a from p"
    sess.execute("set tidb_projection_concurrency = 1")
    serial, w1 = _workers_used(sess, sql)
    sess.execute("set tidb_projection_concurrency = 3")
    par, w3 = _workers_used(sess, sql)
    sess.execute("set tidb_use_tpu = 1")
    # scan fan-out arrival order is nondeterministic (as_completed), so
    # compare as multisets; the pipeline itself preserves its input order
    assert sorted(serial) == sorted(par)
    assert w3 > w1  # the knob reached the pool


def test_hash_join_probe_workers(sess):
    # cpu engine: per-region fan-out yields a multi-chunk probe stream
    # (the lazy pipeline stays inline for single-chunk streams by design)
    sess.execute("set tidb_use_tpu = 0")
    sql = ("select count(*), sum(v) from p join q on p.b = q.k")
    sess.execute("set tidb_hash_join_concurrency = 1")
    serial, _ = _workers_used(sess, sql)
    sess.execute("set tidb_hash_join_concurrency = 4")
    par, w = _workers_used(sess, sql)
    sess.execute("set tidb_use_tpu = 1")
    assert serial == par
    assert w >= 4


def test_hashagg_final_merge_is_one_pass_whatever_the_knob(sess):
    # 12k distinct groups, partial rows past the 8,192 at which the final
    # merge used to hash-partition across tidb_hashagg_final_concurrency
    # workers: that pool was slower than the single vectorised merge at
    # every row count measured (PERF.md section 6, PR 37), so the merge
    # is one pass and the knob, still accepted, starts no worker
    sql = "select g, count(*), sum(a) from p group by g order by g limit 5"
    sess.execute("set tidb_use_tpu = 0")  # host HashAgg path
    sess.execute("set tidb_hashagg_final_concurrency = 1")
    serial, w1 = _workers_used(sess, sql)
    sess.execute("set tidb_hashagg_final_concurrency = 4")
    par, w4 = _workers_used(sess, sql)
    sess.execute("set tidb_use_tpu = 1")
    assert serial == par
    assert w1 == w4


def test_umbrella_executor_concurrency(sess):
    # per-operator knob unset (-1, the registered default) falls back to
    # tidb_executor_concurrency
    sess.execute("set tidb_use_tpu = 0")
    sess.execute("set tidb_projection_concurrency = -1")
    sess.execute("set tidb_executor_concurrency = 6")
    _, w = _workers_used(sess, "select a * 3 from p")
    sess.execute("set tidb_projection_concurrency = 4")
    sess.execute("set tidb_executor_concurrency = 5")
    sess.execute("set tidb_use_tpu = 1")
    assert w >= 6
