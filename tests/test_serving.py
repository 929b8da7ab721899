"""Shape-bucketed plan serving & query micro-batching (tidb_tpu/serving).

Parity is the contract: bucketed/padded layouts, hoisted-parameter
programs and micro-batched dispatches must return results identical to
solo execution — including when a batch member is KILLed mid-window,
hits its deadline mid-window, or the batch dispatch itself dies on the
seeded chaos site `serving/batch_dispatch`.
"""

import threading

import numpy as np
import pytest

from tidb_tpu import serving
from tidb_tpu.errors import MaxExecutionTimeExceeded, QueryKilledError
from tidb_tpu.metrics import REGISTRY
from tidb_tpu.session import Domain
from tidb_tpu.copr.device_health import DeviceFailure
from tidb_tpu.store.fault import failpoint, once


@pytest.fixture(autouse=True)
def _serving_defaults():
    """Serving config is process-global; every test starts and ends at
    the defaults so a SET in one test never bleeds into the next."""
    serving.configure(shape_buckets=True, microbatch_window_ms=0.0,
                      microbatch_max=32)
    yield
    serving.configure(shape_buckets=True, microbatch_window_ms=0.0,
                      microbatch_max=32)


def _load(sess, name: str, n: int = 20_000, regions: int = 4):
    d = sess.domain
    sess.execute(f"create table {name} (k bigint, g bigint, x double)")
    t = d.catalog.info_schema().table("test", name)
    store = d.storage.table(t.id)
    rng = np.random.default_rng(11)
    store.bulk_load_arrays(
        [np.arange(n, dtype=np.int64),
         rng.integers(0, 5, n, dtype=np.int64),
         rng.uniform(0, 100, n)],
        ts=d.storage.current_ts(),
    )
    d.storage.regions.split_even(t.id, regions, store.base_rows)
    return store


@pytest.fixture(scope="module")
def sess():
    d = Domain()
    s = d.new_session()
    _load(s, "t")
    return s


def _snap(*names):
    s = REGISTRY.snapshot()
    return tuple(s.get(n, 0) for n in names)


def _approx_rows(got, want, ctx=""):
    assert len(got) == len(want), (ctx, got, want)
    for ra, rb in zip(sorted(got), sorted(want)):
        for x, y in zip(ra, rb):
            if isinstance(x, float) or isinstance(y, float):
                assert x == pytest.approx(y, rel=1e-9, abs=1e-9), (ctx, ra, rb)
            else:
                assert x == y, (ctx, ra, rb)


# ---------------------------------------------------------------------------
# shape buckets
# ---------------------------------------------------------------------------


def test_shape_bucket_units():
    from tidb_tpu.serving import shape_bucket, topn_budget

    assert shape_bucket(1) == 1
    assert shape_bucket(3) == 4
    assert shape_bucket(4) == 4
    assert shape_bucket(33) == 64
    assert shape_bucket(0, floor=16) == 16
    assert topn_budget(5) == 16  # floor
    assert topn_budget(100) == 128
    serving.configure(shape_buckets=False)
    assert topn_budget(5) == 5  # disabled: exact


# (tiles of rows, shards) -> tiles a shard: the benchmark's tables, of which
# only SF100's lineitem over four chips leaves its power of two (256)
@pytest.mark.parametrize("tiles, shards, per_shard", [
    (573, 4, 144),  # lineitem SF100
    (58, 1, 64),    # lineitem SF10
    (6, 1, 8),      # lineitem SF1
    (2, 1, 2),      # orders SF1
    (1, 1, 1),      # customer SF1
])
def test_tile_bucket_of_the_benchmarks_tables(tiles, shards, per_shard):
    from tidb_tpu.copr import jax_engine as je
    from tidb_tpu.copr.parallel import _layout

    rows = tiles * je.TILE - je.TILE // 3
    assert _layout(rows, shards) == (tiles, per_shard * shards, per_shard)


@pytest.mark.parametrize("shards", [1, 3, 4, 8])
def test_tile_bucket_steps_in_eighths_and_whole_groups_of_8(shards):
    from tidb_tpu.copr import jax_engine as je
    from tidb_tpu.copr.parallel import _layout
    from tidb_tpu.serving import tile_bucket

    last = 0
    for n in range(1, 4097):
        tiles, n_pad, tl = _layout(n * je.TILE, shards)
        need = -(-n // shards)
        assert tiles == n and n_pad == tl * shards and tl == tile_bucket(need)
        assert need <= tl <= need + max(need // 8, 7), (n, tl)
        assert tl % 8 == 0 or tl in (1, 2, 4), (n, tl)
        assert tl >= last, (n, tl, last)  # a table that grows never shrinks
        last = tl
    # eight shapes an octave
    assert len({tile_bucket(n) for n in range(129, 257)}) == 8


@pytest.mark.parametrize("shards", [1, 2, 4, 8])
def test_tile_bucket_keeps_the_small_shapes_of_the_power_of_two_rule(shards):
    """Up to 8 tiles a shard over a power-of-two mesh, n_pad is what
    padding the whole table to a power of two gave: the suite's meshes of
    one and eight devices over small tables keep every program shape."""
    from tidb_tpu.copr import jax_engine as je
    from tidb_tpu.copr.parallel import _layout
    from tidb_tpu.serving import shape_bucket

    for n in range(1, 8 * shards + 1):
        _, n_pad, tl = _layout(n * je.TILE, shards)
        assert tl <= 8
        assert n_pad == -(-shape_bucket(n) // shards) * shards, n


@pytest.mark.parametrize("how", ["tuner-exact", "buckets-off"])
def test_unbucketed_shard_is_still_whole_groups_of_8(how, monkeypatch):
    """The tuner's `exact` decision and `tidb_tpu_shape_buckets = 0` pad
    nothing but the last group of 8: the blocked row view stays."""
    from tidb_tpu.copr import jax_engine as je
    from tidb_tpu.copr import parallel as par

    if how == "tuner-exact":
        monkeypatch.setattr(par, "_tile_bucket", lambda table: "exact")
    else:
        serving.configure(shape_buckets=False)
    for shards in (1, 3, 4):
        for n in range(1, 600):
            tiles, n_pad, tl = par._layout(n * je.TILE, shards)
            need = -(-n // shards)
            assert (tiles, n_pad) == (n, tl * shards)
            assert tl == (need if need <= 8 else -(-need // 8) * 8), (n, tl)


def test_param_hoist_shares_one_mesh_program(sess):
    from tidb_tpu.copr import parallel as pl

    sess.query("select k from t where x < 11.5")  # warm the shape class
    n0 = len(pl._COMPILED)
    r1 = sess.query("select k from t where x < 23.5")
    n1 = len(pl._COMPILED)
    r2 = sess.query("select k from t where x < 42.0")
    n2 = len(pl._COMPILED)
    assert n1 == n0 and n2 == n0, "parameter-different filters recompiled"
    assert len(r2) > len(r1) > 0
    # parity against the CPU oracle
    sess.execute("set tidb_use_tpu = 0")
    cpu = sess.query("select k from t where x < 42.0")
    sess.execute("set tidb_use_tpu = 1")
    _approx_rows(r2, cpu, "hoisted filter")


def test_point_agg_hoist_shares_program(sess):
    from tidb_tpu.copr import parallel as pl

    sess.query("select count(*), sum(x) from t where k = 5")
    n0 = len(pl._COMPILED)
    for k in (9, 123, 19_999):
        rows = sess.query(f"select count(*), sum(x) from t where k = {k}")
        assert rows[0][0] == 1
    assert len(pl._COMPILED) == n0, "point lookups recompiled per literal"


def test_shape_bucket_parity_toggle(sess):
    queries = (
        "select g, sum(x), count(*), min(x), max(x) from t group by g"
        " order by g",
        "select sum(x) from t where k < 15000 and x < 50",
        "select k, x from t order by x desc limit 7",
        "select k from t where x < 2.5",
    )
    serving.configure(shape_buckets=False)
    plain = [sess.query(q) for q in queries]
    serving.configure(shape_buckets=True)
    bucketed = [sess.query(q) for q in queries]
    for q, a, b in zip(queries, plain, bucketed):
        _approx_rows(b, a, q)


def test_topn_budget_shares_program(sess):
    from tidb_tpu.copr import parallel as pl

    r5 = sess.query("select k, x from t order by x desc limit 5")
    n0 = len(pl._COMPILED)
    r7 = sess.query("select k, x from t order by x desc limit 7")
    assert len(pl._COMPILED) == n0, "LIMIT 5 vs 7 compiled two programs"
    assert len(r5) == 5 and len(r7) == 7
    assert [r[0] for r in r7[:5]] == [r[0] for r in r5]


# ---------------------------------------------------------------------------
# plan cache satellites
# ---------------------------------------------------------------------------


def test_plan_cache_size_sysvar(sess):
    sess.execute("set tidb_plan_cache_size = 2")
    try:
        for i in range(4):
            sess.query(f"select k from t where x < {10 + i}.5")
        assert len(sess._plan_cache) <= 2
    finally:
        sess.execute("set tidb_plan_cache_size = 128")


def test_plan_cache_survives_small_dml():
    d = Domain()
    s = d.new_session()
    _load(s, "t_pc", n=4000, regions=2)
    # pin stats first: the stats build-epoch is (deliberately) part of
    # the key, so the test isolates the table-version component
    s.execute("analyze table t_pc")
    q = "select g, count(*) from t_pc group by g order by g"
    s.query(q)
    h0, = _snap("plan_cache_hits_total")
    s.query(q)
    h1, = _snap("plan_cache_hits_total")
    assert h1 == h0 + 1
    # small DML stays inside the table's pow2 row bucket: the cached
    # plan remains valid (results re-read data at execution time)
    s.execute("insert into t_pc values (4000, 1, 2.5)")
    before = s.query(q)
    h2, = _snap("plan_cache_hits_total")
    assert h2 == h1 + 1, "an in-bucket insert invalidated the cached plan"
    s.execute("set tidb_use_tpu = 0")
    cpu = s.query(q)
    s.execute("set tidb_use_tpu = 1")
    _approx_rows(before, cpu, "post-DML cached plan")


def test_program_cache_lru_and_metrics():
    from tidb_tpu.copr.cache import ProgramCache

    h0, m0, e0 = _snap("compiled_programs_hits_total",
                       "compiled_programs_misses_total",
                       "compiled_programs_evictions_total")
    c = ProgramCache("unit-test", capacity=2)
    assert c.get("a") is None
    c.put("a", 1)
    c.put("b", 2)
    assert c.get("a") == 1  # refreshes LRU position
    c.put("c", 3)  # evicts b (a was refreshed)
    assert c.get("b") is None
    assert c.get("a") == 1 and c.get("c") == 3
    st = c.stats()
    assert st["size"] == 2 and st["evictions"] == 1
    h1, m1, e1 = _snap("compiled_programs_hits_total",
                       "compiled_programs_misses_total",
                       "compiled_programs_evictions_total")
    assert h1 - h0 == 3 and m1 - m0 == 2 and e1 - e0 == 1


def test_status_reports_compiled_caches(sess):
    import json
    import urllib.request

    import tidb_tpu.serving.batcher  # noqa: F401 — registers its cache
    from tidb_tpu.server.http_status import StatusServer

    # a mesh statement of this test's own: whichever tests ran before
    # on this worker, the mesh program cache holds at least its program
    sess.execute("set tidb_use_tpu = 1")
    assert len(sess.query("select g, sum(x) from t group by g")) == 5
    srv = StatusServer(sess.domain, port=0)
    host, port = srv.start()
    try:
        with urllib.request.urlopen(
                f"http://{host}:{port}/status", timeout=5) as r:
            body = json.loads(r.read())
    finally:
        srv.stop()
    caches = body["compiled_programs"]
    assert "tile" in caches and "mesh" in caches and "microbatch" in caches
    assert caches["mesh"]["size"] >= 1


# ---------------------------------------------------------------------------
# micro-batching
# ---------------------------------------------------------------------------


def _concurrent(d, sqls, window_ms=250):
    """Run sqls on fresh sessions, one thread each, batching window on;
    returns (results, errors) in input order."""
    serving.configure(microbatch_window_ms=float(window_ms))
    results = [None] * len(sqls)
    errors = [None] * len(sqls)
    sessions = [d.new_session() for _ in sqls]
    barrier = threading.Barrier(len(sqls))

    def run(i):
        barrier.wait()
        try:
            results[i] = sessions[i].query(sqls[i])
        except BaseException as e:  # noqa: BLE001 — asserted by tests
            errors[i] = e

    threads = [threading.Thread(target=run, args=(i,), daemon=True,
                                name=f"serving-test-{i}")
               for i in range(len(sqls))]
    for t in threads:
        t.start()
    for t in threads:
        t.join(30)
    serving.configure(microbatch_window_ms=0.0)
    return results, errors, sessions


def test_microbatch_agg_parity(sess):
    d = sess.domain
    sqls = [f"select count(*), sum(x), min(x) from t where k = {k}"
            for k in (3, 7, 4242, 19_998)]
    solo = [sess.query(q) for q in sqls]
    b0, s0 = _snap("serving_batches_total", "serving_batched_stmts_total")
    results, errors, _ = _concurrent(d, sqls)
    assert errors == [None] * 4, errors
    for q, got, want in zip(sqls, results, solo):
        _approx_rows(got, want, q)
    b1, s1 = _snap("serving_batches_total", "serving_batched_stmts_total")
    assert b1 > b0, "no batch formed"
    assert s1 - s0 >= 2, "fewer than 2 statements batched"
    assert (s1 - s0) > (b1 - b0), "batches never held >1 statement"


def test_microbatch_filter_parity(sess):
    d = sess.domain
    sqls = [f"select k, g, x from t where k = {k}" for k in (5, 42, 777)]
    solo = [sess.query(q) for q in sqls]
    results, errors, _ = _concurrent(d, sqls)
    assert errors == [None] * 3, errors
    for q, got, want in zip(sqls, results, solo):
        _approx_rows(got, want, q)


def test_microbatch_distinct_columns_never_merge(sess):
    """Regression: the DAG fingerprint keys columns by scan-output index,
    so `where k = ?` and `where g = ?` serialize identically — the batch
    key must pin the resolved STORE columns or the two queries would
    batch together and return each other's results."""
    d = sess.domain
    sqls = ["select count(*), sum(x) from t where k = 3",
            "select count(*), sum(x) from t where g = 3"]
    solo = [sess.query(q) for q in sqls]
    assert solo[0] != solo[1]  # the shapes must be distinguishable
    results, errors, _ = _concurrent(d, sqls, window_ms=250)
    assert errors == [None, None], errors
    for q, got, want in zip(sqls, results, solo):
        _approx_rows(got, want, q)


def test_microbatch_leader_kill_unblocks_window():
    """A KILLed leader must not sit out the batching window: the window
    wait wakes on its cancel event and the batch closes early."""
    import time

    from tidb_tpu.lifecycle import QueryScope
    from tidb_tpu.serving.batcher import MicroBatcher, _Member

    b = MicroBatcher()
    sc = QueryScope()
    m = _Member(np.zeros(0, dtype=np.int64), np.zeros(0, dtype=np.float64),
                sc)
    out = {}

    def run():
        t0 = time.monotonic()
        try:
            b.submit(("unit-key",), m, 5.0, 8, lambda live: None)
        except BaseException as e:  # noqa: BLE001
            out["err"] = e
        out["dt"] = time.monotonic() - t0

    th = threading.Thread(target=run, daemon=True)
    th.start()
    time.sleep(0.1)
    sc.cancel("killed")
    th.join(5)
    assert out.get("dt") is not None, "leader never returned"
    assert out["dt"] < 1.0, f"KILL blocked on the window: {out['dt']:.2f}s"
    assert isinstance(out.get("err"), QueryKilledError)


def test_microbatch_member_killed_mid_window(sess):
    d = sess.domain
    sqls = ["select count(*), sum(x) from t where k = 1",
            "select count(*), sum(x) from t where k = 2"]
    solo = sess.query(sqls[1])
    serving.configure(microbatch_window_ms=500.0)
    results = [None, None]
    errors = [None, None]
    sessions = [d.new_session(), d.new_session()]
    started = threading.Barrier(3)

    def run(i):
        started.wait()
        try:
            results[i] = sessions[i].query(sqls[i])
        except BaseException as e:  # noqa: BLE001
            errors[i] = e

    threads = [threading.Thread(target=run, args=(i,), daemon=True)
               for i in range(2)]
    for t in threads:
        t.start()
    started.wait()
    # kill member 0 while the window is still open: it must raise
    # promptly and be masked out; member 1's batch completes normally
    import time

    time.sleep(0.15)
    sessions[0].cancel_query("killed")
    for t in threads:
        t.join(30)
    serving.configure(microbatch_window_ms=0.0)
    assert isinstance(errors[0], QueryKilledError), errors
    assert errors[1] is None, errors
    _approx_rows(results[1], solo, "survivor of killed batch member")
    assert sessions[0].last_termination == "killed"


def test_microbatch_member_deadline_mid_window(sess):
    d = sess.domain
    sqls = ["select count(*), sum(x) from t where k = 8",
            "select count(*), sum(x) from t where k = 9"]
    solo = sess.query(sqls[1])
    serving.configure(microbatch_window_ms=600.0)
    sessions = [d.new_session(), d.new_session()]
    sessions[0].execute("set max_execution_time = 120")  # expires in-window
    results = [None, None]
    errors = [None, None]
    barrier = threading.Barrier(2)

    def run(i):
        barrier.wait()
        try:
            results[i] = sessions[i].query(sqls[i])
        except BaseException as e:  # noqa: BLE001
            errors[i] = e

    threads = [threading.Thread(target=run, args=(i,), daemon=True)
               for i in range(2)]
    for t in threads:
        t.start()
    for t in threads:
        t.join(30)
    serving.configure(microbatch_window_ms=0.0)
    assert isinstance(errors[0], MaxExecutionTimeExceeded), errors
    assert errors[1] is None, errors
    _approx_rows(results[1], solo, "survivor of deadline batch member")
    assert sessions[0].last_termination == "timeout"


def test_microbatch_chaos_batch_dispatch(sess):
    """Seeded chaos: the batch dispatch dies once — every member falls
    back to solo execution with identical results, nothing leaks."""
    d = sess.domain
    sqls = [f"select count(*), sum(x) from t where k = {k}"
            for k in (100, 200)]
    solo = [sess.query(q) for q in sqls]
    e0, = _snap("serving_batch_errors_total")
    with failpoint("serving/batch_dispatch",
                   once(DeviceFailure("chaos: chip died"))):
        results, errors, _ = _concurrent(d, sqls, window_ms=300)
    assert errors == [None, None], errors
    for q, got, want in zip(sqls, results, solo):
        _approx_rows(got, want, q)
    e1, = _snap("serving_batch_errors_total")
    assert e1 == e0 + 1, "chaos site never fired on the batch path"


def test_microbatch_unclassified_error_reaches_the_client(sess):
    """A bug in the batch dispatch (not a runtime device failure) is not
    papered over by the solo rungs: every member sees the exception."""
    d = sess.domain
    sqls = [f"select count(*), sum(x) from t where k = {k}"
            for k in (100, 200)]
    with failpoint("serving/batch_dispatch",
                   once(TypeError("unexpected keyword argument"))):
        _, errors, _ = _concurrent(d, sqls, window_ms=300)
    assert [type(e) for e in errors] == [TypeError, TypeError], errors


def test_microbatch_respects_max_batch(sess):
    d = sess.domain
    serving.configure(microbatch_max=2)
    sqls = [f"select count(*) from t where k = {k}" for k in range(4)]
    solo = [sess.query(q) for q in sqls]
    b0, = _snap("serving_batches_total")
    results, errors, _ = _concurrent(d, sqls, window_ms=250)
    serving.configure(microbatch_max=32)
    assert errors == [None] * 4
    for got, want in zip(results, solo):
        _approx_rows(got, want)
    b1, = _snap("serving_batches_total")
    assert b1 - b0 >= 2, "max=2 should split 4 members into >=2 batches"


def test_microbatch_skips_tables_with_delta():
    """MVCC delta makes the base scan ts-dependent: such tables must
    run solo (parity over throughput)."""
    d = Domain()
    s = d.new_session()
    _load(s, "t_delta", n=4000, regions=2)
    s.execute("insert into t_delta values (4000, 2, 7.5)")
    q = "select count(*), sum(x) from t_delta where k >= 3999"
    solo = s.query(q)
    b0, = _snap("serving_batches_total")
    results, errors, _ = _concurrent(d, [q, q], window_ms=200)
    assert errors == [None, None]
    _approx_rows(results[0], solo)
    _approx_rows(results[1], solo)
    b1, = _snap("serving_batches_total")
    assert b1 == b0, "a delta'd table entered the micro-batch path"
