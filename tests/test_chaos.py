"""Chaos-sweep harness + degraded-mesh device failover.

Tentpole coverage for the device-health subsystem (copr/device_health.py):

- a virtual device failpoint-killed MID-SCAN on the 8-device CPU mesh must
  not demote the query off the mesh path — the breaker trips, sharded
  arrays keyed to the dead device set evict, and the SAME shard_map
  program re-runs over the surviving 7 devices with identical results;
- information_schema.TIDB_TPU_DEVICE_HEALTH surfaces the tripped breaker
  and a later half-open probe restores the full mesh;
- the seeded chaos sweep arms every registered failpoint family across the
  query path (mesh, distsql fan-out, region routing, 2PC, DDL backfill)
  and asserts result parity vs the CPU engine, zero leaked locks and zero
  leaked producer threads.

Everything is deterministic: `once()` injections, seeded data, no sleeps
on the failure paths.
"""

import threading
import time

import numpy as np
import pytest

from tidb_tpu.copr.device_health import (
    DEVICE_HEALTH,
    DeviceFailure,
    HbmOomError,
)
from tidb_tpu.errors import TiDBTPUError, TxnConflictError
from tidb_tpu.metrics import REGISTRY
from tidb_tpu.session import Domain
from tidb_tpu.store.fault import FAILPOINTS, failpoint, once

Q1 = ("select g, sum(x), count(*), min(x), max(x), avg(x) from t "
      "group by g order by g")
Q6 = "select sum(x) from t where k < 15000 and x < 50"
TOPN = "select k, x from t order by x desc limit 7"
FILTER = "select k from t where x < 2.5"

SWEEP_QUERIES = (Q1, Q6, TOPN, FILTER)


@pytest.fixture(scope="module")
def sess():
    d = Domain()
    s = d.new_session()
    s.execute("create table t (k bigint, g bigint, x double)")
    t = d.catalog.info_schema().table("test", "t")
    store = d.storage.table(t.id)
    rng = np.random.default_rng(7)
    n = 20_000
    store.bulk_load_arrays(
        [np.arange(n, dtype=np.int64),
         rng.integers(0, 5, n, dtype=np.int64),
         rng.uniform(0, 100, n)],
        ts=d.storage.current_ts(),
    )
    d.storage.regions.split_even(t.id, 4, store.base_rows)
    return s


@pytest.fixture(autouse=True)
def _healthy_devices():
    """Device health is process-global: every test starts AND ends with
    closed breakers so failures never bleed across tests/modules."""
    DEVICE_HEALTH.reset()
    yield
    DEVICE_HEALTH.reset()


def _approx_eq(a, b):
    if isinstance(a, float) or isinstance(b, float):
        return a == pytest.approx(b, rel=1e-9, abs=1e-9)
    return a == b


def _rows_eq(got, want, ctx=""):
    assert len(got) == len(want), (ctx, got, want)
    for ra, rb in zip(sorted(got), sorted(want)):
        assert all(_approx_eq(x, y) for x, y in zip(ra, rb)), (ctx, ra, rb)


def _cpu_rows(sess, sql):
    sess.execute("set tidb_use_tpu = 0")
    try:
        return sess.query(sql)
    finally:
        sess.execute("set tidb_use_tpu = 1")


def _snap(*names):
    s = REGISTRY.snapshot()
    return tuple(s.get(n, 0) for n in names)


def _mesh_ids():
    from tidb_tpu.copr import parallel as pl

    mesh = pl._MESH
    return tuple(d.id for d in mesh.devices.ravel()) if mesh else ()


def _run_on_mesh(sess, sql):
    """Run `sql` on the tpu engine asserting it was SERVED BY THE MESH:
    mesh_scans_total grew and no per-region cop task ran (the whole-query
    fallback path increments cop_tasks_total)."""
    sess.execute("set tidb_use_tpu = 1")
    m0, c0, f0 = _snap("mesh_scans_total", "cop_tasks_total",
                       "mesh_scan_errors_total")
    rows = sess.query(sql)
    m1, c1, f1 = _snap("mesh_scans_total", "cop_tasks_total",
                       "mesh_scan_errors_total")
    assert m1 > m0, f"not on the mesh path: {sql}"
    assert c1 == c0, f"fell back to per-region fan-out: {sql}"
    assert f1 == f0, f"mesh scan errored into fallback: {sql}"
    return rows


# ---------------------------------------------------------------------------
# degraded-mesh failover (the tentpole acceptance path)
# ---------------------------------------------------------------------------


def test_device_kill_mid_scan_serves_from_rebuilt_mesh(sess):
    """Kill virtual device 3 mid-scan: Q1/Q6/TopN still complete with
    CPU-parity results, served by a REBUILT 7-device mesh (not the
    whole-query fallback); the health table shows the tripped breaker and
    a half-open probe later restores the full 8-device mesh."""
    from tidb_tpu.copr import parallel as pl

    # warm: full mesh in place
    _run_on_mesh(sess, Q6)
    assert len(_mesh_ids()) == 8

    # explicit EXPLAIN ANALYZE attribution: despite the mid-scan kill the
    # scan reports scan_engine == "mesh", served by the 7-device rebuild
    with failpoint("mesh/device_error",
                   once(DeviceFailure("device 3 halted mid-scan",
                                      device_ids=(3,)))):
        plan = "\n".join(str(r) for r in sess.execute(
            "explain analyze " + Q6)[0].rows)
    assert "engine:mesh" in plan, plan
    assert len(_mesh_ids()) == 7 and 3 not in _mesh_ids()

    for sql in (Q1, Q6, TOPN):
        want = _cpu_rows(sess, sql)
        r0 = _snap("mesh_failover_retries_total")[0]
        with failpoint("mesh/device_error",
                       once(DeviceFailure("device 3 halted mid-scan",
                                          device_ids=(3,)))):
            got = _run_on_mesh(sess, sql)
        _rows_eq(got, want, sql)
        assert _snap("mesh_failover_retries_total")[0] > r0
        ids = _mesh_ids()
        assert len(ids) == 7 and 3 not in ids, ids

    # the breaker is visible through information_schema
    h = {r[0]: r for r in sess.query(
        "select device_id, state, error_count, trip_count, in_current_mesh"
        " from information_schema.tidb_tpu_device_health")}
    assert h[3][1] == "tripped" and h[3][2] >= 1 and h[3][3] >= 1
    assert h[3][4] == 0  # quarantined out of the live mesh
    assert h[0][1] == "healthy" and h[0][4] == 1
    assert REGISTRY.snapshot().get("device_health_tripped_count") == 1

    # sharded arrays keyed to the dead device set were evicted: nothing in
    # the mesh cache may reference device 3
    for key in pl.MESH_CACHE._cache:
        assert 3 not in key[3], key

    # half-open probe: cooldown over -> device 3 rejoins for one trial,
    # the trial succeeds, the breaker closes, the FULL mesh is back
    DEVICE_HEALTH.expire_cooldowns()
    want = _cpu_rows(sess, Q1)
    got = _run_on_mesh(sess, Q1)
    _rows_eq(got, want, "post-probe Q1")
    assert len(_mesh_ids()) == 8
    h = {r[0]: r for r in sess.query(
        "select device_id, state, in_current_mesh"
        " from information_schema.tidb_tpu_device_health")}
    assert h[3][1] == "healthy" and h[3][2] == 1
    assert REGISTRY.snapshot().get("device_health_tripped_count") == 0


def test_failed_probe_retrips_breaker(sess):
    """A device that fails AGAIN during its half-open probe goes straight
    back to tripped (no flapping through healthy)."""
    _run_on_mesh(sess, Q6)
    DEVICE_HEALTH.record_error(2, RuntimeError("first failure"))
    assert DEVICE_HEALTH.state_of(2) == "tripped"
    DEVICE_HEALTH.expire_cooldowns()
    with failpoint("mesh/device_error",
                   once(DeviceFailure("still dead", device_ids=(2,)))):
        got = _run_on_mesh(sess, Q6)
    _rows_eq(got, _cpu_rows(sess, Q6), Q6)
    assert DEVICE_HEALTH.state_of(2) == "tripped"
    assert 2 not in _mesh_ids()


def test_hbm_oom_evicts_tile_caches_and_retries(sess):
    """HBM exhaustion is recoverable: evict the device tile caches (HBM is
    a cache over host blocks), re-run the same program, full parity — and
    no breaker trips for an unattributed OOM."""
    from tidb_tpu.copr import parallel as pl

    _run_on_mesh(sess, Q1)
    assert pl.MESH_CACHE._cache  # warm
    want = _cpu_rows(sess, Q1)
    o0 = _snap("mesh_hbm_oom_total")[0]
    with failpoint("mesh/hbm_oom",
                   once(HbmOomError("RESOURCE_EXHAUSTED: HBM space"))):
        got = _run_on_mesh(sess, Q1)
    _rows_eq(got, want, Q1)
    assert _snap("mesh_hbm_oom_total")[0] == o0 + 1
    assert len(_mesh_ids()) == 8  # nobody quarantined
    assert DEVICE_HEALTH.tripped_ids() == ()
    assert pl.MESH_CACHE._cache  # re-warmed by the retry


def test_all_breakers_open_steps_down_ladder(sess):
    """Every breaker open and no probe due: the mesh path steps aside and
    the per-region fan-out serves the query (next failover rung)."""
    import jax

    for d in jax.devices():
        DEVICE_HEALTH.record_error(d.id, RuntimeError(f"dead {d.id}"))
    want = _cpu_rows(sess, Q6)
    sess.execute("set tidb_use_tpu = 1")
    c0 = _snap("cop_tasks_total")[0]
    got = sess.query(Q6)
    _rows_eq(got, want, Q6)
    assert _snap("cop_tasks_total")[0] > c0  # per-region rung served it


# ---------------------------------------------------------------------------
# the seeded chaos sweep
# ---------------------------------------------------------------------------


def _wait_no_select_threads(timeout=3.0):
    deadline = time.monotonic() + timeout
    while time.monotonic() < deadline:
        alive = [t for t in threading.enumerate()
                 if t.name == "tidb-tpu-select" and t.is_alive()]
        if not alive:
            return []
        time.sleep(0.01)
    return alive


def _assert_no_leaks(domain):
    for tid in domain.storage.table_ids():
        assert domain.storage.table(tid).locks == {}, f"leaked locks t{tid}"
    assert _wait_no_select_threads() == [], "leaked producer threads"
    assert FAILPOINTS.armed() == [], "leaked armed failpoints"


def test_chaos_sweep_read_path(sess):
    """Arm each read-path failpoint site in turn (mesh device kill, HBM
    OOM, rebuild interruption, fan-out task error, region routing error)
    and assert every query shape keeps CPU parity with no leaks."""
    from tidb_tpu.errors import RegionError

    baselines = {sql: _cpu_rows(sess, sql) for sql in SWEEP_QUERIES}
    # (site, injected error, engine): mesh sites sit on the tpu mesh path;
    # the fan-out sites sit on the per-region path, exercised directly
    sites = [
        ("mesh/device_error",
         lambda: DeviceFailure("chip 5 died", device_ids=(5,)), "tpu"),
        ("mesh/hbm_oom",
         lambda: HbmOomError("hbm allocation failure"), "tpu"),
        ("mesh/rebuild", lambda: DeviceFailure("rebuild interrupted"), "tpu"),
        ("distsql/task_error", lambda: RuntimeError("chip died"), "cpu"),
        ("copr/region_error", lambda: RegionError("injected"), "cpu"),
    ]
    for name, make_exc, engine in sites:
        sess.execute(f"set tidb_use_tpu = {1 if engine == 'tpu' else 0}")
        for qi, sql in enumerate(SWEEP_QUERIES):
            if name == "mesh/rebuild":
                # a rebuild only happens when the device set changes, and
                # the retry after a classified failure completes it: flip
                # the set before every query
                if qi % 2 == 0:
                    DEVICE_HEALTH.record_error(1, RuntimeError("pre-tripped"))
                else:
                    DEVICE_HEALTH.reset()
            fired = {"n": 0}

            def action(_exc=make_exc, _f=fired, **ctx):
                _f["n"] += 1
                if _f["n"] == 1:
                    raise _exc()

            with failpoint(name, action):
                got = sess.query(sql)
            _rows_eq(got, baselines[sql], f"{name}: {sql}")
            assert fired["n"] >= 1, f"failpoint {name} never fired ({sql})"
        DEVICE_HEALTH.reset()
        sess.execute("set tidb_use_tpu = 1")
    _assert_no_leaks(sess.domain)
    # and the full mesh serves cleanly after the whole sweep
    got = _run_on_mesh(sess, Q1)
    _rows_eq(got, baselines[Q1], "post-sweep Q1")
    assert len(_mesh_ids()) == 8


def test_chaos_sweep_write_and_ddl_path():
    """2PC prewrite conflicts and DDL backfill crashes: statements retry
    or roll back cleanly — committed state stays consistent, no lock or
    thread leaks."""
    d = Domain()
    d.maintenance.stop()
    s = d.new_session()
    s.execute("create table w (a bigint primary key, b bigint)")
    s.execute("insert into w values (1, 10)")

    # 2PC: injected prewrite conflict -> the session's optimistic retry
    # re-runs the autocommit statement and commits
    with failpoint("2pc/prewrite", once(TxnConflictError((0, 0)))):
        s.execute("insert into w values (2, 20)")
    assert s.query("select a, b from w order by a") == [(1, 10), (2, 20)]

    # DDL: backfill (over a bulk-loaded base, so batches actually run)
    # dies -> job rolls back, the index name stays free, data unharmed;
    # a clean re-run succeeds
    s.execute("create table wd (a bigint, b bigint)")
    td = d.catalog.info_schema().table("test", "wd")
    sd = d.storage.table(td.id)
    sd.bulk_load_arrays(
        [np.arange(2000, dtype=np.int64),
         np.arange(2000, dtype=np.int64) % 10],
        ts=d.storage.current_ts())
    with failpoint("ddl/backfill_batch",
                   once(RuntimeError("backfill chip lost"))):
        with pytest.raises(RuntimeError):
            s.execute("create index ib on wd (b)")
    assert d.catalog.info_schema().table("test", "wd").find_index("ib") is None
    assert s.query("select count(*) from wd") == [(2000,)]
    s.execute("create index ib on wd (b)")
    assert s.query("select count(*) from wd where b = 3") == [(200,)]

    _assert_no_leaks(d)


def test_chaos_2pc_decision_point_runs_to_completion():
    """Past 2pc/before_commit_primary the transaction is DECIDED: a
    kill landing at that seam must not abort phase 2 — the primary and
    every secondary (2pc/commit_secondary) still commit, primary
    first."""
    from tidb_tpu.errors import QueryKilledError

    d = Domain()
    d.maintenance.stop()
    s = d.new_session()
    s.execute("create table p2 (a bigint primary key, b bigint)")
    order = []

    def at_decision(**ctx):
        order.append("decide")
        s.cancel_query("killed")  # lands AT the decision point: too late

    def at_secondary(**ctx):
        order.append("secondary")

    with failpoint("2pc/before_commit_primary", at_decision):
        with failpoint("2pc/commit_secondary", at_secondary):
            try:
                s.execute("insert into p2 values (1,10), (2,20), (3,30)")
            except QueryKilledError:
                pass  # the statement may unwind at a LATER seam...
    # ...but the commit itself ran to completion, in decision order
    assert order == ["decide", "secondary", "secondary"], order
    assert s.query("select a, b from p2 order by a") == \
        [(1, 10), (2, 20), (3, 30)]
    _assert_no_leaks(d)


# ---------------------------------------------------------------------------
# mpp/exchange: the eighth chaos site (device failure mid-shuffle)
# ---------------------------------------------------------------------------


def test_mpp_exchange_device_failure_degrades_down_ladder():
    """A device killed mid-shuffle must degrade, not fail: a transient
    kill retries on the REBUILT mesh (still MPP), a persistent one lands
    on the host hash join — CPU parity throughout, zero leaked threads,
    zero leaked failpoints."""
    from tidb_tpu.copr import parallel as pl

    d = Domain()
    s = d.new_session()
    s.execute("create table mo (k bigint primary key, f bigint)")
    s.execute("create table ml (k bigint, q bigint)")
    rng = np.random.default_rng(13)
    t_o = d.catalog.info_schema().table("test", "mo")
    t_l = d.catalog.info_schema().table("test", "ml")
    d.storage.table(t_o.id).bulk_load_arrays(
        [np.arange(4000, dtype=np.int64), rng.integers(0, 3, 4000)],
        ts=d.storage.current_ts())
    d.storage.table(t_l.id).bulk_load_arrays(
        [rng.integers(0, 12000, 16000), rng.integers(1, 9, 16000)],
        ts=d.storage.current_ts())
    s.execute("analyze table mo")
    s.execute("analyze table ml")
    s.execute("set tidb_enforce_mpp = 1")
    q = "select count(*), sum(q), max(f) from ml join mo on ml.k = mo.k"
    want = _cpu_rows(s, q)
    _rows_eq(s.query(q), want, "warm")

    # transient: one kill -> breaker trips, mesh rebuilds, SAME rung
    m0, f0 = _snap("mpp_joins_total", "mpp_fallback_total")
    with failpoint("mpp/exchange",
                   once(DeviceFailure("chip 3 died mid-shuffle",
                                      device_ids=(3,)))):
        got = s.query(q)
    _rows_eq(got, want, "transient mid-shuffle kill")
    m1, f1 = _snap("mpp_joins_total", "mpp_fallback_total")
    assert m1 > m0 and f1 == f0, "transient kill left the mpp rung"
    ids = tuple(dd.id for dd in pl._MESH.devices.ravel())
    assert 3 not in ids and len(ids) == 7, ids
    DEVICE_HEALTH.reset()

    # persistent: every retry dies -> host hash join serves with parity
    f0 = _snap("mpp_fallback_total")[0]
    from tidb_tpu.store.fault import always

    with failpoint("mpp/exchange",
                   always(DeviceFailure("chip 4 stays dead",
                                        device_ids=(4,)))):
        got = s.query(q)
    _rows_eq(got, want, "persistent mid-shuffle failure")
    assert _snap("mpp_fallback_total")[0] > f0, "host rung never served"
    DEVICE_HEALTH.reset()
    # drop this throwaway domain's sharded arrays: they were (re)loaded
    # on degraded meshes and must not linger for later modules
    uids = {d.storage.table(t_o.id).store_uid,
            d.storage.table(t_l.id).store_uid}
    pl.MESH_CACHE._c.evict_if(lambda k: k[0] in uids)
    _assert_no_leaks(d)


def test_tile_path_routes_around_tripped_default_device(sess):
    """ROADMAP PR-2 follow-up (a): the per-region tile path
    (jax_engine.run_base_jax) must not pin work to a tripped default
    device — tiles place on the surviving devices and a completed scan
    closes half-open breakers."""
    import jax

    from tidb_tpu.copr import jax_engine as je

    default_id = jax.devices()[0].id
    DEVICE_HEALTH.record_error(default_id, RuntimeError("chip 0 sick"))
    assert DEVICE_HEALTH.state_of(default_id) == "tripped"
    devs = je._tile_devices()
    assert default_id not in [d.id for d in devs]

    # drive a real per-region scan (mesh path disabled via many ranges is
    # intrusive; call the tile engine directly like distsql's fallback)
    d = sess.domain
    t = d.catalog.info_schema().table("test", "t")
    store = d.storage.table(t.id)
    from tidb_tpu.copr.ir import DAG, TableScanIR

    dag = DAG([TableScanIR(t.id, [0], [t.columns[0].ftype])])
    je.DEVICE_CACHE.clear()
    chunks = je.run_base_jax(store, dag, 0, store.base_rows, set())
    assert sum(c.num_rows for c in chunks) == store.base_rows
    placed = {k[4] for k in je.DEVICE_CACHE._c.items_view}
    assert default_id not in placed, placed
    # the completed scan recorded success for the devices it used; the
    # tripped default stays tripped until its half-open probe
    assert DEVICE_HEALTH.state_of(default_id) == "tripped"
    DEVICE_HEALTH.reset()
    je.DEVICE_CACHE.clear()


# ---------------------------------------------------------------------------
# fail-fast fan-out + configurable equal-jitter backoff (satellites)
# ---------------------------------------------------------------------------


def test_failfast_fanout_abandons_retrying_siblings():
    """First task error flags the stop event: a sibling stuck in its
    transient-retry loop abandons within one backoff step instead of
    burning the full 10s budget for a query that already failed."""
    from tidb_tpu.errors import ExecutorError

    d = Domain()
    s = d.new_session()
    s.execute("create table ff (a bigint)")
    t = d.catalog.info_schema().table("test", "ff")
    store = d.storage.table(t.id)
    store.bulk_load_arrays([np.arange(2000, dtype=np.int64)],
                           ts=d.storage.current_ts())
    d.storage.regions.split_even(t.id, 2, store.base_rows)
    s.execute("set tidb_use_tpu = 0")

    attempts = {"n": 0}

    def action(range=None, **ctx):
        if range.start == 0:
            time.sleep(0.05)
            raise ExecutorError("poison task")  # semantic: no retry
        attempts["n"] += 1
        raise OSError("flaky net")  # transient: retries with backoff

    f0 = _snap("cop_fanout_failfast_total")[0]
    with failpoint("distsql/task_error", action):
        with pytest.raises(ExecutorError):
            s.query("select sum(a) from ff")
        # the flaky sibling must stop retrying once the query failed
        time.sleep(0.7)
        settled = attempts["n"]
        time.sleep(0.7)
        assert attempts["n"] == settled, "sibling kept retrying after error"
    assert settled < 10  # nowhere near a full 10s budget worth of attempts
    assert _snap("cop_fanout_failfast_total")[0] == f0 + 1
    _assert_no_leaks(d)


def test_backoffer_equal_jitter_schedule():
    """Equal jitter (backoff.go NewBackoffFn): each sleep lands in
    [expo/2, expo] of the capped exponential schedule, and two backoffers
    de-synchronize."""
    import random

    from tidb_tpu.distsql.backoff import Backoffer

    sleeps = []
    bo = Backoffer(budget_ms=60_000, sleep=sleeps.append,
                   rng=random.Random(7))
    for _ in range(9):
        bo.backoff("task_error")
    assert bo.attempts("task_error") == 9
    for n, slept in enumerate(sleeps):
        expo_s = min(5 * (2 ** n), 1000) / 1000.0
        assert expo_s / 2 <= slept <= expo_s, (n, slept)
    other = []
    bo2 = Backoffer(budget_ms=60_000, sleep=other.append,
                    rng=random.Random(8))
    for _ in range(9):
        bo2.backoff("task_error")
    assert sleeps != other  # jitter de-synchronizes concurrent retries


def test_backoff_budget_exceeded_surfaces_last_error():
    import random

    from tidb_tpu.distsql.backoff import BackoffBudgetExceeded, Backoffer

    bo = Backoffer(budget_ms=5, sleep=lambda s: None, rng=random.Random(1))
    with pytest.raises(BackoffBudgetExceeded, match="flaky"):
        for _ in range(100):
            bo.backoff("task_error", OSError("flaky"))


def test_backoff_budget_session_var():
    """tidb_backoff_budget_ms replaces the hard-coded 10s: a tiny budget
    makes a permanently failing scan surface its error immediately."""
    from tidb_tpu.store.fault import always

    d = Domain()
    s = d.new_session()
    s.execute("create table bb (a bigint)")
    s.execute("insert into bb values (1), (2)")
    s.execute("set tidb_use_tpu = 0")
    s.execute("set tidb_backoff_budget_ms = 1")
    t0 = time.perf_counter()
    with failpoint("distsql/task_error", always(OSError("flaky net"))):
        with pytest.raises(TiDBTPUError, match="budget exhausted"):
            s.query("select sum(a) from bb")
    assert time.perf_counter() - t0 < 2.0  # not the default 10s budget
    _assert_no_leaks(d)


# ---------------------------------------------------------------------------
# exec/cancel: statement killed mid-distsql / mid-MPP / mid-backfill
# (ISSUE 5 chaos coverage)
# ---------------------------------------------------------------------------


def _cancel_at(site_wanted):
    """Failpoint action for exec/cancel: cancel the statement's scope
    (the way KILL QUERY does) the first time the named site is hit."""
    fired = {"n": 0}

    def action(site=None, scope=None, **ctx):
        if site == site_wanted and scope is not None:
            fired["n"] += 1
            if fired["n"] == 1:
                scope.cancel("killed")

    return action, fired


def test_exec_cancel_mid_distsql(sess):
    """Kill landing between distsql task dispatches: the statement errors
    with ER_QUERY_INTERRUPTED, leaks nothing, and an immediate re-run
    returns full parity."""
    from tidb_tpu.errors import QueryKilledError

    want = _cpu_rows(sess, Q1)
    sess.execute("set tidb_use_tpu = 0")
    action, fired = _cancel_at("distsql")
    with failpoint("exec/cancel", action):
        with pytest.raises(QueryKilledError):
            sess.query(Q1)
    assert fired["n"] >= 1, "exec/cancel never hit the distsql site"
    assert sess.last_termination == "killed"
    sess.execute("set tidb_use_tpu = 1")
    _assert_no_leaks(sess.domain)
    _rows_eq(sess.query(Q1), want, "post-cancel re-run parity")


def test_exec_cancel_mid_mpp():
    """Kill landing at an MPP rung transition: the exchange engine
    surfaces the termination error instead of stepping down the ladder,
    and the rebuilt state serves a clean re-run."""
    from tidb_tpu.errors import QueryKilledError

    d = Domain()
    d.maintenance.stop()
    s = d.new_session()
    s.execute("create table co (k bigint primary key, f bigint)")
    s.execute("create table cl (k bigint, q bigint)")
    rng = np.random.default_rng(23)
    t_o = d.catalog.info_schema().table("test", "co")
    t_l = d.catalog.info_schema().table("test", "cl")
    d.storage.table(t_o.id).bulk_load_arrays(
        [np.arange(3000, dtype=np.int64), rng.integers(0, 3, 3000)],
        ts=d.storage.current_ts())
    d.storage.table(t_l.id).bulk_load_arrays(
        [rng.integers(0, 9000, 12000), rng.integers(1, 9, 12000)],
        ts=d.storage.current_ts())
    s.execute("analyze table co")
    s.execute("analyze table cl")
    s.execute("set tidb_enforce_mpp = 1")
    q = "select count(*), sum(q) from cl join co on cl.k = co.k"
    want = _cpu_rows(s, q)

    action, fired = _cancel_at("mpp")
    with failpoint("exec/cancel", action):
        with pytest.raises(QueryKilledError):
            s.query(q)
    assert fired["n"] >= 1, "exec/cancel never hit the mpp site"
    assert s.last_termination == "killed"
    _assert_no_leaks(d)
    _rows_eq(s.query(q), want, "post-cancel mpp re-run parity")


def test_exec_cancel_mid_backfill():
    """Kill landing between DDL backfill batches: the online add-index
    job rolls back (name reusable, data unharmed), no reorg checkpoints
    leak, and a clean re-run builds the index."""
    from tidb_tpu.errors import QueryKilledError

    d = Domain()
    d.maintenance.stop()
    s = d.new_session()
    s.execute("create table cb (a bigint, b bigint)")
    t = d.catalog.info_schema().table("test", "cb")
    d.storage.table(t.id).bulk_load_arrays(
        [np.arange(9000, dtype=np.int64),
         np.arange(9000, dtype=np.int64) % 10],
        ts=d.storage.current_ts())

    action, fired = _cancel_at("backfill")
    with failpoint("exec/cancel", action):
        with pytest.raises(QueryKilledError):
            s.execute("create index icb on cb (b)")
    assert fired["n"] >= 1, "exec/cancel never hit the backfill site"
    assert d.catalog.info_schema().table("test", "cb") \
        .find_index("icb") is None
    jobs = [j for j in d.catalog.jobs if j.table == "cb"]
    assert jobs and jobs[-1].state == "rollback"
    assert s.query("select count(*) from cb") == [(9000,)]
    _assert_no_leaks(d)
    s.execute("create index icb on cb (b)")
    assert s.query("select count(*) from cb where b = 3") == [(900,)]
