"""One dispatch per mesh statement + per-statement resource groups.

Coverage:

- a mesh statement dispatches its program ONCE over all of its range
  slots, between a pre- and a post-dispatch cancellation seam: a KILL
  that arrives while the dispatch is in flight lands at the
  post-dispatch seam, and the session re-runs with full parity;
- with one dispatch a statement the order in which double partial sums
  are added never changes: a grouped SUM(double) is bit-identical run
  over run;
- resource groups: token-bucket quotas charge per dispatch, depleted
  non-burstable groups raise the typed retriable ResourceGroupThrottled,
  two groups with 1:3 quotas observe device-time share near the ratio,
  and QUERY_LIMIT cancels the runaway statement through its scope with
  reason ``resource_group``;
- the DDL surface (CREATE/ALTER/DROP RESOURCE GROUP, ALTER USER ...
  RESOURCE GROUP, the tidb_tpu_resource_group sysvar) and the
  INFORMATION_SCHEMA.TIDB_TPU_RESOURCE_GROUPS memtable.
"""

import threading
import time

import numpy as np
import pytest

from tidb_tpu.errors import (
    QueryKilledError,
    ResourceGroupThrottled,
    TiDBTPUError,
)
from tidb_tpu.lifecycle import QueryScope, classify_termination
from tidb_tpu.metrics import REGISTRY
from tidb_tpu.session import Domain
from tidb_tpu.store.fault import FAILPOINTS, failpoint

Q_AGG = ("select g, sum(x), count(*), min(x), max(x) from t "
         "group by g order by g")
Q_TOPN = "select k, x from t order by x desc limit 7"
Q_FILTER = "select k from t where x < 2.5"

@pytest.fixture(scope="module")
def sess():
    d = Domain()
    s = d.new_session()
    s.execute("create table t (k bigint, g bigint, x double)")
    t = d.catalog.info_schema().table("test", "t")
    store = d.storage.table(t.id)
    rng = np.random.default_rng(17)
    n = 20_000
    store.bulk_load_arrays(
        [np.arange(n, dtype=np.int64),
         rng.integers(0, 5, n, dtype=np.int64),
         rng.uniform(0, 100, n)],
        ts=d.storage.current_ts(),
    )
    d.storage.regions.split_even(t.id, 4, store.base_rows)
    s.execute("set tidb_use_tpu = 1")
    return s


def _approx_eq(a, b):
    if isinstance(a, float) or isinstance(b, float):
        return a == pytest.approx(b, rel=1e-9, abs=1e-9)
    return a == b


def _rows_eq(got, want, ctx=""):
    assert len(got) == len(want), (ctx, got, want)
    for ra, rb in zip(sorted(got), sorted(want)):
        assert all(_approx_eq(x, y) for x, y in zip(ra, rb)), (ctx, ra, rb)


# ---------------------------------------------------------------------------
# KILL lands at the post-dispatch seam
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("kind,sql", [
    ("agg", Q_AGG), ("filter", Q_FILTER), ("topn", Q_TOPN)],
    ids=["agg", "streamed-filter", "topn"])
def test_kill_at_dispatch_lands_at_post_dispatch_seam(sess, kind, sql):
    """Kill fired from the pre-dispatch failpoint, as if it arrived
    while the program was in flight: the one dispatch runs to its end
    and the statement unwinds at the post-dispatch seam with the typed
    error, before any host finishing."""
    d = sess.domain
    victim = d.new_session()
    victim.execute("set tidb_use_tpu = 1")
    hits = []

    def action(**ctx):
        if ctx.get("kind") != kind:
            return
        hits.append((ctx["chunk"], ctx["total"]))
        d.kill(victim.conn_id, True)

    with failpoint("copr/chunk_dispatch", action):
        with pytest.raises(QueryKilledError):
            victim.query(sql)
    assert hits == [(0, 1)], f"a mesh statement is one dispatch: {hits}"
    # the session is healthy afterwards and re-running has full parity
    oracle = d.new_session()
    oracle.execute("set tidb_use_tpu = 0")
    _rows_eq(victim.query(sql), oracle.query(sql), ctx=sql)


def test_grouped_double_sum_is_bit_identical_run_over_run(sess):
    """One dispatch a statement adds the double partial sums in one
    order, whatever the clock says: ten runs return the same bits, and
    they are the oracle's rows."""
    q = "select g, sum(x), avg(x) from t group by g order by g"
    runs = [sess.query(q) for _ in range(10)]
    assert all(r == runs[0] for r in runs[1:]), runs
    oracle = sess.domain.new_session()
    oracle.execute("set tidb_use_tpu = 0")
    want = oracle.query(q)
    assert len(runs[0]) == len(want) == 5
    _rows_eq(runs[0], want, ctx=q)


def test_no_failpoint_leaks_after_kills(sess):
    # the conftest autouse fixtures assert no armed failpoints and no
    # witness violations leak; this is the explicit no-leak checkpoint
    assert not FAILPOINTS._points


# ---------------------------------------------------------------------------
# resource groups: bucket mechanics
# ---------------------------------------------------------------------------

def test_resgroup_registry_basics():
    from tidb_tpu.lifecycle import ResourceGroupRegistry

    reg = ResourceGroupRegistry()
    g = reg.create("gold", ru_per_sec=100, burstable=True,
                   query_limit_ms=500)
    assert reg.get("gold") is g
    with pytest.raises(ValueError):
        reg.create("gold")
    assert reg.create("gold", if_not_exists=True) is g
    reg.alter("gold", ru_per_sec=200)
    assert g.ru_per_sec == 200
    with pytest.raises(KeyError):
        reg.alter("nope")
    reg.bind_user("alice", "gold")
    assert reg.resolve("alice@%").name == "gold"
    # sysvar wins over binding; unknown names fall back to default
    assert reg.resolve("alice", "default").name == "default"
    assert reg.resolve("bob", "ghost").name == "default"
    with pytest.raises(ValueError):
        reg.drop("default")
    reg.drop("gold")
    assert reg.resolve("alice").name == "default"
    reg.drop("gold", if_exists=True)
    with pytest.raises(KeyError):
        reg.drop("gold")


def test_resgroup_charge_and_refill():
    from tidb_tpu.lifecycle import ResourceGroupRegistry

    reg = ResourceGroupRegistry()
    g = reg.create("bronze", ru_per_sec=1000)
    sc = QueryScope()
    sc.resgroup = g
    g.charge(400.0, sc)
    assert sc.device_ms == pytest.approx(400.0)
    snap = g.snapshot()
    assert snap["consumed_ru"] == pytest.approx(400.0)
    assert snap["tokens"] < 1000.0
    assert REGISTRY.snapshot().get(
        "resgroup_bronze_ru_consumed_total", 0) >= 400.0


def test_dispatch_admission_bills_device_time_not_lock_wait():
    """RU accounting (ISSUE 20 satellite): the charge clock starts
    INSIDE the DISPATCH_LOCK — a tenant stuck behind another tenant's
    dispatch in the lock queue is not billed for the queue time."""
    from tidb_tpu.lifecycle import ResourceGroupRegistry
    from tidb_tpu.lifecycle.resgroup import dispatch_admission
    from tidb_tpu.lifecycle.scope import attach_scope

    reg = ResourceGroupRegistry()
    g = reg.create("metered", ru_per_sec=0)  # unlimited: admit is free
    sc = QueryScope()
    sc.resgroup = g
    lock = threading.Lock()
    entered = threading.Event()
    release = threading.Event()

    def hog():
        with lock:
            entered.set()
            release.wait(5.0)

    t = threading.Thread(target=hog)
    t.start()
    assert entered.wait(5.0)
    timer = threading.Timer(0.25, release.set)
    timer.start()
    try:
        with attach_scope(sc):
            with dispatch_admission(lock):
                time.sleep(0.02)  # the "device" body
    finally:
        release.set()
        t.join()
        timer.cancel()
    consumed = g.snapshot()["consumed_ru"]
    # billed the ~20ms body, never the ~250ms queue wait
    assert 5.0 <= consumed < 150.0, consumed
    assert sc.device_ms == pytest.approx(consumed, abs=0.01)


def test_dispatch_admission_charges_on_exception_without_lock_wait():
    """An exception inside the locked body still charges only the time
    spent holding the lock — never a bogus absolute timestamp."""
    from tidb_tpu.lifecycle import ResourceGroupRegistry
    from tidb_tpu.lifecycle.resgroup import dispatch_admission
    from tidb_tpu.lifecycle.scope import attach_scope

    reg = ResourceGroupRegistry()
    g = reg.create("metered_exc", ru_per_sec=0)
    sc = QueryScope()
    sc.resgroup = g
    lock = threading.Lock()
    with pytest.raises(RuntimeError):
        with attach_scope(sc):
            with dispatch_admission(lock):
                time.sleep(0.01)
                raise RuntimeError("device fault")
    consumed = g.snapshot()["consumed_ru"]
    assert 1.0 <= consumed < 150.0, consumed


def test_resgroup_throttled_typed_error(monkeypatch):
    from tidb_tpu.lifecycle import ResourceGroupRegistry

    monkeypatch.setenv("TIDB_TPU_RESGROUP_MAX_WAIT_MS", "40")
    reg = ResourceGroupRegistry()
    g = reg.create("tiny", ru_per_sec=1)
    sc = QueryScope()
    sc.resgroup = g
    g.charge(50.0, sc)  # drive the bucket deep into debt
    t0 = time.monotonic()
    with pytest.raises(ResourceGroupThrottled) as ei:
        g.admit(sc)
    assert ei.value.group == "tiny"
    assert ei.value.wait_ms >= 40.0
    assert time.monotonic() - t0 < 5.0
    assert REGISTRY.snapshot().get("resgroup_tiny_throttled_total", 0) >= 1


def test_resgroup_admit_interrupted_by_kill(monkeypatch):
    """A statement parked at admission still honors KILL: the poll loop
    checks the scope, so cancellation preempts the throttle wait."""
    from tidb_tpu.lifecycle import ResourceGroupRegistry

    monkeypatch.setenv("TIDB_TPU_RESGROUP_MAX_WAIT_MS", "60000")
    reg = ResourceGroupRegistry()
    g = reg.create("parked", ru_per_sec=1)
    sc = QueryScope()
    sc.resgroup = g
    g.charge(10_000.0, sc)
    t = threading.Timer(0.05, sc.cancel, args=("killed",))
    t.start()
    t0 = time.monotonic()
    with pytest.raises(QueryKilledError):
        g.admit(sc)
    assert time.monotonic() - t0 < 5.0
    t.join()


def test_burstable_runs_on_debt():
    from tidb_tpu.lifecycle import ResourceGroupRegistry

    reg = ResourceGroupRegistry()
    g = reg.create("bursty", ru_per_sec=1, burstable=True)
    sc = QueryScope()
    sc.resgroup = g
    g.charge(500.0, sc)
    # depleted but burstable with nobody else waiting: admits on debt
    assert g.admit(sc) == 0.0


def test_query_limit_cancels_via_scope():
    from tidb_tpu.lifecycle import ResourceGroupRegistry

    reg = ResourceGroupRegistry()
    g = reg.create("capped", ru_per_sec=0, query_limit_ms=100)
    sc = QueryScope()
    sc.resgroup = g
    g.charge(60.0, sc)
    assert not sc.cancelled()
    g.charge(60.0, sc)  # total 120ms > QUERY_LIMIT 100ms
    assert sc.cancelled()
    assert sc.reason == "resource_group"
    with pytest.raises(QueryKilledError):
        sc.check()
    assert classify_termination(QueryKilledError(), sc) == "resource_group"


# ---------------------------------------------------------------------------
# weighted fairness: 1:3 quotas -> ~1:3 device share
# ---------------------------------------------------------------------------

@pytest.mark.slow
def test_two_group_fairness_ratio(sess):
    d = sess.domain
    adm = d.new_session()
    adm.execute("create resource group fair_a ru_per_sec = 40")
    adm.execute("create resource group fair_b ru_per_sec = 120")
    base = REGISTRY.snapshot()
    stop = threading.Event()
    errs = []

    def worker(group):
        s2 = d.new_session()
        s2.execute(f"set tidb_tpu_resource_group = '{group}'")
        s2.execute("set tidb_use_tpu = 1")
        while not stop.is_set():
            try:
                s2.query(Q_AGG)
            except ResourceGroupThrottled:
                pass
            except BaseException as e:  # noqa: BLE001
                errs.append(e)
                return

    threads = [threading.Thread(target=worker, args=(g,))
               for g in ("fair_a", "fair_b")]
    for t in threads:
        t.start()
    time.sleep(3.0)
    stop.set()
    for t in threads:
        t.join(timeout=60)
    adm.execute("drop resource group fair_a")
    adm.execute("drop resource group fair_b")
    assert not errs, errs
    snap = REGISTRY.snapshot()
    ru_a = (snap.get("resgroup_fair_a_ru_consumed_total", 0)
            - base.get("resgroup_fair_a_ru_consumed_total", 0))
    ru_b = (snap.get("resgroup_fair_b_ru_consumed_total", 0)
            - base.get("resgroup_fair_b_ru_consumed_total", 0))
    assert ru_a > 0 and ru_b > 0, (ru_a, ru_b)
    ratio = ru_b / ru_a
    # acceptance: device-time share within 25% of the 3.0 quota ratio
    assert 3.0 * 0.75 <= ratio <= 3.0 * 1.25, \
        f"consumed RU ratio {ratio:.2f} strays from the 1:3 quotas"


def test_depleted_group_throttles_while_other_proceeds(sess, monkeypatch):
    monkeypatch.setenv("TIDB_TPU_RESGROUP_MAX_WAIT_MS", "30")
    d = sess.domain
    adm = d.new_session()
    adm.execute("create resource group starved ru_per_sec = 1")
    try:
        s_starved = d.new_session()
        s_starved.execute("set tidb_tpu_resource_group = 'starved'")
        s_starved.execute("set tidb_use_tpu = 1")
        # burn the 1-RU budget, then a later statement must throttle
        with pytest.raises(ResourceGroupThrottled):
            for _ in range(50):
                s_starved.query(Q_AGG)
        # an unbound session (default group, unlimited) is unaffected
        t0 = time.perf_counter()
        sess.query(Q_AGG)
        assert time.perf_counter() - t0 < 30.0
    finally:
        adm.execute("drop resource group starved")


# ---------------------------------------------------------------------------
# SQL surface + observability
# ---------------------------------------------------------------------------

def test_resource_group_ddl_surface(sess):
    d = sess.domain
    s = d.new_session()
    s.execute("create resource group rg_ddl ru_per_sec = 500 burstable")
    s.execute("alter resource group rg_ddl ru_per_sec = 700, "
              "query_limit = (exec_elapsed = 9000)")
    s.execute("create user 'carol' identified by 'pw'")
    s.execute("alter user 'carol' resource group rg_ddl")
    rows = s.query("select name, ru_per_sec, burstable, query_limit_ms, "
                   "users from information_schema."
                   "tidb_tpu_resource_groups where name = 'rg_ddl'")
    assert rows == [("rg_ddl", 700, 1, 9000, "carol")]
    # duplicate create is a typed error; IF NOT EXISTS is not
    with pytest.raises(TiDBTPUError):
        s.execute("create resource group rg_ddl")
    s.execute("create resource group if not exists rg_ddl")
    s.execute("drop resource group rg_ddl")
    with pytest.raises(TiDBTPUError):
        s.execute("drop resource group rg_ddl")
    s.execute("drop resource group if exists rg_ddl")
    assert s.query("select name from information_schema."
                   "tidb_tpu_resource_groups") == [("default",)]


def test_scope_carries_group_and_charges(sess):
    d = sess.domain
    s = d.new_session()
    s.execute("create resource group rg_scope ru_per_sec = 100000")
    try:
        s.execute("set tidb_tpu_resource_group = 'rg_scope'")
        s.execute("set tidb_use_tpu = 1")
        base = REGISTRY.snapshot().get(
            "resgroup_rg_scope_ru_consumed_total", 0)
        s.query(Q_AGG)
        after = REGISTRY.snapshot().get(
            "resgroup_rg_scope_ru_consumed_total", 0)
        assert after > base, "the dispatch was not charged to the group"
    finally:
        s.execute("set tidb_tpu_resource_group = ''")
        s.execute("drop resource group rg_scope")


def test_explain_analyze_reports_chunks(sess):
    sess.execute("set tidb_enable_slow_log = 1")
    try:
        rows = sess.query("explain analyze " + Q_AGG)
    finally:
        sess.execute("set tidb_enable_slow_log = 0")
    root_extra = rows[0][-1]
    # one mesh statement, one dispatch
    assert root_extra.endswith("chunks: 1"), root_extra


def test_status_and_snapshot_sections(sess):
    snap = sess.domain.resgroups.snapshot()
    assert any(g["name"] == "default" for g in snap)
    from tidb_tpu.server.http_status import _resgroups_section

    sec = _resgroups_section(sess.domain)
    assert "groups" in sec and "error" not in sec


# ---------------------------------------------------------------------------
# PRIORITY: weighted-fair admission order (ISSUE 18 lifecycle (c))
# ---------------------------------------------------------------------------

def test_priority_ddl_and_infoschema():
    d = Domain()
    s = d.new_session()
    s.execute("create resource group rg_prio ru_per_sec = 500 "
              "priority = 4")
    g = d.resgroups.get("rg_prio")
    assert g.priority == 4
    s.execute("alter resource group rg_prio priority = 2")
    assert g.priority == 2
    rows = s.query("select name, priority from information_schema."
                   "tidb_tpu_resource_groups where name = 'rg_prio'")
    assert rows == [("rg_prio", 2)]
    # default group keeps weight 1; priority floor clamps to 1
    assert d.resgroups.get("default").priority == 1
    s.execute("alter resource group rg_prio priority = 0")
    assert g.priority == 1
    s.execute("drop resource group rg_prio")


def test_priority_gate_inert_without_differing_contention():
    """A group running alone — or against equal-priority peers — pays
    nothing for the gate: admission stays the original token behavior."""
    from tidb_tpu.lifecycle import ResourceGroupRegistry

    reg = ResourceGroupRegistry()
    hi = reg.create("solo_hi", priority=8)
    sc = QueryScope()
    sc.resgroup = hi
    for _ in range(50):
        assert hi.admit(sc) == 0.0  # no contender: instant every time
    reg = ResourceGroupRegistry()  # fresh: solo_hi is still "recent"
    eq_a = reg.create("eq_a", priority=3)
    eq_b = reg.create("eq_b", priority=3)
    sa, sb = QueryScope(), QueryScope()
    sa.resgroup, sb.resgroup = eq_a, eq_b
    for _ in range(50):
        assert eq_a.admit(sa) == 0.0
        assert eq_b.admit(sb) == 0.0  # same weight: gate never engages


def test_priority_two_to_one_admission_under_contention():
    """Sustained contention between a PRIORITY=2 and a PRIORITY=1 group
    admits dispatches ~2:1 — the weighted-fair finish tags advance at
    1/priority per admitted dispatch, so the device boundary crossings
    track the weights."""
    from tidb_tpu.lifecycle import ResourceGroupRegistry

    reg = ResourceGroupRegistry()
    hi = reg.create("wfq_hi", priority=2)
    lo = reg.create("wfq_lo", priority=1)
    counts = {"wfq_hi": 0, "wfq_lo": 0}
    stop = threading.Event()

    def pump(g):
        sc = QueryScope()
        sc.resgroup = g
        while not stop.is_set():
            g.admit(sc)
            counts[g.name] += 1

    threads = [threading.Thread(target=pump, args=(g,))
               for g in (hi, lo)]
    for t in threads:
        t.start()
    # measure AFTER both groups are engaged: until the second thread's
    # first arrival the gate is rightly inert (no contention) and the
    # first group tight-loops ungated — that ramp is not contention
    time.sleep(0.15)
    base = dict(counts)
    time.sleep(0.7)
    delta = {k: counts[k] - base[k] for k in counts}
    stop.set()
    for t in threads:
        t.join(timeout=10)
    assert delta["wfq_lo"] >= 30, delta  # no starvation, real contention
    ratio = delta["wfq_hi"] / delta["wfq_lo"]
    assert 1.4 <= ratio <= 2.8, delta


def test_priority_never_throttles_on_priority_alone(monkeypatch):
    """A low-priority group held back ONLY by the weighted-fair gate
    passes through at the bounded wait instead of raising
    ResourceGroupThrottled — priority shapes order, not quota."""
    from tidb_tpu.lifecycle import ResourceGroupRegistry

    monkeypatch.setenv("TIDB_TPU_RESGROUP_MAX_WAIT_MS", "50")
    reg = ResourceGroupRegistry()
    hi = reg.create("rush_hi", priority=64)
    lo = reg.create("rush_lo", priority=1)
    stop = threading.Event()

    def flood():
        sc = QueryScope()
        sc.resgroup = hi
        while not stop.is_set():
            hi.admit(sc)

    t = threading.Thread(target=flood)
    t.start()
    try:
        sc = QueryScope()
        sc.resgroup = lo
        for _ in range(5):
            lo.admit(sc)  # must NEVER raise: tokens are unlimited
    finally:
        stop.set()
        t.join(timeout=10)
    assert lo.snapshot()["throttled"] == 0


# ---------------------------------------------------------------------------
# definition replication through the coord plane (ISSUE 18 lifecycle (e))
# ---------------------------------------------------------------------------

def test_resgroup_defs_replicate_over_local_plane():
    """Two domains attached to one plane converge on the same
    definitions: CREATE/ALTER/bind/DROP on one side shows up on the
    other at its next resolve(), preserving live token balances."""
    from tidb_tpu.coord.plane import LocalPlane

    plane = LocalPlane()
    dA, dB = Domain(), Domain()
    dA.resgroups.attach_plane(plane)
    dB.resgroups.attach_plane(plane)
    sA = dA.new_session()
    sA.execute("create resource group silver ru_per_sec = 800 "
               "burstable priority = 3, query_limit = 1200")
    sA.execute("create user 'dave' identified by 'pw'")
    sA.execute("alter user 'dave' resource group silver")
    # the replica adopts the definitions at resolve time
    g = dB.resgroups.resolve("dave@%")
    assert (g.name, g.ru_per_sec, g.burstable, g.priority,
            g.query_limit_ms) == ("silver", 800, True, 3, 1200)
    # ALTER replicates without resetting the replica's live balance
    sc = QueryScope()
    sc.resgroup = g
    g.charge(300.0, sc)
    tokens_before = g.snapshot()["tokens"]
    sA.execute("alter resource group silver priority = 5, "
               "query_limit = 900")
    g2 = dB.resgroups.resolve("dave@%")
    assert g2 is g  # updated in place, not replaced
    assert g.priority == 5 and g.query_limit_ms == 900
    assert g.snapshot()["tokens"] == pytest.approx(
        tokens_before, abs=50.0)  # balance survived (modulo refill)
    # DROP replicates; the binding falls back to default
    sA.execute("drop resource group silver")
    assert dB.resgroups.resolve("dave@%").name == "default"
    # a DETACHED domain never syncs from the plane
    dC = Domain()
    sA.execute("create resource group silver ru_per_sec = 1")
    assert dC.resgroups.get("silver") is None


def test_resgroup_defs_replicate_over_rpc_plane():
    """The worker-plane path: definitions published on the coordinator
    member ride the membership broadcast (shared store piggyback) and a
    worker-side domain adopts them without any direct RPC of its own."""
    from tidb_tpu.coord.plane import (
        Coordinator, CoordinatorPlane, WorkerPlane)

    coord = Coordinator(port=0, lease_s=4.0, expect=2, self_pid=0)
    host, port = coord.start()
    cp = CoordinatorPlane(coord, pid=0).start((0,))
    wp = WorkerPlane(f"{host}:{port}", 1, lease_s=4.0,
                     heartbeat_s=0.05).start((1,))
    try:
        _wait_for(lambda: cp.view().formed and wp.view().formed)
        dA, dB = Domain(), Domain()
        dA.resgroups.attach_plane(cp)
        dB.resgroups.attach_plane(wp)
        sA = dA.new_session()
        sA.execute("create resource group fleetwide ru_per_sec = 250 "
                   "priority = 7")
        # the worker's local shared cache fills from the heartbeat
        _wait_for(lambda: wp.shared_version("resgroups") >= 1)
        g = dB.resgroups.resolve("", "fleetwide")
        assert (g.name, g.ru_per_sec, g.priority) == \
            ("fleetwide", 250, 7)
    finally:
        try:
            wp.stop(leave=True)
        except Exception:
            pass
        cp.stop()


def _wait_for(pred, timeout=15.0):
    t0 = time.monotonic()
    while time.monotonic() - t0 < timeout:
        if pred():
            return
        time.sleep(0.05)
    raise AssertionError("condition not reached")
