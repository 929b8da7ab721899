"""Interruptible chunked dispatch + per-statement resource groups
(ISSUE 17).

Tentpole coverage:

- chunked-vs-unchunked parity across the fusion corpus (rows, agg,
  TopN) — chunking changes only range-slot operand VALUES on the same
  compiled program, never results;
- the chunk count must NOT enter any program fingerprint: no new
  compiled entries appear when the chunk budget changes;
- KILL of an in-flight oversized scan lands at the between-chunk seam:
  the statement returns within two chunk dispatches of the kill instead
  of running the remaining sequence;
- resource groups: token-bucket quotas charge per chunk, depleted
  non-burstable groups raise the typed retriable ResourceGroupThrottled,
  two groups with 1:3 quotas observe device-time share near the ratio,
  and QUERY_LIMIT cancels the runaway statement through its scope with
  reason ``resource_group``;
- the DDL surface (CREATE/ALTER/DROP RESOURCE GROUP, ALTER USER ...
  RESOURCE GROUP, the tidb_tpu_resource_group sysvar) and the
  INFORMATION_SCHEMA.TIDB_TPU_RESOURCE_GROUPS memtable.
"""

import os
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
Q_SUM = "select sum(x) from t where k < 15000 and x < 50"
Q_TOPN = "select k, x from t order by x desc limit 7"
Q_FILTER = "select k from t where x < 2.5"

CORPUS = (Q_AGG, Q_SUM, Q_TOPN, Q_FILTER)


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


@pytest.fixture()
def chunked():
    """Force multi-chunk dispatch regardless of the latency estimate."""
    os.environ["TIDB_TPU_DISPATCH_CHUNK_ROWS"] = "2048"
    yield
    os.environ.pop("TIDB_TPU_DISPATCH_CHUNK_ROWS", None)


def _approx_eq(a, b):
    if isinstance(a, float) or isinstance(b, float):
        return a == pytest.approx(b, rel=1e-9, abs=1e-9)
    return a == b


def _rows_eq(got, want, ctx=""):
    assert len(got) == len(want), (ctx, got, want)
    for ra, rb in zip(sorted(got), sorted(want)):
        assert all(_approx_eq(x, y) for x, y in zip(ra, rb)), (ctx, ra, rb)


# ---------------------------------------------------------------------------
# chunk_bounds unit behavior
# ---------------------------------------------------------------------------

def test_chunk_bounds_split_and_disabled():
    from tidb_tpu.copr.chunking import chunk_bounds

    # budget 0 => ONE chunk, bounds verbatim (the disabled path)
    assert chunk_bounds([(0, 10), (20, 25)], 0) == [[(0, 10), (20, 25)]]
    assert chunk_bounds([], 100) == []
    # rows split across chunks, ranges stay disjoint + ascending
    assert chunk_bounds([(0, 10)], 4) == [[(0, 4)], [(4, 8)], [(8, 10)]]
    # max_slots caps ranges per chunk even under budget
    out = chunk_bounds([(0, 1), (2, 3), (4, 5), (6, 7), (8, 9)], 100,
                       max_slots=2)
    assert all(len(c) <= 2 for c in out)
    flat = [r for c in out for r in c]
    assert flat == [(0, 1), (2, 3), (4, 5), (6, 7), (8, 9)]
    # coverage is exact: no row lost or duplicated, order preserved
    out = chunk_bounds([(3, 1000), (1500, 1501), (2000, 2500)], 137)
    flat = [r for c in out for r in c]
    assert sum(hi - lo for lo, hi in flat) == (1000 - 3) + 1 + 500
    for (_, a1), (b0, _) in zip(flat, flat[1:]):
        assert a1 <= b0


def test_chunk_budget_settles_when_a_dispatch_scans_the_whole_table():
    """A mesh program masks the rows outside a chunk's bounds, it does not
    skip them: one dispatch costs one pass over the table.  Fed the rows
    scanned, the budget settles near ceil(pass_ms / budget_ms) chunks; fed
    the rows in bounds it shrank geometrically, run over run, whenever a
    pass outlasted the budget."""
    from tidb_tpu.copr.chunking import (chunk_bounds, chunk_budget_rows,
                                        observe_chunk)

    kind, table, pass_ms = "unit-whole-table-pass", 1 << 26, 250.0
    counts = []
    for _run in range(5):
        n = len(chunk_bounds([(0, table)], chunk_budget_rows(kind)))
        counts.append(n)
        for _ in range(n):
            observe_chunk(kind, pass_ms, table)
    assert counts[0] == 82  # the cold-start guess: 8,192 rows/ms
    assert counts[1:] == [counts[1]] * 4 and 1 <= counts[1] <= 4, counts


def test_mesh_dispatch_reports_rows_scanned_not_rows_in_bounds(sess, chunked):
    name = "dispatch_chunk_agg_rows"
    h0 = REGISTRY.hist_stats(name) or {"count": 0, "sum": 0.0}
    sess.query("select g, count(*), sum(x) from t group by g")
    h1 = REGISTRY.hist_stats(name)
    n = h1["count"] - h0["count"]
    assert n > 1, "the query did not take the chunked path"
    n_rows = sess.query("select count(*) from t")[0][0]
    assert (h1["sum"] - h0["sum"]) / n >= n_rows > 2048


# ---------------------------------------------------------------------------
# parity: chunked == unchunked across the corpus
# ---------------------------------------------------------------------------

def test_chunked_parity_corpus(sess, chunked):
    before = REGISTRY.snapshot().get("dispatch_chunks_total", 0)
    got = {q: sess.query(q) for q in CORPUS}
    after = REGISTRY.snapshot().get("dispatch_chunks_total", 0)
    assert after > before + len(CORPUS), \
        "queries did not take the chunked path"
    os.environ.pop("TIDB_TPU_DISPATCH_CHUNK_ROWS", None)
    os.environ["TIDB_TPU_DISPATCH_CHUNK"] = "0"
    try:
        for q, rows in got.items():
            _rows_eq(rows, sess.query(q), ctx=q)
    finally:
        os.environ.pop("TIDB_TPU_DISPATCH_CHUNK", None)


def test_chunked_filter_limit_parity(sess, chunked):
    # LIMIT decrements across chunks: first-N selection must match the
    # single-dispatch selection (ranges ascend, so order is global)
    q = "select k from t where x < 50 limit 100"
    got = sess.query(q)
    os.environ["TIDB_TPU_DISPATCH_CHUNK_ROWS"] = "0"
    assert got == sess.query(q)


# ---------------------------------------------------------------------------
# fingerprint invariance: chunking must never recompile
# ---------------------------------------------------------------------------

def test_chunk_budget_not_in_fingerprint(sess):
    from tidb_tpu.copr import parallel as pl

    for q in CORPUS:
        keys = []
        try:
            for budget in ("2048", "4096", "0"):
                os.environ["TIDB_TPU_DISPATCH_CHUNK_ROWS"] = budget
                sess.query(q)
                keys.append(set(pl._COMPILED._d.keys()))
        finally:
            os.environ.pop("TIDB_TPU_DISPATCH_CHUNK_ROWS", None)
        assert keys[0] == keys[1] == keys[2], \
            f"chunk budget leaked into a program fingerprint: {q}"


# ---------------------------------------------------------------------------
# KILL lands at the between-chunk seam
# ---------------------------------------------------------------------------

def test_kill_bounded_by_chunk_seam(sess, chunked):
    """Kill fired from inside chunk 1's failpoint: the statement must
    unwind at the NEXT seam — at most one more chunk dispatches after
    the kill (the acceptance bound: within 2 chunk budgets)."""
    d = sess.domain
    victim = d.new_session()
    victim.execute("set tidb_use_tpu = 1")
    hits = []

    def action(**ctx):
        if ctx.get("kind") != "agg":
            return
        hits.append(ctx["chunk"])
        if ctx["chunk"] == 1:
            d.kill(victim.conn_id, True)

    with failpoint("copr/chunk_dispatch", action):
        with pytest.raises(QueryKilledError):
            victim.query(Q_AGG)
    assert hits, "chunk failpoint never fired"
    total_chunks = 20_000 // 2048 + 1
    assert max(hits) <= 2, \
        f"kill latency exceeded the chunk bound: chunks ran {hits}"
    assert max(hits) < total_chunks - 1, "kill did not interrupt the scan"
    # the session is healthy afterwards and re-running has full parity
    _rows_eq(victim.query(Q_AGG), sess.query(Q_AGG))


def test_kill_mid_chunk_streaming_filter(sess, chunked):
    """Same bound on the rows-streaming filter path: kill mid-sequence
    produces the scope-bounded typed error, and a re-run full parity."""
    d = sess.domain
    victim = d.new_session()
    victim.execute("set tidb_use_tpu = 1")
    hits = []

    def action(**ctx):
        if ctx.get("kind") != "filter":
            return
        hits.append(ctx["chunk"])
        if ctx["chunk"] == 1:
            d.kill(victim.conn_id, True)

    with failpoint("copr/chunk_dispatch", action):
        with pytest.raises(QueryKilledError):
            victim.query(Q_FILTER)
    assert hits and max(hits) <= 2, hits
    _rows_eq(victim.query(Q_FILTER), sess.query(Q_FILTER), ctx=Q_FILTER)


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
    chunk in the lock queue is not billed for the queue time."""
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
def test_two_group_fairness_ratio(sess, chunked):
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


def test_depleted_group_throttles_while_other_proceeds(sess, chunked,
                                                       monkeypatch):
    monkeypatch.setenv("TIDB_TPU_RESGROUP_MAX_WAIT_MS", "30")
    d = sess.domain
    adm = d.new_session()
    adm.execute("create resource group starved ru_per_sec = 1")
    try:
        s_starved = d.new_session()
        s_starved.execute("set tidb_tpu_resource_group = 'starved'")
        s_starved.execute("set tidb_use_tpu = 1")
        # burn the 1-RU budget, then a later chunk must throttle
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


def test_scope_carries_group_and_charges(sess, chunked):
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
        assert after > base, "chunk charges did not land on the group"
    finally:
        s.execute("set tidb_tpu_resource_group = ''")
        s.execute("drop resource group rg_scope")


def test_explain_analyze_reports_chunks(sess, chunked):
    sess.execute("set tidb_enable_slow_log = 1")
    try:
        rows = sess.query("explain analyze " + Q_AGG)
    finally:
        sess.execute("set tidb_enable_slow_log = 0")
    root_extra = rows[0][-1]
    assert "chunks:" in root_extra, root_extra


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
    admits chunks ~2:1 — the weighted-fair finish tags advance at
    1/priority per admitted chunk, so the device boundary crossings
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
