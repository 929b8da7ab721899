"""Chaos-hardened replicated data plane (ISSUE 20): the seeded sweep
over the three new dataplane failpoints (`dataplane/peer_error`,
`dataplane/peer_stall`, `dataplane/replica_load`), the failover ladder
(primary -> replica chain -> local bypass), hedged reads with
winner-only byte metering, the pooled `PeerClient`, owner-side fragment
dedup, and the bounded-wait KILL contract during a stalled peer RPC.

Everything is deterministic: event-gated stalls, `once()`/`always()`
injections, the same seeded lineitem build in every member — no sleeps
decide correctness, only bounds.
"""

import threading
import time

import pytest

from tidb_tpu.coord.plane import Coordinator, CoordinatorPlane, WorkerPlane
from tidb_tpu.dataplane import (POOL, activate_dataplane,
                                deactivate_dataplane)
from tidb_tpu.errors import QueryKilledError
from tidb_tpu.metrics import REGISTRY
from tidb_tpu.store.fault import FAILPOINTS, always, failpoint
from tidb_tpu.tpch_data import build_lineitem

Q6 = ("select sum(l_extendedprice * l_discount) from lineitem "
      "where l_shipdate >= '1994-01-01' and l_shipdate < '1995-01-01' "
      "and l_discount between 0.05 and 0.07 and l_quantity < 24")
Q1 = ("select l_returnflag, l_linestatus, sum(l_quantity), "
      "sum(l_extendedprice), count(*) from lineitem "
      "where l_shipdate <= '1998-09-02' group by l_returnflag, "
      "l_linestatus order by l_returnflag, l_linestatus")


def _cnt(name):
    return REGISTRY.get(name) or 0.0


def _oracle(sess, sql):
    sess.execute("set tidb_use_tpu = 0")
    try:
        return sess.execute(sql)[0].rows
    finally:
        sess.execute("set tidb_use_tpu = 1")


def _wait(pred, timeout=15.0):
    t0 = time.time()
    while time.time() - t0 < timeout:
        if pred():
            return
        time.sleep(0.05)
    raise AssertionError("condition not reached in %.1fs" % timeout)


@pytest.fixture(scope="module")
def fleet3(tmp_path_factory):
    """Three in-process members at RF=2: every partition has a warm
    replica on a second member, and from any member's view some chains
    are fully remote (the hedge pair) while others include the member
    itself (the local-replica failover rung)."""
    tmp = tmp_path_factory.mktemp("dp3")
    sessions = [build_lineitem(2048, regions=4) for _ in range(3)]
    coord = Coordinator(port=0, lease_s=6.0, expect=3, self_pid=0)
    host, port = coord.start()
    cp = CoordinatorPlane(coord, pid=0).start((0,))
    wps = [WorkerPlane(f"{host}:{port}", pid, lease_s=6.0).start((pid,))
           for pid in (1, 2)]
    _wait(lambda: cp.view().formed and len(cp.view().members) == 3)
    planes = [cp] + wps
    dps = [activate_dataplane(s.domain.storage, plane=pl, pid=i,
                              data_dir=str(tmp), rf=2)
           for i, (s, pl) in enumerate(zip(sessions, planes))]
    _wait(lambda: all(len(pl.view().addrs) == 3 for pl in planes))
    tid = sessions[0].domain.catalog.info_schema().table(
        "test", "lineitem").id
    for dp in dps:
        dp.shard_table(tid)
    try:
        yield sessions, planes, dps, tid
    finally:
        for s in sessions:
            deactivate_dataplane(s.domain.storage)
        for wp in wps:
            try:
                wp.stop(leave=True)
            except Exception:
                pass
        cp.stop()


def test_rf2_replica_placement(fleet3):
    sessions, planes, dps, tid = fleet3
    pmap = dps[0].sync()
    assert pmap.rf() == 2
    for p in range(pmap.n_parts):
        ch = pmap.chain(p)
        assert len(ch) == 2 and len(set(ch)) == 2
        assert ch[0] == pmap.owner(p)
    # every member materialized exactly its chain slots — more than its
    # primaries (warm replicas), and 2x coverage overall
    for i, dp in enumerate(dps):
        st = dp.lookup(tid)
        assert sorted(st.loaded) == sorted(pmap.replica_of(i))
    total_loaded = sum(len(dp.lookup(tid).loaded) for dp in dps)
    assert total_loaded == 2 * pmap.n_parts


def test_peer_error_fails_over_down_the_chain(fleet3):
    """`dataplane/peer_error` armed ALWAYS: every remote rung answers a
    transient exec error, so each fragment walks the ladder — local
    replica where this member is in the chain, local bypass where it is
    not — and the query still answers with parity THROUGH the
    dataplane (never the outer fallback)."""
    sessions, planes, dps, tid = fleet3
    sA = sessions[0]
    want6, want1 = _oracle(sA, Q6), _oracle(sA, Q1)
    before = {n: _cnt(n) for n in (
        "dataplane_queries_total", "dataplane_failovers_total",
        "dataplane_replica_reads_total", "dataplane_failover_bypass_total",
        "dataplane_errors_total")}
    with failpoint("dataplane/peer_error", always(RuntimeError("chaos"))):
        assert sA.execute(Q6)[0].rows == want6
        assert sA.execute(Q1)[0].rows == want1
    assert _cnt("dataplane_queries_total") == \
        before["dataplane_queries_total"] + 2
    assert _cnt("dataplane_failovers_total") > \
        before["dataplane_failovers_total"]
    # some chains include pid 0 (warm local replica rung), some do not
    # (chain exhausted -> pre-shard base bypass); both rungs must fire
    assert _cnt("dataplane_replica_reads_total") > \
        before["dataplane_replica_reads_total"]
    assert _cnt("dataplane_failover_bypass_total") > \
        before["dataplane_failover_bypass_total"]
    assert _cnt("dataplane_errors_total") == \
        before["dataplane_errors_total"]
    # disarmed: the next dispatch exchanges remotely again
    r0 = _cnt("dataplane_remote_fragments_total")
    assert sA.execute(Q6)[0].rows == want6
    assert _cnt("dataplane_remote_fragments_total") > r0


def test_peer_stall_fails_over_within_deadline(fleet3, monkeypatch):
    """`dataplane/peer_stall` wedges every remote owner: the
    per-fragment deadline (not a 30 s socket timeout) bounds each rung,
    the ladder walks to a rung that can answer, and parity holds."""
    sessions, planes, dps, tid = fleet3
    sA = sessions[0]
    want = _oracle(sA, Q6)
    # compile Q6's programs outside the timed stretch: under xdist this
    # test may be the first of its worker to send it, and a cold compile
    # is no part of the deadline arithmetic below
    assert sA.execute(Q6)[0].rows == want
    monkeypatch.setenv("TIDB_TPU_DATAPLANE_FRAG_TIMEOUT_S", "0.3")
    release = threading.Event()

    def stall(**ctx):
        release.wait(5.0)

    f0 = _cnt("dataplane_failovers_total")
    q0 = _cnt("dataplane_queries_total")
    t0 = time.monotonic()
    try:
        with failpoint("dataplane/peer_stall", stall):
            assert sA.execute(Q6)[0].rows == want
    finally:
        release.set()
    elapsed = time.monotonic() - t0
    # every stalled rung cost at most its 0.3s deadline (+ ladder walk),
    # nowhere near the 5s stall or a socket-timeout tail
    assert elapsed < 4.5, elapsed
    assert _cnt("dataplane_failovers_total") > f0
    assert _cnt("dataplane_queries_total") == q0 + 1
    time.sleep(0.1)  # stalled server threads observe the release


def test_replica_load_chaos_is_nonfatal(tmp_path):
    """`dataplane/replica_load` killing a secondary fill must not fail
    the shard — the slot is skipped (counted), the primary still
    serves, and parity holds; the replica fills on first failover
    touch."""
    sA = build_lineitem(1024, regions=2)
    sB = build_lineitem(1024, regions=2)
    coord = Coordinator(port=0, lease_s=6.0, expect=2, self_pid=0)
    host, port = coord.start()
    cp = CoordinatorPlane(coord, pid=0).start((0,))
    wp = WorkerPlane(f"{host}:{port}", 1, lease_s=6.0).start((1,))
    _wait(lambda: cp.view().formed and len(cp.view().members) == 2)
    dpA = activate_dataplane(sA.domain.storage, plane=cp, pid=0,
                             data_dir=str(tmp_path), rf=2)
    dpB = activate_dataplane(sB.domain.storage, plane=wp, pid=1,
                             data_dir=str(tmp_path), rf=2)
    _wait(lambda: len(cp.view().addrs) == 2)
    tid = sA.domain.catalog.info_schema().table("test", "lineitem").id
    try:
        want = _oracle(sA, Q6)
        e0 = _cnt("dataplane_replica_fill_errors_total")
        with failpoint("dataplane/replica_load",
                       always(RuntimeError("fill chaos"))):
            stA = dpA.shard_table(tid)
            dpB.shard_table(tid)
        assert _cnt("dataplane_replica_fill_errors_total") > e0
        pmap = dpA.sync()
        # primaries materialized; the chaos-killed replica slots did not
        assert set(stA.loaded) == set(pmap.owned_by(0))
        assert sA.execute(Q6)[0].rows == want
        # disarmed: ensure_replica heals the missing slot on demand
        missing = sorted(set(pmap.replica_of(0)) - set(stA.loaded))
        assert missing
        assert dpA.ensure_replica(tid, missing[0]) is not None
        assert missing[0] in stA.loaded
    finally:
        deactivate_dataplane(sA.domain.storage)
        deactivate_dataplane(sB.domain.storage)
        try:
            wp.stop(leave=True)
        except Exception:
            pass
        cp.stop()


def test_kill_during_stalled_peer_rpc_is_bounded(fleet3):
    """ISSUE 20 acceptance: KILL QUERY while a fragment waits on a
    stalled peer returns within the scope's bounded wait — the sliced
    recv observes the cancel within one poll, not after a 30 s socket
    timeout (or the 5 s stall)."""
    sessions, planes, dps, tid = fleet3
    sA = sessions[0]
    killer = sA.domain.new_session()
    release = threading.Event()
    stalled = threading.Event()

    def stall(**ctx):
        stalled.set()
        release.wait(6.0)

    result = {}

    def run():
        try:
            sA.execute(Q6)
        except Exception as e:  # noqa: BLE001 - recorded for assertion
            result["err"] = e
        result["t"] = time.monotonic()

    try:
        with failpoint("dataplane/peer_stall", stall):
            th = threading.Thread(target=run)
            th.start()
            assert stalled.wait(10.0), "no fragment reached the stall"
            t_kill = time.monotonic()
            killer.execute(f"kill query {sA.conn_id}")
            th.join(timeout=3.0)
        assert not th.is_alive(), "statement survived KILL"
        assert isinstance(result.get("err"), QueryKilledError), result
        assert result["t"] - t_kill < 1.5, "KILL latency exceeded bound"
    finally:
        release.set()
    time.sleep(0.1)
    # the session is healthy afterwards and the plane still serves
    q0 = _cnt("dataplane_queries_total")
    want = _oracle(sA, Q6)
    assert sA.execute(Q6)[0].rows == want
    assert _cnt("dataplane_queries_total") == q0 + 1


def test_hedged_read_wins_without_double_counting_exchange(fleet3,
                                                           monkeypatch):
    """Slow every owner and hedge after 1ms: the pair races, the first
    answer wins, and `dataplane_exchange_bytes_total` grows by exactly
    the unhedged amount — the loser's bytes land in the wasted counter
    or nowhere, never in the query's exchange."""
    sessions, planes, dps, tid = fleet3
    sA = sessions[0]
    want = _oracle(sA, Q6)
    x0 = _cnt("dataplane_exchange_bytes_total")
    assert sA.execute(Q6)[0].rows == want
    unhedged_delta = _cnt("dataplane_exchange_bytes_total") - x0
    assert unhedged_delta > 0

    monkeypatch.setenv("TIDB_TPU_DATAPLANE_HEDGE_MS", "1")
    h0 = _cnt("dataplane_hedged_fragments_total")
    x1 = _cnt("dataplane_exchange_bytes_total")

    def slow(**ctx):
        time.sleep(0.15)

    with failpoint("dataplane/peer_stall", slow):
        assert sA.execute(Q6)[0].rows == want
    hedged_delta = _cnt("dataplane_exchange_bytes_total") - x1
    assert _cnt("dataplane_hedged_fragments_total") > h0
    # winner-only metering: the hedged run moved the same exchange
    # volume as the unhedged run (a double count would be ~2x)
    assert hedged_delta == unhedged_delta, (hedged_delta, unhedged_delta)
    time.sleep(0.3)  # losers drain before the leak check below


def test_peer_pool_reuses_connections(fleet3):
    sessions, planes, dps, tid = fleet3
    sA = sessions[0]
    sA.execute(Q6)  # warm the pool
    d0, r0 = _cnt("dataplane_conn_dials_total"), \
        _cnt("dataplane_conn_reuse_total")
    sA.execute(Q6)
    sA.execute(Q1)
    assert _cnt("dataplane_conn_dials_total") == d0, "dialed per fragment"
    assert _cnt("dataplane_conn_reuse_total") > r0


def test_server_dedup_never_double_executes(fleet3):
    """Two calls carrying the SAME dedup key execute once: the twin is
    answered from the owner's result cache (hedge-pair idempotence on a
    single server, and retry idempotence after an abandoned response)."""
    from tidb_tpu.dataplane.rpc import PeerClient

    sessions, planes, dps, tid = fleet3
    addr = planes[0].view().addrs[1]
    c = PeerClient(addr)
    try:
        epoch = planes[0].view().epoch
        # an empty-range fragment executes trivially; what matters is
        # that the SECOND call replays the cached result instead of
        # re-entering the executor
        e0 = _cnt("dataplane_remote_fragments_total")
        d0 = _cnt("dataplane_dedup_hits_total")
        r1, _ = c.exec_fragment({"bogus": 1}, [], 0, epoch, "tpu",
                                frag="test-dedup-key-1")
        r2, _ = c.exec_fragment({"bogus": 1}, [], 0, epoch, "tpu",
                                frag="test-dedup-key-1")
        assert r2 == r1
        assert _cnt("dataplane_remote_fragments_total") == e0 + 1
        assert _cnt("dataplane_dedup_hits_total") == d0 + 1
    finally:
        c.close()


def test_chaos_sweep_leaves_no_threads_or_sockets(fleet3):
    """After the whole module's chaos ran: no fragment/hedge worker
    threads linger, no failpoints stay armed, and the pool holds only
    healthy idle sockets to LIVE peers."""
    time.sleep(0.2)
    leaked = [t.name for t in threading.enumerate()
              if t.name.startswith("dataplane-frag")]
    assert not leaked, leaked
    assert FAILPOINTS.armed() == []
    sessions, planes, dps, tid = fleet3
    live = set(planes[0].view().addrs.values())
    with POOL._mu:
        pooled = set(POOL._idle)
    assert pooled <= live, (pooled, live)
