"""Sharded data plane (ISSUE 18): partition-map determinism, the
degenerate LocalPlane path, cross-"host" exchange between two in-process
members, survivor re-sharding, and the dataplane/reshard chaos site.

The 2-OS-process acceptance (SIGKILL survival) lives in
test_dataplane_procs.py on the coord_worker.py pattern; these tests
exercise the SAME map/ownership/re-shard/exchange code in one process,
where failure injection and counter assertions are cheap.
"""

import threading
import time

import pytest

from tidb_tpu.coord import get_plane
from tidb_tpu.coord.plane import Coordinator, CoordinatorPlane, WorkerPlane
from tidb_tpu.dataplane import (PartitionMapMismatch, activate_dataplane,
                                build_partition_map, deactivate_dataplane,
                                get_dataplane)
from tidb_tpu.dataplane.shard import _pack_column, _unpack_column
from tidb_tpu.metrics import REGISTRY
from tidb_tpu.copr.device_health import DeviceFailure
from tidb_tpu.store.fault import FAILPOINTS, failpoint, once
from tidb_tpu.tpch_data import build_lineitem

Q6 = ("select sum(l_extendedprice * l_discount) from lineitem "
      "where l_shipdate >= '1994-01-01' and l_shipdate < '1995-01-01' "
      "and l_discount between 0.05 and 0.07 and l_quantity < 24")
Q1 = ("select l_returnflag, l_linestatus, sum(l_quantity), "
      "sum(l_extendedprice), avg(l_discount), count(*) from lineitem "
      "where l_shipdate <= '1998-09-02' group by l_returnflag, "
      "l_linestatus order by l_returnflag, l_linestatus")
GROUPED = ("select l_returnflag, count(*), sum(l_quantity) from lineitem "
           "group by l_returnflag order by l_returnflag")


def _cnt(name):
    return REGISTRY.get(name) or 0.0


def _oracle(sess, sql):
    sess.execute("set tidb_use_tpu = 0")
    try:
        return sess.execute(sql)[0].rows
    finally:
        sess.execute("set tidb_use_tpu = 1")


class _View:
    def __init__(self, epoch, members):
        self.epoch = epoch
        self.members = {p: () for p in members}
        self.addrs = {}
        self.formed = True


# ---------------------------------------------------------------------------
# partition map (pure)
# ---------------------------------------------------------------------------

def test_partition_map_deterministic_and_epoch_numbered():
    v = _View(7, [0, 1, 2])
    a = build_partition_map(v, 16)
    b = build_partition_map(_View(7, [2, 1, 0]), 16)
    # pure function of the broadcast: member enumeration order is noise
    assert a == b
    assert a.epoch == 7 and a.n_parts == 16
    assert set(a.owners) <= {0, 1, 2}
    # every member owns something at 16 partitions / 3 members (HRW
    # balance is statistical, but 16 draws over 3 buckets never leaves
    # one empty for this fixed hash)
    assert set(a.owners) == {0, 1, 2}


def test_partition_map_minimal_motion_on_member_loss():
    before = build_partition_map(_View(1, [0, 1, 2]), 32)
    after = build_partition_map(_View(2, [0, 2]), 32)
    # rendezvous hashing: ONLY the dead member's partitions move
    for p in range(32):
        if before.owners[p] != 1:
            assert after.owners[p] == before.owners[p]
        else:
            assert after.owners[p] in (0, 2)


def test_partition_map_mismatch_typed_like_coord_epoch_mismatch():
    pmap = build_partition_map(_View(3, [0]), 4)
    pmap.check(3)  # same epoch: fine
    with pytest.raises(PartitionMapMismatch) as ei:
        pmap.check(5)
    assert ei.value.built_at == 3 and ei.value.current == 5
    # retriable-classification hygiene: no device-failure vocabulary
    msg = str(ei.value).lower()
    for word in ("device", "xla", "tpu", "chip"):
        assert word not in msg


def test_pack_roundtrip_all_widths():
    import numpy as np

    for card in (2, 3, 11, 200, 4000):
        rng = np.random.default_rng(card)
        codes = rng.integers(0, card, size=777).astype(np.int32)
        payload, bits = _pack_column(codes, card)
        out = _unpack_column(payload, bits, len(codes))
        assert (out == codes).all()
        if card <= 256:
            assert bits in (1, 2, 4, 8)
            # the point of preferring packed codes for re-replication
            assert payload.nbytes <= codes.nbytes // (8 // bits) + 8
        else:
            assert bits == 0


# ---------------------------------------------------------------------------
# degenerate LocalPlane path (single host owns every partition)
# ---------------------------------------------------------------------------

def test_localplane_dataplane_parity_and_introspection(tmp_path):
    sess = build_lineitem(4096, regions=4)
    storage = sess.domain.storage
    tid = sess.domain.catalog.info_schema().table("test", "lineitem").id
    oracles = {q: _oracle(sess, q) for q in (Q1, Q6, GROUPED)}
    dp = activate_dataplane(storage, plane=get_plane(), pid=0,
                            data_dir=str(tmp_path), serve=False)
    try:
        st = dp.shard_table(tid)
        assert sorted(st.loaded) == list(range(st.n_parts))
        for q in (Q1, Q6, GROUPED):
            before = _cnt("dataplane_queries_total")
            assert sess.execute(q)[0].rows == oracles[q]
            # parity must come FROM the data plane, not a silent bypass
            assert _cnt("dataplane_queries_total") == before + 1
        rows = sess.execute(
            "select table_id, partition_id, row_start, row_end, "
            "owner_pid, local from information_schema."
            "tidb_tpu_partition_map order by partition_id")[0].rows
        assert len(rows) == st.n_parts
        assert all(r[0] == tid and r[4] == 0 and r[5] == 1 for r in rows)
        # contiguous cover of the table
        assert rows[0][2] == 0 and rows[-1][3] == 4096
        for a, b in zip(rows, rows[1:]):
            assert a[3] == b[2]
        snap = dp.snapshot()
        assert snap["tables"][tid]["n_rows"] == 4096
    finally:
        deactivate_dataplane(storage)
    # partitions detach with the plane: no synthetic tables leak
    assert all(t < (1 << 28) for t in storage.table_ids())


def test_dataplane_bypasses_on_dml_delta():
    sess = build_lineitem(2048, regions=4)
    storage = sess.domain.storage
    tid = sess.domain.catalog.info_schema().table("test", "lineitem").id
    dp = activate_dataplane(storage, plane=get_plane(), pid=0, serve=False)
    try:
        dp.shard_table(tid)
        before_q = _cnt("dataplane_queries_total")
        sess.execute(Q6)
        assert _cnt("dataplane_queries_total") == before_q + 1
        # committed DML invalidates the shard snapshot: the plane must
        # step aside (partitions miss the new row) until re-sharded
        sess.execute(
            "insert into lineitem values "
            "(999999, 1.0, 10.0, 0.06, 0.02, 'N', 'O', '1994-06-01')")
        before_b = _cnt("dataplane_bypass_total")
        got = sess.execute(
            "select count(*) from lineitem where l_orderkey = 999999"
        )[0].rows
        assert got == [(1,)]
        assert _cnt("dataplane_bypass_total") > before_b
        assert _cnt("dataplane_queries_total") == before_q + 1
    finally:
        deactivate_dataplane(storage)


# ---------------------------------------------------------------------------
# two in-process members: real exchange, survivor re-shard, chaos site
# ---------------------------------------------------------------------------

def _fleet(tmp_path, rf=None):
    """Coordinator member (pid 0) + worker member (pid 1), each with its
    own Domain holding the SAME deterministic lineitem build — the
    in-process model of two hosts that loaded the same base table."""
    sA = build_lineitem(4096, regions=4)
    sB = build_lineitem(4096, regions=4)
    coord = Coordinator(port=0, lease_s=4.0, expect=2, self_pid=0)
    host, port = coord.start()
    cp = CoordinatorPlane(coord, pid=0).start((0,))
    wp = WorkerPlane(f"{host}:{port}", 1, lease_s=4.0).start((1,))
    _wait(lambda: cp.view().formed and len(cp.view().members) == 2)
    dpA = activate_dataplane(sA.domain.storage, plane=cp, pid=0,
                             data_dir=str(tmp_path), rf=rf)
    dpB = activate_dataplane(sB.domain.storage, plane=wp, pid=1,
                             data_dir=str(tmp_path), rf=rf)
    _wait(lambda: len(cp.view().addrs) == 2 and len(wp.view().addrs) == 2)
    try:
        yield sA, sB, cp, wp, dpA, dpB
    finally:
        deactivate_dataplane(sA.domain.storage)
        deactivate_dataplane(sB.domain.storage)
        try:
            wp.stop(leave=True)
        except Exception:
            pass
        cp.stop()


@pytest.fixture
def two_member_fleet(tmp_path):
    yield from _fleet(tmp_path)


@pytest.fixture
def two_member_fleet_rf1(tmp_path):
    """RF=1 fleet: the PR-18 behavior — no warm replicas, so a member
    loss MUST replay orphaned partitions from the cold tier."""
    yield from _fleet(tmp_path, rf=1)


def _wait(pred, timeout=10.0):
    t0 = time.time()
    while time.time() - t0 < timeout:
        if pred():
            return
        time.sleep(0.05)
    raise AssertionError("condition not reached in %.1fs" % timeout)


def test_two_member_exchange_parity_and_survivor_reshard(two_member_fleet):
    sA, sB, cp, wp, dpA, dpB = two_member_fleet
    tid = sA.domain.catalog.info_schema().table("test", "lineitem").id
    oracle6 = _oracle(sA, Q6)
    oracle1 = _oracle(sA, Q1)
    stA = dpA.shard_table(tid)
    stB = dpB.shard_table(tid)
    # PRIMARY ownership is a partition (disjoint cover) across the two
    # members; each member materializes every chain slot it holds (at
    # RF=2 over 2 hosts, that is everything — warm replicas, not owners)
    pmap = dpA.sync()
    primA, primB = set(pmap.owned_by(0)), set(pmap.owned_by(1))
    assert primA.isdisjoint(primB)
    assert sorted(primA | primB) == list(range(stA.n_parts))
    assert sorted(stA.loaded) == sorted(pmap.replica_of(0))
    assert sorted(stB.loaded) == sorted(pmap.replica_of(1))

    before_remote = _cnt("dataplane_remote_fragments_total")
    before_bytes = _cnt("dataplane_exchange_bytes_total")
    assert sA.execute(Q6)[0].rows == oracle6
    assert sA.execute(Q1)[0].rows == oracle1
    # cross-host execution actually happened (parity alone can't prove
    # it — the local fallback answers identically)
    assert _cnt("dataplane_remote_fragments_total") > before_remote
    assert _cnt("dataplane_exchange_bytes_total") > before_bytes
    # and the other direction: the worker member scatters to pid 0
    sB.execute("set tidb_use_tpu = 1")
    assert sB.execute(Q6)[0].rows == oracle6

    # ---- survivor re-shard: member 1 leaves, epoch bumps ----
    epoch_before = cp.view().epoch
    wp.stop(leave=True)
    deactivate_dataplane(sB.domain.storage)
    _wait(lambda: 1 not in cp.view().members)
    assert cp.view().epoch > epoch_before
    before_reshard = _cnt("dataplane_reshards_total")
    before_q = _cnt("dataplane_queries_total")
    before_promote = _cnt("dataplane_replica_promotions_total")
    before_cold = _cnt("dataplane_cold_reloads_total")
    assert sA.execute(Q6)[0].rows == oracle6
    assert _cnt("dataplane_reshards_total") == before_reshard + 1
    assert _cnt("dataplane_queries_total") == before_q + 1
    # the survivor now owns (and materialized) every partition — and at
    # RF=2 it already HELD the dead member's partitions as warm
    # replicas, so the takeover is pure promotion: zero cold reloads
    assert sorted(stA.loaded) == list(range(stA.n_parts))
    assert _cnt("dataplane_replica_promotions_total") > before_promote
    assert _cnt("dataplane_cold_reloads_total") == before_cold
    assert sA.execute(Q1)[0].rows == oracle1


def test_reshard_chaos_site_falls_back_then_converges(two_member_fleet):
    sA, sB, cp, wp, dpA, dpB = two_member_fleet
    tid = sA.domain.catalog.info_schema().table("test", "lineitem").id
    oracle6 = _oracle(sA, Q6)
    dpA.shard_table(tid)
    dpB.shard_table(tid)
    assert sA.execute(Q6)[0].rows == oracle6

    wp.stop(leave=True)
    deactivate_dataplane(sB.domain.storage)
    _wait(lambda: 1 not in cp.view().members)
    # the chaos site: the FIRST replay of an orphaned partition dies
    # mid-re-shard.  The dispatch must fall back (parity preserved) and
    # the NEXT dispatch must replay the whole transition successfully.
    # an unclassified exception there is a bug, not a device failure: it
    # reaches the client instead of being answered by the local path
    with failpoint("dataplane/reshard", once(TypeError("injected bug"))):
        before_err = _cnt("dataplane_errors_total")
        with pytest.raises(TypeError, match="injected bug"):
            sA.execute(Q6)
        assert _cnt("dataplane_errors_total") == before_err
    with failpoint("dataplane/reshard",
                   once(DeviceFailure("injected device failure"))):
        before_err = _cnt("dataplane_errors_total")
        assert sA.execute(Q6)[0].rows == oracle6
        assert _cnt("dataplane_errors_total") > before_err
    before_q = _cnt("dataplane_queries_total")
    assert sA.execute(Q6)[0].rows == oracle6
    assert _cnt("dataplane_queries_total") == before_q + 1
    assert sorted(dpA.lookup(tid).loaded) == \
        list(range(dpA.lookup(tid).n_parts))


def test_survivor_reshard_replays_persisted_packed_blocks(
        two_member_fleet_rf1):
    sA, sB, cp, wp, dpA, dpB = two_member_fleet_rf1
    tid = sA.domain.catalog.info_schema().table("test", "lineitem").id
    oracle = _oracle(sA, GROUPED)
    dpA.shard_table(tid)
    dpB.shard_table(tid)
    wp.stop(leave=True)
    deactivate_dataplane(sB.domain.storage)
    _wait(lambda: 1 not in cp.view().members)
    before_packed = _cnt("dataplane_replay_packed_total")
    assert sA.execute(GROUPED)[0].rows == oracle
    # orphaned partitions replayed from the persisted bit-packed form,
    # not re-sliced from the live source table
    assert _cnt("dataplane_replay_packed_total") > before_packed


def test_dataplane_threads_reclaimed(two_member_fleet):
    sA, sB, cp, wp, dpA, dpB = two_member_fleet
    tid = sA.domain.catalog.info_schema().table("test", "lineitem").id
    dpA.shard_table(tid)
    dpB.shard_table(tid)
    sA.execute(Q6)
    deactivate_dataplane(sA.domain.storage)
    deactivate_dataplane(sB.domain.storage)
    deadline = time.time() + 5.0
    while time.time() < deadline:
        leaked = [t.name for t in threading.enumerate()
                  if t.name.startswith("dataplane-rpc")]
        if not leaked:
            break
        time.sleep(0.1)
    assert not leaked, leaked
