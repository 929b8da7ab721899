"""Multi-host worker: one process of a 2-process jax.distributed cluster.

Launched by tests/test_multihost.py (and __graft_entry__.dryrun_multihost)
with argv = [process_id, num_processes, coordinator_port].  Each process
contributes 4 virtual CPU devices; the mesh spans all 8 across both
processes, so the shard_map scan's psum merges ride the cross-process
collective fabric — the role of the reference's multi-node NCCL/MPI store
fabric (store/tikv/client_batch.go:38-387), carried by XLA collectives
over DCN in the real deployment.

Every process runs the SAME deterministic script: identical data build,
identical query sequence (multi-controller SPMD contract).
"""

import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def main() -> int:
    pid, nproc, port = int(sys.argv[1]), int(sys.argv[2]), sys.argv[3]
    os.environ["JAX_PLATFORMS"] = "cpu"
    os.environ["XLA_FLAGS"] = (
        os.environ.get("XLA_FLAGS", "")
        + " --xla_force_host_platform_device_count=4").strip()
    os.environ["TIDB_TPU_COORDINATOR"] = f"127.0.0.1:{port}"
    os.environ["TIDB_TPU_NUM_PROCESSES"] = str(nproc)
    os.environ["TIDB_TPU_PROCESS_ID"] = str(pid)
    os.environ["TIDB_TPU_TILE"] = "1024"

    import jax

    jax.config.update("jax_platforms", "cpu")
    jax.config.update("jax_enable_compilation_cache", False)  # per-process compiles

    # join the cluster on the MAIN thread before any worker thread races
    # into backend init (get_mesh -> _maybe_init_multihost)
    from tidb_tpu.copr.parallel import MESH_CACHE, get_mesh

    mesh = get_mesh()
    devs = mesh.devices.ravel()
    assert len(devs) == 4 * nproc, f"mesh spans {len(devs)} devices"
    assert len(jax.devices()) == 4 * nproc

    from tidb_tpu.tpch_data import build_lineitem

    sess = build_lineitem(16384, regions=4)  # deterministic in every proc

    q1 = ("select l_returnflag, l_linestatus, sum(l_quantity),"
          " sum(l_extendedprice), sum(l_extendedprice * (1 - l_discount)),"
          " avg(l_discount), count(*) from lineitem"
          " where l_shipdate <= '1998-09-02'"
          " group by l_returnflag, l_linestatus"
          " order by l_returnflag, l_linestatus")
    q6 = ("select sum(l_extendedprice * l_discount) from lineitem"
          " where l_shipdate >= '1994-01-01' and l_shipdate < '1995-01-01'"
          " and l_discount between 0.05 and 0.07 and l_quantity < 24")

    # device broadcast join across BOTH processes' shards: the payload
    # broadcast and the joined partial-agg psum ride the same collective
    # fabric (deterministic per-process build order is the contract)
    from tidb_tpu.tpch_data import Q3_SQL as q3, build_q3_tables

    s3 = build_q3_tables(16384, 512, regions=4)
    # the broadcast join must actually BE in the cop task here
    plan_ops = [r[0] for r in s3.execute("explain " + q3)[0].rows]
    assert any("DeviceJoinReader" in op for op in plan_ops), plan_ops

    from tidb_tpu.metrics import REGISTRY

    before = REGISTRY.snapshot().get("mesh_scans_total", 0)
    results = {}
    for name, sess_q, q in (("q1", sess, q1), ("q6", sess, q6),
                            ("q3", s3, q3)):
        sess_q.execute("set tidb_use_tpu = 1")
        tpu = sess_q.query(q)
        sess_q.execute("set tidb_use_tpu = 0")
        cpu = sess_q.query(q)
        assert len(tpu) == len(cpu) and tpu, (name, tpu, cpu)
        for ra, rb in zip(tpu, cpu):
            for x, y in zip(ra, rb):
                if isinstance(x, float) or isinstance(y, float):
                    assert abs(x - y) <= 1e-9 * max(1.0, abs(y)), (name, ra, rb)
                else:
                    assert x == y, (name, ra, rb)
        results[name] = tpu
    assert REGISTRY.snapshot().get("mesh_scans_total", 0) > before, \
        "queries did not run on the distributed mesh"

    # the cached column arrays must span BOTH processes' devices: this
    # process only addresses its local shards, and the sharding's device
    # set covers every process index
    data, _ = next(iter(MESH_CACHE._cache.values()))
    all_procs = {d.process_index for d in data.sharding.device_set}
    local_procs = {s.device.process_index for s in data.addressable_shards}
    assert all_procs == set(range(nproc)), all_procs
    assert local_procs == {pid}, (local_procs, pid)

    # coordination plane (ISSUE 9): when the test wired a coord address,
    # the SAME two processes also form the control plane — assert the
    # formed membership broadcast spans both processes' device sets and
    # that a worker-side trace rejoined the coordinator's ring
    if os.environ.get("TIDB_TPU_COORD_ADDR"):
        import time as _time

        from tidb_tpu.coord import get_plane

        plane = get_plane()
        view = plane.view()
        assert set(view.members) == set(range(nproc)), view.members
        assert len(view.device_ids()) == 4 * nproc, view
        assert view.formed, view
        sess.execute("trace format='row' select count(*) from lineitem")
        if pid == 0:
            deadline = _time.time() + 20
            while (_time.time() < deadline
                   and REGISTRY.snapshot().get(
                       "coord_spans_ingested_total", 0) < 1):
                _time.sleep(0.2)
            assert REGISTRY.snapshot().get(
                "coord_spans_ingested_total", 0) >= 1
        else:
            assert REGISTRY.snapshot().get(
                "coord_spans_forwarded_total", 0) >= 1
        print(f"COORD_OK pid={pid} epoch={view.epoch}", flush=True)

    print(f"MULTIHOST_OK pid={pid} devices={len(devs)} "
          f"q1_rows={len(results['q1'])} q6={results['q6'][0][0]:.4f}",
          flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
