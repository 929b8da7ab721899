#!/usr/bin/env python
"""Driver benchmark: TPC-H Q1/Q6-shaped aggregation on the coprocessor path.

Prints ONE JSON line:
  {"metric": ..., "value": N, "unit": ..., "vs_baseline": N, "detail": {...}}

value       = TPC-H Q1 rows/sec through the TPU(jax) engine end-to-end
              (SQL -> planner -> distsql -> mesh-sharded device scan ->
              collective partial agg -> root final merge), steady-state
              (tile cache warm), at the largest row scale that fit the
              wall budget.
vs_baseline = speedup of the TPU engine over the same framework's CPU
              (numpy oracle) engine — the stand-in for the reference's
              8-vCPU mocktikv path.

One process holds the chip: the device is read once, in-process, and a
platform other than `tpu` exits non-zero with no result line.  Work runs on a
daemon worker; the main thread enforces the global wall budget and prints the
best state reached.  Row count starts at 256k and quadruples only while under
budget; warm-up (transfer+compile) is timed separately from steady state.
The exit code is non-zero when any leg recorded an `error`.

Env knobs: BENCH_ROWS (max scale, default 64M), BENCH_ITERS (default 3),
BENCH_REGIONS (default 8), BENCH_WALL_LIMIT (s, default 1500).
"""

from __future__ import annotations

import json
import os
import signal
import sys
import threading
import time

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

MAX_ROWS = int(os.environ.get("BENCH_ROWS", 128_000_000))
ITERS = int(os.environ.get("BENCH_ITERS", 3))
REGIONS = int(os.environ.get("BENCH_REGIONS", 8))
WALL_LIMIT = float(os.environ.get("BENCH_WALL_LIMIT", 1500))
T0 = time.perf_counter()


def log(msg: str):
    print(f"[bench {time.perf_counter() - T0:7.1f}s] {msg}",
          file=sys.stderr, flush=True)


def remaining() -> float:
    return WALL_LIMIT - (time.perf_counter() - T0)


Q1 = """
select l_returnflag, l_linestatus,
       sum(l_quantity), sum(l_extendedprice),
       sum(l_extendedprice * (1 - l_discount)),
       sum(l_extendedprice * (1 - l_discount) * (1 + l_tax)),
       avg(l_quantity), avg(l_extendedprice), avg(l_discount),
       count(*)
from lineitem
where l_shipdate <= '1998-09-02'
group by l_returnflag, l_linestatus
order by l_returnflag, l_linestatus
"""

Q6 = """
select sum(l_extendedprice * l_discount)
from lineitem
where l_shipdate >= '1994-01-01' and l_shipdate < '1995-01-01'
  and l_discount between 0.05 and 0.07 and l_quantity < 24
"""

# canonical Q3 text lives beside its data builder (one plan shape across
# bench/dryruns/tests); imported lazily because jax must not load before
# preflight pins the platform
def _q3_sql():
    from tidb_tpu.tpch_data import Q3_SQL

    return Q3_SQL


def preflight(state: dict) -> bool:
    """Read the device once, in this process (a chip belongs to one process:
    no probe child).  False unless the platform is `tpu` — a measurement
    path that finds no chip fails, it does not fall back to the CPU."""
    import jax

    devs = jax.devices()
    state["device"] = {"platform": devs[0].platform,
                       "kind": devs[0].device_kind, "count": len(devs)}
    state["devices"] = [str(d) for d in devs]
    if devs[0].platform != "tpu":
        log(f"no TPU: jax.devices() = {devs}")
        return False
    log(f"device: {state['device']}")
    return True


def build_lineitem(n: int):
    from tidb_tpu.tpch_data import build_lineitem as build

    return build(n, regions=REGIONS)


# ---------------------------------------------------------------------------
# concurrent-client serving bench (shape buckets + micro-batching under
# contention, through the REAL wire server: admission -> session ->
# distsql -> serving/mesh)
# ---------------------------------------------------------------------------


class _WireClient:
    """Minimal blocking MySQL-wire client (protocol 4.1, text protocol):
    just enough to drive COM_QUERY load from N plain threads."""

    def __init__(self, host: str, port: int, db: str = "test"):
        import socket
        import struct

        self.sock = socket.create_connection((host, port), timeout=60)
        self.seq = 0
        self._recv()  # server greeting
        caps = 0x0200 | 0x8000 | 0x0008  # PROTO41|SECURE_CONN|WITH_DB
        resp = struct.pack("<I", caps) + struct.pack("<I", 1 << 24)
        resp += bytes([33]) + b"\x00" * 23
        resp += b"root\x00" + b"\x00" + db.encode() + b"\x00"
        self._send(resp)
        ok = self._recv()
        if ok[0] != 0x00:
            raise ConnectionError(f"handshake refused: {ok!r}")

    def _read(self, n: int) -> bytes:
        buf = b""
        while len(buf) < n:
            chunk = self.sock.recv(n - len(buf))
            if not chunk:
                raise ConnectionError("connection closed")
            buf += chunk
        return buf

    def _recv(self) -> bytes:
        hdr = self._read(4)
        n = hdr[0] | (hdr[1] << 8) | (hdr[2] << 16)
        self.seq = hdr[3] + 1
        return self._read(n)

    def _send(self, payload: bytes):
        self.sock.sendall(len(payload).to_bytes(3, "little")
                          + bytes([self.seq & 0xFF]) + payload)
        self.seq += 1

    def query(self, sql: str):
        """(result_rows, error_tuple_or_None)."""
        import struct

        self.seq = 0
        self._send(b"\x03" + sql.encode())
        first = self._recv()
        if first[0] == 0x00:
            return 0, None
        if first[0] == 0xFF:
            code = struct.unpack_from("<H", first, 1)[0]
            return 0, (code, first[9:].decode("utf8", "replace"))
        ncols = first[0]  # lenenc; result sets here are narrow (<251)
        for _ in range(ncols):
            self._recv()
        self._recv()  # EOF after column defs
        rows = 0
        while True:
            pkt = self._recv()
            if pkt[0] == 0xFE and len(pkt) < 9:
                break
            rows += 1
        return rows, None

    def close(self):
        try:
            self.seq = 0
            self._send(b"\x01")  # COM_QUIT
            self.sock.close()
        except Exception:
            pass


def _serve_domain(domain, workers: int = 16):
    """Start a MySQLServer for `domain` on an event loop in a daemon
    thread; returns (server, loop, thread)."""
    import asyncio

    from tidb_tpu.server import MySQLServer

    srv = MySQLServer(domain, port=0, workers=workers)
    loop = asyncio.new_event_loop()
    started = threading.Event()

    def run():
        asyncio.set_event_loop(loop)
        loop.run_until_complete(srv.start())
        started.set()
        loop.run_forever()

    th = threading.Thread(target=run, daemon=True, name="bench-server")
    th.start()
    if not started.wait(30):
        raise RuntimeError("bench server failed to start")
    return srv, loop, th


def _stop_server(srv, loop, th):
    import asyncio

    try:
        fut = asyncio.run_coroutine_threadsafe(srv.shutdown(drain_s=2.0),
                                               loop)
        fut.result(20)
    except Exception:
        pass
    loop.call_soon_threadsafe(loop.stop)
    th.join(10)


def _client_loop(host, port, idx, dur_s, mode, n_rows, out, errs):
    rng = np.random.default_rng(1000 + idx)
    kmax = max(n_rows // 4, 2)
    lat = []
    n_err = 0
    try:
        cli = _WireClient(host, port)
    except Exception:
        errs[idx] = -1  # connection-level failure (admission cap etc.)
        out[idx] = lat
        return
    end = time.perf_counter() + dur_s
    try:
        while time.perf_counter() < end:
            r = rng.random() if mode == "mixed" else 0.0
            if r < 0.7:
                # identical-SHAPE point aggregate: parameter-different
                # keys share one hoisted program / one micro-batch class
                k = int(rng.integers(1, kmax))
                sql = ("select count(*), sum(l_quantity) from lineitem"
                       f" where l_orderkey = {k}")
            elif r < 0.9:
                lo = float(rng.uniform(0.02, 0.05))
                sql = ("select sum(l_extendedprice * l_discount) from"
                       f" lineitem where l_discount between {lo:.3f} and"
                       f" {lo + 0.02:.3f} and l_quantity < 24")
            else:
                sql = Q1
            t0 = time.perf_counter()
            rows, err = cli.query(sql)
            dt = time.perf_counter() - t0
            if err is not None:
                n_err += 1  # admission rejection under overload counts
            else:
                lat.append((dt, rows))
    except Exception:
        n_err += 1
    finally:
        cli.close()
    out[idx] = lat
    errs[idx] = n_err


def _pct(sorted_vals, p):
    if not sorted_vals:
        return None
    i = min(int(len(sorted_vals) * p / 100.0), len(sorted_vals) - 1)
    return sorted_vals[i]


def concurrent_bench(state: dict, n_rows: int = None, clients: int = None,
                     dur_s: float = None):
    """N client threads of mixed TPC-H + point lookups through the real
    server: p50/p99 latency, aggregate rows/s, and the micro-batched vs
    unbatched point-agg throughput on the same build."""
    n_rows = n_rows or min(state.get("loaded_rows", 1_048_576), 1_048_576)
    clients = clients or int(os.environ.get("BENCH_CLIENTS", "32"))
    dur_s = dur_s or float(os.environ.get("BENCH_CONC_S", "6"))
    window_ms = int(os.environ.get("BENCH_MB_WINDOW_MS", "5"))
    from tidb_tpu.metrics import REGISTRY

    log(f"concurrent bench: {clients} clients x {dur_s:.0f}s on "
        f"{n_rows} rows...")
    sess = build_lineitem(n_rows)
    # steady state: compile the point-agg/Q6/Q1 shapes once up front so
    # both modes measure dispatch amortization, not XLA compile time
    sess.query("select count(*), sum(l_quantity) from lineitem"
               " where l_orderkey = 1")
    sess.query(Q6)
    sess.query(Q1)
    srv, loop, th = _serve_domain(sess.domain)
    host, port = srv.host, srv.port
    ctrl = _WireClient(host, port)

    def phase(mode: str, window: int) -> dict:
        ctrl.query("set global tidb_tpu_microbatch_window_ms = "
                   f"{window}")
        m0 = REGISTRY.snapshot()
        out = [None] * clients
        errs = [0] * clients
        threads = [
            threading.Thread(target=_client_loop,
                             args=(host, port, i, dur_s, mode, n_rows,
                                   out, errs),
                             daemon=True, name=f"bench-client-{i}")
            for i in range(clients)
        ]
        t0 = time.perf_counter()
        for t in threads:
            t.start()
        for t in threads:
            t.join(dur_s + 120)
        wall = time.perf_counter() - t0
        m1 = REGISTRY.snapshot()
        lats = sorted(d for per in out if per for d, _r in per)
        rows = sum(r for per in out if per for _d, r in per)
        nq = len(lats)
        return {
            "mode": mode, "window_ms": window, "queries": nq,
            "qps": round(nq / wall, 1) if wall else 0.0,
            "p50_ms": (round(_pct(lats, 50) * 1000, 3) if lats else None),
            "p99_ms": (round(_pct(lats, 99) * 1000, 3) if lats else None),
            "result_rows_per_sec": round(rows / wall, 1) if wall else 0.0,
            "errors": sum(e for e in errs if e > 0),
            "batches": round(m1.get("serving_batches_total", 0)
                             - m0.get("serving_batches_total", 0)),
            "batched_stmts": round(
                m1.get("serving_batched_stmts_total", 0)
                - m0.get("serving_batched_stmts_total", 0)),
        }

    try:
        unbatched = phase("point", 0)
        batched = phase("point", window_ms)
        mixed = phase("mixed", window_ms)
    finally:
        ctrl.query("set global tidb_tpu_microbatch_window_ms = 0")
        ctrl.close()
        _stop_server(srv, loop, th)
    speedup = (round(batched["qps"] / unbatched["qps"], 2)
               if unbatched["qps"] else None)
    snap = REGISTRY.snapshot()
    state["concurrent"] = {
        "clients": clients, "duration_s": dur_s, "rows": n_rows,
        "point_agg_unbatched": unbatched,
        "point_agg_batched": batched,
        "microbatch_speedup": speedup,
        "mixed": mixed,
        "admission_rejected": round(
            snap.get("admission_rejected_total", 0)),
        "batch_size_max": round(snap.get("serving_batch_size_max", 0)),
    }
    log(f"concurrent: point-agg {unbatched['qps']} -> {batched['qps']} "
        f"qps (x{speedup}) | mixed p50={mixed['p50_ms']}ms "
        f"p99={mixed['p99_ms']}ms qps={mixed['qps']}")


def time_query(sess, sql: str, iters: int):
    """(warmup_s, steady_best_s)"""
    t0 = time.perf_counter()
    sess.query(sql)
    warm = time.perf_counter() - t0
    best = float("inf")
    for _ in range(iters):
        t0 = time.perf_counter()
        sess.query(sql)
        best = min(best, time.perf_counter() - t0)
    return warm, best


def _count_device_dispatches(sess, sql: str) -> int:
    """Run `sql` once under TRACE and count fused device launches —
    `copr.device.execute` spans (plus compile-labeled first dispatches)."""
    try:
        sess.execute("trace " + sql)
        tr = sess.last_trace
        if tr is None:
            return -1
        n = {"d": 0}

        def walk(s):
            if s.name in ("copr.device.execute", "mpp.rung",
                          "mpp.tree.final") or (
                    s.name == "copr.compile"
                    and (s.attrs or {}).get("cache") == "miss"):
                n["d"] += 1
            for c in s.children:
                walk(c)

        walk(tr.root)
        return n["d"]
    except BaseException:  # noqa: BLE001 — receipt survives trace issues
        return -1


def fusion_bench(sess, n: int) -> dict:
    """Whole-fragment fusion receipt: fused (ONE XLA launch per mesh
    dispatch) vs the per-tile dispatch loop (one launch + readback per
    tile with host glue between them — the unfused comparator,
    TIDB_TPU_FUSION=0), rows/s and dispatch counts for Q1/Q6."""
    out = {}
    prior = os.environ.get("TIDB_TPU_FUSION")
    for qname, sql in (("q1", Q1), ("q6", Q6)):
        try:
            os.environ["TIDB_TPU_FUSION"] = "1"
            _, fused_s = time_query(sess, sql, ITERS)
            fused_d = _count_device_dispatches(sess, sql)
            os.environ["TIDB_TPU_FUSION"] = "0"
            _, unf_s = time_query(sess, sql, ITERS)
            unf_d = _count_device_dispatches(sess, sql)
        finally:
            # restore the operator's setting, not a hardcoded default
            if prior is None:
                os.environ.pop("TIDB_TPU_FUSION", None)
            else:
                os.environ["TIDB_TPU_FUSION"] = prior
        out[qname] = {
            "fused_rows_per_sec": round(n / fused_s, 1),
            "per_phase_rows_per_sec": round(n / unf_s, 1),
            "fused_dispatches": fused_d,
            "per_phase_dispatches": unf_d,
            "speedup": round(unf_s / fused_s, 2),
        }
        log(f"fusion {qname}: fused={n / fused_s:,.0f} rows/s "
            f"({fused_d} dispatches) vs per-phase={n / unf_s:,.0f} rows/s "
            f"({unf_d} dispatches) -> {unf_s / fused_s:.2f}x")
    return out


_LOCKCHECK_WORKER_SRC = r"""
import json
import os
import sys
import threading

os.environ["TIDB_TPU_LOCKCHECK"] = "1"
os.environ.setdefault("TIDB_TPU_TILE", "1024")
import jax

jax.config.update("jax_platforms", "cpu")
sys.path.insert(0, os.environ["LOCKCHECK_REPO"])
from bench import Q1, Q6, build_lineitem

from tidb_tpu.util_concurrency import witness_stats

n = int(os.environ.get("LOCKCHECK_ROWS", "65536"))
sess = build_lineitem(n)
sess.execute("set tidb_use_tpu = 1")
for q in (Q1, Q6):
    sess.query(q)
sess.execute("update lineitem set l_quantity = l_quantity + 1"
             " where l_orderkey = 1")


def client():
    s2 = sess.domain.new_session()
    for _ in range(3):
        s2.query(Q6)


threads = [threading.Thread(target=client) for _ in range(4)]
for t in threads:
    t.start()
for t in threads:
    t.join()
print("LOCKCHECK_JSON " + json.dumps(witness_stats()), flush=True)
"""


def lockcheck_bench(n: int = None) -> dict:
    """Lock-order witness receipt (ISSUE 16): replay the bench corpus
    (Q1/Q6 + DML + 4 concurrent client threads) once in a FRESH
    subprocess with TIDB_TPU_LOCKCHECK=1 — the witness wraps locks at
    construction time, so the parent process (whose locks are already
    plain) cannot flip it on after import — and report total guarded
    acquisitions, max held-lock depth and violations (must be zero)."""
    import subprocess

    n = int(n or 65_536)
    # the child pins JAX_PLATFORMS=cpu and never needs the chip, which this
    # process holds
    env = dict(os.environ, JAX_PLATFORMS="cpu", LOCKCHECK_ROWS=str(n),
               LOCKCHECK_REPO=os.path.dirname(os.path.abspath(__file__)))
    t0 = time.perf_counter()
    proc = subprocess.run([sys.executable, "-c", _LOCKCHECK_WORKER_SRC],
                          capture_output=True, text=True, timeout=300,
                          env=env)
    for ln in proc.stdout.splitlines():
        if ln.startswith("LOCKCHECK_JSON "):
            stats = json.loads(ln[len("LOCKCHECK_JSON "):])
            # per-lock contention (ISSUE 17): most-contended locks by
            # cumulative blocking wait, from the witness's log2
            # wait-histograms
            locks = stats.get("locks", {})
            hot = sorted(locks.items(),
                         key=lambda kv: -kv[1]["wait_ms"])[:5]
            return {
                "rows": n,
                "acquisitions": stats["acquisitions"],
                "max_held_depth": stats["max_depth"],
                "violations": stats["violations"],
                "wait_trips": stats.get("wait_trips", 0),
                "contended_locks": len(locks),
                "hot_locks": [
                    {"name": nm, "contended": rec["contended"],
                     "wait_ms": rec["wait_ms"]} for nm, rec in hot],
                "ok": (stats["violations"] == 0
                       and stats["acquisitions"] > 0),
                "wall_s": round(time.perf_counter() - t0, 2),
            }
    raise RuntimeError("lockcheck worker emitted no stats: "
                       + (proc.stderr or proc.stdout)[-400:])


def dataplane_bench(n: int) -> dict:
    """Sharded data-plane receipt (ISSUE 18): warm Q6 scan throughput
    with the whole table resident on ONE member (LocalPlane degenerate
    path) vs hash-sharded across TWO in-process members — coordinator
    + worker planes over real loopback RPC, fragments for remotely
    owned partitions fetched cross-host — plus the exchange bytes the
    2-host leg actually moved."""
    import tempfile

    from tidb_tpu.coord import get_plane
    from tidb_tpu.coord.plane import (Coordinator, CoordinatorPlane,
                                      WorkerPlane)
    from tidb_tpu.dataplane import activate_dataplane, deactivate_dataplane
    from tidb_tpu.metrics import REGISTRY

    n = min(n, 65_536)  # 3 extra table builds; keep the legs modest
    reps = max(ITERS, 3)
    out: dict = {"rows": n}

    def _tid(sess):
        return sess.domain.catalog.info_schema().table(
            "test", "lineitem").id

    def _leg(sess):
        sess.execute("set tidb_use_tpu = 1")
        sess.execute(Q6)  # warm: compile + partition materialization
        t0 = time.perf_counter()
        for _ in range(reps):
            sess.execute(Q6)
        return (time.perf_counter() - t0) / reps

    def _until(pred, timeout=20.0):
        t0 = time.time()
        while time.time() - t0 < timeout and not pred():
            time.sleep(0.05)

    with tempfile.TemporaryDirectory() as td:
        # ---- 1-host leg: degenerate LocalPlane ownership -----------------
        s1 = build_lineitem(n)
        dp1 = activate_dataplane(s1.domain.storage, plane=get_plane(),
                                 pid=0, data_dir=os.path.join(td, "one"),
                                 serve=False)
        dp1.shard_table(_tid(s1))
        q0 = REGISTRY.get("dataplane_queries_total") or 0.0
        try:
            one_s = _leg(s1)
            snap1 = dp1.snapshot()
        finally:
            deactivate_dataplane(s1.domain.storage)
        served = (REGISTRY.get("dataplane_queries_total") or 0.0) - q0
        out["one_host_s"] = round(one_s, 4)
        out["one_host_rows_per_sec"] = round(n / one_s, 1)
        out["n_parts"] = max((t["n_parts"]
                              for t in snap1["tables"].values()),
                             default=0)
        if served <= 0:
            out["error"] = "1-host leg bypassed the data plane"
            return out

        # ---- 2-host leg: coordinator + worker member over loopback ------
        sA = build_lineitem(n)
        sB = build_lineitem(n)
        coord = Coordinator(port=0, lease_s=4.0, expect=2, self_pid=0)
        host, port = coord.start()
        cp = CoordinatorPlane(coord, pid=0).start((0,))
        wp = WorkerPlane(f"{host}:{port}", 1, lease_s=4.0).start((1,))
        _until(lambda: cp.view().formed and len(cp.view().members) == 2)
        dpA = activate_dataplane(sA.domain.storage, plane=cp, pid=0,
                                 data_dir=os.path.join(td, "a"))
        dpB = activate_dataplane(sB.domain.storage, plane=wp, pid=1,
                                 data_dir=os.path.join(td, "b"))
        _until(lambda: len(cp.view().addrs) == 2
               and len(wp.view().addrs) == 2)
        dpA.shard_table(_tid(sA))
        dpB.shard_table(_tid(sB))
        b0 = REGISTRY.get("dataplane_exchange_bytes_total") or 0.0
        f0 = REGISTRY.get("dataplane_remote_fragments_total") or 0.0
        try:
            two_s = _leg(sA)
        finally:
            deactivate_dataplane(sA.domain.storage)
            deactivate_dataplane(sB.domain.storage)
            try:
                wp.stop(leave=True)
            except Exception:  # noqa: BLE001 — lease may already be gone
                pass
            cp.stop()
    out["two_host_s"] = round(two_s, 4)
    out["two_host_rows_per_sec"] = round(n / two_s, 1)
    out["exchange_bytes_per_query"] = round(
        ((REGISTRY.get("dataplane_exchange_bytes_total") or 0.0) - b0)
        / (reps + 1), 1)
    out["remote_fragments"] = int(
        (REGISTRY.get("dataplane_remote_fragments_total") or 0.0) - f0)
    out["two_host_overhead_x"] = round(two_s / one_s, 2) if one_s else None
    log(f"dataplane scan: 1-host {out['one_host_rows_per_sec']:.0f} "
        f"rows/s vs 2-host {out['two_host_rows_per_sec']:.0f} rows/s, "
        f"{out['exchange_bytes_per_query']:.0f} exchange B/query")

    # ---- kill-recovery leg (ISSUE 20): RF=1 cold replay vs RF=2 ---------
    # replica promotion.  One member leaves mid-steady-state; the
    # receipt is the survivor's first post-loss query (re-shard
    # included) — the time replication buys back on the critical path.
    n_k = min(n, 16_384)
    out["kill_recovery"] = {"rows": n_k}
    for rf in (1, 2):
        with tempfile.TemporaryDirectory() as td:
            sA = build_lineitem(n_k)
            sB = build_lineitem(n_k)
            coord = Coordinator(port=0, lease_s=4.0, expect=2, self_pid=0)
            host, port = coord.start()
            cp = CoordinatorPlane(coord, pid=0).start((0,))
            wp = WorkerPlane(f"{host}:{port}", 1, lease_s=4.0).start((1,))
            _until(lambda: cp.view().formed
                   and len(cp.view().members) == 2)
            dpA = activate_dataplane(sA.domain.storage, plane=cp, pid=0,
                                     data_dir=os.path.join(td, "k"),
                                     rf=rf)
            dpB = activate_dataplane(sB.domain.storage, plane=wp, pid=1,
                                     data_dir=os.path.join(td, "k"),
                                     rf=rf)
            _until(lambda: len(cp.view().addrs) == 2
                   and len(wp.view().addrs) == 2)
            dpA.shard_table(_tid(sA))
            dpB.shard_table(_tid(sB))
            try:
                sA.execute("set tidb_use_tpu = 1")
                sA.execute(Q6)  # warm steady state
                p0 = REGISTRY.get(
                    "dataplane_replica_promotions_total") or 0.0
                c0 = REGISTRY.get("dataplane_cold_reloads_total") or 0.0
                wp.stop(leave=True)
                deactivate_dataplane(sB.domain.storage)
                _until(lambda: 1 not in cp.view().members)
                t0 = time.perf_counter()
                sA.execute(Q6)  # triggers the survivor's re-shard
                rec_s = time.perf_counter() - t0
            finally:
                deactivate_dataplane(sA.domain.storage)
                try:
                    wp.stop(leave=True)
                except Exception:  # noqa: BLE001 — already left
                    pass
                cp.stop()
            out["kill_recovery"][f"rf{rf}"] = {
                "recovery_s": round(rec_s, 4),
                "promotions": int((REGISTRY.get(
                    "dataplane_replica_promotions_total") or 0.0) - p0),
                "cold_reloads": int((REGISTRY.get(
                    "dataplane_cold_reloads_total") or 0.0) - c0),
            }
    kr = out["kill_recovery"]
    if kr.get("rf1") and kr.get("rf2") and kr["rf2"]["recovery_s"]:
        kr["rf2_speedup_x"] = round(
            kr["rf1"]["recovery_s"] / kr["rf2"]["recovery_s"], 2)
    log(f"dataplane kill-recovery: rf1 {kr['rf1']['recovery_s']*1e3:.0f}ms"
        f" ({kr['rf1']['cold_reloads']} cold) vs rf2 "
        f"{kr['rf2']['recovery_s']*1e3:.0f}ms "
        f"({kr['rf2']['promotions']} promotions, "
        f"{kr['rf2']['cold_reloads']} cold)")
    return out


def trace_overhead_bench(sess, iters: int = None) -> dict:
    """Trace-overhead receipt (ISSUE 4, extended by ISSUE 13): steady-
    state Q1 untraced vs traced vs traced+profiled.  The continuous
    profiler folds every finished trace into the flame windows, so the
    profiled leg is the real production configuration — both deltas
    must stay under 2%."""
    from tidb_tpu.trace import PROFILER

    iters = ITERS if iters is None else iters
    prof_prev = PROFILER.enabled
    try:
        sess.execute("set tidb_enable_slow_log = 0")
        _, t_off = time_query(sess, Q1, iters)
        PROFILER.enabled = False
        sess.execute("set tidb_enable_slow_log = 1")
        _, t_on = time_query(sess, Q1, iters)
        PROFILER.enabled = True
        _, t_prof = time_query(sess, Q1, iters)
    finally:
        PROFILER.enabled = prof_prev
        sess.execute("set tidb_enable_slow_log = 1")
    delta_pct = (t_on - t_off) / t_off * 100.0
    prof_pct = (t_prof - t_off) / t_off * 100.0
    return {
        "untraced_s": round(t_off, 5),
        "traced_s": round(t_on, 5),
        "profiled_s": round(t_prof, 5),
        "delta_pct": round(delta_pct, 3),
        "profiled_delta_pct": round(prof_pct, 3),
        "ok": delta_pct < 2.0,
        "profiled_ok": prof_pct < 2.0,
        "flame_stacks": len(PROFILER.folded().splitlines()),
    }


def _trace_span_sum(sess, sql: str, span_name: str, attr: str) -> int:
    """Run `sql` once under TRACE and sum `attr` over `span_name` spans
    (e.g. host-readback bytes across copr.readback)."""
    try:
        sess.execute("trace " + sql)
        tr = sess.last_trace
        if tr is None:
            return -1
        total = {"n": 0}

        def walk(s):
            if s.name == span_name:
                total["n"] += int((s.attrs or {}).get(attr, 0) or 0)
            for c in s.children:
                walk(c)

        walk(tr.root)
        return total["n"]
    except BaseException:  # noqa: BLE001 — receipt survives trace issues
        return -1


def mpp_grouped_bench(sess_m, n_li: int) -> dict:
    """Grouped-pushdown receipt: GROUP BY over the MPP shuffle join with
    the grouped partial agg merged ON DEVICE (only O(G) rows read back)
    vs the host-merge comparator (TIDB_TPU_MPP_GROUPED=0: same device
    join, every joined row ships to the host and aggregates there)."""
    from tidb_tpu.metrics import REGISTRY

    GQ = ("select o_shippriority, count(*), sum(l_extendedprice),"
          " max(l_discount) from lineitem join orders"
          " on l_orderkey = o_orderkey where l_shipdate > '1995-03-15'"
          " group by o_shippriority")
    prior = os.environ.get("TIDB_TPU_MPP_GROUPED")
    try:
        os.environ["TIDB_TPU_MPP_GROUPED"] = "1"
        m0 = REGISTRY.snapshot()
        _, g_s = time_query(sess_m, GQ, ITERS)
        m1 = REGISTRY.snapshot()
        g_bytes = _trace_span_sum(sess_m, GQ, "copr.readback", "bytes")
        pushed = (m1.get("mpp_grouped_agg_pushed_total", 0)
                  - m0.get("mpp_grouped_agg_pushed_total", 0)) > 0
        os.environ["TIDB_TPU_MPP_GROUPED"] = "0"
        _, h_s = time_query(sess_m, GQ, ITERS)
        h_bytes = _trace_span_sum(sess_m, GQ, "copr.readback", "bytes")
    finally:
        if prior is None:
            os.environ.pop("TIDB_TPU_MPP_GROUPED", None)
        else:
            os.environ["TIDB_TPU_MPP_GROUPED"] = prior
    out = {
        "rows": n_li,
        "grouped_s": round(g_s, 5),
        "host_merge_s": round(h_s, 5),
        "grouped_rows_per_sec": round(n_li / g_s, 1),
        "host_merge_rows_per_sec": round(n_li / h_s, 1),
        "speedup": round(h_s / g_s, 2),
        "served_by_grouped_pushdown": pushed,
        "grouped_readback_bytes": g_bytes,
        "host_merge_readback_bytes": h_bytes,
    }
    log(f"MPP grouped agg: pushed={g_s:.4f}s host-merge={h_s:.4f}s "
        f"-> {h_s / g_s:.2f}x | readback {g_bytes} vs {h_bytes} bytes")
    return out


def host_tail_bench(sess, n: int) -> dict:
    """Zero-host-tail receipt (ISSUE 11): the shapes that used to split
    to a host tail — computed string group keys (device dict-code
    re-mapping) and multi-column TopN (packed compound ordering) — run
    fully fused vs the TIDB_TPU_FUSION=0 ladder comparator, with the
    fusion_splits_total delta across the corpus (must stay 0 fused)."""
    from tidb_tpu.metrics import REGISTRY

    shapes = (
        ("computed_key",
         "select concat(l_returnflag, '#'), count(*), sum(l_quantity)"
         " from lineitem group by concat(l_returnflag, '#')"),
        ("compound_order",
         "select l_orderkey from lineitem"
         " order by l_returnflag desc, l_shipdate, l_orderkey limit 10"),
    )
    from tidb_tpu.copr.fusion import SPLIT_REASONS

    def _reason_snap():
        snap = REGISTRY.snapshot()
        return {r: snap.get("fusion_splits_reason_"
                            + r.replace("-", "_") + "_total", 0)
                for r in SPLIT_REASONS}

    out = {}
    base_reasons = _reason_snap()  # deltas, like every other field
    prior = os.environ.get("TIDB_TPU_FUSION")
    for qname, sql in shapes:
        try:
            os.environ["TIDB_TPU_FUSION"] = "1"
            s0 = REGISTRY.get("fusion_splits_total")
            _, fused_s = time_query(sess, sql, ITERS)
            splits = REGISTRY.get("fusion_splits_total") - s0
            fused_d = _count_device_dispatches(sess, sql)
            os.environ["TIDB_TPU_FUSION"] = "0"
            _, unf_s = time_query(sess, sql, ITERS)
        finally:
            if prior is None:
                os.environ.pop("TIDB_TPU_FUSION", None)
            else:
                os.environ["TIDB_TPU_FUSION"] = prior
        out[qname] = {
            "fused_rows_per_sec": round(n / fused_s, 1),
            "unfused_rows_per_sec": round(n / unf_s, 1),
            "fused_dispatches": fused_d,
            "fusion_splits": int(splits),
            "speedup": round(unf_s / fused_s, 2),
        }
        log(f"host_tail {qname}: fused={n / fused_s:,.0f} rows/s "
            f"({fused_d} dispatches, {int(splits)} splits) vs "
            f"unfused={n / unf_s:,.0f} rows/s -> {unf_s / fused_s:.2f}x")
    end_reasons = _reason_snap()
    out["splits_by_reason"] = {
        r: int(end_reasons[r] - base_reasons[r]) for r in SPLIT_REASONS
    }
    return out


def layout_bench(sess, n: int) -> dict:
    """Adaptive-layout receipt (ISSUE 10) on a price-grid table (one
    group key + six low-NDV DOUBLE measure columns — the wide-wire
    shape the cold tier exists for), with the hot-tier byte cap set to
    ~a fifth of the working set:

    - ADAPTIVE (TIDB_TPU_LAYOUT on): the tuner keeps the highest-
      priority column hot within the budget and parks the measure
      columns on device as 2-4 bit packed blocks that decode
      in-register — steady state runs with ZERO host reloads (cold
      hits counted);
    - FIXED (TIDB_TPU_LAYOUT=0): the pre-layout hot-only byte-LRU —
      the working set over the cap re-transfers its f64 wire arrays
      every query (the full-reload comparator).

    Reports steady qps for both legs + the autotuned/fixed speedup;
    legs interleave and keep per-leg bests so host noise cancels."""
    import numpy as _np

    import tidb_tpu.layout.coldtier as coldtier
    from tidb_tpu.copr.parallel import MESH_CACHE
    from tidb_tpu.layout import LAYOUT, set_hot_cap_bytes
    from tidb_tpu.layout.autotuner import _table_wire_bytes
    from tidb_tpu.metrics import REGISTRY

    domain = sess.domain
    n_rows = min(max(n, 1 << 18), 1 << 20)
    s = domain.new_session()
    isc = domain.catalog.info_schema()
    if not isc.has_table("test", "layout_grid"):
        s.execute("create table layout_grid (g bigint, "
                  + ", ".join(f"v{i} double" for i in range(6)) + ")")
        rng = _np.random.default_rng(11)
        ladder = _np.round(_np.linspace(0.5, 3.5, 13), 2)
        tg = domain.catalog.info_schema().table("test", "layout_grid")
        domain.storage.table(tg.id).bulk_load_arrays(
            [rng.integers(0, 4, n_rows, dtype=_np.int64)]
            + [ladder[rng.integers(0, 13, n_rows)] for _ in range(6)],
            ts=domain.storage.current_ts())
    store = domain.storage.table(
        domain.catalog.info_schema().table("test", "layout_grid").id)
    wire = _table_wire_bytes(store)
    cap = max(int(wire * 0.2), 1 << 20)
    LQ = ("select g, count(*), " + ", ".join(
        f"sum(v{i})" for i in range(6)) + " from layout_grid group by g")
    out = {"rows": n_rows, "table_wire_bytes": wire,
           "hot_cap_bytes": cap}
    old_cap = MESH_CACHE._c.capacity
    saved = {k: os.environ.get(k) for k in
             ("TIDB_TPU_HBM_BYTES", "TIDB_TPU_LAYOUT",
              "TIDB_TPU_LAYOUT_RETUNE_S")}
    try:
        os.environ["TIDB_TPU_LAYOUT_RETUNE_S"] = "0"
        set_hot_cap_bytes(cap)

        def leg(adaptive: bool) -> float:
            if adaptive:
                os.environ.pop("TIDB_TPU_LAYOUT", None)
            else:
                os.environ["TIDB_TPU_LAYOUT"] = "0"
            MESH_CACHE.clear()
            coldtier.clear()
            LAYOUT.reset()
            _, best = time_query(s, LQ, ITERS + 5)
            return best

        # interleave the legs and keep each leg's best across rounds:
        # the structural cost (per-query reloads vs in-kernel decode)
        # survives a min; host noise does not
        m0 = REGISTRY.snapshot()
        ad_s = leg(True)
        m1 = REGISTRY.snapshot()
        fx_s = leg(False)
        ad_s = min(ad_s, leg(True))
        fx_s = min(fx_s, leg(False))
        out.update({
            "autotuned_s": round(ad_s, 5),
            "fixed_full_reload_s": round(fx_s, 5),
            "autotuned_rows_per_sec": round(n_rows / ad_s, 1),
            "fixed_rows_per_sec": round(n_rows / fx_s, 1),
            "speedup": round(fx_s / ad_s, 2),
            "cold_hits": round(
                m1.get("layout_cold_hits_total", 0)
                - m0.get("layout_cold_hits_total", 0)),
            "cold_demotions": round(
                m1.get("layout_cold_demotions_total", 0)
                - m0.get("layout_cold_demotions_total", 0)),
        })
        log(f"layout: autotuned={n_rows / ad_s:,.0f} rows/s vs "
            f"fixed/full-reload={n_rows / fx_s:,.0f} rows/s -> "
            f"{fx_s / ad_s:.2f}x (cap {cap} / wire {wire} bytes)")
    finally:
        for k, v in saved.items():
            if v is None:
                os.environ.pop(k, None)
            else:
                os.environ[k] = v
        MESH_CACHE._c.capacity = old_cap
        MESH_CACHE.clear()
        coldtier.clear()
        LAYOUT.reset()
    return out


def tpch_matrix_bench(scale: float = 2.0) -> dict:
    """Full-suite residency matrix (ISSUE 12): all 22 TPC-H queries
    classified fused (every scan/join/agg engine-attributed to the
    device: mesh or mpp) / partial (mixed) / host, with steady-state
    rows/s and device-dispatch counts — the fused fraction is the
    PR-over-PR tracking number for the paper's all-22-on-device arc."""
    import re

    from tidb_tpu.tpch_data import (TPCH_N_TABLES, TPCH_QUERIES,
                                    build_tpch_domain)

    sess = build_tpch_domain(scale=scale)
    # per-table row counts measured off the built domain (not
    # re-derived formulas, which would silently drift from the recipe)
    sess.execute("set tidb_use_tpu = 0")
    counts = {t: sess.query(f"select count(*) from {t}")[0][0]
              for t in ("lineitem", "orders", "customer", "part",
                        "partsupp", "supplier", "nation", "region")}
    sess.execute("set tidb_use_tpu = 1")
    out: dict = {"scale": scale, "queries": {}}
    matrix = {"fused": [], "partial": [], "host": []}
    for name in sorted(TPCH_QUERIES,
                       key=lambda q: int(q.lstrip("q"))):
        sql = TPCH_QUERIES[name]
        entry: dict = {"n_tables": TPCH_N_TABLES[name]}
        try:
            rows_in = sum(c for t, c in counts.items()
                          if re.search(rf"\b{t}\b", sql))
            _, secs = time_query(sess, sql, 1)
            engines = set()
            for r in sess.execute("explain analyze " + sql)[0].rows:
                for m in re.finditer(r"engine:([^\s|]+)", str(r[4])):
                    engines.add(m.group(1).rstrip(","))
            device = {e for e in engines
                      if e.startswith(("mesh", "mpp-"))}
            if engines and device == engines:
                klass = "fused"
            elif device:
                klass = "partial"
            else:
                klass = "host"
            entry.update({
                "class": klass,
                "engines": sorted(engines),
                "s": round(secs, 4),
                "rows_per_sec": round(rows_in / secs, 1),
                "device_dispatches": _count_device_dispatches(sess, sql),
            })
        except BaseException as e:  # noqa: BLE001 — receipt survives
            klass = "host"
            entry.update({"class": "host", "error": repr(e)})
        matrix[klass].append(name)
        out["queries"][name] = entry
    out["matrix"] = matrix
    out["fused_count"] = len(matrix["fused"])
    out["fused_ge4_tables"] = [q for q in matrix["fused"]
                               if TPCH_N_TABLES[q] >= 4]
    log(f"tpch_matrix: fused={len(matrix['fused'])}/22 "
        f"(>=4-table fused: {out['fused_ge4_tables']}) "
        f"partial={len(matrix['partial'])} host={len(matrix['host'])}")
    return out


def _run(state: dict):
    try:
        _run_inner(state)
    except BaseException as e:  # surfaced in the output JSON
        state["worker_error"] = repr(e)
        import traceback

        traceback.print_exc(file=sys.stderr)


def _run_inner(state: dict):
    state.setdefault("phases", {})["worker_start"] = round(
        time.perf_counter() - T0, 1)
    scales = [s for s in (262_144, 1_048_576, 4_000_000, 64_000_000,
                          MAX_ROWS)
              if s <= MAX_ROWS]
    if not scales:
        scales = [MAX_ROWS]
    scales = sorted(set(scales))
    # chaos knob: simulate the round-1/3/5 failure mode (a wedge at a
    # LATE scale) — earlier scales' receipts must survive in the emitted
    # detail and in BENCH_PARTIAL.json (test-asserted)
    fail_at = int(os.environ.get("BENCH_FAIL_AT_SCALE", "0"))
    for n in scales:
        # only attempt the next (bigger) scale while at least 35% of the
        # wall budget remains — a completed smaller scale is always kept
        if state.get("q1") and remaining() < 0.35 * WALL_LIMIT:
            log(f"skipping scale {n}: {remaining():.0f}s left")
            break
        if fail_at and n >= fail_at:
            raise RuntimeError(f"injected late-scale failure at {n} rows")
        log(f"loading {n} rows...")
        t0 = time.perf_counter()
        sess = build_lineitem(n)
        load_s = time.perf_counter() - t0
        log(f"loaded {n} rows in {load_s:.1f}s")
        state["loaded_rows"] = n

        sess.execute("set tidb_use_tpu = 1")
        log("Q1 tpu warmup (transfer + compile)...")
        q1_warm, q1_best = time_query(sess, Q1, ITERS)
        log(f"Q1 tpu: warm={q1_warm:.3f}s steady={q1_best:.4f}s "
            f"({n / q1_best:,.0f} rows/s)")
        q6_warm, q6_best = time_query(sess, Q6, ITERS)
        log(f"Q6 tpu: warm={q6_warm:.3f}s steady={q6_best:.4f}s")
        state["q1"] = {
            "rows": n, "warm_s": round(q1_warm, 4),
            "steady_s": round(q1_best, 5),
            "rows_per_sec": round(n / q1_best, 1),
        }
        state["q6"] = {
            "rows": n, "warm_s": round(q6_warm, 4),
            "steady_s": round(q6_best, 5),
            "rows_per_sec": round(n / q6_best, 1),
        }
        state["load_s"] = round(load_s, 2)
        # whole-fragment fusion receipt: fused one-launch dispatch vs the
        # per-tile dispatch loop, with dispatch counts (ISSUE 7)
        fus = None
        if remaining() > 0.2 * WALL_LIMIT:
            try:
                fus = fusion_bench(sess, n)
                state["fusion"] = fus
            except BaseException as e:  # noqa: BLE001 — receipt survives
                fus = {"error": repr(e)}
        # per-scale receipt: a later-scale wedge (a load hang)
        # must never zero the measured trajectory — every completed scale
        # survives in the emitted detail
        state.setdefault("scales", []).append({
            "rows": n, "load_s": round(load_s, 2),
            "q1_rows_per_sec": round(n / q1_best, 1),
            "q6_rows_per_sec": round(n / q6_best, 1),
            "fusion": fus,
            "at_s": round(time.perf_counter() - T0, 1),
        })
        state["phases"][f"scale_{n}_done"] = round(
            time.perf_counter() - T0, 1)
        persist_partial(state)

    # trace-overhead receipt: the span recorder runs on every statement
    # when the slow log is enabled (the default) — steady-state Q1 with
    # tracing off vs on vs on+profiler must stay within 2% (ISSUE 4
    # acceptance, profiler leg added by ISSUE 13)
    if state.get("q1") and remaining() > 60:
        to = trace_overhead_bench(sess)
        state["trace_overhead"] = to
        log(f"trace overhead: off={to['untraced_s']}s "
            f"on={to['traced_s']}s (+{to['delta_pct']}%) "
            f"profiled={to['profiled_s']}s (+{to['profiled_delta_pct']}%)"
            f" ok={to['ok']} profiled_ok={to['profiled_ok']}")
        state["phases"]["trace_overhead_done"] = round(
            time.perf_counter() - T0, 1)
        persist_partial(state)

    # lock-order witness receipt (ISSUE 16): corpus replay with the
    # witness on (fresh CPU subprocess; it never touches the chip)
    if remaining() > 90:
        try:
            lc = lockcheck_bench()
            state["lockcheck"] = lc
            log(f"lockcheck: acquisitions={lc['acquisitions']} "
                f"max_depth={lc['max_held_depth']} "
                f"violations={lc['violations']} ok={lc['ok']}")
        except BaseException as e:  # noqa: BLE001
            state["lockcheck"] = {"error": repr(e)}
        state["phases"]["lockcheck_done"] = round(
            time.perf_counter() - T0, 1)
        persist_partial(state)

    # sharded data plane (ISSUE 18): 1-host vs 2-host scan throughput
    # plus the cross-host fragment bytes actually exchanged
    if state.get("q1") and remaining() > 120:
        try:
            state["dataplane_scan"] = dataplane_bench(
                state.get("loaded_rows", 65_536))
        except BaseException as e:  # noqa: BLE001
            state["dataplane_scan"] = {"error": repr(e)}
        state["phases"]["dataplane_done"] = round(
            time.perf_counter() - T0, 1)
        persist_partial(state)

    # Q3-shaped device join: scan+filter+JOIN+partial agg in ONE device
    # program (JoinLookupIR) vs the CPU oracle's root-side hash join
    if state.get("q1") and remaining() > 180:
        from tidb_tpu.tpch_data import build_q3_tables

        n_li = min(state.get("loaded_rows", 4_000_000), 16_000_000)
        n_ord = max(n_li // 8, 1000)
        log(f"Q3 join bench: {n_li} lineitem x {n_ord} orders...")
        sess3 = build_q3_tables(n_li, n_ord)
        Q3 = _q3_sql()
        plan = [r[0] for r in sess3.execute("explain " + Q3)[0].rows]
        in_cop = any("DeviceJoinReader" in op for op in plan)
        sess3.execute("set tidb_use_tpu = 1")
        q3_warm, q3_best = time_query(sess3, Q3, ITERS)
        sess3.execute("set tidb_use_tpu = 0")
        _, q3_cpu = time_query(sess3, Q3, 1)
        state["q3"] = {
            "rows": n_li, "warm_s": round(q3_warm, 4),
            "steady_s": round(q3_best, 5),
            "cpu_s": round(q3_cpu, 4),
            "speedup": round(q3_cpu / q3_best, 2),
            "join_in_cop_task": in_cop,
        }
        log(f"Q3 tpu: steady={q3_best:.4f}s cpu={q3_cpu:.3f}s "
            f"speedup={q3_cpu / q3_best:.1f}x cop-join={in_cop}")
        state["phases"]["q3_done"] = round(time.perf_counter() - T0, 1)
        persist_partial(state)

    # MPP shuffle join: both sides too big to broadcast — the exchange
    # engine (tidb_tpu/mpp) hash-partitions both scans across the mesh
    # with all_to_all and joins co-partitioned shards on device, vs the
    # same query on the root-side host hash join
    if state.get("q1") and remaining() > 150:
        from tidb_tpu.metrics import REGISTRY
        from tidb_tpu.tpch_data import build_q3_tables

        n_li = min(state.get("loaded_rows", 2_000_000), 8_000_000)
        n_ord = max(n_li // 4, 20_000)  # big build side: shuffle territory
        log(f"MPP join bench: {n_li} lineitem x {n_ord} orders...")
        sess_m = build_q3_tables(n_li, n_ord)
        MPPQ = ("select count(*), sum(l_extendedprice), max(o_shippriority)"
                " from lineitem join orders on l_orderkey = o_orderkey"
                " where l_shipdate > '1995-03-15'")
        sess_m.execute("set tidb_enforce_mpp = 1")
        plan = [r[0] for r in sess_m.execute("explain " + MPPQ)[0].rows]
        in_mpp = any("ExchangeSender" in op for op in plan)
        m0 = REGISTRY.snapshot()
        mpp_warm, mpp_best = time_query(sess_m, MPPQ, ITERS)
        m1 = REGISTRY.snapshot()
        served = (m1.get("mpp_joins_total", 0) - m0.get("mpp_joins_total", 0)
                  > 0)
        sess_m.execute("set tidb_allow_mpp = 0")
        sess_m.execute("set tidb_enforce_mpp = 0")
        _, mpp_host = time_query(sess_m, MPPQ, 1)
        state["mpp_join"] = {
            "rows": n_li, "build_rows": n_ord,
            "warm_s": round(mpp_warm, 4),
            "steady_s": round(mpp_best, 5),
            "host_join_s": round(mpp_host, 4),
            "speedup": round(mpp_host / mpp_best, 2),
            "plan_is_exchange": in_mpp,
            "served_by_mpp": served,
            "exchange_bytes": round(
                m1.get("mpp_exchange_bytes_total", 0)
                - m0.get("mpp_exchange_bytes_total", 0)),
        }
        log(f"MPP join: steady={mpp_best:.4f}s host={mpp_host:.3f}s "
            f"speedup={mpp_host / mpp_best:.1f}x exchange-plan={in_mpp}")
        state["phases"]["mpp_join_done"] = round(
            time.perf_counter() - T0, 1)
        persist_partial(state)

        # grouped partial aggregates below the exchange (ISSUE 8):
        # device-merged GROUP BY pushdown vs the host-merge rows path
        if remaining() > 90:
            try:
                sess_m.execute("set tidb_allow_mpp = 1")
                sess_m.execute("set tidb_enforce_mpp = 1")
                state["mpp_grouped_agg"] = mpp_grouped_bench(sess_m, n_li)
            except BaseException as e:  # noqa: BLE001 — receipt survives
                state["mpp_grouped_agg"] = {"error": repr(e)}
            state["phases"]["mpp_grouped_agg_done"] = round(
                time.perf_counter() - T0, 1)
            persist_partial(state)

    # adaptive-layout receipt (ISSUE 10): cold-tier qps vs the
    # fixed-layout full-reload comparator under a squeezed byte cap
    if state.get("q1") and remaining() > 90:
        try:
            state["layout"] = layout_bench(sess, state["loaded_rows"])
        except BaseException as e:  # noqa: BLE001 — receipt survives
            state["layout"] = {"error": repr(e)}
        state["phases"]["layout_done"] = round(
            time.perf_counter() - T0, 1)
        persist_partial(state)

    # zero-host-tail receipt (ISSUE 11): computed-key + compound-order
    # shapes fused vs the ladder comparator, splits-by-reason breakdown
    if state.get("q1") and remaining() > 60:
        try:
            state["host_tail"] = host_tail_bench(sess,
                                                 state["loaded_rows"])
        except BaseException as e:  # noqa: BLE001 — receipt survives
            state["host_tail"] = {"error": repr(e)}
        state["phases"]["host_tail_done"] = round(
            time.perf_counter() - T0, 1)
        persist_partial(state)

    # TPC-H residency matrix (ISSUE 12): per-query fused/partial/host
    # classification over all 22 queries — the join-tree compiler's
    # fused fraction, tracked PR over PR.  Gate above the stubbed-loop
    # wall budget (tests run _run_inner with WALL_LIMIT=140): the
    # matrix builds its own real domain, ~22 compiles
    if remaining() > 240:
        try:
            state["tpch_matrix"] = tpch_matrix_bench()
        except BaseException as e:  # noqa: BLE001 — receipt survives
            state["tpch_matrix"] = {"error": repr(e)}
        state["phases"]["tpch_matrix_done"] = round(
            time.perf_counter() - T0, 1)
        persist_partial(state)

    # concurrent-client serving bench: N wire clients of mixed TPC-H +
    # point lookups through the real server (admission, shape buckets,
    # micro-batcher under contention); reports p50/p99 + batched-vs-
    # unbatched point-agg throughput
    if state.get("q1") and remaining() > 150 \
            and os.environ.get("BENCH_CONCURRENT", "1") == "1":
        try:
            concurrent_bench(state)
        except BaseException as e:  # noqa: BLE001 — receipt must survive
            state["concurrent"] = {"error": repr(e)}
            log(f"concurrent bench failed: {e!r}")
        state["phases"]["concurrent_done"] = round(
            time.perf_counter() - T0, 1)
        persist_partial(state)

    # CPU oracle baseline on a bounded subsample, scaled linearly
    n = state.get("loaded_rows", 0)
    if n and remaining() > 60:
        cpu_rows = min(n, 1_000_000)
        log(f"cpu baseline on {cpu_rows} rows...")
        sess = build_lineitem(cpu_rows)
        sess.execute("set tidb_use_tpu = 0")
        _, q1_cpu = time_query(sess, Q1, 1)
        _, q6_cpu = time_query(sess, Q6, 1)
        scale = n / cpu_rows
        state["cpu"] = {
            "rows": cpu_rows,
            "q1_s_scaled": round(q1_cpu * scale, 4),
            "q6_s_scaled": round(q6_cpu * scale, 4),
        }
        log(f"cpu baseline: q1={q1_cpu:.3f}s q6={q6_cpu:.3f}s "
            f"(x{scale:.0f} scaled)")
    state["done"] = True
    persist_partial(state)


def persist_partial(state: dict):
    """Crash insurance: after every phase the full state lands in
    BENCH_PARTIAL.json (path overridable via BENCH_PARTIAL_PATH), so an
    externally killed run still leaves its best measured numbers on disk
    for the judge."""
    try:
        snap = dict(state)
        snap["phases"] = dict(snap.get("phases") or {})
        snap["scales"] = list(snap.get("scales") or [])
        path = os.environ.get("BENCH_PARTIAL_PATH") or os.path.join(
            os.path.dirname(os.path.abspath(__file__)),
            "BENCH_PARTIAL.json")
        tmp = path + ".tmp"
        with open(tmp, "w") as f:
            json.dump(snap, f)
        os.replace(tmp, path)
    except Exception:
        pass  # insurance must never kill the bench


def leg_errors(state: dict) -> list:
    """(where, error) for every leg that recorded one: the legs catch their
    own exceptions so that later legs still run, and the exit code says so."""
    found = []

    def walk(where: str, v):
        if isinstance(v, dict):
            if v.get("error"):
                found.append((where, v["error"]))
            for k, x in v.items():
                walk(f"{where}.{k}", x)
        elif isinstance(v, list):
            for i, x in enumerate(v):
                walk(f"{where}[{i}]", x)

    snap = dict(state)
    if snap.get("worker_error"):
        found.append(("worker", snap["worker_error"]))
    for k, v in snap.items():
        walk(k, v)
    return found


def emit(state: dict):
    # snapshot worker-shared mutables: the worker may still be appending
    # phase marks while we serialize (partial-emit path)
    state = dict(state)
    state["phases"] = dict(state.get("phases") or {})
    q1 = state.get("q1")
    if q1:
        cpu = state.get("cpu", {})
        q6 = state.get("q6", {})
        vs = None
        if cpu.get("q1_s_scaled"):
            vs = round(cpu["q1_s_scaled"] / q1["steady_s"], 3)
        out = {
            "metric": "tpch_q1_rows_per_sec",
            "value": q1["rows_per_sec"],
            "unit": "rows/s",
            "vs_baseline": vs,
            "device": state.get("device"),
            "detail": {
                "rows": q1["rows"],
                "q1_steady_s": q1["steady_s"],
                "q1_warm_s": q1["warm_s"],
                "q1_cpu_est_s": cpu.get("q1_s_scaled"),
                "q6_rows_per_sec": q6.get("rows_per_sec"),
                "q6_speedup": (
                    round(cpu["q6_s_scaled"] / q6["steady_s"], 3)
                    if cpu.get("q6_s_scaled") and q6.get("steady_s") else None
                ),
                "load_s": state.get("load_s"),
                "load_rows_per_sec": (
                    round(state["loaded_rows"] / state["load_s"], 1)
                    if state.get("load_s") and state.get("loaded_rows")
                    else None
                ),
                "q3": state.get("q3"),
                "mpp_join": state.get("mpp_join"),
                "mpp_grouped_agg": state.get("mpp_grouped_agg"),
                "concurrent": state.get("concurrent"),
                "fusion": state.get("fusion"),
                "layout": state.get("layout"),
                "scales": state.get("scales"),
                "trace_overhead": state.get("trace_overhead"),
                "lockcheck": state.get("lockcheck"),
                "devices": state.get("devices"),
                "complete": bool(state.get("done")),
                "worker_error": state.get("worker_error"),
                "phases": state.get("phases"),
            },
        }
    else:
        out = {
            "metric": "tpch_q1_rows_per_sec", "value": 0.0,
            "unit": "rows/s", "vs_baseline": 0.0,
            "device": state.get("device"),
            "detail": {
                "error": state.get(
                    "worker_error",
                    "bench timed out before first Q1 completed"),
                "loaded_rows": state.get("loaded_rows", 0),
                "scales": state.get("scales"),
                "devices": state.get("devices"),
                "wall_limit_s": WALL_LIMIT,
                "phases": state.get("phases"),
            },
        }
    print(json.dumps(out), flush=True)


def main() -> int:
    state: dict = {}
    emitted = [False]
    emit_mu = threading.Lock()

    def emit_once():
        with emit_mu:
            if not emitted[0]:
                emit(state)
                emitted[0] = True

    def on_term(signum, frame):
        # the driver's timeout must still harvest our best numbers.
        # Signal handlers run ON the main thread: if the normal end-of-run
        # emit already holds the lock (we interrupted it mid-write), a
        # blocking acquire would self-deadlock and os._exit would truncate
        # the line — so try-acquire, and when busy just return and let the
        # interrupted emit finish on the resumed outer frame.
        log(f"signal {signum}: emitting best state before exit")
        persist_partial(state)
        if emit_mu.acquire(blocking=False):
            try:
                if not emitted[0]:
                    emit(state)
                    emitted[0] = True
            finally:
                emit_mu.release()
            os._exit(0)

    for sig in (signal.SIGTERM, signal.SIGINT):
        try:
            signal.signal(sig, on_term)
        except (ValueError, OSError):
            pass
    if not preflight(state):
        return 2
    worker = threading.Thread(target=_run, args=(state,), daemon=True)
    worker.start()
    # reserve time to print: join with a margin before the hard limit
    worker.join(max(remaining() - 10, 5))
    if worker.is_alive():
        log("wall budget reached with worker still running; emitting "
            "partial results")
    emit_once()
    errors = leg_errors(state)
    for where, err in errors:
        log(f"leg failed: {where}: {err}")
    return 1 if errors else 0


if __name__ == "__main__":
    sys.exit(main())
