#!/usr/bin/env python3
"""chip_smoke.py — the quickest proof that the served path starts on the chip.

    python chip_smoke.py            # one TPU chip
    python chip_smoke.py --chips 4  # the four-chip path and nothing else

One process.  Builds TPC-H-shaped data from a seed, serves it through
`tidb_tpu.server.MySQLServer` (the server `python -m tidb_tpu` runs), and
drives Q1, Q6 and Q3 from a plain socket client speaking the MySQL protocol:
EXPLAIN, a first run, a second run under `TRACE FORMAT='json'` (the rung that
served it and compile hit or miss come from the program's own span tree), and
one run on the oracle engine (`tidb_use_tpu = 0`) to compare rows with.

Every line of standard output is one JSON object.  The last one is
`{"ok": true, "device": {...}}` and is printed only when every check held; a
failed check, an error from any phase, or a platform other than `tpu` exits
non-zero without it.  The wall times printed are a smoke's, not a
benchmark's.
"""

from __future__ import annotations

import argparse
import asyncio
import json
import socket
import struct
import sys
import threading
import time

#: SF10's 59,986,052 lineitem rows rounded up to the 2^20-row tile (64 tiles):
#: the BASELINE.json "TPC-H Q1 SF10" deployment
FULL_ROWS = 64 << 20
REGIONS = 8
#: TPC-H's 4:1 lineitem:orders ratio at a quarter of SF10 — the oracle's host
#: hash join at full size would cost more than every device phase together
Q3_ROWS = (16_777_216, 4_194_304)
#: the MPP shuffle-join shape of bench.py: both sides too big to broadcast
MPP_ROWS = (8_000_000, 2_000_000)
Q1 = (
    "select l_returnflag, l_linestatus, sum(l_quantity), sum(l_extendedprice),"
    " sum(l_extendedprice * (1 - l_discount)),"
    " sum(l_extendedprice * (1 - l_discount) * (1 + l_tax)),"
    " avg(l_quantity), avg(l_extendedprice), avg(l_discount), count(*)"
    " from lineitem where l_shipdate <= '1998-09-02'"
    " group by l_returnflag, l_linestatus"
    " order by l_returnflag, l_linestatus"
)
Q6 = (
    "select sum(l_extendedprice * l_discount) from lineitem"
    " where l_shipdate >= '1994-01-01' and l_shipdate < '1995-01-01'"
    " and l_discount between 0.05 and 0.07 and l_quantity < 24"
)
MPPQ = (
    "select count(*), sum(l_extendedprice), max(o_shippriority)"
    " from lineitem join orders on l_orderkey = o_orderkey"
    " where l_shipdate > '1995-03-15'"
)

#: how a plan is served: the operator EXPLAIN names -> (the rungs the
#: executed trace may then report, the counter every device run must move)
SCAN = {"TableReader": (("mesh",), "mesh_scans_total")}
#: the planner picks Q3's device join by size: the broadcast lookup inside the
#: cop task, or the MPP exchange when the build side is too big to broadcast
JOIN = {
    "DeviceJoinReader": (("mesh",), "mesh_scans_total"),
    "MPPJoin": (("mpp-shuffle", "mpp-broadcast"), "mpp_joins_total"),
}
SHUFFLE_JOIN = {"ExchangeSender": (("mpp-shuffle",), "mpp_joins_total")}
LINEITEM_SCANS = [("q1", Q1, SCAN), ("q6", Q6, SCAN)]

#: a run in which any of these moved was not served by the device alone
FALLBACK_COUNTERS = (
    "mesh_scan_errors_total",
    "cop_tasks_device_fallback_total",
    "mpp_fallback_total",
    "mpp_tree_fallback_total",
    "mesh_failover_retries_total",
)
_MYSQL_FLOAT_TYPES = (4, 5)  # FLOAT, DOUBLE; decimals and ints compare exact


def emit(obj: dict):
    print(json.dumps(obj), flush=True)


class SmokeFailed(Exception):
    pass


# ---------------------------------------------------------------------------
# a plain MySQL-protocol client (4.1, text protocol) over one socket
# ---------------------------------------------------------------------------


class WireClient:
    def __init__(self, host: str, port: int, db: str = "test"):
        self.sock = socket.create_connection((host, port), timeout=900)
        self.seq = 0
        self._recv()  # server greeting
        caps = 0x0200 | 0x8000 | 0x0008  # PROTO41 | SECURE_CONN | WITH_DB
        resp = struct.pack("<II", caps, 1 << 24) + bytes([33]) + b"\x00" * 23
        resp += b"root\x00" + b"\x00" + db.encode() + b"\x00"
        self._send(resp)
        ok = self._recv()
        if ok[0] != 0x00:
            raise ConnectionError(f"handshake refused: {ok!r}")

    def _read(self, n: int) -> bytes:
        buf = bytearray()
        while len(buf) < n:
            chunk = self.sock.recv(n - len(buf))
            if not chunk:
                raise ConnectionError("connection closed")
            buf += chunk
        return bytes(buf)

    def _recv(self) -> bytes:
        payload = b""
        while True:  # a payload of 2^24-1 bytes continues in the next packet
            hdr = self._read(4)
            n = hdr[0] | (hdr[1] << 8) | (hdr[2] << 16)
            self.seq = hdr[3] + 1
            payload += self._read(n)
            if n < 0xFFFFFF:
                return payload

    def _send(self, payload: bytes):
        self.sock.sendall(len(payload).to_bytes(3, "little")
                          + bytes([self.seq & 0xFF]) + payload)
        self.seq += 1

    @staticmethod
    def _lenenc(buf: bytes, pos: int):
        """(value or None for NULL, next position)."""
        b = buf[pos]
        if b < 0xFB:
            return b, pos + 1
        if b == 0xFB:
            return None, pos + 1
        width = {0xFC: 2, 0xFD: 3, 0xFE: 8}[b]
        return int.from_bytes(buf[pos + 1: pos + 1 + width], "little"), \
            pos + 1 + width

    def query(self, sql: str):
        """(column type codes, rows of str-or-None tuples); a server error
        raises — no statement of the smoke is allowed to fail."""
        self.seq = 0
        self._send(b"\x03" + sql.encode())
        first = self._recv()
        if first[0] == 0x00:
            return [], []
        if first[0] == 0xFF:
            code = struct.unpack_from("<H", first, 1)[0]
            raise SmokeFailed(
                f"server error {code} on {sql[:60]!r}: "
                f"{first[9:].decode('utf8', 'replace')}")
        ncols, _ = self._lenenc(first, 0)
        types = []
        for _ in range(ncols):
            col = self._recv()
            pos = 0
            for _ in range(6):  # catalog, schema, table, org_table, name, org_name
                n, pos = self._lenenc(col, pos)
                pos += n
            types.append(col[pos + 1 + 2 + 4])  # 0x0c, charset, length, TYPE
        self._recv()  # EOF after the column definitions
        rows = []
        while True:
            pkt = self._recv()
            if pkt[0] == 0xFE and len(pkt) < 9:
                return types, rows
            pos, row = 0, []
            for _ in range(ncols):
                n, pos = self._lenenc(pkt, pos)
                if n is None:
                    row.append(None)
                else:
                    row.append(pkt[pos: pos + n].decode())
                    pos += n
            rows.append(tuple(row))

    def close(self):
        self.seq = 0
        self._send(b"\x01")  # COM_QUIT
        self.sock.close()


class Served:
    """One MySQLServer for one Domain, on an event loop in a thread, port 0 —
    what `python -m tidb_tpu` runs through serve_forever."""

    def __init__(self, domain):
        from tidb_tpu.server import MySQLServer

        self.srv = MySQLServer(domain, port=0)
        self.loop = asyncio.new_event_loop()
        started = threading.Event()

        def run():
            asyncio.set_event_loop(self.loop)
            self.loop.run_until_complete(self.srv.start())
            started.set()
            self.loop.run_forever()

        self.thread = threading.Thread(target=run, daemon=True,
                                       name="smoke-server")
        self.thread.start()
        if not started.wait(30):
            raise SmokeFailed("server failed to start")

    def client(self) -> WireClient:
        return WireClient(self.srv.host, self.srv.port)

    def stop(self):
        asyncio.run_coroutine_threadsafe(
            self.srv.shutdown(drain_s=2.0), self.loop).result(30)
        self.loop.call_soon_threadsafe(self.loop.stop)
        self.thread.join(10)


# ---------------------------------------------------------------------------
# checks
# ---------------------------------------------------------------------------


class Smoke:
    """Collects failed checks.  A check that fails is printed and fails the
    run at its end; an exception from a phase ends the run at once."""

    def __init__(self):
        self.problems = []
        self._compiles = 0
        import jax.monitoring

        jax.monitoring.register_event_duration_secs_listener(self._on_event)

    def _on_event(self, event: str, _secs: float, **_kw):
        if event == "/jax/core/compile/backend_compile_duration":
            self._compiles += 1

    def check(self, cond: bool, msg: str):
        if not cond:
            self.problems.append(msg)
            emit({"check_failed": msg})

    @staticmethod
    def counters() -> dict:
        from tidb_tpu.metrics import REGISTRY

        return dict(REGISTRY.snapshot())

    # -- one query through the wire ------------------------------------
    def run_query(self, cli: WireClient, name: str, sql: str,
                  served_by: dict):
        """EXPLAIN, first run, traced second run, oracle run; one JSON
        line.  `served_by`: see SCAN / JOIN above — the first operator of
        it that EXPLAIN shows decides which rungs and counter are right."""
        cli.query("set tidb_use_tpu = 1")
        _, plan = cli.query("explain " + sql)
        ops = [r[0].strip(" └─│├") for r in plan]
        named = [p for p in served_by if any(p in op for op in ops)]
        self.check(bool(named),
                   f"{name}: EXPLAIN names none of {list(served_by)}: {ops}")
        expect_rungs, expect_counter = served_by[named[0]] if named else (
            (), "mesh_scans_total")

        c0 = self.counters()
        t0 = time.perf_counter()
        types, rows = cli.query(sql)
        first_s = time.perf_counter() - t0
        c1 = self.counters()
        n_compiles = self._compiles
        t0 = time.perf_counter()
        _, traced = cli.query("trace format='json' " + sql)
        second_s = time.perf_counter() - t0
        second_compiles = self._compiles - n_compiles
        c2 = self.counters()

        spans = []
        _flatten(json.loads(traced[0][0])["root"], spans)
        rungs = [s["attrs"].get("scan_engine") for s in spans
                 if s["name"] == "distsql.fanout"]
        rungs += [f"mpp-{s['attrs'].get('rung')}" for s in spans
                  if s["name"] == "mpp.exchange"]
        fallback_tasks = sum(int(s["attrs"].get("fallback_tasks", 0))
                             for s in spans if s["name"] == "distsql.fanout")
        misses = [s["attrs"].get("kind") for s in spans
                  if s["name"] == "copr.compile"
                  and s["attrs"].get("cache") != "hit"]
        second_rows = sum(int(s["attrs"].get("rows", 0)) for s in spans
                          if s["name"] == "executor.next")
        # the operator-level attribution of a third, untimed run
        _, analyzed = cli.query("explain analyze " + sql)
        engines = sorted({tok[len("engine:"):] for r in analyzed
                          for tok in (r[-1] or "").split()
                          if tok.startswith("engine:")})

        cli.query("set tidb_use_tpu = 0")
        t0 = time.perf_counter()
        otypes, oracle = cli.query(sql)
        oracle_s = time.perf_counter() - t0
        cli.query("set tidb_use_tpu = 1")

        moved = {k: c2.get(k, 0) - c0.get(k, 0) for k in FALLBACK_COUNTERS}
        served = [c1.get(expect_counter, 0) - c0.get(expect_counter, 0),
                  c2.get(expect_counter, 0) - c1.get(expect_counter, 0)]
        mismatch = _compare_rows(types, rows, oracle)
        emit({
            "query": name, "plan": named, "rung": rungs, "engine": engines,
            "first_s": first_s, "second_s": second_s, "oracle_s": oracle_s,
            "second_run_compile": "miss" if misses or second_compiles
            else "hit",
            "second_run_backend_compiles": second_compiles,
            "second_run_chunks": sum(1 for s in spans
                                     if s["name"] == "copr.chunk"),
            "rows": len(rows), "second_rows": second_rows,
            "served_counter": {expect_counter: served},
            "fallback_counters_moved": moved,
            "parity": mismatch is None,
            "note": "smoke, not a benchmark",
        })
        self.check(bool(rows), f"{name}: no rows returned")
        self.check(second_rows == len(rows),
                   f"{name}: second run returned {second_rows} rows, "
                   f"first {len(rows)}")
        self.check(bool(rungs) and all(r in expect_rungs for r in rungs),
                   f"{name}: served by {rungs}, expected {expect_rungs}")
        self.check(fallback_tasks == 0,
                   f"{name}: {fallback_tasks} tasks re-ran on the oracle")
        self.check(not any("cpu" in e or "host" in e or "rejected" in e
                           for e in engines),
                   f"{name}: operator attribution {engines}")
        self.check(min(served) > 0,
                   f"{name}: {expect_counter} moved by {served}")
        self.check(not misses and not second_compiles,
                   f"{name}: second run compiled (program-cache misses "
                   f"{misses}, backend compiles {second_compiles})")
        self.check(not any(moved.values()),
                   f"{name}: fallback counters moved: {moved}")
        self.check(types == otypes and mismatch is None,
                   f"{name}: parity with the oracle engine fails: {mismatch}")


def _flatten(span: dict, out: list):
    out.append({"name": span.get("name"), "attrs": span.get("attrs") or {}})
    for c in span.get("children", ()):
        _flatten(c, out)


def _compare_rows(types, rows, oracle):
    """None when equal: exact for integers and decimals, 1e-9 relative for
    doubles; else a description of the first difference."""
    if len(rows) != len(oracle):
        return f"{len(rows)} rows against the oracle's {len(oracle)}"
    for i, (ra, rb) in enumerate(zip(rows, oracle)):
        for t, x, y in zip(types, ra, rb):
            if x is None or y is None or t not in _MYSQL_FLOAT_TYPES:
                same = x == y
            else:
                same = abs(float(x) - float(y)) <= 1e-9 * max(
                    1.0, abs(float(y)))
            if not same:
                return f"row {i}: {ra} against the oracle's {rb}"
    return None


# ---------------------------------------------------------------------------
# phases
# ---------------------------------------------------------------------------


def _load_lineitem(rows: int, seed: int):
    from tidb_tpu.tpch_data import build_lineitem

    t0 = time.perf_counter()
    sess = build_lineitem(rows, regions=REGIONS, seed=seed)
    emit({"loaded": "lineitem", "rows": rows, "regions": REGIONS,
          "load_s": time.perf_counter() - t0})
    return sess.domain


def _load_q3(n_li: int, n_orders: int, seed: int):
    from tidb_tpu.tpch_data import build_q3_tables

    t0 = time.perf_counter()
    sess = build_q3_tables(n_li, n_orders, regions=REGIONS, seed=seed)
    emit({"loaded": "q3 pair", "lineitem_rows": n_li, "orders_rows": n_orders,
          "load_s": time.perf_counter() - t0})
    return sess.domain


def _scans(smoke: Smoke, domain, queries, setup: tuple = ()):
    served = Served(domain)
    cli = served.client()
    for stmt in setup:
        cli.query(stmt)
    for name, sql, served_by in queries:
        smoke.run_query(cli, name, sql, served_by)
    cli.close()
    served.stop()


def run_one_chip(smoke: Smoke, rows: int, q3_rows: tuple, seed: int = 7):
    """Q1 and Q6 over lineitem, Q3 over its own pair, each over the wire."""
    _scans(smoke, _load_lineitem(rows, seed), LINEITEM_SCANS)
    from tidb_tpu.tpch_data import Q3_SQL

    _scans(smoke, _load_q3(*q3_rows, seed), [("q3", Q3_SQL, JOIN)])


def run_pallas_parity(smoke: Smoke, n: int):
    """The Pallas kernels are off the Q1/Q6/Q3 path (computed string keys,
    cold-tier columns): run each once, compiled, against the jnp composition
    it replaces."""
    import jax
    import numpy as np

    from tidb_tpu.copr.pallas import kernels

    rng = np.random.default_rng(0)
    codes = rng.integers(0, 1 << 4, n).astype(np.uint8)
    mapping = rng.integers(0, 1 << 30, kernels.REMAP_MAX_CAP).astype(np.int32)
    keys = rng.integers(0, kernels.REMAP_MAX_CAP, n).astype(np.int32)
    packed = (codes[0::2] | (codes[1::2] << 4)).astype(np.uint8)
    cases = [
        ("remap_codes", lambda: kernels.remap_codes(keys, mapping, n),
         mapping[keys]),
        ("unpack_codes", lambda: kernels.unpack_codes(packed, 4, n), codes),
    ]
    for name, fn, want in cases:
        t0 = time.perf_counter()
        got = np.asarray(jax.jit(fn)())
        equal = bool(np.array_equal(got, want))
        emit({"pallas": name, "rows": n, "compiled": not kernels._interpret(),
              "equal": equal, "first_s": time.perf_counter() - t0})
        smoke.check(equal, f"pallas {name} disagrees with its reference")


def run_four_chip(smoke: Smoke, rows: int, mpp_rows: tuple, n_devices: int,
                  seed: int = 7):
    """What exists only across chips: lineitem sharded over the mesh with
    Q1 and Q6 (psum merges), and the MPP shuffle join (all_to_all)."""
    from tidb_tpu.copr.parallel import MESH_CACHE, get_mesh

    mesh_devices = get_mesh().devices.ravel()
    smoke.check(len(mesh_devices) == n_devices,
                f"get_mesh() spans {len(mesh_devices)} devices, "
                f"expected {n_devices}")
    _scans(smoke, _load_lineitem(rows, seed), LINEITEM_SCANS)
    spans = sorted({len(data.sharding.device_set)
                    for data, _ in MESH_CACHE._cache.values()})
    emit({"mesh_cache_arrays": len(MESH_CACHE._cache),
          "device_set_sizes": spans})
    smoke.check(spans == [n_devices],
                f"MESH_CACHE arrays span {spans} devices, "
                f"expected all {n_devices}")
    in_use = [(d.memory_stats() or {}).get("bytes_in_use")
              for d in mesh_devices]
    emit({"bytes_in_use": in_use})
    if None not in in_use:  # the CPU backend reports none: rehearsal only
        smoke.check(max(in_use) - min(in_use) < max(in_use) / 4,
                    f"bytes_in_use unbalanced across the mesh: {in_use}")

    _scans(smoke, _load_q3(*mpp_rows, seed),
           [("mpp-shuffle-join", MPPQ, SHUFFLE_JOIN)],
           setup=("set tidb_enforce_mpp = 1",))


def report_device():
    import jax

    from tidb_tpu.layout import hot_cap_bytes

    stats = jax.devices()[0].memory_stats() or {}
    emit({"peak_bytes_in_use": stats.get("peak_bytes_in_use"),
          "bytes_limit": stats.get("bytes_limit"),
          "hot_tier_bytes": hot_cap_bytes(),
          "compile_cache_dir": jax.config.jax_compilation_cache_dir})


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, default=1, choices=(1, 4))
    ap.add_argument("--rows", type=int, default=FULL_ROWS,
                    help="lineitem rows (default: the full 67,108,864)")
    ap.add_argument("--seed", type=int, default=7,
                    help="seed of the data generators")
    args = ap.parse_args()

    import jax

    import tidb_tpu.ops  # noqa: F401 — configures jax (x64, compile cache)

    devices = jax.devices()
    if devices[0].platform != "tpu":
        print(f"chip_smoke: no TPU: jax.devices() = {devices}",
              file=sys.stderr)
        return 2
    if len(devices) < args.chips:
        print(f"chip_smoke: --chips {args.chips} but jax.devices() = "
              f"{devices}", file=sys.stderr)
        return 2
    device = {"platform": devices[0].platform,
              "kind": devices[0].device_kind, "count": len(devices)}
    emit({"scale": {"lineitem_rows": args.rows, "full": args.rows == FULL_ROWS,
                    "q3_rows": Q3_ROWS, "mpp_rows": MPP_ROWS,
                    "chips": args.chips, "seed": args.seed},
          "device": device})

    smoke = Smoke()
    if args.chips == 4:
        run_four_chip(smoke, args.rows, MPP_ROWS, 4, args.seed)
    else:
        run_one_chip(smoke, args.rows, Q3_ROWS, args.seed)
        run_pallas_parity(smoke, 1 << 20)
    report_device()
    if smoke.problems:
        print(f"chip_smoke: {len(smoke.problems)} checks failed",
              file=sys.stderr)
        return 1
    emit({"ok": True, "device": device})
    return 0


if __name__ == "__main__":
    sys.exit(main())
