"""Query tracing & slow-query subsystem (tidb_tpu/trace).

Tentpole coverage (ISSUE 4 acceptance):

- TRACE [FORMAT='row'|'json'] <stmt> returns a span tree over the
  session API with compile / transfer / device-execute / readback spans
  carrying nonzero durations and byte counts;
- the same query past tidb_slow_log_threshold appears in
  INFORMATION_SCHEMA.SLOW_QUERY with per-phase columns, on BOTH device
  paths (the one-program mesh engine and the per-tile fan-out engine);
- tracing disabled is strictly zero-cost: span() returns the no-op
  singleton and nothing is recorded;
- chaos: a slow-log writer killed mid-record neither corrupts
  SLOW_QUERY nor leaks a file handle, and recovery drops the torn tail
  (the delta-log torn-tail contract);
- satellites: XLA error text attributes device ordinals (PR-2 (b)).
"""

import json
import time

import numpy as np
import pytest

from tidb_tpu import trace as trace_mod
from tidb_tpu.metrics import REGISTRY
from tidb_tpu.session import Domain

N = 6000


def _mk_session(tmp_dir=None):
    d = Domain(data_dir=tmp_dir)
    d.maintenance.stop()
    s = d.new_session()
    s.execute("create table li (l_orderkey bigint, l_qty bigint,"
              " l_price double, l_flag varchar(1))")
    rng = np.random.default_rng(5)
    t = d.catalog.info_schema().table("test", "li")
    flags = np.array(list("ANR"), dtype=object)
    d.storage.table(t.id).bulk_load_arrays([
        rng.integers(0, 500, N),
        rng.integers(1, 50, N),
        rng.uniform(1.0, 999.0, N),
        flags[rng.integers(0, 3, N)],
    ], ts=d.storage.current_ts())
    s.execute("analyze table li")
    return d, s


@pytest.fixture(scope="module")
def env():
    return _mk_session()


Q1ISH = ("select l_flag, sum(l_qty), avg(l_price), count(*) from li"
         " where l_qty < 40 group by l_flag")


def _span_names(tr):
    names = []

    def walk(s):
        names.append(s.name)
        for c in s.children:
            walk(c)

    walk(tr.root)
    return names


def _spans_by_name(tr, name):
    out = []

    def walk(s):
        if s.name == name:
            out.append(s)
        for c in s.children:
            walk(c)

    walk(tr.root)
    return out


# ---------------------------------------------------------------------------
# TRACE statement surfaces
# ---------------------------------------------------------------------------


def test_trace_row_output_has_device_phases(env):
    d, s = env
    rs = s.execute("trace " + Q1ISH)[-1]
    assert rs.headers == ["operation", "startTS", "duration"]
    ops = [r[0].strip() for r in rs.rows]
    assert ops[0] == "session.execute"
    for needed in ("parse", "plan", "executor.next", "distsql.fanout"):
        assert any(o.startswith(needed) for o in ops), (needed, ops)
    # device phases with nonzero durations
    tr = s.last_trace
    for phase in ("copr.compile", "copr.transfer", "copr.device.execute",
                  "copr.readback"):
        assert _spans_by_name(tr, phase), (phase, _span_names(tr))
    xfer = _spans_by_name(tr, "copr.transfer")
    assert sum(sp.attrs.get("bytes", 0) for sp in xfer) > 0
    rb = _spans_by_name(tr, "copr.readback")
    assert sum(sp.attrs.get("bytes", 0) for sp in rb) > 0
    exe = _spans_by_name(tr, "copr.device.execute")
    assert any(sp.dur_ns > 0 for sp in exe)
    # indentation encodes the tree
    assert any(r[0].startswith("  ") for r in rs.rows)


def test_trace_json_output(env):
    d, s = env
    rs = s.execute("trace format='json' select count(*) from li")[-1]
    doc = json.loads(rs.rows[0][0])
    assert doc["root"]["name"] == "session.execute"
    names = json.dumps(doc)
    assert "distsql.fanout" in names and "plan" in names


def test_trace_bad_format_rejected(env):
    d, s = env
    from tidb_tpu.errors import TiDBTPUError

    with pytest.raises(TiDBTPUError):
        s.execute("trace format='yaml' select 1")


def test_compile_cache_hit_attributed(env):
    d, s = env
    sql = "select sum(l_price) from li where l_qty < 17"
    s.execute("trace " + sql)
    s.execute("trace " + sql)  # second run: program cache hit
    hits = [sp for sp in _spans_by_name(s.last_trace, "copr.compile")
            if sp.attrs and sp.attrs.get("cache") == "hit"]
    assert hits, "second run must record a compile cache hit span"


# ---------------------------------------------------------------------------
# SLOW_QUERY + statement summary on both device engines
# ---------------------------------------------------------------------------


def test_slow_query_populates_with_phase_columns(env):
    d, s = env
    s.execute("set tidb_slow_log_threshold = 0")
    try:
        s.query(Q1ISH)
    finally:
        s.execute("set tidb_slow_log_threshold = 300")
    rows = s.query(
        "select query, compile_ms, transfer_bytes, device_ms, readback_ms,"
        " engines, cop_tasks from information_schema.slow_query")
    mine = [r for r in rows if r[0] == Q1ISH]
    assert mine, rows
    q, compile_ms, xfer, device_ms, readback_ms, engines, tasks = mine[-1]
    assert compile_ms + device_ms + readback_ms > 0
    assert engines  # tpu / mesh attribution recorded
    # mesh path: transfer happened at least once (per-column sharded load)
    assert xfer >= 0


def test_slow_query_covers_tile_fanout_engine(env, monkeypatch):
    """Force the per-tile fan-out rung (mesh declined) and verify the
    same per-phase spans appear — 'both engines' acceptance."""
    d, s = env
    from tidb_tpu.copr import parallel

    monkeypatch.setattr(parallel, "try_run_mesh",
                        lambda *a, **k: None)
    sql = "select l_flag, min(l_price) from li group by l_flag"
    s.execute("trace " + sql)
    tr = s.last_trace
    fanout = _spans_by_name(tr, "distsql.fanout")
    assert fanout and fanout[0].attrs.get("scan_engine") == "tile-fanout"
    for phase in ("copr.transfer", "copr.readback"):
        assert _spans_by_name(tr, phase), (phase, _span_names(tr))
    assert (_spans_by_name(tr, "copr.compile")
            or _spans_by_name(tr, "copr.device.execute"))


def test_statement_summary_gains_phase_aggregates(env):
    d, s = env
    s.execute("set tidb_slow_log_threshold = 0")
    try:
        s.query("select count(l_qty) from li where l_qty < 33")
    finally:
        s.execute("set tidb_slow_log_threshold = 300")
    rows = s.query(
        "select digest_text, sum_device_ms, sum_compile_ms from"
        " information_schema.statements_summary"
        " where digest_text like '%count(l_qty)%'")
    assert rows and rows[0][1] + rows[0][2] >= 0


# ---------------------------------------------------------------------------
# zero-cost disabled mode
# ---------------------------------------------------------------------------


def test_disabled_mode_is_noop(env):
    d, s = env
    s.execute("set tidb_enable_slow_log = 0")
    try:
        before = len(trace_mod.TRACE_RING)
        s.query("select count(*) from li")
        assert len(trace_mod.TRACE_RING) == before  # nothing recorded
        # the hook itself degenerates to the no-op singleton
        assert trace_mod.span("anything") is trace_mod.NOOP
        assert not trace_mod.tracing_active()
    finally:
        s.execute("set tidb_enable_slow_log = 1")


def test_trace_statement_works_with_slow_log_disabled(env):
    d, s = env
    s.execute("set tidb_enable_slow_log = 0")
    try:
        rs = s.execute("trace select count(*) from li")[-1]
        ops = [r[0].strip() for r in rs.rows]
        assert any(o.startswith("distsql.fanout") for o in ops)
    finally:
        s.execute("set tidb_enable_slow_log = 1")


# ---------------------------------------------------------------------------
# chaos: slow-log writer killed mid-record (torn-tail recovery)
# ---------------------------------------------------------------------------


def _slowlog_fds() -> int:
    import os

    n = 0
    for fd in os.listdir("/proc/self/fd"):
        try:
            if os.readlink(f"/proc/self/fd/{fd}").endswith("slow_query.log"):
                n += 1
        except OSError:
            pass
    return n


def test_slow_log_torn_write_recovers(tmp_path):
    from tidb_tpu.store.fault import failpoint, once
    from tidb_tpu.trace.slowlog import SlowQueryLog

    d, s = _mk_session(str(tmp_path))
    s.execute("set tidb_slow_log_threshold = 0")
    s.query("select count(*) from li")  # one clean entry on disk
    n_ok = len(d.slow_log.entries())
    assert n_ok >= 1
    with failpoint("trace/slow_log_write", once(OSError("writer killed"))):
        # writer dies mid-record: the statement must still succeed and
        # the in-memory table stays consistent
        s.query("select sum(l_qty) from li")
    s.execute("set tidb_slow_log_threshold = 300")
    assert _slowlog_fds() == 0, "slow-log writer leaked a file handle"
    assert REGISTRY.snapshot().get("slow_log_write_errors_total", 0) >= 1
    # SLOW_QUERY (in-memory ring) not corrupted: still queryable
    rows = s.query("select query from information_schema.slow_query")
    assert len(rows) == len(d.slow_log.entries()) == n_ok + 1
    # a record written AFTER the torn one must not merge into it (the
    # failed append resyncs the stream with a terminating newline)
    s.execute("set tidb_slow_log_threshold = 0")
    s.query("select max(l_price) from li")
    s.execute("set tidb_slow_log_threshold = 300")
    # restart: recovery drops ONLY the torn record (resync'd mid-file,
    # so it counts under the corrupt-record metric), keeps clean entries
    # on both sides of it

    def _dropped():
        snap = REGISTRY.snapshot()
        return (snap.get("slow_log_torn_tail_total", 0)
                + snap.get("slow_log_corrupt_records_total", 0))

    d0 = _dropped()
    recovered = SlowQueryLog(str(tmp_path / "slow_query.log"))
    assert _dropped() == d0 + 1
    qs = [e["query"] for e in recovered.entries()]
    assert any("count(*)" in q for q in qs)       # pre-torn entry kept
    assert any("max(l_price)" in q for q in qs)   # post-torn entry kept
    assert not any("sum(l_qty)" in q for q in qs)  # torn record dropped
    assert all("query" in e for e in recovered.entries())


# ---------------------------------------------------------------------------
# satellites
# ---------------------------------------------------------------------------


def test_xla_error_text_attributes_device_ids():
    """ROADMAP PR-2 (b): real XLA/jaxlib error shapes resolve to device
    ordinals so the RIGHT breaker trips instead of a blind retry."""
    from tidb_tpu.copr.device_health import attribute_devices

    cases = [
        ("XlaRuntimeError: INTERNAL: failed to enqueue program on "
         "TPU:3 (core halted)", (3,)),
        ("jaxlib.xla_extension.XlaRuntimeError: DATA_LOSS: device "
         "ordinal 2 lost", (2,)),
        ("RuntimeError: /device:TPU:1 unreachable", (1,)),
        ("INTERNAL: TpuDevice(id=7) returned DataLoss", (7,)),
        ("collective abort on chip 0 and chip 4", (0, 4)),
        ("RESOURCE_EXHAUSTED: out of memory on device 5", (5,)),
        ("some unattributable failure", ()),
    ]
    for msg, want in cases:
        assert attribute_devices(RuntimeError(msg)) == want, msg


def test_backoff_wait_lands_in_trace(env):
    d, s = env
    from tidb_tpu.store.fault import failpoint, once

    with failpoint("distsql/task_error", once(RuntimeError("transient"))):
        s.execute("trace select count(*) from li where l_qty < 7")
    tr = s.last_trace
    tasks = _spans_by_name(tr, "cop.task")
    # the mesh path may absorb the scan; only assert when fan-out ran
    if tasks:
        assert any((sp.attrs or {}).get("backoff_ms", 0) > 0
                   for sp in tasks)
    assert tr.phase_totals()["backoff_ms"] >= 0


def test_2pc_spans_recorded(env):
    d, s = env
    s.execute("create table if not exists w (a bigint primary key,"
              " b bigint)")
    s.execute("insert into w values (1, 10), (2, 20)")
    tr = s.last_trace  # BEFORE any further statement replaces it
    assert _spans_by_name(tr, "txn.prewrite")
    assert _spans_by_name(tr, "txn.commit")


def test_trace_ring_feeds_status_surface(env):
    d, s = env
    s.query("select count(*) from li")
    assert len(trace_mod.TRACE_RING) > 0
    tr = list(trace_mod.TRACE_RING)[-1]
    tot = tr.phase_totals()
    assert set(tot) >= {"compile_ms", "transfer_bytes", "device_ms",
                        "readback_ms", "backoff_ms", "engines"}


# ---------------------------------------------------------------------------
# continuous profiling + SLO plane (ISSUE 13)
# ---------------------------------------------------------------------------


def test_profiler_folds_finished_traces(env):
    d, s = env
    from tidb_tpu.trace import PROFILER

    f0 = REGISTRY.get("profile_traces_folded_total")
    s.query(Q1ISH)
    assert REGISTRY.get("profile_traces_folded_total") == f0 + 1
    folded = PROFILER.folded()
    assert folded.strip()
    stacks = dict(ln.rsplit(" ", 1) for ln in folded.strip().splitlines())
    assert any(st.startswith("session.execute") for st in stacks)
    # engine attribution rides the frames (compiled vs interpreted path)
    assert any(":" in st for st in stacks), stacks


def test_profiler_chains_export_hook(env):
    """The profiler hook CHAINS onto the recorder export chain — a
    directly-installed forwarder (the coord seam) and the profiler both
    see every finished trace, and unchaining is list-removal: either
    participant can leave without dropping the other."""
    from tidb_tpu.trace import Profiler, recorder

    d, s = env
    seen = []

    def forwarder(tr):
        seen.append(tr.sql)

    prev = recorder.TRACE_EXPORT_HOOK
    recorder.TRACE_EXPORT_HOOK = forwarder  # direct install (third party)
    p = Profiler(enabled=True)
    try:
        p.install()  # adopts the direct hook into the chain
        s.query("select count(*) from li")
        assert seen and "count(*)" in seen[-1]  # forwarder still ran
        assert p.folded().strip()               # and the profiler folded
        # list-removal semantics: the forwarder leaves mid-chain while
        # the profiler (chained AFTER it) keeps running
        recorder.unchain_export_hook(forwarder)
        n = len(seen)
        s.query("select count(*) from li")
        assert len(seen) == n  # forwarder gone, regardless of order
    finally:
        recorder.unchain_export_hook(forwarder)
        recorder.unchain_export_hook(p.fold)
        recorder.TRACE_EXPORT_HOOK = prev


def test_profiler_disabled_paths_are_noop(env):
    d, s = env
    from tidb_tpu.trace import PROFILER

    # tracing disabled: nothing reaches the export hook, and the span
    # seam degenerates to the no-op singleton (one contextvar read)
    s.execute("set tidb_enable_slow_log = 0")
    try:
        f0 = REGISTRY.get("profile_traces_folded_total")
        s.query("select count(*) from li")
        assert REGISTRY.get("profile_traces_folded_total") == f0
        assert trace_mod.span("anything") is trace_mod.NOOP
    finally:
        s.execute("set tidb_enable_slow_log = 1")
    # profiler disabled: traces still record, the fold is a no-op
    prev = PROFILER.enabled
    PROFILER.enabled = False
    try:
        PROFILER.reset()
        f0 = REGISTRY.get("profile_traces_folded_total")
        s.query("select count(*) from li")
        assert REGISTRY.get("profile_traces_folded_total") == f0
        assert PROFILER.folded() == ""
    finally:
        PROFILER.enabled = prev


def test_stmt_class_and_latency_histograms(env):
    from tidb_tpu.trace import stmt_class

    assert stmt_class("select * from t where a = 1") == "point"
    assert stmt_class("SELECT sum(a) FROM t") == "agg"
    assert stmt_class("select a from t group by a") == "agg"
    assert stmt_class("select * from a join b on a.x = b.x") == "join"
    assert stmt_class("insert into t values (1)") == "dml"
    assert stmt_class("update t set a = 1") == "dml"
    assert stmt_class("show tables") == "other"
    d, s = env
    h0 = (REGISTRY.hist_stats("stmt_latency_agg_ms") or
          {"count": 0})["count"]
    s.query("select count(*) from li")
    assert REGISTRY.hist_stats("stmt_latency_agg_ms")["count"] == h0 + 1


def test_explain_analyze_reports_hbm_peak(env):
    """Device-memory telemetry (ISSUE 13): EXPLAIN ANALYZE surfaces the
    statement's HBM high-water mark stamped on the execute spans."""
    d, s = env
    s.query(Q1ISH)  # warm the mesh cache so resident bytes are nonzero
    rs = s.execute("explain analyze " + Q1ISH)[-1]
    extra = rs.rows[0][4]
    assert "hbm_peak:" in extra, rs.rows
    peak = int(extra.split("hbm_peak:")[1].split()[0])
    assert peak > 0


# ---------------------------------------------------------------------------
# slow-log rotation (ISSUE 13 satellite)
# ---------------------------------------------------------------------------


def test_slow_log_rotation_caps_size(tmp_path):
    import os

    from tidb_tpu.trace.slowlog import SlowQueryLog

    path = str(tmp_path / "slow_query.log")
    log = SlowQueryLog(path, max_bytes=500, keep=2)
    r0 = REGISTRY.get("slow_log_rotations_total")
    for i in range(40):
        log.record({"query": f"q{i}", "time": "t", "conn_id": i})
    assert REGISTRY.get("slow_log_rotations_total") > r0
    assert os.path.exists(path + ".1")
    assert not os.path.exists(path + ".3")  # keep=2 drops older files
    assert os.path.getsize(path) <= 500 + 128  # one record past the cap
    assert len(log.entries()) == 40  # the in-memory ring is unaffected
    # torn-tail recovery still honored on the ACTIVE file post-rotation
    with open(path, "ab") as f:
        f.write(b'{"query": "torn-tail')
    t0 = REGISTRY.get("slow_log_torn_tail_total")
    recovered = SlowQueryLog(path)
    assert REGISTRY.get("slow_log_torn_tail_total") == t0 + 1
    assert all("torn-tail" not in e.get("query", "")
               for e in recovered.entries())


def test_slow_log_rotation_rides_global_sysvar(tmp_path):
    d, s = _mk_session(str(tmp_path))
    s.execute("set global tidb_tpu_slow_log_max_bytes = 400")
    s.execute("set tidb_slow_log_threshold = 0")
    r0 = REGISTRY.get("slow_log_rotations_total")
    try:
        for _ in range(4):
            s.query("select count(*) from li")
    finally:
        s.execute("set tidb_slow_log_threshold = 300")
    assert REGISTRY.get("slow_log_rotations_total") > r0
    import os

    assert os.path.exists(str(tmp_path / "slow_query.log.1"))


def test_profiler_persists_windows_across_restart(env, tmp_path):
    """ISSUE 17 trace (b): windows persist atomically on rotation and a
    fresh Profiler (the restarted process) restores them at install —
    /flame survives a rolling restart instead of starting cold."""
    import os.path

    from tidb_tpu.trace import Profiler, recorder

    d, s = env
    pdir = str(tmp_path / "prof")
    p = Profiler(enabled=True, window_s=0.01, persist_dir=pdir)
    s.query(Q1ISH)
    p.fold(s.last_trace)
    time.sleep(0.02)
    s.query(Q1ISH)
    p.fold(s.last_trace)  # rotates -> persists the closed window
    assert os.path.exists(os.path.join(pdir, "profile_windows.json"))
    before = p.folded()
    assert before.strip()

    # "restart": a new profiler over the same dir restores the windows
    p2 = Profiler(enabled=True, window_s=0.01, persist_dir=pdir)
    try:
        p2.install()
        assert p2.folded().strip()
        assert set(p2.folded().splitlines()) & set(before.splitlines())
        sec = p2.status_section()
        assert sec["windows"], "restored windows missing from /status"
    finally:
        recorder.unchain_export_hook(p2.fold)

    # persist_now drains the live window unconditionally (graceful stop)
    p.persist_now()
    p3 = Profiler(enabled=True, window_s=0.01, persist_dir=pdir)
    try:
        p3.install()
        assert p3.folded().strip()
    finally:
        recorder.unchain_export_hook(p3.fold)


def test_profiler_torn_persist_file_starts_fresh(env, tmp_path):
    from tidb_tpu.trace import Profiler, recorder

    pdir = tmp_path / "prof"
    pdir.mkdir()
    (pdir / "profile_windows.json").write_text('{"windows": [{"bad"')
    p = Profiler(enabled=True, persist_dir=str(pdir))
    try:
        p.install()  # torn/foreign file: fresh start, no raise
        assert p.folded() == ""
    finally:
        recorder.unchain_export_hook(p.fold)


# ---------------------------------------------------------------------------
# ISSUE 29: spans where the statements' dark time was
# ---------------------------------------------------------------------------


def _end(sp):
    return sp.start_ns + sp.dur_ns


def _assert_in_order(spans):
    """On the clock, in the order given, none overlapping the next."""
    for a, b in zip(spans, spans[1:]):
        assert _end(a) <= b.start_ns, (a.name, _end(a), b.name, b.start_ns)


@pytest.mark.parametrize("start_ns", [12_345, None])
def test_add_span_places_a_pretimed_span_where_told(start_ns):
    tr, token = trace_mod.start_trace("select 1")
    try:
        before = time.perf_counter_ns()
        sp = tr.add_span("wire.read", 700, start_ns=start_ns, bytes=9)
        after = time.perf_counter_ns()
    finally:
        trace_mod.finish_trace(tr, token)
    assert sp in tr.root.children and sp.dur_ns == 700
    assert sp.attrs == {"bytes": 9}
    if start_ns is None:
        assert before <= sp.start_ns <= after  # the moment of the append
    else:
        # before the root's start: the envelope renders as it comes
        assert sp.start_ns == 12_345 < tr.root.start_ns
        row = [r for r in tr.rows() if r[0].startswith("  wire.read")][0]
        assert row[1].startswith("-")
        d = tr.to_dict()["root"]["children"][0]
        assert d["name"] == "wire.read" and d["start_us"] < 0
        back = trace_mod.import_trace(trace_mod.trace_payload(tr))
        child = back.root.children[0]
        assert child.start_ns < back.root.start_ns  # survives the export


def test_nothing_emits_the_span_name_no_phase_maps():
    from tidb_tpu.trace.recorder import PHASES

    assert "copr.execute" not in PHASES
    assert PHASES["copr.device.execute"] == "device_ms"


SERVED = {
    "result_set": "select l_flag, count(*) from li group by l_flag",
    "ok_packet": "insert into envelope_t values (1)",
    "error": "select no_such_column from li",
}


@pytest.mark.parametrize("kind", sorted(SERVED))
def test_served_statement_carries_the_servers_envelope(env, kind):
    """A statement served by MySQLServer to a socket client: wire.read,
    admission.wait, server.handoff, the root, session.account,
    server.respond (around wire.write), each at its true place."""
    import asyncio

    from test_lifecycle import WireClient, run
    from tidb_tpu.server import MySQLServer

    d, _s = env
    sql = SERVED[kind]

    async def body():
        srv = MySQLServer(d, port=0)
        await srv.start()
        try:
            mine = set(srv.domain.sessions)
            cli = WireClient(srv.host, srv.port)
            await cli.connect()
            mine = set(srv.domain.sessions) - mine
            await cli.query("create table if not exists envelope_t"
                            " (a bigint)")
            await asyncio.sleep(0.05)  # think time: wire.read starts early
            t_send = time.perf_counter_ns()
            got = await cli.query(sql)
            t_done = time.perf_counter_ns()
            sess = srv.domain.sessions[mine.pop()]
            cli.close()
            return sess.last_trace, got, t_send, t_done
        finally:
            await srv.stop()

    tr, got, t_send, t_done = run(body())
    assert tr.sql == sql and tr.finished
    assert ("error" in got) == (kind == "error")
    top = {sp.name: sp for sp in tr.root.children}
    chain = [top["wire.read"], top["admission.wait"], top["server.handoff"],
             tr.root, top["session.account"], top["server.respond"]]
    _assert_in_order(chain)
    # the envelope abuts the root on both sides
    assert _end(top["server.handoff"]) == tr.root.start_ns
    assert top["session.account"].start_ns == _end(tr.root)
    # wire.read began while the client was thinking and ends inside the
    # client's send-to-done interval; everything later lies inside it
    assert top["wire.read"].start_ns < t_send
    assert top["wire.read"].dur_ns >= int(0.04 * 1e9)
    assert t_send <= _end(top["wire.read"]) <= t_done
    for sp in chain[1:]:
        assert t_send <= sp.start_ns and _end(sp) <= t_done, sp.name
    assert top["wire.read"].attrs["bytes"] == len(sql)
    assert top["admission.wait"].attrs["queued"] == 0
    # whether it crossed tidb_slow_log_threshold (300 ms; a loaded test
    # machine can take that long)
    assert top["session.account"].attrs["slow"] is (tr.duration_ms() >= 300)
    resp = top["server.respond"]
    assert resp.attrs["bytes"] > 0
    if kind == "result_set":
        ww = top["wire.write"]
        assert resp.start_ns <= ww.start_ns and _end(ww) <= _end(resp)
        assert ww.attrs["rows"] == resp.attrs["rows"] == len(got["rows"])
        assert ww.attrs["bytes"] == resp.attrs["bytes"]
    else:
        assert "wire.write" not in top and resp.attrs["rows"] == 0


MESH = {
    "agg": Q1ISH,
    "filter": "select l_orderkey, l_qty from li where l_qty < 7",
    "topn": "select l_orderkey, l_price from li order by l_price desc"
            " limit 5",
}
CHUNK_CHILDREN = ["copr.dispatch.wait", "copr.args", "copr.device.execute",
                  "copr.readback", "copr.unpack"]


@pytest.mark.parametrize("kind", sorted(MESH))
def test_every_mesh_dispatch_is_divided(env, kind, monkeypatch):
    import jax

    from tidb_tpu.copr import parallel as pl

    real = pl._call_args

    def no_device_work(*a):
        # a jnp scalar made in here would be a transfer and a
        # jit_convert_element_type of its own among the device programs
        with jax.transfer_guard_host_to_device("disallow"):
            return real(*a)

    monkeypatch.setattr(pl, "_call_args", no_device_work)
    d, s = env
    s.query(MESH[kind])  # the first dispatch is labelled copr.compile
    s.query(MESH[kind])
    tr = s.last_trace
    chunks = _spans_by_name(tr, "copr.chunk")
    assert chunks and all(c.attrs["kind"] == kind for c in chunks)
    for c in chunks:
        assert [k.name for k in c.children] == CHUNK_CHILDREN
        _assert_in_order(c.children)
        assert c.start_ns <= c.children[0].start_ns
        assert _end(c.children[-1]) <= _end(c)
        assert sum(k.dur_ns for k in c.children) <= c.dur_ns
        by = {k.name: k for k in c.children}
        # the operand vector (eight range slots at least, eight bytes
        # each) and at most a float64 parameter vector beside it
        assert by["copr.args"].attrs["operands"] in (1, 2)
        assert by["copr.args"].attrs["bytes"] >= 64
        assert by["copr.device.execute"].attrs["program"].startswith(
            f"mesh_{kind}_")
        rb = by["copr.readback"]
        assert [k.name for k in rb.children] == ["copr.device.wait"]
        wait = rb.children[0]
        assert rb.start_ns <= wait.start_ns and _end(wait) <= _end(rb)
        assert rb.attrs["bytes"] == by["copr.unpack"].attrs["bytes"] > 0
        assert by["copr.unpack"].attrs["rows"] > 0
    # one program a statement shape: every pass launches the same one
    assert len({c.children[2].attrs["program"] for c in chunks}) == 1


def test_program_names_follow_the_fingerprint():
    from tidb_tpu.copr.parallel import _program_name

    a = _program_name("agg", "fp one")
    assert a == _program_name("agg", "fp one") == "mesh_agg_" + a[-8:]
    assert a != _program_name("agg", "fp two")
    assert int(a[-8:], 16) >= 0 and len(a) == len("mesh_agg_") + 8


STREAMED = {
    "plain": ("select l_orderkey, l_qty from li where l_qty < 9", None),
    # a folded constant of 19 digits or more is a wide decimal: host-only,
    # so its conjunct stays at the root (what Q6's shape did before PR 30)
    "root_selection": ("select l_orderkey from li where l_qty < 30 and l_price"
                       " between 500.5 - 100.25 and"
                       " 10000000000000000000.5 + 100.25", None),
    "limit": ("select l_orderkey from li where l_qty < 30 limit 17", 17),
}


@pytest.mark.parametrize("case", sorted(STREAMED))
def test_streamed_filter_finishing_is_spanned(env, case):
    d, s = env
    sql, limit = STREAMED[case]
    rows = s.query(sql)
    tr = s.last_trace
    from test_lifecycle import _wait_no_select_threads

    # a LIMIT closes the result early: let the producer finish its span
    assert _wait_no_select_threads() == []
    fanout = _spans_by_name(tr, "distsql.fanout")
    assert len(fanout) == 1 and fanout[0].attrs["scan_engine"] == "mesh"
    selects = _spans_by_name(tr, "copr.select")
    gathers = _spans_by_name(tr, "copr.gather")
    chunks = _spans_by_name(tr, "copr.chunk")
    assert selects and gathers
    assert len(selects) == len(chunks)  # once a chunk
    assert all(sp in fanout[0].children for sp in selects + gathers)
    passed = sum(sp.attrs["rows"] for sp in selects)
    assert sum(sp.attrs["rows"] for sp in gathers) == passed
    assert all(sp.attrs["rows_in"] >= sp.attrs["rows"] for sp in selects)
    assert all(sp.attrs["bytes"] > 0 for sp in gathers)
    nxt = [sp for sp in tr.root.children if sp.name == "executor.next"]
    assert len(nxt) == 1
    ops = nxt[0].attrs["ops"]
    assert all(len(op) == 5 and op[4] >= 0 for op in ops)
    assert ops[0][2] == nxt[0].attrs["rows"] == len(rows)  # the root's rows
    reader = [op for op in ops if op[1] == "TableReaderExec"]
    assert len(reader) == 1
    if limit is None:
        assert reader[0][2] == passed  # what the device let through
    else:
        assert len(rows) == limit <= passed
    if case == "root_selection":
        assert any(op[1] == "SelectionExec" for op in ops)
        assert len(rows) < passed
    # self times are parts of the drain: they cannot exceed it
    assert sum(op[4] for op in ops) <= nxt[0].dur_ns / 1e6 + 1e-6


@pytest.mark.parametrize("kind", sorted(MESH) + ["root_selection"])
def test_recorder_off_same_rows_nothing_recorded(env, kind):
    d, s = env
    sql = MESH.get(kind) or STREAMED[kind][0]
    want = s.query(sql)
    s.execute("set tidb_enable_slow_log = 0")
    try:
        ring = list(trace_mod.TRACE_RING)
        last = s.last_trace
        got = s.query(sql)
        assert list(trace_mod.TRACE_RING) == ring
        assert s.last_trace is last
    finally:
        s.execute("set tidb_enable_slow_log = 1")
    assert sorted(got) == sorted(want)


def test_recorder_off_served_statement_leaves_no_stamp(env):
    from test_lifecycle import WireClient, run
    from tidb_tpu.server import MySQLServer

    d, _s = env

    async def body():
        srv = MySQLServer(d, port=0)
        await srv.start()
        try:
            mine = set(srv.domain.sessions)
            cli = WireClient(srv.host, srv.port)
            await cli.connect()
            sess = srv.domain.sessions[(set(srv.domain.sessions) - mine).pop()]
            await cli.query("set tidb_enable_slow_log = 0")
            marker = sess.last_trace
            ring = list(trace_mod.TRACE_RING)
            got = await cli.query(SERVED["result_set"])
            assert list(trace_mod.TRACE_RING) == ring
            assert sess.last_trace is marker
            assert sess._pending_envelope is None  # consumed, not kept
            cli.close()
            return got
        finally:
            await srv.stop()

    assert len(run(body())["rows"]) == 3


# ---------------------------------------------------------------------------
# the host's floor of a statement (ISSUE 41): spans inside `distsql.fanout`
# and the executor's frame, across the two threads, in the collector's
# pauses; compile seconds under `compile_ms`
# ---------------------------------------------------------------------------

FANOUT_CHILDREN = ["distsql.spawn", "distsql.route", "mesh.analyze",
                   "mesh.columns", "mesh.program", "mesh.delta",
                   "copr.chunk", "mesh.result"]
ROOT_CHILDREN = ["parse", "plan", "executor.build", "executor.open",
                 "executor.next", "executor.close"]


def _kids(sp):
    """A span's children without the collector's pauses, which may fall
    anywhere."""
    return [c for c in sp.children if c.name != "py.gc"]


@pytest.fixture
def span_threads(monkeypatch):
    """{id(span): name of the thread that made it}, for every span made
    through the recorder while the fixture lives."""
    import threading

    from tidb_tpu.trace import recorder

    made = {}
    for meth in ("child", "add_span"):
        real = getattr(recorder.QueryTrace, meth)

        def noting(self, *a, _real=real, **kw):
            sp = _real(self, *a, **kw)
            made[id(sp)] = threading.current_thread().name
            return sp

        monkeypatch.setattr(recorder.QueryTrace, meth, noting)
    return made


@pytest.mark.parametrize("kind", ["agg", "topn"])
def test_mesh_statement_floor_is_tiled_by_named_spans(env, kind,
                                                      span_threads):
    import threading

    d, s = env
    s.query(MESH[kind])
    s.query(MESH[kind])  # every column resident, the program cached
    tr = s.last_trace
    me = threading.current_thread().name
    root = _kids(tr.root)
    assert [c.name for c in root] == ROOT_CHILDREN
    _assert_in_order(root)
    assert all(span_threads[id(c)] == me for c in root)
    by = {c.name: c for c in root}
    # nothing between `plan` and `executor.open` but the build
    assert _end(by["plan"]) <= by["executor.build"].start_ns
    assert _end(by["executor.build"]) <= by["executor.open"].start_ns
    # the producer's tree hangs under `executor.open`, on its own thread
    fan = _kids(by["executor.open"])
    assert [c.name for c in fan] == ["distsql.fanout"]
    fan = fan[0]
    kids = _kids(fan)
    assert [c.name for c in kids] == FANOUT_CHILDREN
    assert all(span_threads[id(c)] == "tidb-tpu-select" for c in kids)
    spawn, rest = kids[0], kids[1:]
    assert _end(spawn) == fan.start_ns  # ends where the fan-out starts
    assert by["executor.open"].start_ns <= spawn.start_ns
    _assert_in_order(rest)
    assert fan.start_ns <= rest[0].start_ns and _end(rest[-1]) <= _end(fan)
    at = {c.name: c for c in kids}
    assert at["distsql.route"].attrs["declined"] == "dataplane,microbatch"
    assert at["mesh.analyze"].attrs["kind"] == kind
    cols = at["mesh.columns"].attrs
    assert cols["cols"] == cols["resident"] >= 1
    assert not _spans_by_name(tr, "copr.transfer")
    hit = _kids(at["mesh.program"])
    assert [c.name for c in hit] == ["copr.compile"]
    assert hit[0].attrs["cache"] == "hit"
    assert at["mesh.delta"].attrs == {"deleted": 0, "inserted": 0}
    assert at["mesh.result"].attrs["chunks"] == 1
    assert "chunk" not in at["copr.chunk"].attrs
    # the hand-off back: on the statement's thread, under its drain
    wakes = _spans_by_name(tr, "distsql.wake")
    assert wakes and all(w in by["executor.next"].children for w in wakes)
    assert all(span_threads[id(w)] == me for w in wakes)
    assert all(w.attrs["items"] >= 1 for w in wakes)
    assert all(by["executor.next"].start_ns <= w.start_ns
               and _end(w) <= _end(by["executor.next"]) for w in wakes)


def test_first_fetch_of_a_column_is_not_resident_and_is_counted():
    from tidb_tpu.copr import parallel as pl

    d, s = _mk_session()
    before = REGISTRY.get(pl.COLUMN_LOAD_SECONDS)
    s.query("select sum(l_orderkey) from li where l_qty < 11")
    cols = _spans_by_name(s.last_trace, "mesh.columns")
    assert len(cols) == 1 and cols[0].attrs == {"cols": 2, "resident": 0}
    # the transfers nest in it, whichever thread of the pool ran them
    assert len([c for c in _kids(cols[0])
                if c.name == "copr.transfer"]) == 2
    moved = REGISTRY.get(pl.COLUMN_LOAD_SECONDS) - before
    assert 0 < moved <= cols[0].dur_ns / 1e9 + 1e-3
    s.query("select sum(l_orderkey) from li where l_qty < 12")
    cols = _spans_by_name(s.last_trace, "mesh.columns")
    assert cols[0].attrs == {"cols": 2, "resident": 2}
    assert REGISTRY.get(pl.COLUMN_LOAD_SECONDS) - before == moved


def _gc_counters():
    snap = REGISTRY.snapshot()
    return (snap["py_gc_pause_seconds_total"],
            snap["py_gc_collections_total"])


def test_gc_pause_is_a_span_under_the_current_one_and_two_counters(
        env, monkeypatch):
    import gc
    import threading

    from tidb_tpu.copr import parallel as pl

    real = pl._call_args

    def collecting(*a):
        gc.collect()  # inside `copr.chunk`, before `copr.args` opens
        return real(*a)

    monkeypatch.setattr(pl, "_call_args", collecting)
    d, s = env
    secs, n = _gc_counters()
    s.query(MESH["agg"])
    tr = s.last_trace
    full = [g for g in _spans_by_name(tr, "py.gc") if g.attrs["gen"] == 2]
    assert full and full[0].attrs["collected"] >= 0
    chunk = _spans_by_name(tr, "copr.chunk")[0]
    assert full[0] in chunk.children
    assert chunk.start_ns <= full[0].start_ns and _end(full[0]) <= _end(chunk)
    secs1, n1 = _gc_counters()
    assert n1 >= n + 1
    assert secs1 >= secs + full[0].dur_ns / 1e9 - 1e-9 > secs
    # on a thread with no trace: the counters only
    ring = list(trace_mod.TRACE_RING)
    spans_before = len(_span_names(tr))
    t = threading.Thread(target=gc.collect)
    t.start()
    t.join()
    secs2, n2 = _gc_counters()
    assert n2 >= n1 + 1 and secs2 > secs1
    assert list(trace_mod.TRACE_RING) == ring
    assert len(_span_names(tr)) == spans_before


def test_gc_callback_is_installed_once(env):
    import gc

    from tidb_tpu.trace import recorder

    trace_mod.install_gc_spans()
    Domain().maintenance.stop()
    assert gc.callbacks.count(recorder._on_gc) == 1


def test_compile_seconds_are_compile_ms_and_not_device_ms(env):
    d, s = env
    # a shape no other test of the module sends: a fresh program
    sql = "select max(l_qty), min(l_orderkey), count(*) from li" \
          " where l_qty between 3 and 23"
    c0 = REGISTRY.snapshot()
    s.query(sql)
    tr = s.last_trace
    miss = [c for c in _spans_by_name(tr, "copr.compile")
            if c.attrs["cache"] == "miss"]
    assert len(miss) == 1
    exe = _spans_by_name(tr, "copr.device.execute")
    assert len(exe) == 1 and exe[0] in miss[0].children
    ns = exe[0].attrs["compile_ns"]
    assert 0 < ns <= exe[0].dur_ns
    tot = tr.phase_totals()
    assert tot["compile_ms"] >= ns / 1e6
    assert tot["device_ms"] == pytest.approx((exe[0].dur_ns - ns) / 1e6)
    assert tot["compile_misses"] == 1
    c1 = REGISTRY.snapshot()
    assert c1["xla_compiles_total"] >= c0["xla_compiles_total"] + 1
    assert c1["xla_compile_seconds_total"] \
        >= c0["xla_compile_seconds_total"] + ns / 1e9 - 1e-6
    # the second dispatch of the program compiles nothing
    s.query(sql)
    tr = s.last_trace
    exe = _spans_by_name(tr, "copr.device.execute")
    assert len(exe) == 1 and "compile_ns" not in exe[0].attrs
    tot = tr.phase_totals()
    assert tot["compile_ms"] == 0.0 and tot["compile_hits"] == 1
    assert tot["device_ms"] == pytest.approx(exe[0].dur_ns / 1e6)
    assert REGISTRY.snapshot()["xla_compiles_total"] \
        == c1["xla_compiles_total"]


def test_nested_compile_stages_count_as_wall_time():
    """JAX reports every stage whole; one that ended inside another is
    taken out of it, so the counter is wall time."""
    import threading

    from tidb_tpu.trace import recorder

    trace_ev, _lower, backend = recorder._COMPILE_EVENTS
    got = {}

    def body():
        tr, token = trace_mod.start_trace("compile stages")
        try:
            with trace_mod.span("holder") as sp:
                c0 = REGISTRY.snapshot()
                recorder.note_compile(trace_ev, 0.010)   # an inner jit
                recorder.note_compile(backend, 0.020)    # an eager op
                recorder.note_compile(trace_ev, 0.100)   # encloses both
                recorder.note_compile("/jax/other", 5.0)
                c1 = REGISTRY.snapshot()
                got.update(ns=sp.attrs["compile_ns"], secs=(
                    c1["xla_compile_seconds_total"]
                    - c0["xla_compile_seconds_total"]), n=(
                    c1["xla_compiles_total"] - c0["xla_compiles_total"]))
        finally:
            trace_mod.finish_trace(tr, token)

    t = threading.Thread(target=body)  # its own stack of stages
    t.start()
    t.join()
    assert got["secs"] == pytest.approx(0.100)
    assert got["ns"] == pytest.approx(0.100e9, rel=1e-6)
    assert got["n"] == 1


def test_slow_log_off_no_span_is_made_at_any_new_site(env, monkeypatch):
    import gc

    from tidb_tpu.copr import parallel as pl
    from tidb_tpu.trace import recorder

    made = []

    class Counting(recorder.Span):
        __slots__ = ()

        def __init__(self, name, trace):
            made.append(name)
            super().__init__(name, trace)

    real = pl._call_args

    def collecting(*a):
        gc.collect()
        return real(*a)

    d, s = env
    want = s.query(MESH["agg"])
    monkeypatch.setattr(recorder, "Span", Counting)
    monkeypatch.setattr(pl, "_call_args", collecting)
    s.execute("set tidb_enable_slow_log = 0")
    try:
        made.clear()
        n = REGISTRY.snapshot()["py_gc_collections_total"]
        got = s.query(MESH["agg"])
        from test_lifecycle import _wait_no_select_threads

        assert _wait_no_select_threads() == []
        assert made == []
        # the collector is still counted
        assert REGISTRY.snapshot()["py_gc_collections_total"] >= n + 1
    finally:
        s.execute("set tidb_enable_slow_log = 1")
    assert sorted(got) == sorted(want)
    s.query(MESH["agg"])
    assert "mesh.analyze" in made and "distsql.wake" in made
