"""The seven readers of the program's finer spans, on hand-made span
lists: present, absent (None), two passes summed, spans outside the
client's interval clipped."""

import importlib

import pytest

from harness.traffic import Statement

MS = 1_000_000


def span(name, start_ms, dur_ms, depth=2, **attrs):
    return {"name": name, "start_ns": int(start_ms * MS),
            "dur_ns": int(dur_ms * MS), "depth": depth, "attrs": attrs}


def stmt(send_ms, done_ms):
    st = Statement()
    st.t_send_ns, st.t_done_ns = int(send_ms * MS), int(done_ms * MS)
    return st


def one_pass(at, wait=40.0):
    """The children of one `copr.chunk` that begins at `at` ms."""
    return [
        span("copr.chunk", at, wait + 4.0, depth=3),
        span("copr.dispatch.wait", at, 0.25, depth=4),
        span("copr.args", at + 0.25, 0.75, depth=4),
        span("copr.device.execute", at + 1.0, 1.0, depth=4, program="mesh_x"),
        span("copr.readback", at + 2.0, wait + 0.5, depth=4),
        span("copr.device.wait", at + 2.0, wait, depth=5),
        span("copr.unpack", at + wait + 2.5, 1.5, depth=4),
    ]


def served(passes=1, gap=50.0):
    """One served statement, the client's send at 100 ms and its last row
    at 100 + 10 + passes * gap + 4 ms; `wire.read` begins before the send
    and everything after it lies inside the client's interval."""
    end = 110.0 + passes * gap
    sp = [
        span("session.execute", 103.0, end - 103.0, depth=0),
        span("wire.read", 60.0, 41.0, depth=1, bytes=80),
        span("admission.wait", 101.0, 0.5, depth=1, queued=0),
        span("server.handoff", 101.5, 1.5, depth=1),
        span("parse", 103.0, 1.0, depth=1),
        span("executor.next", 104.0, end - 104.0, depth=1, rows=1, ops=[
            [3, "HashAggExec", 1, 2, 2.0],
            [2, "SelectionExec", 900, 3, 5.5],
            [1, "TableReaderExec", 4000, 3, 70.0]]),
        span("distsql.fanout", 105.0, end - 106.0, depth=2),
        span("session.account", end, 1.0, depth=1, slow=False),
        span("server.respond", end + 1.0, 2.0, depth=1, rows=1, bytes=90),
        span("wire.write", end + 2.0, 1.0, depth=1, rows=1, bytes=90),
    ]
    for i in range(passes):
        at = 110.0 + i * gap
        sp += one_pass(at)
        sp += [span("copr.select", at + 44.0, 1.0, depth=3, rows=9),
               span("copr.gather", at + 45.0, 2.0, depth=3, rows=9),
               span("copr.tail", at + 47.0, 0.5, depth=3, rows=9)]
    return stmt(100.0, end + 4.0), sp


def run_of(*pairs):
    return {"statements": [st for st, _ in pairs],
            "spans": [sp for _, sp in pairs]}


def read(name, run):
    return importlib.import_module(f"metrics.{name}").read(run)


ONE = {"server_envelope_ms": 0.5 + 1.5 + 1.0 + 2.0,
       # 100..101 is under wire.read's clipped end, 101..163 under spans
       # that abut; the last millisecond is the client's alone
       "stmt_unattributed_ms": 1.0,
       "device_wait_ms": 1.0 + 40.0,
       "dispatch_wait_ms": 0.25,
       "chunk_unpack_ms": 0.75 + 1.5,
       "host_gather_ms": 1.0 + 2.0 + 0.5,
       "root_exec_ms": 2.0 + 5.5}
PER_PASS = ("device_wait_ms", "dispatch_wait_ms", "chunk_unpack_ms",
            "host_gather_ms")


@pytest.mark.parametrize("name", sorted(ONE))
def test_reader_on_one_served_statement(name):
    assert read(name, run_of(served())) == pytest.approx(ONE[name])


@pytest.mark.parametrize("name", sorted(ONE))
def test_two_passes_are_summed_and_the_median_is_per_statement(name):
    run = run_of(served(1), served(2), served(2))
    want = ONE[name] * (2 if name in PER_PASS else 1)
    assert read(name, run) == pytest.approx(want)


@pytest.mark.parametrize("name", sorted(ONE))
def test_reader_finds_nothing_in_an_older_programs_spans(name):
    """The parent's tree: no envelope, no span inside `copr.chunk` but the
    enqueue and the readback, no `ops`.  Every reader but the one that
    measures the dark time itself returns None and raises nothing."""
    old = [span("session.execute", 103.0, 50.0, depth=0),
           span("admission.wait", 103.0, 0.5, depth=1),
           span("executor.next", 104.0, 48.0, depth=1, rows=1),
           span("copr.chunk", 110.0, 43.0, depth=3),
           span("copr.device.execute", 111.0, 1.0, depth=4),
           span("copr.readback", 112.0, 40.5, depth=4)]
    run = run_of((stmt(100.0, 160.0), old), (stmt(200.0, 260.0), []))
    got = read(name, run)
    if name == "stmt_unattributed_ms":
        assert got == pytest.approx(3.0 + 7.0)
    else:
        assert got is None
    assert read(name, {"statements": [], "spans": []}) is None


def test_spans_outside_the_clients_interval_are_clipped():
    from metrics.stmt_unattributed_ms import uncovered_ns

    spans = [span("before", 10.0, 20.0), span("across_send", 90.0, 15.0),
             span("inside", 120.0, 10.0), span("overlaps", 125.0, 10.0),
             span("across_done", 190.0, 50.0), span("after", 300.0, 5.0),
             span("empty", 150.0, 0.0)]
    # covered inside [100, 200): 100..105, 120..135, 190..200
    assert uncovered_ns(100 * MS, 200 * MS, spans) == 70 * MS
    assert uncovered_ns(100 * MS, 200 * MS, []) == 100 * MS
    assert uncovered_ns(100 * MS, 200 * MS,
                        [span("all", 0.0, 1000.0)]) == 0


def test_root_exec_leaves_nested_drains_and_readers_out():
    sp = [span("executor.next", 1.0, 9.0, depth=1, ops=[
              [2, "ProjectionExec", 4, 2, 0.25],
              [1, "DeviceJoinReaderExec", 4, 2, 8.0]]),
          span("executor.next", 2.0, 1.0, depth=3, ops=[
              [9, "HashAggExec", 1, 2, 100.0]])]
    assert read("root_exec_ms", run_of((stmt(0.0, 12.0), sp))) == 0.25
