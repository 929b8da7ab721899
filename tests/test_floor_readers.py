"""The eight readers ISSUE 41 brought (`benchmarks/metrics/`), on hand-made
span lists: what each sums, that a program without the spans or counters
(the parent's) gives None and raises nothing, and that every new entry of
`BENCHMARK.json` is as the issue wrote it.
"""

import importlib
import json
import os
import sys
from types import SimpleNamespace

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BENCH = os.path.join(ROOT, "benchmarks")

with open(os.path.join(ROOT, "BENCHMARK.json")) as _f:
    SPEC = json.load(_f)

SPAN_READERS = ["fanout_prepare_ms", "fanout_finish_ms", "executor_frame_ms",
                "thread_handoff_ms", "session_glue_ms", "gc_pause_ms"]
COUNTER_READERS = {"setup_compile_s": "xla_compile_seconds_total",
                   "setup_transfer_s": "mesh_column_load_seconds_total"}
LAYERS = {"fanout_prepare_ms": "mesh engine",
          "fanout_finish_ms": "mesh engine",
          "executor_frame_ms": "root executors",
          "thread_handoff_ms": "distsql ladder",
          "session_glue_ms": "session, parse, plan",
          "gc_pause_ms": "server, wire",
          "setup_compile_s": "set-up", "setup_transfer_s": "set-up"}


@pytest.fixture(scope="module")
def bench_path():
    """`benchmarks/` and the root on `sys.path`, as `run.py` puts them."""
    added = [p for p in (BENCH, ROOT) if p not in sys.path]
    for p in added:
        sys.path.insert(0, p)
    try:
        yield
    finally:
        for p in added:
            sys.path.remove(p)


def _read(name, spans):
    st = SimpleNamespace(ok=True)
    run = {"statements": [st] * len(spans), "spans": spans}
    return importlib.import_module(f"metrics.{name}").read(run)


def _sp(name, start_us, dur_us, depth=2, **attrs):
    return {"name": name, "start_ns": start_us * 1000,
            "dur_ns": dur_us * 1000, "depth": depth, "attrs": attrs}


def _statement(t0=1000, gc_us=0):
    """One mesh statement as the program's tree flattens: the root from t0
    for 6,000 us, the server's envelope outside it, the producer's
    `distsql.fanout` a tree child of `executor.open` that outlives it."""
    sp = [
        _sp("session.execute", t0, 6000, 0),
        _sp("server.handoff", t0 - 200, 200, 1),       # before the root
        _sp("wire.read", t0 - 900, 500, 1),
        _sp("parse", t0 + 100, 300, 1),
        _sp("plan", t0 + 400, 200, 1),
        _sp("executor.build", t0 + 700, 100, 1),
        _sp("executor.open", t0 + 800, 1000, 1),
        _sp("distsql.fanout", t0 + 1000, 4000, 2),     # ends after the open
        _sp("distsql.spawn", t0 + 900, 100, 3),
        _sp("distsql.route", t0 + 1000, 10, 3),
        _sp("mesh.analyze", t0 + 1010, 400, 3),
        _sp("mesh.columns", t0 + 1410, 300, 3),
        _sp("mesh.program", t0 + 1710, 200, 3),
        _sp("copr.compile", t0 + 1900, 5, 4),
        _sp("mesh.delta", t0 + 1910, 90, 3),
        _sp("copr.chunk", t0 + 2000, 2800, 3),
        _sp("mesh.result", t0 + 4800, 150, 3),
        _sp("executor.next", t0 + 1800, 3500, 1),
        _sp("distsql.wake", t0 + 5000, 80, 2),
        _sp("distsql.wake", t0 + 5100, 5, 2),
        _sp("executor.close", t0 + 5300, 50, 1),
        _sp("session.account", t0 + 6000, 200, 1),     # after the root
        _sp("server.respond", t0 + 6200, 500, 1),
    ]
    if gc_us:
        sp.append(_sp("py.gc", t0 + 2100, gc_us, 4, gen=2, collected=7))
    return sp


PARENT = [_sp("session.execute", 0, 6000, 0), _sp("parse", 100, 300, 1),
          _sp("plan", 400, 200, 1), _sp("executor.open", 800, 1000, 1),
          _sp("distsql.fanout", 1000, 4000, 2),
          _sp("copr.chunk", 2000, 2800, 3),
          _sp("executor.next", 1800, 3500, 1),
          _sp("executor.close", 5300, 50, 1)]

PRESENT = {
    # route 10 + analyze 400 + columns 300 + program 200 + delta 90
    "fanout_prepare_ms": 1.0,
    "fanout_finish_ms": 0.15,
    # build 100 + open 1,000 + close 50, less the spawn's 100 in the open
    "executor_frame_ms": 1.05,
    "thread_handoff_ms": 0.185,
    # the root's 6,000 less parse 300, plan 200, build 100, open 1,000,
    # next 3,500 and close 50
    "session_glue_ms": 0.85,
}


@pytest.mark.parametrize("name", sorted(PRESENT))
def test_span_reader_sums_its_spans_per_statement(bench_path, name):
    spans = [_statement(1000), _statement(50_000), []]
    assert _read(name, spans) == pytest.approx(PRESENT[name])


@pytest.mark.parametrize("name", SPAN_READERS)
def test_span_reader_gives_none_on_a_program_without_the_spans(
        bench_path, name, monkeypatch):
    import tidb_tpu.metrics as metrics_mod

    # the parent's tree: none of the spans, no collector counter
    monkeypatch.setattr(metrics_mod, "REGISTRY", metrics_mod.Registry())
    assert _read(name, [PARENT, PARENT, []]) is None
    assert _read(name, [[], []]) is None


def test_executor_frame_takes_out_what_has_its_own_metric(bench_path):
    q3 = _statement(0) + [
        _sp("mpp.exchange", 850, 600, 2),          # inside the open
        _sp("join.build", 1460, 200, 2, phase="sort"),
        _sp("copr.readback", 900, 100, 3),         # under the exchange
        _sp("join.build", 2500, 300, 3, phase="upload"),  # after the open
    ]
    # open 1,000 less exchange 600, sort 200 and the spawn's 100 (it lies
    # inside the exchange's interval here: the union, taken out once);
    # the fan-out that outlives the open stays whole in it
    assert _read("executor_frame_ms", [q3]) \
        == pytest.approx((100 + 1000 - 800 + 50) / 1e3)
    # a nested executor tree (a subplan under `plan`) is not the root's
    sub = _statement(0) + [_sp("executor.open", 450, 100, 2)]
    assert _read("executor_frame_ms", [sub]) \
        == pytest.approx(PRESENT["executor_frame_ms"])


def test_session_glue_clips_children_that_lie_outside_the_root(bench_path):
    early = _statement(1000)
    # a child that starts before the root and ends inside it counts only
    # from the root's start; one that runs past its end only up to it
    early += [_sp("early", 500, 600, 1), _sp("late", 6900, 900, 1)]
    # 850 dark before: the root's first 100 and its last 100 are among it
    assert _read("session_glue_ms", [early]) == pytest.approx(0.65)


def test_gc_pause_is_a_mean_over_the_traced_statements(bench_path):
    from tidb_tpu.trace import install_gc_spans

    install_gc_spans()
    spans = [_statement(0), _statement(10_000, gc_us=3000),
             _statement(20_000), _statement(30_000), []]
    # one pause of 3 ms in four traced statements: a median would read 0
    assert _read("gc_pause_ms", spans) == pytest.approx(0.75)
    assert _read("gc_pause_ms", [_statement(0)]) == 0.0


@pytest.mark.parametrize("name", sorted(COUNTER_READERS))
def test_counter_reader_reads_the_whole_process(bench_path, name,
                                                monkeypatch):
    import tidb_tpu.metrics as metrics_mod
    from tidb_tpu.copr import parallel  # noqa: F401 — registers both

    snap = metrics_mod.REGISTRY.snapshot()
    assert COUNTER_READERS[name] in snap
    assert _read(name, []) == snap[COUNTER_READERS[name]]
    fresh = metrics_mod.Registry()
    monkeypatch.setattr(metrics_mod, "REGISTRY", fresh)
    assert _read(name, []) is None  # the parent has no such counter
    fresh.inc(COUNTER_READERS[name], 2.5)
    assert _read(name, []) == 2.5


@pytest.mark.parametrize("name", SPAN_READERS + sorted(COUNTER_READERS))
def test_new_per_layer_entry_is_as_the_issue_wrote_it(bench_path, name):
    m = next(m for m in SPEC["per_layer"] if m["name"] == name)
    span = name in SPAN_READERS
    assert m == {"name": name, "unit": "ms" if span else "s",
                 "better": "lower",
                 "source": "program_span" if span else "program_counter",
                 "layer": LAYERS[name],
                 "moves": "stmt_p50_ms" if span else "setup_s"}
    # no `workloads` list: every cell reports it, and every cell reports
    # the end-to-end metric it moves
    moved = next(e for e in SPEC["end_to_end"] if e["name"] == m["moves"])
    assert "workloads" not in moved
    assert LAYERS[name] in {x["layer"] for x in SPEC["per_layer"]
                            if x["name"] not in LAYERS}
    assert callable(importlib.import_module(f"metrics.{name}").read)


def _prepared(*said):
    """A statement whose `mesh.analyze` spans say `said` (two where the
    MeshAggOverflow peel re-entered); None for a span without the
    attribute, as the parent's are."""
    sp = [s for s in _statement(0) if s["name"] != "mesh.analyze"]
    for i, v in enumerate(said):
        attrs = {"kind": "agg"}
        if v is not None:
            attrs.update(prepared=v.split(":")[0])
            if ":" in v:
                attrs.update(reason=v.split(":")[1])
        sp.append(_sp("mesh.analyze", 1010 + 500 * i, 400, 3, **attrs))
    return sp


@pytest.mark.parametrize("statements,want", [
    ([("hit",), ("hit",), ("hit", "hit")], 100.0),
    ([("miss:new",), ("hit",), ("hit",), ("hit",)], 75.0),
    # a statement is a hit only where every analysis of it is
    ([("hit", "miss:new"), ("hit",)], 50.0),
    # statements with no mesh analysis are not counted
    ([("hit",), (), ("miss:mesh",), ()], 50.0),
    ([(), ()], None),
    # the parent's spans carry no `prepared`
    ([(None,), (None,)], None),
], ids=["all-hits", "one-miss", "peel", "no-fanout", "no-analyze",
        "parent"])
def test_prepared_hit_pct_counts_statements_whose_every_analysis_hit(
        bench_path, statements, want):
    got = _read("prepared_hit_pct", [_prepared(*s) for s in statements])
    assert got == (None if want is None else pytest.approx(want))


def test_prepared_hit_pct_entry_is_as_the_issue_wrote_it(bench_path):
    m = next(m for m in SPEC["per_layer"] if m["name"] == "prepared_hit_pct")
    assert m == {"name": "prepared_hit_pct", "unit": "%", "better": "higher",
                 "source": "program_span", "layer": "mesh engine",
                 "moves": "stmt_p50_ms"}
    # appended after the set-up readers, nothing before it moved; the
    # metrics of later cells come after it
    names = [x["name"] for x in SPEC["per_layer"]]
    assert names.index("prepared_hit_pct") \
        == names.index("setup_transfer_s") + 1
