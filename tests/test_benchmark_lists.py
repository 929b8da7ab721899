"""`BENCHMARK.json` against the files it names, as `benchmarks/run.py`
resolves a cell: a cell whose configuration, traffic mix, query,
reference or per-layer reader is not there under its name fails on the
chip, after the chip time is spent (PR 31's lesson).  Then the four
readers the four-chip cell brought, on hand-made runs, and the cell itself
rehearsed through `run.main` on the CPU at SF 0.02.
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
CELLS = [c["name"] for c in SPEC["workloads"]]
METRICS = [m["name"] for m in SPEC["per_layer"]]


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


def _load(*parts):
    with open(os.path.join(BENCH, *parts)) as f:
        return json.load(f)


@pytest.mark.parametrize("name", CELLS)
def test_cell_resolves_as_run_py_resolves_it(bench_path, name):
    run = importlib.import_module("run")
    bench, cell = run.resolve(name)
    configs = {c["name"]: c for c in bench["configs"]}
    assert cell["config"] in configs
    entry = configs[cell["config"]]
    assert entry["file"] == f"benchmarks/configs/{cell['config']}.json"
    config = _load("configs", cell["config"] + ".json")
    assert config["name"] == cell["config"]
    assert config["chips"] == cell["chips"] and cell["chips"] in (1, 4)
    assert sorted(config["reduced"]) == sorted(entry["reduced"])
    mix = _load("traffic", cell["traffic"] + ".json")
    assert mix["name"] == cell["traffic"] and mix["expect_rungs"]
    for cls in mix["classes"]:
        q = _load("queries", cls["query"] + ".json")
        assert q["name"] == cls["query"] and q["params"]
        ref = importlib.import_module(f"queries.{cls['query']}")
        assert callable(ref.reference) and callable(ref.control)
        for table, columns in q["columns"].items():
            assert set(columns) <= set(config["tables"][table]["columns"])
            assert set(columns) <= set(config["wire_bytes"])
    # what the cell reports: set-up, another end-to-end metric, a layer
    e2e = [m["name"] for m in run.metrics_of(bench, "end_to_end", name)]
    assert "setup_s" in e2e and len(e2e) >= 2
    assert run.metrics_of(bench, "per_layer", name)


@pytest.mark.parametrize("name", METRICS)
def test_per_layer_metric_has_its_reader_and_names_cells(bench_path, name):
    m = next(m for m in SPEC["per_layer"] if m["name"] == name)
    assert callable(importlib.import_module(f"metrics.{name}").read)
    assert set(m.get("workloads", CELLS)) <= set(CELLS)
    moved = next(e for e in SPEC["end_to_end"] if e["name"] == m["moves"])
    assert set(m.get("workloads", CELLS)) <= set(moved.get("workloads",
                                                           CELLS))


def test_the_lists_are_inside_the_contracts_limits():
    assert 1 <= len(CELLS) <= 24 and len(set(CELLS)) == len(CELLS)
    four = [c for c in SPEC["workloads"] if c["chips"] == 4]
    assert len(four) <= max(len(CELLS) // 2, 1)
    assert {c["config"] for c in SPEC["workloads"]} \
        == {c["name"] for c in SPEC["configs"]}
    pairs = [(c["config"], c["traffic"]) for c in SPEC["workloads"]]
    assert len(set(pairs)) == len(pairs)
    for e in SPEC["end_to_end"]:
        assert set(e.get("workloads", CELLS)) <= set(CELLS)
    # the two shares of the roofline divide the cells between them
    shares = {m["name"]: m["workloads"] for m in SPEC["per_layer"]
              if m["name"].endswith("_roofline")}
    assert sorted(sum(shares.values(), [])) == sorted(CELLS)


# ---- the four readers of the four-chip cell, on hand-made runs -------------

def _span(name, **attrs):
    return {"name": name, "start_ns": 0, "dur_ns": 1, "depth": 1,
            "attrs": attrs}


def _run(**kw):
    st = SimpleNamespace(ok=True)
    run = {"statements": [st, st], "spans": [[], []], "peaks": {
        "hbm_bytes_per_s": 819e9}, "stmt_bytes": [8.19e9, 8.19e9],
        "device_trace": None}
    run.update(kw)
    return run


def _read(name, run):
    return importlib.import_module(f"metrics.{name}").read(run)


def test_mesh_scan_roofline_divides_by_the_devices(bench_path):
    # 8.19 GB a statement: 10 ms on one chip, 2.5 ms on four
    dev = {"stmt_busy_s": [0.005, 0.005], "devices": 4}
    assert _read("mesh_scan_roofline", _run(device_trace=dev)) \
        == pytest.approx(50.0)
    assert _read("scan_roofline", _run(device_trace=dev)) \
        == pytest.approx(200.0)  # why the four-chip cell is off its list
    dev1 = {"stmt_busy_s": [0.02, 0.02], "devices": 1}
    assert _read("mesh_scan_roofline", _run(device_trace=dev1)) \
        == pytest.approx(_read("scan_roofline", _run(device_trace=dev1)))
    assert _read("mesh_scan_roofline", _run()) is None
    assert _read("mesh_scan_roofline", _run(device_trace=dev, peaks=None)) \
        is None


def test_collective_ms_is_by_operation_name_over_devices_and_statements(
        bench_path):
    dev = {"devices": 4, "op_time": {
        "all-reduce.3": 0.004, "all-reduce-start.1": 0.002,
        "all-gather.9": 0.001, "all-to-all": 0.0005,
        "reduce-scatter.2": 0.0003, "collective-permute-done": 0.0002,
        "fusion.22": 5.0, "reduce.4": 1.0}}
    # 8 ms over four devices and two statements
    assert _read("collective_ms", _run(device_trace=dev)) \
        == pytest.approx(1.0)
    none = {"devices": 1, "op_time": {"fusion.22": 5.0}}
    assert _read("collective_ms", _run(device_trace=none)) == 0.0
    assert _read("collective_ms", _run()) is None


def test_mesh_devices_is_the_fanouts_device_ids(bench_path):
    four = [_span("distsql.fanout", device_ids=[0, 1, 2, 3])]
    three = [_span("distsql.fanout", device_ids=[0, 1, 3])]
    assert _read("mesh_devices", _run(spans=[four, four, three])) == 4
    assert _read("mesh_devices", _run(spans=[three, three, four])) == 3
    assert _read("mesh_devices", _run(spans=[[_span("parse")], []])) is None


def test_wide_sums_per_stmt_reads_the_unpack_spans(bench_path):
    one = [_span("copr.unpack", wide_sums=1, rows=40)]
    two = [_span("copr.unpack", wide_sums=1), _span("copr.unpack",
                                                    wide_sums=1)]
    assert _read("wide_sums_per_stmt", _run(spans=[one, one, two])) == 1
    zero = [_span("copr.unpack", wide_sums=0)]
    assert _read("wide_sums_per_stmt", _run(spans=[zero, zero])) == 0
    # the parent's span has no such attribute: nothing, and no error
    parent = [_span("copr.unpack", rows=40, bytes=320)]
    assert _read("wide_sums_per_stmt", _run(spans=[parent, []])) is None


def test_pad_tile_pct_is_the_fanouts_tiles_against_the_padded(bench_path):
    sf100 = [_span("distsql.fanout", tiles=573, tiles_padded=576)]
    parent = [_span("distsql.fanout", tiles=573, tiles_padded=1024)]
    assert _read("pad_tile_pct", _run(spans=[sf100, sf100])) \
        == pytest.approx(100 * 3 / 576)
    assert _read("pad_tile_pct", _run(spans=[parent, parent])) \
        == pytest.approx(100 * 451 / 1024)
    # a statement of several dispatches: both summed, then the share
    two = [_span("distsql.fanout", tiles=6, tiles_padded=8),
           _span("distsql.fanout", tiles=2, tiles_padded=2)]
    assert _read("pad_tile_pct", _run(spans=[two, two])) \
        == pytest.approx(20.0)
    # the parent's span has no such attributes: nothing, and no error
    bare = [_span("distsql.fanout", device_ids=[0, 1, 2, 3])]
    assert _read("pad_tile_pct", _run(spans=[bare, []])) is None


# ---- the new cell, rehearsed ------------------------------------------------

def test_sf100_q1_4chip_rehearsed_is_correct_and_its_control_is_caught(
        bench_path, capsys):
    from tidb_tpu.copr import parallel

    run = importlib.import_module("run")
    devs, epoch = parallel._eligible_devices()
    mp = pytest.MonkeyPatch()
    mp.setattr(parallel, "_eligible_devices", lambda: (devs[:4], epoch))
    try:
        rc = run.main(["--workload", "sf100-q1-4chip", "--seed",
                       str(2**31 + 39), "--seconds", "1", "--trace", "1",
                       "--sf", "0.02", "--rehearse-cpu", "1",
                       "--control", "1"])
    finally:
        mp.undo()
        parallel.MESH_CACHE.clear()
    assert rc == 0
    lines = capsys.readouterr().out.strip().splitlines()
    result, notes = json.loads(lines[-1]), [json.loads(x) for x in lines[:-1]]
    assert result["correct"] is True and result["rehearsal"] is True
    assert result["attempted"] > 0 and result["failed"] == 0
    assert result["device"]["count"] == 4
    control = [n["control"] for n in notes if "control" in n][0]
    assert control["caught"] is True
    got = result["metrics"]
    assert got["mesh_devices"]["value"] == 4
    # 118 tiles of 1,024 rows over four shards: 30 a shard, laid out as 32
    assert got["pad_tile_pct"]["value"] == pytest.approx(100 * 10 / 128)
    assert got["wide_sums_per_stmt"]["value"] == 0   # SF 0.02: none can pass
    assert got["device_rung_pct"]["value"] == 100.0
    assert got["passes_per_stmt"]["value"] == 1
    assert got["collective_ms"]["unit"] == "ms"
    assert "scan_roofline" not in got   # and no peaks in a rehearsal
