"""bench.py reliability (ISSUE 8 satellite, ROADMAP carried item).

The driver's BENCH runs have repeatedly zeroed out on late-run wedges
(device preflight flakes, load hangs at a big scale) even though earlier
scales completed.  These tests drive bench's scale loop with stubbed
phases and assert the crash-insurance contract: every COMPLETED scale's
receipt survives an injected late-scale failure, both in the worker
state (what `emit` serializes) and in the BENCH_PARTIAL.json file."""

import json
import sys
import time
from pathlib import Path

import pytest

sys.path.insert(0, str(Path(__file__).resolve().parent.parent))

import bench  # noqa: E402


class _StubSession:
    """Just enough session surface for bench's scale loop."""

    def execute(self, *a, **k):
        return [type("R", (), {"rows": []})()]

    def query(self, *a, **k):
        return []


@pytest.fixture
def stubbed(monkeypatch, tmp_path):
    monkeypatch.setenv("BENCH_PARTIAL_PATH", str(tmp_path / "partial.json"))
    monkeypatch.setattr(bench, "MAX_ROWS", 4_000_000)
    # small enough that the device-heavy phases (q3 join needs >180s
    # remaining, mpp join >150s) gate themselves off; big enough that
    # every scale in the stubbed loop still runs (gate: 35% remaining)
    monkeypatch.setattr(bench, "WALL_LIMIT", 140.0)
    monkeypatch.setattr(bench, "T0", time.perf_counter())
    monkeypatch.setattr(bench, "build_lineitem", lambda n: _StubSession())
    monkeypatch.setattr(bench, "time_query",
                        lambda s, q, iters: (0.1, 0.05))
    monkeypatch.setattr(bench, "fusion_bench",
                        lambda s, n: {"stub": True})
    return tmp_path


def test_partial_receipts_survive_injected_late_scale_failure(
        stubbed, monkeypatch):
    monkeypatch.setenv("BENCH_FAIL_AT_SCALE", "1048576")
    state: dict = {}
    bench._run(state)
    # the wedge surfaced, it did not zero the receipts
    assert "injected late-scale failure" in state.get("worker_error", "")
    done = [sc["rows"] for sc in state.get("scales", [])]
    assert done == [262_144], state
    # the per-scale receipt also landed on disk before the wedge
    data = json.loads((stubbed / "partial.json").read_text())
    assert [sc["rows"] for sc in data["scales"]] == [262_144]
    assert data["scales"][0]["q1_rows_per_sec"] > 0
    # and emit() keeps the completed scales in the detail payload
    import contextlib
    import io

    buf = io.StringIO()
    with contextlib.redirect_stdout(buf):
        bench.emit(state)
    out = json.loads(buf.getvalue())
    assert out["detail"]["scales"] and out["value"] > 0


def test_all_scales_complete_without_injection(stubbed):
    state: dict = {}
    bench._run(state)
    assert "worker_error" not in state
    assert [sc["rows"] for sc in state.get("scales", [])] == [
        262_144, 1_048_576, 4_000_000]
    data = json.loads((stubbed / "partial.json").read_text())
    assert len(data["scales"]) == 3


def test_leg_errors_finds_every_recorded_error():
    """The legs catch their own exceptions so later legs still run; the
    exit code must still say that one failed."""
    assert bench.leg_errors({"q1": {"rows": 1}, "phases": {"a": 1.0}}) == []
    state = {
        "q1": {"rows": 1},
        "layout": {"error": "RuntimeError('x')"},
        "scales": [{"rows": 1}, {"rows": 2, "fusion": {"error": "boom"}}],
        "tpch_matrix": {"queries": {"q8": {"class": "host", "error": "e8"}}},
        "worker_error": "KeyError('k')",
    }
    assert sorted(w for w, _ in bench.leg_errors(state)) == [
        "layout", "scales[1].fusion", "tpch_matrix.queries.q8", "worker"]


def test_main_fails_without_a_tpu_and_prints_no_result(monkeypatch, capsys):
    """A measurement path that finds no chip fails; no CPU number is ever
    printed under the metric's name."""
    monkeypatch.setattr(bench.signal, "signal", lambda *a: None)
    assert bench.main() == 2
    assert capsys.readouterr().out == ""


def test_emit_names_the_device(stubbed):
    import contextlib
    import io

    state: dict = {"device": {"platform": "tpu", "kind": "TPU v5 lite",
                              "count": 1}}
    bench._run(state)
    buf = io.StringIO()
    with contextlib.redirect_stdout(buf):
        bench.emit(state)
    out = json.loads(buf.getvalue())
    assert out["device"] == state["device"] and out["value"] > 0
