"""chip_smoke.py's phases, in-process at 8,192 rows on the CPU harness.

The smoke is the proof that the served path starts on the chip; this file is
the proof that the smoke cannot pass on a lower rung: a mesh program that
raises must fail it — the regression that hid the shard_map keyword the
installed JAX refuses.
"""

import os
import sys

import pytest

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import chip_smoke  # noqa: E402

ROWS = 8192
Q3_ROWS = (8192, 2048)


@pytest.fixture(scope="module")
def report():
    """One rehearsal of the one-chip phases: (problems, printed JSON lines)."""
    import contextlib
    import io
    import json

    smoke = chip_smoke.Smoke()
    out = io.StringIO()
    with contextlib.redirect_stdout(out):
        chip_smoke.run_one_chip(smoke, ROWS, Q3_ROWS)
    lines = [json.loads(ln) for ln in out.getvalue().splitlines()]
    return smoke.problems, {ln["query"]: ln for ln in lines if "query" in ln}


def test_every_query_reports_a_device_rung(report):
    problems, queries = report
    assert problems == []
    assert set(queries) == {"q1", "q6", "q3"}
    for name, q in queries.items():
        assert q["rung"] and set(q["rung"]) == {"mesh"}, (name, q["rung"])
        assert q["engine"] == ["mesh"], (name, q["engine"])
        assert q["parity"] and q["rows"] > 0, (name, q)
    assert queries["q3"]["plan"] == ["DeviceJoinReader"]
    assert queries["q3"]["served_counter"] == {"mesh_scans_total": [2.0, 2.0]}


def test_fallback_counters_did_not_move(report):
    _, queries = report
    for name, q in queries.items():
        assert not any(q["fallback_counters_moved"].values()), (name, q)
        assert set(q["fallback_counters_moved"]) == set(
            chip_smoke.FALLBACK_COUNTERS)
        assert q["second_run_compile"] == "hit", (name, q)


def test_broken_mesh_program_fails_the_smoke(monkeypatch):
    """A mesh builder that raises TypeError (what a shard_map keyword the
    installed JAX refuses did) must fail the smoke, not pass on the tile rung."""
    from tidb_tpu.copr import parallel

    def broken(*a, **kw):
        raise TypeError("shard_map() got an unexpected keyword argument")

    monkeypatch.setattr(parallel, "_build_mesh_core", broken)
    # the rehearsal above left the programs in the cache: start cold
    fresh = parallel.ProgramCache("mesh")
    # not in /status's registry: a second "mesh" entry left there would
    # hide the real cache from every later test of this worker
    parallel.PROGRAM_CACHES.remove(fresh)
    monkeypatch.setattr(parallel, "_COMPILED", fresh)
    smoke = chip_smoke.Smoke()
    with pytest.raises(chip_smoke.SmokeFailed, match="TypeError|shard_map"):
        chip_smoke.run_one_chip(smoke, ROWS, Q3_ROWS)


def test_lower_rung_answer_is_a_failed_check(monkeypatch):
    """Were a rung below the mesh to answer (mesh ineligible, no error), the
    rung check alone fails the run."""
    from tidb_tpu.copr import parallel

    monkeypatch.setattr(parallel, "try_run_mesh", lambda storage, req: None)
    smoke = chip_smoke.Smoke()
    chip_smoke.run_one_chip(smoke, ROWS, Q3_ROWS)
    assert any("served by ['tile-fanout']" in p for p in smoke.problems)
    assert any("mesh_scans_total moved by" in p for p in smoke.problems)


def test_no_tpu_no_ok_line(capsys, monkeypatch):
    monkeypatch.setattr(sys, "argv", ["chip_smoke.py", "--rows", str(ROWS)])
    assert chip_smoke.main() != 0
    assert '"ok"' not in capsys.readouterr().out


def test_compare_rows_exact_for_decimals_relative_for_doubles():
    cmp = chip_smoke._compare_rows
    assert cmp([246, 5], [("1.10", "2.0000000000001")],
               [("1.10", "2.0")]) is None
    assert cmp([246, 5], [("1.10", "2.1")], [("1.10", "2.0")]) is not None
    assert cmp([246], [("1.10",)], [("1.1",)]) is not None
    assert cmp([8], [("1",)], []) is not None
    assert cmp([8], [(None,)], [(None,)]) is None


def test_four_chip_phases_on_the_virtual_mesh():
    """`--chips 4`'s phases over the harness's eight virtual devices: sharded
    lineitem, psum-merged Q1/Q6, and the MPP shuffle join served by its
    exchange with `mpp_joins_total` moving."""
    import contextlib
    import io
    import json

    import jax

    from tidb_tpu.copr.parallel import MESH_CACHE

    # the smoke's process is new; this worker's may hold arrays that a
    # failover test (test_chaos.py) sharded over its seven survivors
    MESH_CACHE.clear()
    smoke = chip_smoke.Smoke()
    out = io.StringIO()
    with contextlib.redirect_stdout(out):
        chip_smoke.run_four_chip(smoke, 16384, (16384, 4096),
                                 len(jax.devices()))
    assert smoke.problems == []
    lines = [json.loads(ln) for ln in out.getvalue().splitlines()]
    join = next(ln for ln in lines if ln.get("query") == "mpp-shuffle-join")
    assert join["plan"] == ["ExchangeSender"]
    assert join["rung"] == ["mpp-shuffle"] and join["parity"]
    assert min(join["served_counter"]["mpp_joins_total"]) > 0
    sizes = next(ln for ln in lines if "device_set_sizes" in ln)
    assert sizes["device_set_sizes"] == [len(jax.devices())]
