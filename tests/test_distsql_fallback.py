"""No rung answers for the device in silence.

A device rung steps down only on what copr/device_health.classify_failure
recognises as a runtime device failure.  TypeError, AttributeError, lowering
and compile errors and anything else unclassified reach the client — a silent
step down is how a shard_map keyword the installed JAX refuses went
unnoticed on every mesh dispatch.  (The micro-batch and data-plane rungs are
held to the same rule in test_serving.py and test_dataplane.py.)
"""

import os
import subprocess
import sys

import pytest

from tidb_tpu.copr import parallel
from tidb_tpu.copr.device_health import DeviceFailure
from tidb_tpu.metrics import REGISTRY
from tidb_tpu.store.fault import failpoint, once
from tidb_tpu.tpch_data import build_lineitem

Q6 = ("select sum(l_extendedprice * l_discount) from lineitem"
      " where l_discount between 0.05 and 0.07 and l_quantity < 24")
FILTER = "select l_orderkey from lineitem where l_quantity < 2"
REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture(scope="module")
def sess():
    s = build_lineitem(8192, regions=4)
    s.execute("set tidb_use_tpu = 0")
    s.oracle = {q: s.query(q) for q in (Q6, FILTER)}
    s.execute("set tidb_use_tpu = 1")
    return s


def _cnt(name):
    return REGISTRY.snapshot().get(name, 0)


@pytest.fixture
def no_mesh(monkeypatch):
    """The mesh declines (a plan choice, no error): the tile rung serves."""
    monkeypatch.setattr(parallel, "try_run_mesh", lambda storage, req: None)


def test_classified_failure_on_the_tile_rung_falls_back_and_counts(
        sess, no_mesh):
    before = _cnt("cop_tasks_device_fallback_total")
    with failpoint("distsql/task_error", once(DeviceFailure("chip 3 died"))):
        assert sess.query(Q6) == sess.oracle[Q6]
    assert _cnt("cop_tasks_device_fallback_total") == before + 1


@pytest.mark.parametrize("exc", [
    TypeError("shard_map() got an unexpected keyword argument"),
    AttributeError("module 'jax' has no attribute 'nope'"),
    NotImplementedError("Strided store with non 32-bit data"),
    RuntimeError("a bare runtime error names no device"),
], ids=lambda e: type(e).__name__)
def test_unclassified_exception_on_the_tile_rung_reaches_the_client(
        sess, no_mesh, exc):
    before = _cnt("cop_tasks_device_fallback_total")
    with failpoint("distsql/task_error", once(exc)):
        with pytest.raises(type(exc)):
            sess.query(Q6)
    assert _cnt("cop_tasks_device_fallback_total") == before


def test_classified_failure_on_the_mesh_rung_steps_down_and_counts(
        sess, monkeypatch):
    def sick(storage, req):
        raise DeviceFailure("device 2 halted")

    monkeypatch.setattr(parallel, "try_run_mesh", sick)
    before = _cnt("mesh_scan_errors_total")
    assert sess.query(Q6) == sess.oracle[Q6]
    assert _cnt("mesh_scan_errors_total") == before + 1


def _quiet(name):
    """The counter once it has stood still for a tenth of a second: a
    producer thread that an earlier test of this worker left draining
    its region tasks must not be counted against this one."""
    import time

    seen = _cnt(name)
    while True:
        time.sleep(0.1)
        if _cnt(name) == seen:
            return seen
        seen = _cnt(name)


def test_unclassified_exception_on_the_mesh_rung_reaches_the_client(
        sess, monkeypatch):
    def broken(*a, **kw):
        raise TypeError("shard_map() got an unexpected keyword argument")

    # the real regression: the program BUILDER raises on every dispatch
    monkeypatch.setattr(parallel, "_build_mesh_core", broken)
    fresh = parallel.ProgramCache("mesh")
    # not in /status's registry: a second "mesh" entry left there would
    # hide the real cache from every later test of this worker
    parallel.PROGRAM_CACHES.remove(fresh)
    monkeypatch.setattr(parallel, "_COMPILED", fresh)
    before = (_cnt("mesh_scan_errors_total"), _quiet("cop_tasks_total"))
    with pytest.raises(TypeError, match="unexpected keyword"):
        sess.query(Q6)
    assert (_cnt("mesh_scan_errors_total"), _cnt("cop_tasks_total")) == before


def test_unclassified_exception_in_the_mesh_stream_reaches_the_client(
        sess, monkeypatch):
    """Filter results stream lazily: an error before the first chunk used to
    step down too."""
    def stream(storage, req):
        def gen():
            raise AttributeError("'NoneType' object has no attribute 'x'")
            yield  # pragma: no cover
        return gen()

    monkeypatch.setattr(parallel, "try_run_mesh", stream)
    with pytest.raises(AttributeError):
        sess.query(FILTER)
    # and a classified failure before the first chunk still steps down
    def sick_stream(storage, req):
        def gen():
            raise DeviceFailure("device 1 lost")
            yield  # pragma: no cover
        return gen()

    monkeypatch.setattr(parallel, "try_run_mesh", sick_stream)
    assert sorted(sess.query(FILTER)) == sorted(sess.oracle[FILTER])


@pytest.mark.parametrize("env_dir", [None, "/tmp/placed-from-outside"],
                         ids=["unset", "set"])
def test_compile_cache_dir_can_be_placed_from_outside(env_dir):
    """With JAX_COMPILATION_CACHE_DIR set tidb_tpu.ops sets no directory in
    code (jax reads the variable itself); unset, the cache is
    <checkout>/.jax_cache."""
    env = {k: v for k, v in os.environ.items()
           if k != "JAX_COMPILATION_CACHE_DIR"}
    env["JAX_PLATFORMS"] = "cpu"
    if env_dir is not None:
        env["JAX_COMPILATION_CACHE_DIR"] = env_dir
    code = ("import jax; before = jax.config.jax_compilation_cache_dir;"
            "import tidb_tpu.ops;"
            "print(before); print(jax.config.jax_compilation_cache_dir)")
    out = subprocess.run([sys.executable, "-c", code], env=env, cwd=REPO,
                         capture_output=True, text=True, timeout=120)
    assert out.returncode == 0, out.stderr[-2000:]
    before, after = out.stdout.split()[-2:]
    if env_dir is None:
        assert after == os.path.join(REPO, ".jax_cache")
    else:
        assert before == after == env_dir
