"""Test harness configuration.

Tests run on CPU with 8 virtual XLA devices so every multi-chip sharding path
(mesh creation, shard_map scans, psum merges) executes without TPU hardware —
the moral equivalent of the reference testing the whole distributed stack
against in-process mocktikv (store/mockstore/tikv.go:100).
"""

import os

# Small device tiles so ordinary test tables (a few thousand rows) span
# multiple tiles AND multiple mesh shards — the cross-tile merge, deletion
# masks beyond tile 0, and shard_map collective paths all execute under test.
os.environ.setdefault("TIDB_TPU_TILE", "1024")

# Run the whole suite under the lock-order witness (ISSUE 16): every
# make_lock/make_rlock returns a RankedLock that raises on rank
# inversion.  Must be set before tidb_tpu is imported anywhere — the
# factories read it at lock construction time.
os.environ.setdefault("TIDB_TPU_LOCKCHECK", "1")

# Must be set before jax is imported anywhere.
os.environ["JAX_PLATFORMS"] = "cpu"
_flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in _flags:
    os.environ["XLA_FLAGS"] = (
        _flags + " --xla_force_host_platform_device_count=8"
    ).strip()

# Pin the config too: unit tests never touch a chip.
import jax  # noqa: E402

jax.config.update("jax_platforms", "cpu")

import numpy as np  # noqa: E402
import pytest  # noqa: E402


@pytest.fixture(scope="session")
def rng():
    return np.random.default_rng(42)


@pytest.fixture(autouse=True)
def _no_leaked_failpoints():
    """A failpoint left armed by one test silently injects faults into
    every later test — fail the LEAKING test, not its victims.  Use the
    scoped `with failpoint(name, action):` manager (store/fault.py) to
    make disarm structural."""
    from tidb_tpu.store.fault import FAILPOINTS

    yield
    leaked = FAILPOINTS.armed()
    if leaked:
        FAILPOINTS.clear()
        pytest.fail(f"test leaked armed failpoints: {leaked}")


@pytest.fixture(autouse=True)
def _no_lock_order_violations():
    """The witness raises LockOrderError at the acquire site, but a
    violation swallowed by a broad except (RPC boundaries, hook
    dispatch) still counts — fail the test that produced it."""
    from tidb_tpu.util_concurrency import witness_stats

    before = witness_stats()["violations"]
    yield
    after = witness_stats()["violations"]
    if after > before:
        pytest.fail(
            f"lock-order witness recorded {after - before} violation(s)"
            " during this test (TIDB_TPU_LOCKCHECK)")
