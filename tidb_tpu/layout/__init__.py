"""Workload-adaptive data layout engine + compressed device cold tier.

"Fine-Tuning Data Structures for Analytical Query Processing" (PAPERS.md)
argues that storage representations should be CHOSEN from the observed
workload, not hard-coded; "Query Processing on Tensor Computation
Runtimes" shows tensor backends only reach peak when operand encodings
match the kernels.  This subsystem applies both to the TPU coprocessor:

- **Autotuner** (`autotuner.py`): observes per-column access patterns
  from the planes earlier PRs built — scan frequency from the mesh
  column loads, predicate selectivity from the statistics feedback
  plane, agg-key vs probe-key usage from the fragment analysis — and
  CHOOSES a per-column device layout: dictionary vs direct encoding,
  packed code width, device-cache residency priority, and the table's
  tile-size bucket (padded shape classes, an eighth of a power of two
  apart, vs exact tiling when HBM is scarce).

- **Cold tier** (`coldtier.py`): tables larger than the hot-tier byte
  cap stay queryable — cold columns live ON DEVICE as compressed blocks
  (bit-packed dictionary codes, 1/2/4/8 bits per row) and decode
  IN-REGISTER inside the fused kernel (`copr/fusion.decode_packed`), so
  a cold-tier hit is still exactly one `copr.device.execute` with no
  host->device transfer.  `ByteCapCache` evictions are value-weighted:
  the lowest-priority column demotes to the cold tier before anything
  is dropped outright.

Layout VALUES ride runtime operands (the dictionary-value vectors are
dispatch arguments, kernelcheck-guarded), so re-tuning that keeps a
column's layout CLASS moves no fingerprints and recompiles nothing;
class changes (packed-width/tier/tiling) may refingerprint and are
rate-limited by the tuner (`TIDB_TPU_LAYOUT_RETUNE_S`).

`TIDB_TPU_LAYOUT=0` restores the fixed layout (everything hot, byte-LRU
eviction) — the bench's comparator.
"""

from __future__ import annotations

import os

from .autotuner import LAYOUT, ColumnPlan  # noqa: F401
from .coldtier import (  # noqa: F401
    COLD_CACHE,
    ColdColumn,
    DECOMPRESS_FAILPOINT,
    compress_column,
)


def layout_enabled() -> bool:
    """Adaptive-layout switch (TIDB_TPU_LAYOUT=0 restores the fixed
    hot-only layout — the bench's fixed-layout comparator)."""
    return os.environ.get("TIDB_TPU_LAYOUT", "1") != "0"


def layout_epoch() -> int:
    """Monotonic layout-decision generation: bumps whenever any column's
    layout CLASS changes.  Plan-cache keys carry it, so a re-tune
    invalidates cached plans instead of serving a stale cost choice."""
    return LAYOUT.epoch


#: the share of the devices' memory the hot tier may fill.  Half: beside
#: the resident columns a chip holds the all-true row masks (as long as
#: the columns, a byte a row), the cold tier, join payloads, and the
#: programs' own temporaries, which for a flat-view program are several
#: full-length arrays; on one v5e chip it comes to 8.45 GB, where the
#: constant it replaces said 8 GiB for any number of chips
HOT_SHARE = 0.5
_DEVICE_CAP = None


def hot_cap_bytes() -> int:
    """Hot-tier (mesh column cache) byte cap — the pressure signal the
    autotuner's residency decisions key off, and `parallel.MESH_CACHE`'s
    capacity.  `TIDB_TPU_HBM_BYTES` where set; else HOT_SHARE of what the
    process's devices say they hold (`bytes_limit`, summed: a table is
    sharded over all of them), 8 GiB where they say nothing (the CPU
    backend)."""
    global _DEVICE_CAP
    env = os.environ.get("TIDB_TPU_HBM_BYTES")
    if env:
        return int(env)
    if _DEVICE_CAP is None:
        import jax

        limits = [(d.memory_stats() or {}).get("bytes_limit")
                  for d in jax.local_devices()]
        _DEVICE_CAP = (int(HOT_SHARE * sum(limits))
                       if limits and all(limits) else 8 << 30)
    return _DEVICE_CAP


def set_hot_cap_bytes(n: int):
    """Test/embedder knob: move the hot cap at runtime (updates the live
    MESH_CACHE and the autotuner's pressure signal together)."""
    os.environ["TIDB_TPU_HBM_BYTES"] = str(int(n))
    from ..copr.parallel import MESH_CACHE

    MESH_CACHE._c.capacity = int(n)
    LAYOUT.invalidate_plans()


def status_section() -> dict:
    """The /status "layout" payload: decisions + tier byte gauges."""
    from ..copr.parallel import MESH_CACHE
    from ..metrics import LAYOUT_STATUS_METRICS, REGISTRY

    snap = REGISTRY.snapshot()
    return {
        "enabled": layout_enabled(),
        "epoch": LAYOUT.epoch,
        "hot_cap_bytes": hot_cap_bytes(),
        "hot_bytes": MESH_CACHE._c._bytes,
        "cold_bytes": COLD_CACHE._bytes,
        "columns": LAYOUT.decisions_snapshot(),
        "metrics": {
            name: snap.get(name, 0) for name in LAYOUT_STATUS_METRICS
        },
    }
