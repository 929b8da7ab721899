"""Device kernels (jax).

Importing this package configures jax for the framework:
- x64 enabled: SQL semantics need int64 handles/sums and float64 agg
  accumulation (XLA emulates 64-bit on TPU; elementwise hot loops below keep
  32-bit types where safe and widen only at the reduction boundary).
"""

import os

import jax
import jax.monitoring

from ..metrics import REGISTRY

jax.config.update("jax_enable_x64", True)

# Persistent compilation cache.  Where JAX_COMPILATION_CACHE_DIR is set jax
# reads it itself and no directory is set here; otherwise the cache lives in
# <checkout>/.jax_cache (untracked) — a fixed path, because the path is part
# of the cache key.
if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
    jax.config.update(
        "jax_compilation_cache_dir",
        os.path.join(os.path.dirname(os.path.dirname(os.path.dirname(
            os.path.abspath(__file__)))), ".jax_cache"),
    )
jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.5)


def _on_duration(event: str, secs: float, **_kw):
    # the recorder is looked up at the event (a compile), not here: this
    # package is imported by modules the trace package itself imports
    from ..trace.recorder import note_compile

    note_compile(event, secs)


# the program's one jax.monitoring listener: what XLA compiles cost, as
# counters (there from the start, at 0) and on the span that compiled
# (trace/recorder.py::note_compile)
for _name in ("xla_compile_seconds_total", "xla_compiles_total"):
    REGISTRY.inc(_name, 0.0)
jax.monitoring.register_event_duration_secs_listener(_on_duration)

from .segment import (  # noqa: E402
    UNROLL_G,
    masked_segment_sum,
    masked_segment_count,
    masked_segment_min,
    masked_segment_max,
    masked_segment_argfirst,
    segment_min,
    prefix_counts,
    prefix_max,
    prefix_sums,
    first_marked,
)
from .topk import masked_top_k  # noqa: E402

__all__ = [
    "UNROLL_G",
    "masked_segment_sum",
    "masked_segment_count",
    "masked_segment_min",
    "masked_segment_max",
    "masked_segment_argfirst",
    "segment_min",
    "prefix_counts",
    "prefix_max",
    "prefix_sums",
    "first_marked",
    "masked_top_k",
]
