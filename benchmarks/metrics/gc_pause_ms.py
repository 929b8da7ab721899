"""server, wire: pauses of Python's collector inside the traced statements:
`py.gc` span time summed over all of them, over their number.  A mean:
pauses are rare and a median would read 0.  None where the program has no
`py_gc_collections_total` counter (it installs no collector callback, so
no pause would be seen)."""

from harness.spans import named


def read(run):
    from tidb_tpu.metrics import REGISTRY

    traced = [sp for sp in run["spans"] if sp]
    if not traced or "py_gc_collections_total" not in REGISTRY.snapshot():
        return None
    return sum(s["dur_ns"] for sp in traced
               for s in named(sp, "py.gc")) / 1e6 / len(traced)
