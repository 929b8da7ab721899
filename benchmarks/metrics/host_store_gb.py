"""set-up: gigabytes the host's table stores hold in their base blocks
once loaded, from the program's `store_base_bytes` gauge (every live
store's blocks as held, set at the end of each bulk load and
compaction).  None where the program has no such gauge."""


def read(run):
    from tidb_tpu.metrics import REGISTRY

    v = REGISTRY.snapshot().get("store_base_bytes")
    return None if v is None else v / 1e9
