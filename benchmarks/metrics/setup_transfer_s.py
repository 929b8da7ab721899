"""set-up: seconds statements waited for columns that were not resident
on the device (the wall time of every such `mesh.columns` interval) over
the whole process, from the program's `mesh_column_load_seconds_total`:
the set-up's once every column is resident.  None where the program has
no such counter."""


def read(run):
    from tidb_tpu.metrics import REGISTRY

    return REGISTRY.snapshot().get("mesh_column_load_seconds_total")
