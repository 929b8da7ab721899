"""set-up: seconds XLA spent compiling (tracing, lowering, backend compile
or cache read) over the whole process, from the program's
`xla_compile_seconds_total`: the set-up's while `compiles_in_window` reads
0.  None where the program has no such counter."""


def read(run):
    from tidb_tpu.metrics import REGISTRY

    return REGISTRY.snapshot().get("xla_compile_seconds_total")
