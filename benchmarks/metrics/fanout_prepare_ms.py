"""mesh engine: what the producer thread does before the device can start,
per statement, median: `distsql.route` (the rungs that declined) +
`mesh.analyze` (the DAG, the fusion plan, the hoisted parameters, the
layout) + `mesh.columns` (the lookup of every column the program reads,
the MPP join's sides included) + `mesh.program` (fingerprint and program
cache) + `mesh.delta` (delta overlay, deletion mask, bounds).  None where
the program has no such span."""

from harness.spans import per_statement_ms
from harness.stats import median


def read(run):
    per = per_statement_ms(run["spans"], "distsql.route", "mesh.analyze",
                           "mesh.columns", "mesh.program", "mesh.delta")
    return median(per) if per else None
