"""mesh engine: `mesh.result` per statement, median: from the dispatch's
unpacked result to the chunks the fan-out hands on (accumulate, chunks,
the delta rows, the TopN merge, the host tail).  None where the program
has no such span."""

from harness.spans import per_statement_ms
from harness.stats import median


def read(run):
    per = per_statement_ms(run["spans"], "mesh.result")
    return median(per) if per else None
