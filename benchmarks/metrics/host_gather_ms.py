"""distsql, streamed filter: `copr.select` (`np.flatnonzero` of the mask)
+ `copr.gather` (`gather_chunk` slices) + `copr.tail` (a peeled host tail)
per statement, median: the producer thread's finishing of a filter pass."""

from harness.spans import per_statement_ms
from harness.stats import median


def read(run):
    per = per_statement_ms(run["spans"], "copr.select", "copr.gather",
                           "copr.tail")
    return median(per) if per else None
