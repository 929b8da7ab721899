"""mesh engine: `copr.dispatch.wait` per statement, median: from asking for
`DISPATCH_LOCK` to holding it, summed over the statement's passes.  About 0
with one client; the number a cell with several clients is read for."""

from harness.spans import per_statement_ms
from harness.stats import median


def read(run):
    per = per_statement_ms(run["spans"], "copr.dispatch.wait")
    return median(per) if per else None
