"""MPP / join tree: `mpp.exchange` (or `mpp.tree`) span time per statement,
median; nothing where no statement has such a span."""

from harness.spans import per_statement_ms
from harness.stats import median


def read(run):
    per = per_statement_ms(run["spans"], "mpp.exchange", "mpp.tree")
    return median(per) if per else None
