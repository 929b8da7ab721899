"""device join reader: `join.build` span time per statement (the build
side's host work between the MPP result and the probe program: sort,
upload), median; nothing where no statement has such a span."""

from harness.spans import per_statement_ms
from harness.stats import median


def read(run):
    per = per_statement_ms(run["spans"], "join.build")
    return median(per) if per else None
