"""session, parse, plan: `parse` + `plan` span time per statement, median."""

from harness.spans import per_statement_ms
from harness.stats import median


def read(run):
    per = per_statement_ms(run["spans"], "parse", "plan")
    return median(per) if per else None
