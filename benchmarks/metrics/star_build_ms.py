"""device join reader: `star.build` span time per statement (a star's
build sides' host work: each dimension's sort, its group codes and direct
table, their upload), median over the statements that have any; nothing
on a program without the span."""

from harness.spans import per_statement_ms
from harness.stats import median


def read(run):
    per = per_statement_ms(run["spans"], "star.build")
    return median(per) if per else None
