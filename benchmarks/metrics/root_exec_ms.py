"""root executors: the sum of `self_ms` over the `ops` the top-level
`executor.next` spans carry ([plan_id, operator, rows, loops, self_ms] from
the program's OperatorStats), the reader operators left out (theirs is the
wait for the cop result), per statement, median: the root's `Selection`,
aggregate and projection on the statement's own thread."""

from harness.spans import named
from harness.stats import median


def read(run):
    per = []
    for sp in run["spans"]:
        ops = [op for s in named(sp, "executor.next") if s["depth"] == 1
               for op in s["attrs"].get("ops") or ()]
        if ops:
            per.append(sum(op[4] for op in ops if "Reader" not in op[1]))
    return median(per) if per else None
