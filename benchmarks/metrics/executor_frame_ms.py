"""root executors: the frame around the operators' work, per statement,
median: the root's `executor.build` + `executor.open` + `executor.close`,
less the time that `mpp.exchange`, `mpp.tree`, `join.build` and
`distsql.spawn` take inside `executor.open` (each has a metric of its
own).  By name and interval, not by the tree: `distsql.fanout` is a tree
child of `executor.open`, runs on another thread and outlives it.  None
where the program has no `executor.build` span."""

from harness.spans import named
from harness.stats import median

from metrics.stmt_unattributed_ms import uncovered_ns

INSIDE_OPEN = ("mpp.exchange", "mpp.tree", "join.build", "distsql.spawn")


def read(run):
    per = []
    for sp in run["spans"]:
        if not named(sp, "executor.build"):
            continue
        inside = named(sp, *INSIDE_OPEN)
        # of the open, only what none of those spans covers
        ns = sum(uncovered_ns(s["start_ns"], s["start_ns"] + s["dur_ns"],
                              inside)
                 if s["name"] == "executor.open" else s["dur_ns"]
                 for s in named(sp, "executor.build", "executor.open",
                                "executor.close") if s["depth"] == 1)
        per.append(ns / 1e6)
    return median(per) if per else None
