"""session, parse, plan: the root's duration less the union of its
children clipped to it, per statement, median: what `Session.execute`
does under no span of its own (statement dispatch, binding capture, rows
to text, the result set, `record_stmt`); the root's own
`stmt_unattributed_ms`.  None where the program has no `executor.build`
span: before it the stretch from `plan` to `executor.open` was dark too,
and the number would not be the same quantity."""

from harness.spans import named
from harness.stats import median

from metrics.stmt_unattributed_ms import uncovered_ns


def read(run):
    per = []
    for sp in run["spans"]:
        root = [s for s in sp if s["depth"] == 0]
        if not root or not named(sp, "executor.build"):
            continue
        t0 = root[0]["start_ns"]
        per.append(uncovered_ns(t0, t0 + root[0]["dur_ns"],
                                [s for s in sp if s["depth"] == 1]) / 1e6)
    return median(per) if per else None
