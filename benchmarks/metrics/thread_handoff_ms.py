"""distsql ladder: the two hand-offs of the GIL a fan-out makes, per
statement, median: `distsql.spawn` (from before `Thread.start()` to the
producer thread's first line) + `distsql.wake` (from the producer's `put`
to the consumer's return from `get`).  None where the program has no such
span."""

from harness.spans import per_statement_ms
from harness.stats import median


def read(run):
    per = per_statement_ms(run["spans"], "distsql.spawn", "distsql.wake")
    return median(per) if per else None
