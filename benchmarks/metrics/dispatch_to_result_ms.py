"""mesh engine: `copr.device.execute` + `copr.readback` span time per
statement, median.  Host clock from enqueue to packed result: neither span
alone is device time (the first returns at enqueue, the wait lands in the
second); only their sum means something."""

from harness.spans import per_statement_ms
from harness.stats import median


def read(run):
    per = per_statement_ms(run["spans"], "copr.device.execute",
                           "copr.readback")
    return median(per) if per else None
