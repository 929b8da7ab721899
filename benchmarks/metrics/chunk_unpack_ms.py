"""mesh engine: `copr.args` (the call's runtime operands) + `copr.unpack`
(from the packed buffer to what the caller gets: hi/lo recombination and
shard merge, or `np.unpackbits` of a filter's mask) per statement, median:
the host's work inside a pass on either side of the device's."""

from harness.spans import per_statement_ms
from harness.stats import median


def read(run):
    per = per_statement_ms(run["spans"], "copr.args", "copr.unpack")
    return median(per) if per else None
