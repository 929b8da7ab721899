"""mesh engine: `copr.device.execute` (the enqueue) + `copr.device.wait`
(`block_until_ready`, ends when the device has finished) per statement,
median: the device's part of a statement as the host sees it, without the
copy that `dispatch_to_result_ms` includes.  None where the program has no
`copr.device.wait` span."""

from harness.spans import named, per_statement_ms
from harness.stats import median


def read(run):
    if not any(named(sp, "copr.device.wait") for sp in run["spans"]):
        return None
    return median(per_statement_ms(
        run["spans"], "copr.device.execute", "copr.device.wait"))
