"""mesh engine: full passes over the resident table per statement, median:
`copr.chunk` spans, else `copr.device.execute` spans."""

from harness.spans import named
from harness.stats import median


def read(run):
    per = []
    for sp in run["spans"]:
        if sp:
            n = len(named(sp, "copr.chunk")) or len(
                named(sp, "copr.device.execute"))
            per.append(n)
    return median(per) if per else None
