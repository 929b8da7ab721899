"""mesh engine: sums of a statement that left the device as limb sums and
were put together on the host as Python integers, because their bound
passes int64: `wide_sums` summed over the statement's `copr.unpack` spans,
median over the statements.  A program whose `copr.unpack` carries no such
attribute gives nothing."""

from harness.spans import named
from harness.stats import median


def read(run):
    per = []
    for sp in run["spans"]:
        wide = [s["attrs"]["wide_sums"] for s in named(sp, "copr.unpack")
                if s["attrs"].get("wide_sums") is not None]
        if wide:
            per.append(sum(int(w) for w in wide))
    return median(per) if per else None
