"""device join reader: megabytes a star's build sides send to the
devices a statement: the `bytes` of its `star.build` spans of phase
`upload` (each a dimension's code table, summed over the devices it went
to), summed a statement, median over the statements that have any;
nothing on a program whose upload spans do not name their `devices`."""

from harness.spans import named
from harness.stats import median


def read(run):
    per = []
    for sp in run["spans"]:
        up = [s["attrs"] for s in named(sp, "star.build")
              if s["attrs"].get("phase") == "upload"
              and s["attrs"].get("devices") is not None]
        if up:
            per.append(sum(int(a.get("bytes") or 0) for a in up) / 1e6)
    return median(per) if per else None
