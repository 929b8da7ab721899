"""distsql ladder: devices of the mesh a statement's dispatch ran on: the
`device_ids` of its `distsql.fanout` spans (the most, where a statement
has several), median over the statements.  Under the cell's chips means a
shrunken mesh: a tripped breaker, or a table kept on fewer shards."""

from harness.spans import named
from harness.stats import median


def read(run):
    per = []
    for sp in run["spans"]:
        ids = [s["attrs"]["device_ids"] for s in named(sp, "distsql.fanout")
               if s["attrs"].get("device_ids") is not None]
        if ids:
            per.append(max(len(i) for i in ids))
    return median(per) if per else None
