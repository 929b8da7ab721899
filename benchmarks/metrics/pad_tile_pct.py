"""mesh engine: the share of the tiles a statement's mesh dispatches
scanned that hold no row: `tiles` (tiles of the table with a row in them)
against `tiles_padded` (the tile count the shards were laid out for) on
its `distsql.fanout` spans, both summed over the statement's dispatches,
median over the statements.  Every such tile is scanned, masked, at full
price.  A program whose `distsql.fanout` carries no such attributes gives
nothing."""

from harness.spans import named
from harness.stats import median


def read(run):
    per = []
    for sp in run["spans"]:
        laid = [s["attrs"] for s in named(sp, "distsql.fanout")
                if s["attrs"].get("tiles_padded")]
        if laid:
            tiles = sum(a["tiles"] for a in laid)
            padded = sum(a["tiles_padded"] for a in laid)
            per.append(100.0 * (1.0 - tiles / padded))
    return median(per) if per else None
