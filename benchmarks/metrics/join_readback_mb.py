"""device join reader: megabytes a statement's join programs hand back to
the host: the `bytes` of the `copr.readback` spans under `mpp.exchange` /
`mpp.tree` and under a `distsql.fanout` that carries a lookup join (its
`join` attribute), per statement, median; nothing where no statement has
such a span."""

from harness.stats import median

JOIN_SPANS = ("mpp.exchange", "mpp.tree")


def _is_join(span) -> bool:
    return span["name"] in JOIN_SPANS or (
        span["name"] == "distsql.fanout" and bool(span["attrs"].get("join")))


def read(run):
    per = []
    for sp in run["spans"]:
        # `sp` is a pre-order walk: a span's descendants follow it until
        # the depth comes back to its own
        total, seen, under = 0, False, None
        for s in sp:
            if under is not None and s["depth"] <= under:
                under = None
            if under is None:
                if _is_join(s):
                    under = s["depth"]
                continue
            if s["name"] == "copr.readback":
                seen = True
                total += int(s["attrs"].get("bytes") or 0)
        if seen:
            per.append(total / 1e6)
    return median(per) if per else None
