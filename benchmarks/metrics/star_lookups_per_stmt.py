"""mesh engine: lookup joins chained inside a statement's mesh programs:
the `lookups` of its `copr.chunk` spans summed, mean over the statements
that have any such attribute; nothing on a program whose `copr.chunk`
carries none."""


def read(run):
    per = []
    for sp in run["spans"]:
        n = [int(s["attrs"]["lookups"]) for s in sp
             if s["name"] == "copr.chunk"
             and s["attrs"].get("lookups") is not None]
        if n:
            per.append(sum(n))
    return sum(per) / len(per) if per else None
