"""mesh engine: the share of the lookup joins chained inside the traced
statements' mesh programs that compared the probe key with every build
key instead of gathering: the `compares` of their `copr.chunk` spans
summed, over the `lookups` summed, times 100; nothing where no span
carries `compares` (a program without the compare form)."""


def read(run):
    compares = lookups = 0
    seen = False
    for sp in run["spans"]:
        for s in sp:
            if s["name"] != "copr.chunk" \
                    or s["attrs"].get("compares") is None:
                continue
            seen = True
            compares += int(s["attrs"]["compares"])
            lookups += int(s["attrs"].get("lookups") or 0)
    return 100.0 * compares / lookups if seen and lookups else None
