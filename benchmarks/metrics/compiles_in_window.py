"""mesh engine (program cache): backend compiles JAX reported inside the
window plus `copr.compile` spans that did not hit; should read 0."""

from harness.spans import named


def read(run):
    misses = sum(1 for sp in run["spans"] for s in named(sp, "copr.compile")
                 if s["attrs"].get("cache") != "hit")
    return run["compile_events"] + misses
