"""distsql ladder: share of the statements attempted that the device rung
the traffic file expects served alone: every `distsql.fanout` and
`mpp.exchange` span names an expected rung, no task re-ran elsewhere, and
no fallback counter moved in the window."""

from harness.spans import named


def read(run):
    expect = set(run["mix"]["expect_rungs"])
    if not any(run["spans"]):
        return None
    good = 0
    for sp in run["spans"]:
        fan = named(sp, "distsql.fanout")
        rungs = [s["attrs"].get("scan_engine") for s in fan]
        rungs += ["mpp-" + str(s["attrs"].get("rung"))
                  for s in named(sp, "mpp.exchange")]
        fell = sum(int(s["attrs"].get("fallback_tasks", 0)) for s in fan)
        if rungs and set(rungs) <= expect and not fell:
            good += 1
    good = max(0, good - int(sum(run["fallbacks_moved"].values())))
    return 100.0 * good / len(run["spans"])
