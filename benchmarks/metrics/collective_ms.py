"""mesh engine: time of the collective operations (all-reduce, all-gather,
all-to-all, reduce-scatter, collective-permute, by the HLO instruction's
name) in the traced window, averaged over the traced devices, per
statement completed: what merging the shards' partial states on the device
costs a statement.  They run inside the jitted program, so no span of the
program sees them.  0 on a trace that holds none (a mesh of one)."""

COLLECTIVES = ("all-reduce", "all-gather", "all-to-all", "reduce-scatter",
               "collective-permute")


def read(run):
    dev = run["device_trace"]
    done = sum(1 for st in run["statements"] if st.ok)
    if not dev or not dev.get("devices") or not done \
            or "op_time" not in dev:
        return None
    total = sum(t for name, t in dev["op_time"].items()
                if name.startswith(COLLECTIVES))
    return 1e3 * total / dev["devices"] / done
