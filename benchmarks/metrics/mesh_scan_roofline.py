"""kernels: `scan_roofline` for a table sharded over a mesh: the least
time the mesh's chips could take for the statements of the window (bytes
each must read, from the query and configuration files, over the traced
devices' count times one chip's peak HBM bandwidth: every chip reads its
share at once) over the device-busy time inside those statements on the
first device, from the profiler trace.  Bound by bandwidth."""


def read(run):
    dev = run["device_trace"]
    if not dev or not dev.get("stmt_busy_s") or not dev.get("devices") \
            or not run["peaks"]:
        return None
    busy = sum(dev["stmt_busy_s"])
    if busy <= 0:
        return None
    least = sum(run["stmt_bytes"]) / (
        dev["devices"] * run["peaks"]["hbm_bytes_per_s"])
    return 100.0 * least / busy
