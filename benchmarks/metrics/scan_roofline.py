"""kernels: the least time the chip could take for the statements of the
window (bytes each must read, from the query and configuration files, over
the chip's peak HBM bandwidth) over the device-busy time inside those
statements, from the profiler trace.  Bound by bandwidth: the scans do a
few integer operations a byte."""


def read(run):
    dev = run["device_trace"]
    if not dev or not dev["stmt_busy_s"] or not run["peaks"]:
        return None
    busy = sum(dev["stmt_busy_s"])
    if busy <= 0:
        return None
    least = sum(run["stmt_bytes"]) / run["peaks"]["hbm_bytes_per_s"]
    return 100.0 * least / busy
