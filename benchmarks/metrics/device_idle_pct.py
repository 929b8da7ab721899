"""device: 1 minus the union of device-operation intervals over the traced
window."""


def read(run):
    dev = run["device_trace"]
    if not dev or dev["window_s"] <= 0:
        return None
    return 100.0 * (1.0 - dev["busy_s"] / dev["window_s"])
