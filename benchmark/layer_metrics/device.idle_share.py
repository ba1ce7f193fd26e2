"""One minus the union of the device's operations over the traced window,
averaged over the devices."""


def read(record):
    trace = record.get("trace") or {}
    if not trace.get("window_s"):
        return None
    return 1.0 - trace["busy_s"] / trace["window_s"]
