"""Share of the traced window in which a collective (all-gather,
reduce-scatter, all-reduce, all-to-all, collective-permute) ran or was in
flight on a device, averaged over the devices. Hidden behind compute or
not: telling the two apart is for the tracing issue."""


def read(record):
    trace = record.get("trace") or {}
    if not trace.get("window_s") or record["cell"]["chips"] < 2:
        return None
    return trace["collective_s"] / trace["window_s"]
