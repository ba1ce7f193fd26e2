"""Share of the device's busy time in Mosaic (Pallas) kernels, found by
their custom-call target since the kernels carry no names yet."""


def read(record):
    trace = record.get("trace") or {}
    if not trace.get("busy_s"):
        return None
    return trace["mosaic_s"] / trace["busy_s"]
