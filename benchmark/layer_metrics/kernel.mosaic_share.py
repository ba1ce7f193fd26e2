"""Share of the device's busy time in Mosaic (Pallas) kernels, by their
custom-call target: ``trace.mosaic_s`` over ``trace.busy_s``. In a hybrid
state-space model's step that is the chunked scans and the flash kernels,
the work that is not plain matmul."""


def read(record):
    trace = record.get("trace") or {}
    if not trace.get("busy_s") or trace.get("mosaic_s") is None:
        return None
    return trace["mosaic_s"] / trace["busy_s"]
