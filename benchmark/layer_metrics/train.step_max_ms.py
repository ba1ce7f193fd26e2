"""The longest host-clock step of the window (dispatch to the loss read
back), in milliseconds: beside a device step that repeats to the
millisecond, what is over is the host stalling the loop."""


def read(record):
    steps = record["spans"].get("step", [])
    return max(t1 - t0 for t0, t1 in steps) * 1e3 if steps else None
