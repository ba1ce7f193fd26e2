"""Median device milliseconds of one run of the step program, from the
trace's ``XLA Modules`` line (first device operation of a step to its
last), over the traced steps."""

import harness


def read(record):
    m = harness.median((record.get("trace") or {}).get("steps_device_s", []))
    return None if m is None else m * 1e3
