"""Median milliseconds the loop is blocked in ``session.report`` (the
round trip to the driver's result gather), over the window."""

import harness


def read(record):
    m = harness.median(t1 - t0 for t0, t1 in
                       record["spans"].get("report", []))
    return None if m is None else m * 1e3
