"""Median milliseconds a step waits for its batch: ``next`` on
``iter_jax_batches`` plus the transfer to the device (a fingerprint of the
batch is read back inside the span)."""

import harness


def read(record):
    if record["cell"]["traffic"].get("data") != "dataset":
        return None
    m = harness.median(t1 - t0 for t0, t1 in
                       record["spans"].get("data", []))
    return None if m is None else m * 1e3
