"""Seconds of a save in ``ckpt::gather`` (``np.asarray`` of each leaf: device
to host), summed over the leaves, median over the window's saves."""

import program_spans


def read(record):
    return program_spans.phase_seconds(record, "ckpt::gather")
