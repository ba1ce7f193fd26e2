"""Seconds of a save in ``ckpt::copy`` (per leaf: the rank's slice made
contiguous, then ``tobytes``), summed, median over the window's saves."""

import program_spans


def read(record):
    return program_spans.phase_seconds(record, "ckpt::copy")
