"""Seconds of a save's ``ckpt::commit`` on the driver's thread (manifest
write, index, and the pruning of the checkpoint it replaces): the cost that
runs beside the loop, after the stall. Median over the window's saves."""

import program_spans


def read(record):
    return program_spans.commit_seconds(record)
