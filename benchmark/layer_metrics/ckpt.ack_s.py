"""Seconds of a save in its ack: the ``train::report`` nested in
``train::report_sharded`` (the shard record handed to the driver, and the
wait until the driver lets the loop go on), median over the saves."""

import program_spans


def read(record):
    return program_spans.phase_seconds(record, "train::report")
