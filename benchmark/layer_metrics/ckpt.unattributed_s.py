"""Seconds of a save that no phase accounts for: ``train::report_sharded``
less what its child spans cover, median over the window's saves."""

import program_spans


def read(record):
    return program_spans.self_seconds(record)
