"""Median milliseconds of ``train::report_wait``: inside
``session.report``, the wait for the driver to take the result and let the
loop go on. ``train.report_ms`` times the whole call from outside."""

import program_spans


def read(record):
    m = program_spans.median_seconds(record, "train::report_wait")
    return None if m is None else m * 1e3
