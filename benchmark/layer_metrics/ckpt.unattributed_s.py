"""Seconds of a save's stall that no phase accounts for:
``train::report_sharded`` less what its child spans on the loop's own
thread cover (the writer's run beside the next steps and are no part of the
stall), median over the window's saves."""

import program_spans


def read(record):
    return program_spans.self_seconds(record)
