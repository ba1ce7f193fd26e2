"""Median milliseconds a call of the jitted step holds the loop's thread, over
the window's ``train::step`` spans (``parallel/compile_events.py``: entry to
return of a call that found its program, the recorder of the model's scalars
inside it as ``step::record``). The device runs the step meanwhile and
after: held against the device's idle a step (``idle_gaps`` ``step``), it
says whether the gap lies before the call returned or after. None where the
program records no such span (the parent of the PR that added it)."""

import program_spans


def read(record):
    m = program_spans.median_seconds(record, "train::step")
    return None if m is None else m * 1e3
