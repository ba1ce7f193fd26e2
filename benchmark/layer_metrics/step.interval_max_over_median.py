"""The longest over the median of the window's step intervals: 1.0 and a
little in a steady window, and the one long step of a run whose process was
kept off the CPU shows here and not as a spread. Where the loop sends steps
ahead (``ahead_s``), an interval is the difference of two consecutive ends
of the runner's ``wait`` spans, the reads of the losses, which end with
their steps while the device is behind the loop (the entries of
``train::step`` then come in a burst while the queue fills); else the
difference of two consecutive ``train::step`` entries (7 of 8 traced
steps). None where the window holds fewer than three such spans."""

import harness
import program_spans


def read(record):
    waits = record.get("spans", {}).get("wait")
    if waits:
        marks = sorted(t1 for _, t1 in waits)
    else:
        marks = sorted(s.perf_start for s in program_spans.in_window(record)
                       if s.name == "train::step")
    intervals = [b - a for a, b in zip(marks, marks[1:])]
    if len(intervals) < 2:
        return None
    return max(intervals) / harness.median(intervals)
