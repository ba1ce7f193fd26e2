"""The longest over the median of the window's step intervals, an interval
the difference of two consecutive ``train::step`` entries (7 of 8 traced
steps): 1.0 and a little in a steady window, and the one long step of a run
whose process was kept off the CPU shows here and not as a spread. None
where the window holds fewer than three such spans."""

import harness
import program_spans


def read(record):
    entries = sorted(s.perf_start for s in program_spans.in_window(record)
                     if s.name == "train::step")
    intervals = [b - a for a, b in zip(entries, entries[1:])]
    if len(intervals) < 2:
        return None
    return max(intervals) / harness.median(intervals)
