"""Milliseconds by which the latest of the window's ``host::tick`` spans woke
late: the continuous profiler's 10 Hz tick in the benchmark's process
(``_private/profiling.py``), a span's duration its lateness. A fraction of a
millisecond where the process ran when it asked to; a tick more than 20 ms
late carries its ``cause``. None where the program records no such span."""

import program_spans


def read(record):
    late = [s.duration for s in program_spans.in_window(record)
            if s.name == "host::tick"]
    return max(late) * 1e3 if late else None
