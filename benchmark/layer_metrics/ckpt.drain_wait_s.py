"""Seconds of a save's stall spent waiting for the writer of the save
before it (``ckpt::drain_wait``, a direct child of
``train::report_sharded`` on the loop's thread), median over the window's
saves: 0 while the writer keeps up with ``save_every`` steps; what a
slower one gives back to the stall. A program that writes inside the stall
opens no such span and reads None."""

import program_spans


def read(record):
    return program_spans.phase_seconds(record, "ckpt::drain_wait")
