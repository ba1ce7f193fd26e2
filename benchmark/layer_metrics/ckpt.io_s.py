"""Seconds of a save in ``ckpt::write`` (the backend's write, fsync and
rename of the shard file), median over the window's saves. ``ckpt.write_s``
is this plus the manifest commit, from the program's histogram."""

import program_spans


def read(record):
    return program_spans.phase_seconds(record, "ckpt::write")
