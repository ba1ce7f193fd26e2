"""Seconds of a save in ``ckpt::checksum`` (two ``zlib.crc32`` passes over
each leaf's bytes), summed, median over the window's saves."""

import program_spans


def read(record):
    return program_spans.phase_seconds(record, "ckpt::checksum")
