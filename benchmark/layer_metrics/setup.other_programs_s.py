"""Seconds JAX spent making the programs that are neither the step nor
``init``, all phases summed: the reference check's, the checksum's, the
data split's, the eager operations' (and, in a cell that saves, the
read-back's after the window: ``program_setup.py``)."""

import program_setup


def read(record):
    return program_setup.other_programs_seconds()
