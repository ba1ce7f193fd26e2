"""Seconds of the backend's part of the step's first call: XLA's compile on
a miss of the persistent cache; the key, the retrieval and the deserialising
on a hit."""

import program_setup


def read(record):
    return program_setup.first_call_phase("backend")
