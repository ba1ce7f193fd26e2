"""Seconds JAX spent tracing inside the step's first call: the step to a
jaxpr, with every jitted function it calls."""

import program_setup


def read(record):
    return program_setup.first_call_phase("trace")
