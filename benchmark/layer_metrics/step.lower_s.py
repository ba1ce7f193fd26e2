"""Seconds JAX spent lowering the step's jaxpr to an MLIR module inside its
first call: where every Pallas kernel goes through Mosaic."""

import program_setup


def read(record):
    return program_setup.first_call_phase("lower")
