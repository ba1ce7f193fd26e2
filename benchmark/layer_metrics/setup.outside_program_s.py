"""``setup_s`` less every stage the program observed and every program JAX
reported outside one: ``import jax`` and the TPU client's start, the
reference check's run, the first step's run on the device, whatever is
nobody's. A true remainder: ``within`` keeps a second from being counted
twice, so the stages, ``setup.other_programs_s`` and this sum to
``setup_s``."""

import program_setup


def read(record):
    stages = program_setup.outermost_stages_seconds()
    if stages is None:
        return None
    return record["setup"]["setup_s"] - stages \
        - program_setup.other_programs_seconds()
