"""``init_train_state``, the whole call: the state's layout, tracing and
compiling (or loading) ``init``, dispatching it. The wait for the arrays is
the caller's."""

import program_setup


def read(record):
    return program_setup.stage_seconds("state_init")
