"""What is left of the step's first call when tracing, lowering and the
backend's part are taken out: jit's bookkeeping, argument checks, the
program's load where the runtime defers it, the dispatch. The call returns
before the device has run the step: that wait is the caller's and stays in
``step.compile_s``."""

import program_setup


def read(record):
    whole = program_setup.stage_seconds("first_call")
    if whole is None:
        return None
    return whole - sum(program_setup.first_call_phase(phase)
                       for phase in program_setup.PHASES)
