"""Host clock around the first call of the step (trace, lower, and compile
or load from the persistent cache, and the step itself). The cache's hits
and misses are printed beside it on the run's diagnostics line."""


def read(record):
    return record["setup"]["first_step_s"]
