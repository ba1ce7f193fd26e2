"""Mean share of a query's softmax sum that lay on blocks chosen by score
and not forced (the first block, the local window), from the program's gauge
``ray_tpu_train_sala_free_mass`` (a stride of query rows, every head and
sparse layer of the last recorded step): 0 would say the selection does
nothing. None where the program has no such gauge."""

import program_counters


def read(record):
    return program_counters.value("ray_tpu_train_sala_free_mass")
