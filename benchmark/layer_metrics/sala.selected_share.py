"""(Query, key) pairs block-sparse attention attended over the causal pairs,
from the program's gauge ``ray_tpu_train_sala_selected_share`` (counted from
the last recorded step's selection itself, the mean over the sparse layers):
``flops_minicpm_sala.selected_share``, 0.43460 at 16384 with the top 64
blocks of 64, or the selection keeps another number of blocks than it
says. None where the program has no such gauge (a parent without the
family)."""

import program_counters


def read(record):
    return program_counters.value("ray_tpu_train_sala_selected_share")
