"""Mean share of a query's softmax sum that lay on chunk summaries in the
last recorded step, over queries, heads and layers, from the program's gauge
``ray_tpu_train_eva_summary_mass`` (the forward kernel keeps the summary
tiles' part of its running sum beside the sum): how much of the attention
looks past its window. None where the program has no such gauge (a parent
without the family)."""

import program_counters


def read(record):
    return program_counters.value("ray_tpu_train_eva_summary_mass")
