"""(Query, key or summary) pairs EVA attention's tables cover over the causal
pairs, from the program's gauge ``ray_tpu_train_eva_pairs_share`` (counted
from the table and mask the last recorded step's kernels were traced with):
``flops_evabyte.pairs_share``, 0.12112 at 32768 with a window of 2048 and
chunks of 16, or the tables are wrong. None where the program has no such
gauge (a parent without the family)."""

import program_counters


def read(record):
    return program_counters.value("ray_tpu_train_eva_pairs_share")
