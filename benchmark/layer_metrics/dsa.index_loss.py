"""The indexers' own loss ``L_I`` in the last recorded step, from the
program's gauge ``ray_tpu_train_dsa_index_loss``: the KL of the main
attention's head-summed probabilities over the selection against the softmax
of the indexer's scores there, mean over rows, summed over the layers that
own an indexer. It falls as the indexers learn to rank keys as the main
attention weighs them. None where the program has no such gauge."""

import program_counters


def read(record):
    return program_counters.value("ray_tpu_train_dsa_index_loss")
