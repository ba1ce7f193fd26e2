"""Tile pairs of the sparse attention kernels' causal table in which any
query of any KV group selected a key, over the table, from the program's
gauge ``ray_tpu_train_sala_live_tile_share`` (the last recorded step's
selection): 1 minus it is what a kernel that left out empty tiles' steps
could skip; data-dependent. None where the program has no such gauge."""

import program_counters


def read(record):
    return program_counters.value("ray_tpu_train_sala_live_tile_share")
