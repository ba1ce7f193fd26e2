"""Pairs the learned selections kept over the causal pairs, from the
program's gauge ``ray_tpu_train_dsa_selected_share`` (counted on the device
from the selections the last recorded step made): ``sum_t min(t + 1,
index_topk)`` over ``S (S + 1) / 2`` (``flops_glm_moe_dsa.selected_share``),
or some row kept another count. None where the program has no such gauge (a
parent without the family)."""

import program_counters


def read(record):
    return program_counters.value("ray_tpu_train_dsa_selected_share")
