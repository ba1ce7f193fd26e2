"""The share of the routing's assignments that fell on experts held here:
the program's ``ray_tpu_train_moe_tokens_total`` over its
``ray_tpu_train_moe_routed_total`` (tokens x experts per token x expert
layers). 8 of 256 experts held is 0.031 under even routing: how much of the
routing's work this chip's share gets. None where the program has no such
counter (a model that holds every expert feeds none)."""

import program_counters


def read(record):
    held = program_counters.value("ray_tpu_train_moe_tokens_total")
    routed = program_counters.value("ray_tpu_train_moe_routed_total")
    if held is None or not routed:
        return None
    return held / routed
