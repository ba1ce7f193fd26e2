"""Expert-layer calls whose assignments to the held experts fit the one
buffer of the program's static bound over expert-layer calls in all: the
program's ``ray_tpu_train_moe_calls_within_bound_total`` over its
``ray_tpu_train_moe_calls_total`` (expert layers x microbatches, per step).
1.0, or some call's routing gave the held experts more than twice their even
share and took further buffers: still every assignment computed
(``moe.assigned_share``), at more than one buffer's cost. Both counters are
fed together by every step of the process, the warm-up step included. None
where the program feeds none (a parent without the bound; a model that holds
every expert in a family that has no share)."""

import program_counters


def read(record):
    within = program_counters.value(
        "ray_tpu_train_moe_calls_within_bound_total")
    calls = program_counters.value("ray_tpu_train_moe_calls_total")
    if within is None or not calls:
        return None
    return within / calls
