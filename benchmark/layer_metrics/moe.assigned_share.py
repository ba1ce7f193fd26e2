"""Assignments the expert layers computed over the assignments the routing
asked for: the program's ``ray_tpu_train_moe_assignments_total`` over its
``ray_tpu_train_moe_tokens_total`` (tokens x experts per token x expert
layers). 1.0 exactly, or an assignment was dropped: the engagement counter
of "dropless". Both counters are fed together by every step of the process,
the warm-up step included, so the ratio is the window's too."""

import program_counters


def read(record):
    assigned = program_counters.value("ray_tpu_train_moe_assignments_total")
    asked = program_counters.value("ray_tpu_train_moe_tokens_total")
    if assigned is None or not asked:
        return None
    return assigned / asked
