"""The busiest expert's assignments over the mean, worst expert layer of
the last step the program recorded: its gauge
``ray_tpu_train_moe_expert_load_max_over_mean``. It tells a slow run from a
differently routed one: the grouped matmuls see the same rows in all, but
the largest group grows with it."""

import program_counters


def read(record):
    return program_counters.value(
        "ray_tpu_train_moe_expert_load_max_over_mean")
