"""Of the hidden activations the held squared-ReLU experts computed, the
share the ReLU zeroed, the expert layers' mean of the last step the program
recorded: its gauge ``ray_tpu_train_moe_relu2_zero_share``. About a half at
random weights, 0 if the ReLU were missing: what a grouped product that
skipped zero columns of a row could save of the down product. None where
the program has no such gauge (SwiGLU experts; a parent without the
family)."""

import program_counters


def read(record):
    return program_counters.value("ray_tpu_train_moe_relu2_zero_share")
