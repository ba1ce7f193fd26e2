"""Of a token's router probability (a softmax over all the experts), the
share its picked experts hold before the weights are renormalised, the mean
over tokens and expert layers of the last step the program recorded: its
gauge ``ray_tpu_train_moe_picked_mass``. experts per token over experts
(0.125) is a flat router, 1.0 one whose picks hold everything: it tells how
much a routing flip between two precisions moves the comparison, and
changes nothing in the rows moved. None where the program has no such gauge
(a sigmoid router; a parent without the family)."""

import program_counters


def read(record):
    return program_counters.value("ray_tpu_train_moe_picked_mass")
