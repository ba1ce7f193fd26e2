"""The mean over heads, tokens and layers of the sigmoid gate a head on the
attention's output in the window layers of a ``dots3_note`` step, from the
program's gauge ``ray_tpu_train_attn_gate_mean`` (the series tagged
``window``, set from the last recorded step): about a half at random weights;
exactly 0.5 or 1 would be a gate that is dropped or dead. None where the
program has no such gauge (a parent without the family)."""

import dots3_rooflines


def read(record):
    return dots3_rooflines.gate_mean("window")
