"""Roofline share of the Pallas kernel ``kda_fwd`` (the delta rule's chunked
forward) in per cent: the least time the chip could take for one call's
executed FLOPs and bytes (``flops_kimi_linear.kda_call`` against
``peaks.json``) over the time a call took, read on the busiest instruction
of that name among the trace's ten longest operations (the longest run of
KDA layers); None where it is not among them."""

import kda_rooflines


def read(record):
    return kda_rooflines.kernel(record, "kda_fwd")
