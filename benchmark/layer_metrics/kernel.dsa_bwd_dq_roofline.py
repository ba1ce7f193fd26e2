"""Roofline share of the Pallas kernel ``dsa_bwd_dq`` (dq of the attention over
a selection: q k^T, dO v^T and ds k under the selection's tile) in per cent:
the least time the chip could take for one call's products over the
**selected** pairs and its bytes (``flops_glm_moe_dsa.attention_call``
against ``peaks.json``; the masked form also computes the pairs of a tile
that are not selected, which the count does not credit) over the time a call
took, read on the busiest instruction of that name among the trace's ten
longest operations (the run of layers that share a selection); None where it
is not among them."""

import glm_moe_dsa_rooflines


def read(record):
    return glm_moe_dsa_rooflines.kernel(record, "dsa_bwd_dq")
