"""Roofline share of the Pallas kernel ``dsa_bwd_dkv`` in a ``dots3_note`` step (a
full layer's attention over its selection at 128 heads of 192 | 128) in per
cent: the least time the chip could take for one call's products over the
**selected** pairs and its bytes (``flops_dots3_note.attention_call``
against ``peaks.json``; the masked form also computes the pairs of a tile
that are not selected, which the count does not credit) over the time a call
took, read on the busiest instruction of that name among the trace's ten
longest operations; None where it is not among them."""

import dots3_rooflines


def read(record):
    return dots3_rooflines.kernel(record, "dsa_bwd_dkv")
