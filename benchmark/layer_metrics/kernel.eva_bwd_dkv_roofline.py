"""Roofline share of the Pallas kernel ``eva_bwd_dkv`` (EVA attention's dk/dv
kernel over the stacked rows, the summaries' cotangents among them: four
products a tile) in per cent: the least time the chip could take for one
call's products over the pairs EVA defines and its bytes
(``flops_evabyte.attention_call`` against ``peaks.json``; what the tiles
compute and mask beyond those pairs is not credited) over the time a call
took, read on the busiest instruction of that name among the trace's ten
longest operations; None where it is not among them."""

import eva_rooflines


def read(record):
    return eva_rooflines.kernel(record, "eva_bwd_dkv")
