"""Roofline share of one call of ``ssd_bwd`` (``ops/ssd.py``: the chunked
Mamba-2 scan at 64 heads of 64 in 8 B/C groups, a head block a group) on a
Nemotron-H cell's [batch, 16384] tokens, in per cent: the products a grid
step makes (``flops_nemotron_h.ssd_call``: ``C B^T`` once a chunk and
group) over the bf16 peak, or its operands and results once over the HBM
bandwidth, whichever is larger, over the time a call took, read on the
busiest ``ssd_bwd`` instruction among the trace's ten longest operations
(the scan over the longest run of units); None where none is among them, or
on another family."""

import nemotron_rooflines


def read(record):
    return nemotron_rooflines.ssd(record, "ssd_bwd")
