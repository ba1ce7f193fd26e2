"""Roofline share of the Pallas kernel ``selective_scan_bwd`` (Mamba-1's
selective scan backward: the chunks in reverse, each chunk's states formed
again, six cotangents) in per cent: the least time the chip could take for
one call's bytes (``flops_phi4flash.selective_scan_call`` against
``peaks.json``: ``xs``, ``delta``, ``dy`` read and two cotangents written,
bfloat16) over the time a call took, read on the busiest instruction of that
name among the trace's ten longest operations (the self pairs' run); None
where it is not among them."""

import phi4flash_rooflines


def read(record):
    return phi4flash_rooflines.kernel(record, "selective_scan_bwd")
