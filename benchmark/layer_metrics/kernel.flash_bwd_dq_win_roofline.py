"""Roofline share of the Pallas kernel ``flash_bwd_dq_win`` (a window layer's
``flash_bwd_dq``) in per cent: the least time the chip could take for one
call's executed FLOPs and bytes (``flops_afmoe.flash_call``: the tiles the
window leaves, against ``peaks.json``) over the time a call took, read on
the busiest instruction of that name among the trace's ten longest
operations (the longest run of window layers); None where it is not among
them."""

import window_rooflines


def read(record):
    return window_rooflines.flash(record, "flash_bwd_dq")
