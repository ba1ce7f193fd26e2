"""Roofline share of the Pallas kernel ``flash_bwd_dkv_win`` in a Mellum step (a
window of 1024 under 16384 tokens, eight query heads a KV head) in per cent:
the least time the chip could take for one call's executed FLOPs and bytes
(``flops_mellum.step_kernel_calls``: the tiles the window leaves, against
``peaks.json``) over the time a call took, read on the busiest instruction
of that name among the trace's ten longest operations (the run of three
window layers); None where it is not among them, or on another family."""

import mellum_rooflines


def read(record):
    return mellum_rooflines.flash(record, "flash_bwd_dkv")
