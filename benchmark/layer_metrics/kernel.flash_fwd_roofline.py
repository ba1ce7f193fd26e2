"""Roofline share of the Pallas kernel ``flash_fwd`` in per cent: the least
time the chip could take for one call's executed FLOPs and bytes
(``flops_deepseek.flash_call`` against ``peaks.json``) over the time a
call took, read on the busiest instruction of that name among the trace's
ten longest operations (the expert layers' scan); None where it is not
among them."""

import kernel_rooflines


def read(record):
    return kernel_rooflines.flash(record, "flash_fwd")
