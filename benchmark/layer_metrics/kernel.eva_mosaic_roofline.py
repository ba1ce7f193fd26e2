"""The least time the chip could take for everything the step's Mosaic
kernels have to do over the time they took (``trace.mosaic_s``), in per
cent: EVA attention's three kernels, each at its products over the pairs EVA
defines, each call's larger of FLOPs over the bf16 peak and bytes over the
HBM bandwidth (``flops_evabyte.step_kernel_calls``: ``eva_fwd`` twice a
layer where the block is rematerialised and its outputs are not kept). It
needs no kernel's name among the trace's ten operations. None on a record of
another family or without a trace."""

import eva_rooflines


def read(record):
    return eva_rooflines.mosaic(record)
