"""The least time the chip could take for everything the step's Mosaic
kernels have to do over the time they took (``trace.mosaic_s``), in per
cent: block-sparse attention's three kernels at their products over the
pairs the selection defines, the linear-attention recurrence's two and the
gated norm's two, each call's larger of FLOPs over the bf16 peak and bytes
over the HBM bandwidth (``flops_minicpm_sala.step_kernel_calls``: a forward
kernel twice a layer where the block is rematerialised and its outputs are
not kept). It needs no kernel's name among the trace's ten operations. None
on a record of another family or without a trace."""

import sala_rooflines


def read(record):
    return sala_rooflines.mosaic(record)
