"""The least time the chip could take for everything the step's Mosaic
kernels have to do over the time they took (``trace.mosaic_s``), in per
cent: the four kernels of the full layers' attention over the selection at
their products over the selected pairs, the three of the window layers'
attention at theirs over the window's pairs, and the expert layers' grouped
matmuls at the rows this chip computed, each call's larger of FLOPs over the
bf16 peak and bytes over the HBM bandwidth
(``flops_dots3_note.step_kernel_calls``). It needs no kernel's name among
the trace's ten operations. None on a record of another family or without a
trace."""

import dots3_rooflines


def read(record):
    return dots3_rooflines.mosaic(record)
