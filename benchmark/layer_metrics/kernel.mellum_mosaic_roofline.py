"""The least time the chip could take for everything a Mellum step's Mosaic
kernels execute over the time they took (``trace.mosaic_s``), in per cent:
the flash kernels of the window layers (the tiles a window of 1024 leaves)
and of the full layer, and the grouped products at tokens x experts per
token rows, each call's larger of FLOPs over the bf16 peak and bytes over
the HBM bandwidth (``flops_mellum.step_kernel_calls``). It needs no
kernel's name among the trace's ten operations. None on a record of another
family or without a trace."""

import mellum_rooflines


def read(record):
    return mellum_rooflines.mosaic(record)
