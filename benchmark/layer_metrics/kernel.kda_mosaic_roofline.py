"""The least time the chip could take for everything the step's Mosaic
kernels execute over the time they took (``trace.mosaic_s``), in per cent:
the delta rule's ``kda_fwd`` and ``kda_bwd``, the three flash kernels of
the latent layers and the expert layers' grouped matmuls at the rows this
chip computed, each call's larger of FLOPs over the bf16 peak and bytes over
the HBM bandwidth (``flops_kimi_linear.step_kernel_calls``: ``kda_fwd`` and
the grouped matmuls' forward twice where the block is rematerialised, the
flash forward once). It needs no kernel's name among the trace's ten
operations. None on a record of another family or without a trace."""

import kda_rooflines


def read(record):
    return kda_rooflines.mosaic(record)
