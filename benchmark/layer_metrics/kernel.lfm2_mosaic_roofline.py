"""The least time the chip could take for everything the step's Mosaic
kernels execute over the time they took (``trace.mosaic_s``), in per cent:
the gated short convolution's ``short_conv_fwd`` and ``short_conv_bwd``
(bytes-bound), the three flash kernels of the attention layers and the
expert layers' grouped matmuls at the rows this chip computed, each call's
larger of FLOPs over the bf16 peak and bytes over the HBM bandwidth
(``flops_lfm2.step_kernel_calls``: ``short_conv_fwd`` and the grouped
matmuls' forward twice where the block is rematerialised, the flash forward
once where its outputs are kept). It needs no kernel's name among the
trace's ten operations. None on a record of another family or without a
trace."""

import lfm2_rooflines


def read(record):
    return lfm2_rooflines.mosaic(record)
