"""Executed FLOPs of the step's Mosaic kernels over what the chip's bf16
peak could do in the time they took, in per cent: the three flash kernels
(causal tiles counted once, the forward twice where the block is
rematerialised) and the expert layers' grouped matmuls ``gmm`` and ``tgmm``
(``flops_deepseek.step_kernel_flops``), against ``trace.mosaic_s`` per
step."""

import kernel_rooflines


def read(record):
    return kernel_rooflines.mosaic(record)
