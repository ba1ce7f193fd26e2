"""The least time the chip could take for everything the step's Mosaic
kernels have to do over the time they took (``trace.mosaic_s``), in per
cent: the three kernels of the attention over the selection and the
head-summed probabilities, each at its products over the selected pairs,
and the expert layers' grouped matmuls at the rows this chip computed, each
call's larger of FLOPs over the bf16 peak and bytes over the HBM bandwidth
(``flops_glm_moe_dsa.step_kernel_calls``: ``dsa_fwd``, ``dsa_probs`` and the
grouped matmuls' forward twice where the block is rematerialised). It needs
no kernel's name among the trace's ten operations. None on a record of
another family or without a trace."""

import glm_moe_dsa_rooflines


def read(record):
    return glm_moe_dsa_rooflines.mosaic(record)
