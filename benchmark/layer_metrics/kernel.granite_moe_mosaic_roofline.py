"""The least time the chip could take for everything a Granite 4.0-H step
with experts' Mosaic kernels execute over the time they took
(``trace.mosaic_s``), in per cent: the chunked scans ``ssd_fwd`` /
``ssd_bwd`` at 128 heads, their ``conv_silu_*`` and ``gated_norm_*`` passes
(bytes-bound), the three flash kernels of the attention layer, every layer's
grouped products at 768 and ``moe_rows_to_tokens`` at the rows this chip
computed, each call's larger of FLOPs over the bf16 peak and bytes over the
HBM bandwidth (``flops_granite_moe.step_kernel_calls``). It needs no
kernel's name among the trace's ten operations. None on a record of another
family or without a trace."""

import granite_moe_rooflines


def read(record):
    return granite_moe_rooflines.mosaic(record)
