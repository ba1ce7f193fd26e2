"""The least time the chip could take for everything a Nemotron-H step's
Mosaic kernels execute over the time they took (``trace.mosaic_s``), in per
cent: the grouped chunked scans ``ssd_fwd`` / ``ssd_bwd`` (``C B^T`` once a
chunk and group), their ``conv_silu_*`` and grouped ``gated_norm_*`` passes
(bytes-bound), the three flash kernels of the attention layer, the expert
layers' grouped products at 1856 and ``moe_rows_to_tokens`` at the rows this
chip computed, each call's larger of FLOPs over the bf16 peak and bytes over
the HBM bandwidth (``flops_nemotron_h.step_kernel_calls``). It needs no
kernel's name among the trace's ten operations. None on a record of another
family or without a trace."""

import nemotron_rooflines


def read(record):
    return nemotron_rooflines.mosaic(record)
