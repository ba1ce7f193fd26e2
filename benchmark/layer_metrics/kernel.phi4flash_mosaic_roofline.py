"""The least time the chip could take for everything the step's Mosaic
kernels execute over the time they took (``trace.mosaic_s``), in per cent:
the selective scan's ``selective_scan_fwd`` / ``selective_scan_bwd`` and the
short convolution's ``conv_silu_fwd`` / ``conv_silu_bwd`` (bytes-bound) and
the flash kernels of the window, the full and the cross layers at 64 | 128,
each call's larger of FLOPs over the bf16 peak and bytes over the HBM
bandwidth (``flops_phi4flash.step_kernel_calls``: the scan's and the
convolution's forward twice where the block is rematerialised, the flash
forward once where its outputs are kept). It needs no kernel's name among
the trace's ten operations. None on a record of another family or without a
trace."""

import phi4flash_rooflines


def read(record):
    return phi4flash_rooflines.mosaic(record)
