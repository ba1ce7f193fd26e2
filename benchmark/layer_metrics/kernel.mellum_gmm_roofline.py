"""Roofline share of one grouped product of a Mellum expert layer (the
``megablox`` kernel ``gmm`` at tokens x experts per token rows, 2304 x 896
or 896 x 2304) in per cent: ``2 rows d f`` over the bf16 peak, or the rows'
operand and result and the experts' weights once over the HBM bandwidth,
whichever is larger (``flops_mellum.grouped_matmul_call``), over the time a
call took, read on the busiest ``gmm`` instruction among the trace's ten
longest operations (the slowest product of the longest run of layers); None
where none is among them, or on another family."""

import mellum_rooflines


def read(record):
    return mellum_rooflines.grouped_matmul(record)
