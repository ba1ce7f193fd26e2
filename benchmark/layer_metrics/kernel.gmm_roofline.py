"""Roofline share of the Pallas kernel ``gmm`` (a product of the expert layers' grouped matmul or its rows' cotangent;
``jax.experimental.pallas.ops.tpu.megablox`` through ``ops/moe.py``) in per
cent: the least time the chip could take for one product's FLOPs and bytes
(``flops_deepseek.grouped_matmul_layer``) over the time one took, read on the
busiest instruction of that name among the trace's ten longest operations,
the slowest of a layer's products; None where it is not among them."""

import kernel_rooflines


def read(record):
    return kernel_rooflines.grouped_matmul(record)
