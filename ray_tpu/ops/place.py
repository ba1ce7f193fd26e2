"""Slices of S placed into one array at offsets known only on the chip.

``place_slices([n arrays or trees of them], slots, axis)`` is the array n
times as long along ``axis`` whose block ``slots[t]`` there is the t-th
slice:
what a ``concatenate`` is when the order is static. A ring exchange over a
mesh axis (``models/exchange.py: gathered_product``) hands a chip the slices
of S in an order that starts at its own place on the ring, so the order is
data.
XLA's forms of it cost three to ten passes over the result on a v5e (zeros
and one ``dynamic_update_slice`` a slice: 1.40 ms for 134 MB, and the
updates do not fuse into the matmuls that make the slices; a ``select`` a
block: 0.61); this kernel is n DMAs from HBM to HBM, one pass (0.45 ms,
where a static ``concatenate`` takes 0.42; my chip run, PR 32).
"""

from __future__ import annotations

import importlib
from typing import Any, Sequence

import jax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def place_slices(slices: Sequence[Any], slots: jax.Array, axis: int = 1):
    """slices: n trees of arrays, one tree structure, every array with its
    slice of S along ``axis``; slots: int32 [n], a permutation of
    0 .. n - 1 -> the tree of arrays with ``axis`` n times as long. One
    kernel for the whole tree (q, k and v of a layer together: a kernel
    more in a program is a tenth of a second more to load it).
    Operands and results are row-major, as every kernel's are: place along
    the axis that lies where the producer's layout has S (a [b, h, s, k]
    product's axis 2), or XLA copies every slice into row-major order and
    the result back out of it."""
    n = len(slices)
    leaves = [jax.tree.leaves(tree) for tree in slices]
    lead = (slice(None),) * axis
    # One answer to "is there a TPU to compile for" for every kernel here:
    # the flash kernels', which the off-chip compiles steer.
    flash = importlib.import_module("ray_tpu.ops.flash_attention")

    def kernel(slots_ref, *refs):
        wholes, done = refs[n * width:-1], refs[-1]
        copies = []
        for t in range(n):
            for i, whole in enumerate(wholes):
                source = refs[t * width + i]
                rows = source.shape[axis]
                copies.append(pltpu.make_async_copy(
                    source, whole.at[lead + (pl.ds(pl.multiple_of(
                        slots_ref[t] * rows, rows), rows),)],
                    done.at[t * width + i]))
        for copy in copies:
            copy.start()
        for copy in copies:
            copy.wait()

    width = len(leaves[0])
    placed = pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1, grid=(),
            in_specs=[pl.BlockSpec(memory_space=pl.ANY)] * (n * width),
            out_specs=[pl.BlockSpec(memory_space=pl.ANY)] * width,
            scratch_shapes=[pltpu.SemaphoreType.DMA((n * width,))]),
        out_shape=[jax.ShapeDtypeStruct(
            leaf.shape[:axis] + (n * leaf.shape[axis],)
            + leaf.shape[axis + 1:], leaf.dtype) for leaf in leaves[0]],
        interpret=flash._interpret(), name="place_slices",
    )(slots, *[leaf for step in leaves for leaf in step])
    return jax.tree.unflatten(jax.tree.structure(slices[0]), placed)
