"""A block's tensor-parallel traffic as exchanges of slices of S: the part of
``models/lm.py`` ("Where S lives on a mesh") that only a block run per shard
of tp uses, ``models/gpt.py``'s today. Like ``lm``, it is no model and
imports none.

A block whose weights are split over tp (q / k / v / ``w_in`` by columns,
``wo`` / ``w_out`` by rows) takes a residual stream that is split over tp
too, along S. It needs every row for its column-split products and owes
every chip the sum of the row-split ones on that chip's rows: an
all-gather and a reduce-scatter of [B, S, d], which as two instructions
nothing hides. The helpers below write them as a ring of
``ppermute``s of one slice of S (1 / tp of the rows) each, between which
the products on the slice in hand run, in the manner of
``ops/ring_attention.py``'s K/V rotation. All run per shard, inside a
``shard_map`` that binds ``axis_name`` (``exchanged_over_tp`` is it).
Autodiff transposes a ``ppermute`` into the reverse ``ppermute``, so the
recomputed forward and the backward pass are exchanges of the same kind.

Ring step t = 0 .. tp - 1: chip c has in hand slice ``(c - t) % tp`` of
S, its own first. ``gathered_product`` and ``ring_split`` give their
results in that order; ``ring_place`` takes its slices and
``scattered_product`` asks for its products in it.
"""

from __future__ import annotations

import math
from functools import partial

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec

from ray_tpu.models.lm import per_shard
from ray_tpu.parallel.sharding import _spec_dim_axes as _axes, \
    ambient_spec, constrain


def _ring(axis_name):
    """(chips on the ring, this chip's place, every chip to the next)."""
    n = jax.lax.psum(1, axis_name)
    return n, jax.lax.axis_index(axis_name), [(j, (j + 1) % n)
                                              for j in range(n)]


def gathered_product(rows, products, axis_name: str = "tp"):
    """``[[product(slice) for product in products] for every slice of S]``
    in the ring's order, from this chip's own ``rows`` [b, S / tp, ...].

    The slice in hand goes on to the next chip before the last product on
    it and after the others: put the longest last. The chip's transfers
    queue behind one another, and a collective it waits for (the gather
    of a weight that the partitioner starts one matmul ahead, the
    synchronous one of a scan body's first matmul) then waits for the slice
    in flight too: 0.65-1.3 ms each where the slice takes 1.6 (my chip
    runs, PR 32). So the slice flies beside one product that is long
    enough, whose own weights are there when it starts."""
    n, _, onward = _ring(axis_name)
    parts = []
    for step in range(n):
        first = [product(rows) for product in products[:-1]]
        if first and step < n - 1:
            rows, first = jax.lax.optimization_barrier((rows, first))
        coming = jax.lax.ppermute(rows, axis_name, onward) \
            if step < n - 1 else None
        last = products[-1](rows)
        if coming is not None:
            coming, last = jax.lax.optimization_barrier((coming, last))
        parts.append(first + [last])
        rows = coming
    return parts


def _slots(axis_name):
    """int32 [tp]: the slice of S this chip has in hand at each ring step."""
    n, my, _ = _ring(axis_name)
    return (my - jnp.arange(n, dtype=jnp.int32)) % n


def _placed(parts, axis, axis_name):
    from ray_tpu.ops.place import place_slices
    place = partial(place_slices, axis=axis)
    slots = _slots(axis_name)
    inside = jax.sharding.get_abstract_mesh()
    if len(inside.manual_axes) == len(inside.axis_names):
        return place(parts, slots)
    # The batch is still the partitioner's, and it cannot cut a kernel:
    # per shard of the axes that are left, as the flash kernels run.
    rows = jax.tree.map(
        lambda _: PartitionSpec(ambient_spec(inside, "batch")[0]),
        list(parts))
    return per_shard(place, inside, (rows, PartitionSpec()), rows[0])(
        list(parts), slots)


def _split(whole, axis, axis_name):
    n, my, _ = _ring(axis_name)
    return [jax.tree.map(lambda a: jax.lax.dynamic_slice_in_dim(
        a, ((my - step) % n) * (a.shape[axis] // n), a.shape[axis] // n,
        axis=axis), whole) for step in range(n)]


@partial(jax.custom_vjp, nondiff_argnums=(1, 2))
def ring_place(parts, axis: int = 1, axis_name: str = "tp"):
    """A list of per-slice arrays (or trees of them: one kernel places q, k
    and v) in the ring's order -> the array with ``axis`` (where they have
    their slice of S) tp times as long, every slice at its own offset, in
    one pass (``ops/place.py``: the offsets
    are known only on the chip, and XLA's ``dynamic_update_slice`` into
    zeros is three passes that fuse into nothing). Its cotangent is
    ``ring_split``'s result, and the reverse."""
    return _placed(parts, axis, axis_name)


ring_place.defvjp(
    lambda parts, axis, axis_name: (_placed(parts, axis, axis_name), None),
    lambda axis, axis_name, _, whole: (_split(whole, axis, axis_name),))


@partial(jax.custom_vjp, nondiff_argnums=(1, 2))
def ring_split(whole, axis: int = 1, axis_name: str = "tp"):
    """An array (or a tree of them) with S whole along ``axis`` -> the
    list of its slices in the ring's order: element ``step`` is the slice
    this chip has in hand at that ring step. Reads where they lie; the
    cotangent is one ``ring_place``."""
    return _split(whole, axis, axis_name)


ring_split.defvjp(
    lambda whole, axis, axis_name: (_split(whole, axis, axis_name), None),
    lambda axis, axis_name, _, parts: (_placed(parts, axis, axis_name),))


@partial(jax.custom_vjp, nondiff_argnums=(2,))
def _sent_on(part, after, axis_name):
    return jax.lax.ppermute(part, axis_name, _ring(axis_name)[2])


def _sent_on_fwd(part, after, axis_name):
    return _sent_on(part, after, axis_name), after


def _sent_on_bwd(axis_name, after, arrived):
    # The cotangent goes back the way the partial sum came, and not before
    # ``after`` is there. A barrier none of whose results is used is
    # dropped with the order it states: ``after``'s zero cotangent is made
    # from the barrier's (one pass over ``after``: name something small).
    arrived, after = jax.lax.optimization_barrier((arrived, after))
    back = [(to, frm) for frm, to in _ring(axis_name)[2]]
    one = after[(0,) * after.ndim]
    zero = 0 * jnp.where(jnp.isfinite(one), one, 0)
    return (jax.lax.ppermute(arrived, axis_name, back),
            jnp.broadcast_to(zero, after.shape))


_sent_on.defvjp(_sent_on_fwd, _sent_on_bwd)


def _backward_after_inputs(fn):
    """``fn(inputs, shared)`` with its values and gradients, whose backward
    pass does not begin before ``inputs`` are there: in a rematerialised
    block, before they are recomputed. (``shared``, the weights, stays out
    of the barrier: through it they would be other values than the ones
    every other product takes, gathered a second time.)"""
    @jax.custom_vjp
    def tied(inputs, shared):
        return fn(inputs, shared)

    def backward(args, cotangent):
        inputs, shared = args
        cotangent, inputs = jax.lax.optimization_barrier((cotangent, inputs))
        return jax.vjp(fn, inputs, shared)[1](cotangent)

    tied.defvjp(lambda *args: (fn(*args), args), backward)
    return tied


def scattered_product(product, slices, shared, after=None,
                      axis_name: str = "tp"):
    """The sum over the ring of every chip's ``product(slices[step],
    shared)`` for this chip's own slice of S: ``slices[step]`` is what the
    product takes of the slice of ring step ``step``, ``shared`` what it
    takes every time (the weights), and its result [b, S / tp, ...] this
    chip's partial product for that slice. The other chips' slices come
    first, the farthest first: each partial sum is sent on while the next
    slice is multiplied, and this chip's own slice is multiplied last and
    added to what arrives. For tp = 2 the two addends an all-reduce would
    add, in the products' dtype as it adds them.

    In the backward pass the cotangent of this chip's rows is there when
    the layer's backward begins. Left alone, it is sent round the other
    way at once, and the products of its own slice run at once: a transfer
    in flight and matmuls carrying weight gathers, beside a rematerialised
    block's first matmuls, which then wait for both
    (``gathered_product``). So the own slice's products wait for their
    forward inputs to be recomputed, and the cotangent is sent once
    ``after`` (an array of the forward pass: name one that is there when
    the forward's own exchange has landed) is."""
    n, _, onward = _ring(axis_name)
    arriving = None
    for step in [*range(1, n), 0]:
        if step:
            part = product(slices[step], shared)
        else:
            part = _backward_after_inputs(product)(slices[0], shared)
        if arriving is not None:
            # The product is whole before what arrives is added to it:
            # fused into the matmul, the addition makes the matmul wait for
            # the arrival it was to run beside.
            part, arriving = jax.lax.optimization_barrier((part, arriving))
            part = part + arriving
        if step:
            arriving = jax.lax.ppermute(part, axis_name, onward) \
                if after is None else _sent_on(part, after, axis_name)
    return part


def tp_exchange_mesh(cfg, seq_len: int):
    """The current mesh if a block may take its tp traffic as exchanges of
    slices of S over it, else None: a tp axis above 1 that divides S, to
    which the rules give the residual stream's S (``stream``), the heads
    and the MLP's width, no other axis splitting S (context parallelism has
    its own attention), and the attention whose kernels run per shard on
    whole sequences (``flash``). What the code can see of the mesh, the
    rules and the shape; nothing is configured."""
    from ray_tpu.parallel.mesh import current_mesh
    mesh = current_mesh()
    if mesh is None or cfg.attn_impl != "flash":
        return None
    sizes = dict(mesh.shape)
    tp = sizes.get("tp", 1)
    if tp == 1 or seq_len % tp:
        return None

    def axes(name):  # one name a call: an axis two names share stays with one
        return _axes(ambient_spec(mesh, name)[0])

    if math.prod(sizes[a] for a in axes("sequence")) > 1:
        return None
    return mesh if all(axes(name) == ("tp",) for name in (
        "stream", "heads", "kv_heads", "mlp")) else None


def exchanged_over_tp(block, mesh, layers, layer_specs):
    """``(block, layers)`` for ``scan_blocks``: ``block(x, layer,
    positions)`` per shard of tp alone, over the stacked ``layers``
    prepared for it. x enters and leaves as this chip's slice of S
    [B, S / tp, d], a layer's leaves as ``layer_specs`` (the stacked
    leaves' PartitionSpecs, the stack's leading axis first) split them
    over tp, positions whole. Every other axis of the mesh stays the
    partitioner's: the batch over dp and fsdp, the weights' gathers over
    fsdp and their gradients' reductions come out as they do without this.

    A leaf tp does not split (a norm's vectors, a bias added after the
    sum) is whole on every chip, each of which sees its own rows, so its
    gradient is summed over tp. Those stacks go in float32 (the rows' own
    sum is; and in bfloat16 XLA's CPU backend aborts: its
    AllReducePromotion cannot read the reduction this leaves) and whole
    over every axis, gathered once before the scan: a few kB a layer, and
    gathered in the body they are synchronous collectives that wait for
    whatever slice is in flight (``gathered_product``)."""
    from ray_tpu._private.jax_compat import shard_map

    def tp_only(spec):
        return PartitionSpec(*("tp" if "tp" in _axes(dim) else None
                               for dim in spec[1:]))

    stream = PartitionSpec(None, "tp", None)
    specs = jax.tree.map(tp_only, layer_specs,
                         is_leaf=lambda s: isinstance(s, PartitionSpec))
    layers = jax.tree.map(
        lambda leaf, spec: leaf if "tp" in spec else constrain(
            leaf.astype(jnp.float32), *[None] * leaf.ndim), layers, specs)
    return shard_map(block, mesh=mesh,
                     in_specs=(stream, specs, PartitionSpec()),
                     out_specs=(stream, None), axis_names=frozenset({"tp"}),
                     check_vma=False), layers
