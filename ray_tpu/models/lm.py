"""What a causal language model of this package is made of, and no model
owns: the embedding lookup, the layer scan with rematerialisation, the
attention dispatch (which attention runs, and how it is laid over a mesh),
the state-space scan's, a block's tp traffic as exchanges of slices of S,
and the chunked head and loss. ``models/gpt.py``,
``models/deepseek.py``, ``models/granite.py``, ``models/afmoe.py``,
``models/kimi_linear.py`` and ``models/lfm2.py`` are built from these, and
from the three pieces two families share as they stand (``rmsnorm``,
``rope``, ``swiglu``); a new family brings its config, parameters, block and head
and is written against this module, not against another model.

Where S lives on a mesh. Everything a whole model carries along S (the
batch, q / k / v, the loss) is ``sequence``: whole, or over sp under context
parallelism. The residual stream between blocks is stated apart
(``embed``'s ``stream``): for a model whose block runs on slices of S
(``exchanged_over_tp``; ``models/gpt.py``), over tp, the axis its weights
are split over, from the lookup to the scan's exit, where it is gathered
once for the final norm and the vocabulary-parallel head. Such a block's
sum over tp and the gather that undoes it are then exchanges of slices
beside the block's matmuls (``gathered_product``, ``scattered_product``)
where an all-reduce of [B, S, d] a layer and pass ran alone. A block that
says nothing of slices keeps the stream under ``sequence`` and the
partitioner's collectives.

A model's config is read here for the program's own choices only, under the
names ``GPTConfig`` gives them: ``attn_impl``, ``attn_blk_q``,
``attn_blk_k`` (``attention``), ``remat``, ``remat_policy``
(``scan_blocks``). This module imports ``parallel/`` and ``ops/`` and no
model module.
"""

from __future__ import annotations

import itertools
import math
from functools import partial
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec

from ray_tpu.parallel.sharding import _spec_dim_axes as _axes, \
    ambient_spec, constrain


# -- lookup and layer scan ------------------------------------------------

def positions_of(tokens: jax.Array) -> jax.Array:
    """Positions 0..S-1 for every row of tokens [B, S]."""
    B, S = tokens.shape
    return jnp.broadcast_to(jnp.arange(S, dtype=jnp.int32), (B, S))


def embed(wte, tokens, dtype, stream: str = "sequence"):
    """wte[tokens] in ``dtype``, batch-split: the residual stream is split
    by batch from the lookup to the loss and says so at both ends of the
    layer scan; derived from the weights it would be d over fsdp, resharded
    wherever an operation wants the batch split. ``stream`` is the rule its
    S is stated under: ``"sequence"`` (whole, or over sp under context
    parallelism), or ``"stream"`` for a model whose blocks run on slices of
    S over tp (``exchanged_over_tp``): the lookup in a table split over
    tp by vocabulary gives partial sums over tp, and with S over tp their
    sum is a scatter, each chip keeping the rows its block will take.
    The lookup itself leaves d split as the table has it. Stated so first,
    rows are cut where they lie, and the change that follows is one
    all-to-all over those axes (from the lookup's own layout the
    partitioner can only replicate x whole when dp and fsdp are both
    above 1)."""
    x = jnp.take(wte, tokens, axis=0).astype(dtype)
    x = constrain(x, "batch", stream, "embed")
    return constrain(x, "batch", stream, None)


def layer_runs(layer_types):
    """[(kind, layers)] of every run of one kind in ``layer_types``."""
    return [(kind, len(list(run)))
            for kind, run in itertools.groupby(layer_types)]


def scan_blocks(cfg, block, x, layers, positions, layer_types=None):
    """``block(x, layer, positions) -> (x, aux)`` over stacked layer
    parameters in one ``lax.scan``, each block rematerialised by
    ``cfg.remat`` / ``cfg.remat_policy``. Returns (x, aux stacked over
    layers; None where the block returns None).

    ``"full"`` keeps the block's input and, of everything inside it, only
    the flash forward kernel's output and log-sum-exp
    (``flash_attention.RESIDUAL_NAMES``): the backward pass recomputes the
    block's XLA operations (norms, projections, rope, the MLP's first
    product) and not the kernel, which is O(S^2) work for O(S) bytes. The
    kernel names them only from the S / Dv at which a kept byte buys
    enough (``flash_attention.worth_keeping``, with the v5e's numbers:
    887 ms of step a GB at S = 32768 and heads of 64, 12-16 at 2048 and
    256, where the output of a 4096-wide matmul would buy 21); below
    it, and in a block without the kernels (``dot``, a ragged
    sequence's blockwise path, a state-space layer), ``"full"`` keeps
    nothing. ``"selective"`` keeps the same two beside the five values a
    model names in its block (``attn_q``, ``attn_k``, ``attn_v``,
    ``attn_raw``, ``ffn_in``).

    A stack of several kinds of layer gives ``layer_types``, the kind of
    every layer in order, ``block`` as a dict by kind and ``layers`` as a
    sequence with one stack for every run of one kind, in order (a model
    keeps its parameters that way: a slice of one stack of all a kind's
    layers is a copy, and the slices' gradients a second one). Every run
    is one scan, the runs one after the other. Returns (x, [each run's
    aux])."""
    if layer_types is not None:
        runs = layer_runs(layer_types)
        depths = [jax.tree.leaves(stack)[0].shape[0] for stack in layers]
        if depths != [n for _, n in runs]:
            raise ValueError(f"stacks of {depths} layers for runs {runs}")
        auxes = []
        for (kind, _), stack in zip(runs, layers):
            x, aux = scan_blocks(cfg, block[kind], x, stack, positions)
            auxes.append(aux)
        return x, auxes
    if cfg.remat:
        from ray_tpu.ops.flash_attention import RESIDUAL_NAMES
        if cfg.remat_policy == "selective":
            kept = ("attn_q", "attn_k", "attn_v", "attn_raw", "ffn_in")
        elif cfg.remat_policy == "full":
            kept = ()
        else:
            raise ValueError(
                f"Unknown remat_policy {cfg.remat_policy!r}; "
                "expected 'full' or 'selective'")
        policy = jax.checkpoint_policies.save_only_these_names(
            *kept, *RESIDUAL_NAMES)
        block = jax.checkpoint(block, policy=policy)

    def scan_body(x, layer):
        with jax.named_scope("block"):
            return block(x, layer, positions)

    return jax.lax.scan(scan_body, x, layers)


# -- attention ------------------------------------------------------------

def dot_attention(q, k, v, scale: Optional[float] = None,
                  window: Optional[int] = None):
    """Causal attention; fp32 softmax. q: [B, S, H, D], k: [B, S, KVH, D],
    v: [B, S, KVH, Dv] (Dv may differ from D) -> [B, S, H, Dv]. Scores are
    multiplied by ``scale`` (1/sqrt(D) if None). With ``window`` a query
    sees itself and the ``window - 1`` keys before it."""
    B, S, H, D = q.shape
    kvh = k.shape[2]
    if kvh != H:  # GQA: repeat KV heads
        rep = H // kvh
        k = jnp.repeat(k, rep, axis=2)
        v = jnp.repeat(v, rep, axis=2)
    if scale is None:
        scale = 1.0 / math.sqrt(D)
    logits = jnp.einsum("bqhd,bkhd->bhqk", q, k) * scale
    logits = logits.astype(jnp.float32)
    qpos = jax.lax.broadcasted_iota(jnp.int32, (S, S), 0)
    kpos = jax.lax.broadcasted_iota(jnp.int32, (S, S), 1)
    causal = qpos >= kpos
    if window is not None:
        causal &= qpos - kpos < window
    logits = jnp.where(causal[None, None], logits, -1e30)
    probs = jax.nn.softmax(logits, axis=-1).astype(q.dtype)
    return jnp.einsum("bhqk,bkhd->bqhd", probs, v)


def attention_specs(mesh, n_heads: int, n_kv_heads: int, seq_axis):
    """shard_map specs for [B, S, H, D] activations: batch as the rules
    have it (where a model puts the residual stream), sequence over
    ``seq_axis``, heads over tp. A head count tp does not divide
    (GQA/MQA KV heads) stays replicated, and the per-shard op must then
    bridge sharded-q / replicated-kv heads itself (ring_attention's
    _repeat_kv does)."""
    tp = dict(zip(mesh.axis_names, mesh.devices.shape)).get("tp", 1)
    batch = ambient_spec(mesh, "batch")[0]
    q_spec = PartitionSpec(batch, seq_axis,
                           "tp" if n_heads % tp == 0 else None, None)
    kv_spec = PartitionSpec(batch, seq_axis,
                            "tp" if n_kv_heads % tp == 0 else None, None)
    return q_spec, kv_spec


def _per_shard(fn, mesh, in_specs, out_specs):
    """``fn`` per shard of ``mesh`` as the specs lay its arguments out. In a
    block that already runs per shard of some axes (``exchanged_over_tp``:
    the heads there are this chip's), per shard of the others, under the
    mesh of that trace, the taken axes left out of the specs."""
    from ray_tpu._private.jax_compat import shard_map
    inside = jax.sharding.get_abstract_mesh()
    taken = set(inside.manual_axes)
    if taken:
        def rest(spec):
            dims = (tuple(a for a in _axes(dim) if a not in taken)
                    for dim in spec)
            return PartitionSpec(*(dim or None for dim in dims))
        in_specs, out_specs = jax.tree.map(
            rest, (in_specs, out_specs),
            is_leaf=lambda s: isinstance(s, PartitionSpec))
        mesh = inside
    return shard_map(fn, mesh=mesh, in_specs=in_specs, out_specs=out_specs,
                     check_vma=False,
                     axis_names=frozenset(mesh.axis_names) - taken)


def attention(q, k, v, cfg, scale: Optional[float] = None,
              window: Optional[int] = None):
    """Causal attention by ``cfg.attn_impl`` (and, for the flash kernels,
    ``cfg.attn_blk_q`` / ``cfg.attn_blk_k``): the one dispatch every model
    of this package goes through. cfg is any model's config. Scores are
    multiplied by ``scale``, the model's own where its config publishes
    one, 1/sqrt(D) if None; ``window`` is the layer's own where it has
    one: a query then sees itself and the ``window - 1`` keys before it
    (both ``dot`` and ``flash`` only)."""
    if cfg.attn_impl == "dot":
        return dot_attention(q, k, v, scale, window)
    if cfg.attn_impl == "flash":
        from ray_tpu.ops.flash_attention import flash_attention
        from ray_tpu.parallel.mesh import current_mesh
        fn = partial(flash_attention, causal=True,
                     blk_q=cfg.attn_blk_q, blk_k=cfg.attn_blk_k, scale=scale,
                     window=window)
        mesh = current_mesh()
        if mesh is None or mesh.size == 1:
            return fn(q, k, v)
        # GSPMD cannot partition a Mosaic kernel, so under a mesh it runs
        # per shard. Attention is independent per (batch row, head): each
        # shard sees whole sequences, and heads split over tp only when
        # the KV heads split with them (the kernel pairs q and kv heads
        # by position within the shard).
        q_spec, kv_spec = attention_specs(
            mesh, q.shape[2], k.shape[2], seq_axis=None)
        if kv_spec[2] is None:
            q_spec = kv_spec
        return _per_shard(fn, mesh, (q_spec, kv_spec, kv_spec), q_spec)(
            q, k, v)
    if scale is not None:
        raise NotImplementedError(
            f"attn_impl={cfg.attn_impl!r} scales scores by 1/sqrt(D) only; "
            "a model with its own score scale needs 'dot' or 'flash'")
    if window is not None:
        raise NotImplementedError(
            f"attn_impl={cfg.attn_impl!r} has no window (window={window}): "
            "a layer of sliding-window attention needs 'dot' or 'flash'")
    if cfg.attn_impl == "ring":
        from ray_tpu.ops.ring_attention import make_ring_attention
        from ray_tpu.parallel.mesh import current_mesh
        mesh = current_mesh()
        if mesh is None or "sp" not in mesh.axis_names:
            raise ValueError(
                "attn_impl='ring' needs a registered mesh with an 'sp' "
                "axis (parallel.mesh.set_current_mesh; make_train_step/"
                "make_eval_step do this automatically)")
        q_spec, kv_spec = attention_specs(
            mesh, q.shape[2], k.shape[2], seq_axis="sp")
        fn = make_ring_attention(mesh, "sp", causal=True, q_spec=q_spec,
                                 kv_spec=kv_spec)
        return fn(q, k, v)
    if cfg.attn_impl == "ulysses":
        from ray_tpu.ops.ulysses import make_ulysses_attention
        from ray_tpu.parallel.mesh import current_mesh
        mesh = current_mesh()
        if mesh is None or "sp" not in mesh.axis_names:
            raise ValueError(
                "attn_impl='ulysses' needs a registered mesh with an 'sp' "
                "axis (parallel.mesh.set_current_mesh)")
        return make_ulysses_attention(mesh)(q, k, v)
    raise ValueError(f"Unknown attn_impl {cfg.attn_impl!r}")


# -- the block's tp traffic as exchanges of slices of S --------------------
# A block whose weights are split over tp (q / k / v / ``w_in`` by columns,
# ``wo`` / ``w_out`` by rows) takes a residual stream that is split over tp
# too, along S. It needs every row for its column-split products and owes
# every chip the sum of the row-split ones on that chip's rows: an
# all-gather and a reduce-scatter of [B, S, d], which as two instructions
# nothing hides. The helpers below write them as a ring of
# ``ppermute``s of one slice of S (1 / tp of the rows) each, between which
# the products on the slice in hand run, in the manner of
# ``ops/ring_attention.py``'s K/V rotation. All run per shard, inside a
# ``shard_map`` that binds ``axis_name`` (``exchanged_over_tp`` is it).
# Autodiff transposes a ``ppermute`` into the reverse ``ppermute``, so the
# recomputed forward and the backward pass are exchanges of the same kind.
#
# Ring step t = 0 .. tp - 1: chip c has in hand slice ``(c - t) % tp`` of
# S, its own first. ``gathered_product`` and ``ring_split`` give their
# results in that order; ``ring_place`` takes its slices and
# ``scattered_product`` asks for its products in it.

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
    return _per_shard(place, inside, (rows, PartitionSpec()), rows[0])(
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

# -- state-space scan -----------------------------------------------------

def _over_batch_shards(fn, args, has_rows, out_rank: int = 4):
    """``fn(*args)`` -> [B, S, ...] of ``out_rank`` axes ([B, S, H, P] if
    not given), per shard of the batch under a mesh
    (GSPMD cannot partition a Mosaic kernel): a recurrence along S is
    independent per batch row, so each shard scans its own rows whole, with
    every head. ``has_rows`` says which arguments lead with the batch axis;
    the others go in whole."""
    from ray_tpu.parallel.mesh import current_mesh
    mesh = current_mesh()
    if mesh is None or mesh.size == 1:
        return fn(*args)
    from ray_tpu._private.jax_compat import shard_map
    batch = ambient_spec(mesh, "batch")[0]
    rows = lambda rank: PartitionSpec(batch, *[None] * (rank - 1))
    return shard_map(
        fn, mesh=mesh,
        in_specs=tuple(rows(a.ndim) if own else PartitionSpec(None)
                       for a, own in zip(args, has_rows)),
        out_specs=rows(out_rank), check_vma=False)(*args)


def state_space(u, dt, A, B, C, D, chunk: int):
    """The Mamba-2 recurrence ``S_t = exp(dt_t A) S_(t-1) + dt_t u_t B_t^T``,
    ``y_t = S_t C_t + D u_t`` by ``ops/ssd.py``'s chunked scan. u: [B, S, H,
    P], dt: [B, S, H] (positive), A, D: [H], B, C: [B, S, N] -> [B, S, H,
    P]. Under a mesh the kernels run per shard of the batch, as the flash
    kernels do."""
    from ray_tpu.ops.ssd import ssd
    return _over_batch_shards(
        partial(ssd, chunk=chunk), (u, dt, A, B, C, D),
        (True, True, False, True, True, False))


def causal_conv(x, w, b=None):
    """Depthwise causal convolution along S of x [B, S, C] with taps w [K,
    C] and, if given, bias b [C], in float32: y_t = b + sum_k w_k x_(t - K +
    1 + k), zeros before the first token (``ops/short_conv.py``'s
    ``causal_conv``). No layer calls it: it is the pre-activation that
    the ``jax.numpy`` forms of ``conv_silu`` (the short convolutions of
    ``models/granite.py`` and ``models/kimi_linear.py``) and of
    ``short_conv`` (the gated one that is a layer's mixer;
    ``models/lfm2.py``) stand on there, the kernels' oracle in the tests."""
    from ray_tpu.ops.short_conv import causal_conv as op
    return op(x, w, b)


def conv_silu(x, w, b=None, start: int = 0, width: Optional[int] = None):
    """``silu(b + causal_conv(x))`` over columns ``start .. start + width``
    (all, if not given) of ``x`` [B, S, W] by ``ops/short_conv.py`` (one
    fused Pallas pass each way over the columns where they lie, where the
    shapes tile, else its ``jax.numpy`` form on the slice): taps ``w`` [K,
    width], bias ``b`` [width] or None -> [B, S, width] in ``x``'s dtype,
    products, the K-term sum and the SiLU in float32. The short convolution
    of a state-space layer (``models/granite.py``: a slice of the
    in-projection's output) and those of a delta-rule layer's q, k and v
    (``models/kimi_linear.py``). Under a mesh the kernels run per shard of
    the batch, as ``state_space``'s do."""
    from ray_tpu.ops.short_conv import conv_silu as op
    args = (x, w) if b is None else (x, w, b)
    return _over_batch_shards(
        partial(op, start=start, width=width), args,
        (True,) + (False,) * (len(args) - 1), out_rank=3)


def short_conv(bcx, w):
    """The double-gated short convolution ``C * conv(B * x)`` by
    ``ops/short_conv.py`` (the fused Pallas pass each way where the shapes
    tile, else its ``jax.numpy`` form) over ``bcx`` [B, S, 3 d] (the chunks
    B, C, x of one projection) with taps ``w`` [K, d] -> [B, S, d]. Under a
    mesh the kernels run per shard of the batch, as ``state_space``'s do."""
    from ray_tpu.ops.short_conv import short_conv as op
    return _over_batch_shards(op, (bcx, w), (True, False), out_rank=3)


def delta_rule(q, k, v, a, beta):
    """The gated delta rule with a decay a channel, ``S_t = Diag(exp(a_t))
    S_(t-1)``, ``S_t += beta_t k_t (v_t - S_t^T k_t)^T``, ``o_t = S_t^T
    q_t``, by ``ops/kda.py``'s chunked kernels. q, k, a: [B, S, H, K], v:
    [B, S, H, V], beta: [B, S, H] -> [B, S, H, V]. Under a mesh the kernels
    run per shard of the batch, as ``state_space``'s do."""
    from ray_tpu.ops.kda import kda
    return _over_batch_shards(kda, (q, k, v, a, beta), (True,) * 5)


# -- pieces two families share as they stand ------------------------------

def rmsnorm(x, scale, eps):
    """RMSNorm over the last axis, statistics in float32, in x's dtype."""
    x32 = x.astype(jnp.float32)
    y = x32 * jax.lax.rsqrt((x32 ** 2).mean(-1, keepdims=True) + eps)
    return (y * scale.astype(jnp.float32)).astype(x.dtype)


def rope(x, positions, theta: float):
    """Rotary embedding over the whole last axis of x [B, S, H, D], pairing
    dimension i with i + D / 2 (angle pos * theta^(-2i/D)), as published
    for ``models/afmoe.py`` and ``models/lfm2.py``."""
    half = x.shape[-1] // 2
    freqs = 1.0 / (theta ** (jnp.arange(half, dtype=jnp.float32) / half))
    angles = positions[..., None].astype(jnp.float32) * freqs  # [B, S, half]
    cos, sin = jnp.cos(angles)[:, :, None, :], jnp.sin(angles)[:, :, None, :]
    x32 = x.astype(jnp.float32)
    first, second = x32[..., :half], x32[..., half:]
    return jnp.concatenate([first * cos - second * sin,
                            second * cos + first * sin], -1).astype(x.dtype)


def swiglu(x, w_gate, w_up, w_down):
    """``(silu(x w_gate) * x w_up) w_down`` in x's dtype."""
    dt = x.dtype
    gate = jnp.einsum("...d,df->...f", x, w_gate.astype(dt))
    up = jnp.einsum("...d,df->...f", x, w_up.astype(dt))
    return jnp.einsum("...f,fd->...d", jax.nn.silu(gate) * up,
                      w_down.astype(dt))


# -- head and loss --------------------------------------------------------

def ce_stats(logits: jax.Array, targets: jax.Array, mask: jax.Array,
             z_loss: float) -> Tuple[jax.Array, jax.Array]:
    """fp32 CE pieces for one [..., vocab] logits slab → (Σ nll·m, Σ hit·m)."""
    logits = logits.astype(jnp.float32)
    logz = jax.scipy.special.logsumexp(logits, axis=-1)
    tgt_logit = jnp.take_along_axis(
        logits, targets[..., None], axis=-1)[..., 0]
    nll = logz - tgt_logit
    if z_loss:
        nll = nll + z_loss * logz ** 2
    hits = (logits.argmax(-1) == targets).astype(jnp.float32)
    return (nll * mask).sum(), (hits * mask).sum()


def chunked_ce(head, x: jax.Array, targets: jax.Array, mask32: jax.Array,
               chunk: int, z_loss: float = 0.0
               ) -> Tuple[jax.Array, jax.Array]:
    """(Σ nll·mask, Σ hit·mask) of ``head(x)`` against targets, in fp32.

    ``head`` maps hidden [..., d] to logits [..., vocab]; x is [B, S, d].
    With ``chunk > 0`` the head matmul and the fp32 softmax run ``chunk``
    tokens at a time under a rematerialised lax.scan, so the [tokens, vocab]
    fp32 logits never exist whole. A chunk is a slice of S across the whole
    batch, [B, S / n, d]: the scanned dimension is not the one the batch's
    sharding lies on, so every data shard walks its own tokens and no chip
    sees another's (chunks of whole rows put the sharding on the scanned
    dimension, and the partitioner then splits d and sums every chunk's
    logits instead)."""
    with jax.named_scope("head_loss"):
        B, S = targets.shape
        if not (chunk and B * S > chunk):
            return ce_stats(head(x), targets, mask32, z_loss)
        # The fewest slices of S that hold at most ``chunk`` tokens each:
        # where ``chunk`` does not divide, the largest slice under it that
        # does, never the whole logits (the feature's memory bound stands).
        n = next((n for n in range(2, S)
                  if S % n == 0 and B * S // n <= chunk), S)

        def slices(a):
            a = a.reshape(B, n, S // n, *a.shape[2:]).swapaxes(0, 1)
            return constrain(a, None, "batch", "sequence",
                             *[None] * (a.ndim - 3))

        @jax.checkpoint
        def chunk_stats(carry, xtm):
            # One row of tokens: [B * S / n, ...], as head and loss see it
            # on one device too.
            x_c, t_c, m_c = (a.reshape(-1, *a.shape[2:]) for a in xtm)
            nll_sum, hit_sum = ce_stats(head(x_c), t_c, m_c, z_loss)
            return (carry[0] + nll_sum, carry[1] + hit_sum), None

        sums, _ = jax.lax.scan(
            chunk_stats, (jnp.zeros((), jnp.float32),) * 2,
            (slices(x), slices(targets), slices(mask32)))
        return sums


def head_gathered(params: Dict[str, Any], tied: bool) -> Dict[str, Any]:
    """params with the head's weight (``wte`` if ``tied``, else ``lm_head``)
    whole along d and split over the vocabulary alone: stated before the
    chunk loop, it is gathered over fsdp once a step and its gradient
    summed over the chunks before it is reduced, once; left to the
    partitioner both happen in every chunk."""
    if tied:
        return dict(params, wte=constrain(params["wte"], "vocab", None))
    return dict(params, lm_head=constrain(params["lm_head"], None, "vocab"))


def next_token_loss(head, x: jax.Array, targets: jax.Array,
                    mask: Optional[jax.Array], chunk: int, z_loss: float
                    ) -> Tuple[jax.Array, Dict[str, jax.Array]]:
    """Next-token cross-entropy in fp32 of ``head(x)`` over the tokens
    ``mask`` keeps (all, if None), plus ``z_loss`` times the squared log
    partition -> (loss, {"loss", "accuracy", "perplexity"}).

    ``head`` maps hidden [..., d] to logits [..., vocab], over
    ``head_gathered``'s params; x is the final hidden states [B, S, d];
    ``chunk`` is ``chunked_ce``'s."""
    mask32 = jnp.ones(targets.shape, jnp.float32) if mask is None \
        else mask.astype(jnp.float32)
    denom = jnp.maximum(mask32.sum(), 1.0)
    nll_sum, hit_sum = chunked_ce(head, x, targets, mask32, chunk, z_loss)
    loss = nll_sum / denom
    return loss, {"loss": loss, "accuracy": hit_sum / denom,
                  "perplexity": jnp.exp(jnp.minimum(loss, 20.0))}
