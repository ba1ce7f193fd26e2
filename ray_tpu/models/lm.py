"""What a causal language model of this package is made of, and no model
owns: the embedding lookup, the layer scan with rematerialisation, the
attention dispatch (which attention runs, and how it is laid over a mesh),
the state-space scans', the chunked head and loss (``chunked_ce``: one walk
over the chunks that forms the loss and, where a gradient is asked, its
cotangents, with nothing rematerialised), the pieces families
share as they stand (``rmsnorm``, ``layernorm``, the two ropes, ``swiglu``,
``mla``, ``expert_ffn``, each with the leaves it reads) and the decoder's shell
(``Decoder``). A block's tp traffic as exchanges of slices of S is the
sibling ``models/exchange.py``'s. This module imports ``parallel/`` and
``ops/`` and no model module, and no model module imports another: what a
second family needs of a first moves here.

**A family** (``models/deepseek.py``, ``granite.py``, ``afmoe.py``,
``kimi_linear.py``, ``lfm2.py``, ``phi4flash.py``, ``glm_moe_dsa.py``,
``evabyte.py``, ``minicpm_sala.py``, ``mellum.py``, ``nemotron_h.py``,
``dots3_note.py``) is three things, written against this module:

* its config, a frozen dataclass under the published keys, with
  ``vocab_size``, ``hidden_size``, the epsilon of its norms, the program's
  own choices under the names ``GPTConfig`` gives them (``dtype``,
  ``param_dtype``, ``remat``, ``remat_policy``, ``loss_chunk``,
  ``attn_impl``, ``attn_blk_q``, ``attn_blk_k``) and, unless it names its
  stacks itself (``Decoder.runs_of``), ``layers``: the kind of every layer
  that runs, in order;
* its table of leaves, ``_shapes(cfg)``: ``{leaf: (shape without the
  layers axis, logical axes for ``ShardingRules``, init)}`` grouped as the
  family likes, with ``leaves_of(table, kind)`` giving one layer's leaves
  in the order they are drawn. ``init`` is the std of a normal draw or a
  callable ``(key, shape) -> float32 array`` (``ones``, ``zeros``, a
  family's own beside its table). The one source of parameters and specs;
* its block, ``(cfg, kind, h, layer, positions) -> (h, aux or None)``: one
  layer of ``kind`` (or one unit of several layers, where neighbours
  always differ: ``phi4flash.py``'s pairs) on the residual stream h [B, S,
  d] from the layer's leaves, aux a dict of arrays (an expert layer's:
  ``expert_aux``). A family whose later runs read what an earlier run
  made says ``shares`` and takes ``shared`` as a last argument
  (``scan_blocks``); one whose norms are LayerNorms names the final norm's
  bias leaf (``final_norm_bias``) and calls ``layernorm`` in its blocks;
  one whose blocks read a per-layer value no gradient moves gives
  ``constants``; one whose loss has a second term that its blocks' aux
  carries gives ``extra_loss``; one whose hidden state feeds several
  prediction heads gives ``pred_heads`` (``multi_token_loss``).

``Decoder`` makes of them ``init``, ``param_specs``, ``hidden_states``,
``head``, ``forward``, ``forward_with_aux``, ``loss_of_hidden`` and
``loss_fn``, and the family's module binds those names (the benchmark and
checkpoints read them there), beside ``config``, ``PRESETS`` and, where its
loss has metrics that count or feed the registry, ``SUMMED_METRICS`` /
``RECORDED_METRICS``: ``parallel/train_step.py`` reads both off the module
that defines ``type(cfg)``. ``models/gpt.py`` is older than the shell and
takes the lookup, the scan, the attention and the loss only.

Where S lives on a mesh. Everything a whole model carries along S (the
batch, q / k / v, the loss) is ``sequence``: whole, or over sp under context
parallelism. The residual stream between blocks is stated apart
(``embed``'s ``stream``): for a model whose block runs on slices of S
(``exchange.exchanged_over_tp``; ``models/gpt.py``), over tp, the axis its
weights are split over, from the lookup to the scan's exit, where it is
gathered once for the final norm and the vocabulary-parallel head. A block
that says nothing of slices keeps the stream under ``sequence`` and the
partitioner's collectives.
"""

from __future__ import annotations

import itertools
import math
from dataclasses import dataclass
from functools import partial
from typing import Any, Callable, Dict, Mapping, Optional, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec

from ray_tpu._private import builtin_metrics
from ray_tpu.parallel.sharding import ShardingRules, \
    _spec_dim_axes as _axes, ambient_spec, constrain


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
    S over tp (``exchange.exchanged_over_tp``): the lookup in a table split
    over tp by vocabulary gives partial sums over tp, and with S over tp their
    sum is a scatter, each chip keeping the rows its block will take.
    The lookup itself leaves d split as the table has it. Stated so first,
    rows are cut where they lie, and the change that follows is one
    all-to-all over those axes (from the lookup's own layout the
    partitioner can only replicate x whole when dp and fsdp are both
    above 1)."""
    x = jnp.take(wte, tokens, axis=0).astype(dtype)
    x = constrain(x, "batch", stream, "embed")
    return constrain(x, "batch", stream, None)


#: The key of a block's aux under which it hands values on to the runs
#: behind it (``scan_blocks``, ``shares``).
HANDED_ON = "handed_on"


def layer_runs(layer_types):
    """[(kind, layers)] of every run of one kind in ``layer_types``."""
    return [(kind, len(list(run)))
            for kind, run in itertools.groupby(layer_types)]


def runs(layers) -> Tuple[Tuple[str, str, int], ...]:
    """(name in the parameter tree, kind, layers) of every run of one kind
    of layer in ``layers``, in order: ``run00_dense_kda``,
    ``run01_moe_kda``, ... A run is one stack of parameters and one
    ``lax.scan``."""
    return tuple((f"run{i:02d}_{kind}", kind, n)
                 for i, (kind, n) in enumerate(layer_runs(layers)))


def rematerialised(cfg, block):
    """``block`` rematerialised by ``cfg.remat`` / ``cfg.remat_policy``
    (``scan_blocks`` says what each policy keeps); as it is without
    ``cfg.remat``."""
    if not cfg.remat:
        return block
    from ray_tpu.ops.dsa import LOSS_GRADIENT_NAME, SELECTION_NAME
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
        *kept, *RESIDUAL_NAMES, SELECTION_NAME, LOSS_GRADIENT_NAME)
    return jax.checkpoint(block, policy=policy)


def scan_blocks(cfg, block, x, layers, positions, runs=None,
                shares: bool = False, remat_in_block: bool = False):
    """``block(x, layer, positions) -> (x, aux)`` over stacked layer
    parameters in one ``lax.scan``, each block rematerialised by
    ``cfg.remat`` / ``cfg.remat_policy`` (``rematerialised``), unless
    ``remat_in_block``: a block that is a unit of several layers wraps each
    of them itself, so that a backward pass holds one layer's intermediates
    at a time (``models/nemotron_h.py``). Returns (x, aux stacked over
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
    nothing but a learned selection of keys where a block makes one
    (``ops/dsa.py`` ``SELECTION_NAME``: a byte a pair, so that the backward
    pass neither searches it again nor attends over other keys than the
    forward pass did) and, where its indexer has a loss, that loss's
    gradient by the scores (``ops/dsa.py`` ``LOSS_GRADIENT_NAME``: 4 S^2
    bytes a layer with an indexer, for which the backward pass runs
    neither ``dsa_probs`` nor the scores' forward kernel again: 85 ms of
    step a GB at 128 heads of 192, and H x D_qk / 8 products a kept byte
    whatever S, the kernel's products and the array's bytes both growing
    as S^2; kept wherever such a loss exists, since a layer that cannot
    hold one more [S, S] float32 array cannot run its forward pass, which
    holds the scores, the target and the loss's temporaries at once).
    ``"selective"`` keeps the same beside the five values a model names in
    its block (``attn_q``, ``attn_k``, ``attn_v``, ``attn_raw``,
    ``ffn_in``).

    A stack of several kinds of layer gives ``runs``, the (kind, layers) of
    every run of one kind in order (``layer_runs``), ``block`` as a dict by
    kind and ``layers`` as a sequence with one stack for every run (a model
    keeps its parameters that way: a slice of one stack of all a kind's
    layers is a copy, and the slices' gradients a second one). Every run
    is one scan, the runs one after the other. Returns (x, [each run's
    aux]).

    With ``shares`` a run's block may hand values on to the runs behind it:
    ``block(x, layer, positions, shared) -> (x, aux)``, where ``shared`` is
    the dict of everything handed on so far and a block hands on what its
    aux holds under ``HANDED_ON`` (a dict of arrays; of a run of several
    layers, the last layer's). A handed-on value is one array: an output of
    the run that made it, and to every run behind it a constant its one
    ``lax.scan`` closes over, neither in the carry nor kept once a reading
    layer. The scan's transpose sums the readers' cotangents, and the block
    that made the value receives the sum; a value of integers
    (``models/glm_moe_dsa.py``'s selection) has no cotangent and receives
    none."""
    if runs is not None:
        runs = list(runs)
        depths = [jax.tree.leaves(stack)[0].shape[0] for stack in layers]
        if depths != [n for _, n in runs]:
            raise ValueError(f"stacks of {depths} layers for runs {runs}")
        auxes, shared = [], {}
        for (kind, _), stack in zip(runs, layers):
            fn = partial(block[kind], shared=dict(shared)) if shares \
                else block[kind]
            x, aux = scan_blocks(cfg, fn, x, stack, positions,
                                 remat_in_block=remat_in_block)
            if shares and aux and HANDED_ON in aux:
                aux = dict(aux)
                shared.update(jax.tree.map(lambda a: a[-1],
                                           aux.pop(HANDED_ON)))
            auxes.append(aux)
        return x, auxes
    if not remat_in_block:
        block = rematerialised(cfg, block)

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


def per_shard(fn, mesh, in_specs, out_specs):
    """``fn`` per shard of ``mesh`` as the specs lay its arguments out. In a
    block that already runs per shard of some axes
    (``exchange.exchanged_over_tp``: the heads there are this chip's), per
    shard of the others, under the mesh of that trace, the taken axes left
    out of the specs."""
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
        return per_shard(fn, mesh, (q_spec, kv_spec, kv_spec), q_spec)(
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


def window_tile_fill(cfg, window: int, seq_len: int) -> Optional[float]:
    """Of the (query, key) pairs in the tiles the flash kernels execute for
    a layer under ``window`` (``window_tile_census`` of the pair table the
    step is built with), the share the mask keeps; None where such a layer
    does not run the kernels on tiles (``dot``, a sequence that is one tile
    or less, or one the window does not cut)."""
    from ray_tpu.ops.flash_attention import window_tile_census
    S = seq_len
    blk_q, blk_k = min(cfg.attn_blk_q, S), min(cfg.attn_blk_k, S)
    if cfg.attn_impl != "flash" or window >= S or S % blk_q or S % blk_k:
        return None
    kept = window * (window + 1) // 2 + (S - window) * window
    executed = window_tile_census(S, window, blk_q, blk_k)["executed"]
    return kept / (executed * blk_q * blk_k)


def unless_nan(record):
    """``record`` (what feeds a gauge) for a metric that reads not a number
    where it does not apply (no layer of the kind runs): nothing is
    recorded then."""
    return lambda value: record(value) if value == value else None


#: A model's ``RECORDED_METRICS`` entry for ``attn_window_tile_fill``.
record_window_tile_fill = unless_nan(
    lambda value: builtin_metrics.train_attn_window_tile_fill().set(value))


def eva_attention(q, k, v, phi, mu, cfg, window: int, chunk: int):
    """EVA attention by ``cfg.attn_impl`` (``ops/eva.py``): q's softmax over
    the keys of its own block window of ``window`` and, under the same
    softmax, one summary of every ``chunk`` keys of the windows before it,
    pooled against ``phi`` with ``mu`` added to the pooled key (both [H,
    D]). q, k [B, S, H, D], v [B, S, H, Dv] -> (out [B, S, H, Dv], mass [B,
    H, S] float32, the share of each query's softmax sum on summaries; no
    gradient). ``dot`` or ``flash``; under a mesh the kernels run per shard
    of the batch and, where tp divides them, of the heads."""
    from ray_tpu.ops import eva
    with jax.named_scope("eva_pool"):
        kc, vc = eva.pool(k, v, phi, mu, chunk)
    with jax.named_scope("eva_attn"):
        if cfg.attn_impl == "dot":
            return eva.dot_eva_attention(q, k, v, kc, vc, window, chunk)
        if cfg.attn_impl != "flash":
            raise NotImplementedError(
                f"attn_impl={cfg.attn_impl!r}: EVA attention runs as 'dot' "
                "or 'flash' (ops/eva.py)")
        from ray_tpu.parallel.mesh import current_mesh
        fn = partial(eva.eva_attention, window=window, chunk=chunk,
                     blk_q=cfg.attn_blk_q, blk_k=cfg.attn_blk_k)
        mesh = current_mesh()
        if mesh is None or mesh.size == 1:
            return fn(q, k, v, kc, vc)
        spec, _ = attention_specs(mesh, q.shape[2], k.shape[2],
                                  seq_axis=None)
        return per_shard(fn, mesh, (spec,) * 5,
                         (spec, PartitionSpec(spec[0], spec[2], None)))(
            q, k, v, kc, vc)


def block_sparse_attention(q, k, v, cfg, sizes):
    """Attention over the blocks each query selects by the model's own
    scores (``ops/infllm.py``; ``sizes`` its ``Sizes``): q [B, S, H, D], k, v
    [B, S, G, D] with H a multiple of G -> (out [B, S, H, D], {
    ``selected_share``, ``live_tile_share``, ``free_mass``}: what the
    selection kept of the causal pairs, of the kernels' causal tiles, and
    the share of a query's softmax sum on blocks chosen by score; no
    gradient). ``dot`` or ``flash``. One device: the selection is a whole
    sequence's and no mesh axis splits it yet."""
    from ray_tpu.ops import infllm
    from ray_tpu.parallel.mesh import current_mesh
    mesh = current_mesh()
    if mesh is not None and mesh.size > 1:
        raise NotImplementedError(
            "block-sparse attention (ops/infllm.py) selects over a whole "
            f"sequence on one device; the mesh has {mesh.size}")
    if cfg.attn_impl not in ("dot", "flash"):
        raise NotImplementedError(
            f"attn_impl={cfg.attn_impl!r}: block-sparse attention runs as "
            "'dot' or 'flash' (ops/infllm.py)")
    S, block = q.shape[1], sizes.block
    with jax.named_scope("sala_select"):
        selection = infllm.select(
            infllm.block_scores(q, infllm.compress(k, sizes), sizes), sizes)
    with jax.named_scope("sala_attention"):
        if cfg.attn_impl == "dot":
            out, _ = infllm.dot_selected_attention(q, k, v, selection, block)
            tiles = (infllm.whole_tile(S, block),) * 2
        else:
            out, _ = infllm.selected_attention(
                q, k, v, selection, block, cfg.attn_blk_q, cfg.attn_blk_k,
                None, infllm.keeps_forward(S, v.shape[-1], sizes))
            tiles = infllm.tiles(q, k, block, cfg.attn_blk_q,
                                 cfg.attn_blk_k)
    with jax.named_scope("sala_gauges"):
        aux = {"selected_share": infllm.selected_pairs_share(selection,
                                                             block),
               "live_tile_share": infllm.live_tile_share(selection, block,
                                                         *tiles),
               "free_mass": infllm.free_mass(q, k, selection, sizes)}
    return out, aux


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
    P], dt: [B, S, H] (positive), A, D: [H], B, C: [B, S, G, N], head i
    reading group ``i // (H / G)`` (or [B, S, N]: one group) -> [B, S, H,
    P]. Under a mesh the kernels run per shard of the batch, as the flash
    kernels do."""
    from ray_tpu.ops.ssd import ssd
    return _over_batch_shards(
        partial(ssd, chunk=chunk), (u, dt, A, B, C, D),
        (True, True, False, True, True, False))


def linear_attention(q, k, v, slope):
    """Linear attention with a fixed decay a head, ``S_t = exp(-slope_h)
    S_(t-1) + k_t v_t^T``, ``o_t = q_t S_t / sqrt(K)``, by
    ``ops/lightning.py``'s chunked kernels. q, k [B, S, H, K], v [B, S, H,
    V], slope [H] positive (a constant of the layer: no gradient) -> [B, S,
    H, V]. Under a mesh the kernels run per shard of the batch, as
    ``state_space``'s do."""
    from ray_tpu.ops.lightning import lightning
    with jax.named_scope("lightning"):
        return _over_batch_shards(lightning, (q, k, v, slope),
                                  (True, True, True, False))


def selective_scan(xs, delta, A, B, C, D):
    """The Mamba-1 recurrence ``H_t = exp(delta_t (x) A) * H_(t-1) + (delta_t
    * xs_t) (x) B_t``, ``y_t = H_t C_t + D * xs_t`` by
    ``ops/selective_scan.py``'s kernel pair. xs, delta: [B, S, C] (delta
    positive), A: [C, N] (negative), B, C: [B, S, N], D: [C] -> [B, S, C].
    Under a mesh the kernels run per shard of the batch, as ``state_space``'s
    do."""
    from ray_tpu.ops.selective_scan import selective_scan as op
    return _over_batch_shards(
        op, (xs, delta, A, B, C, D), (True, True, False, True, True, False),
        out_rank=3)


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


def gated_norm(x, z, scale, eps, *, gate_first: bool, activation: str,
               group: Optional[int] = None):
    """The gate and the RMSNorm behind a recurrence by
    ``ops/gated_norm.py`` (one fused Pallas pass each way where the shapes
    tile, else its ``jax.numpy`` form): ``x`` [B, S, width] the recurrence's
    output, the gate's argument the first ``width`` columns of ``z`` [B, S,
    >= width] read where they lie, the groups of ``group`` channels
    (``scale``'s length if not given) side by side along the width,
    ``scale`` [group] or [width] -> [B, S, width] in ``x``'s dtype, gate,
    statistics and products in float32. The layer says what it computes:
    ``gate_first`` with a ``"silu"`` and one group of the whole row is a
    Mamba-2 layer's ``RMSNorm(x * silu(z))`` (``models/granite.py``), the
    same with a group a B/C group under a scale as wide as the row Mamba-2's
    own grouped norm (``models/nemotron_h.py``), the norm first with a
    ``"sigmoid"`` and a group a head a delta-rule layer's ``RMSNorm(x) *
    sigmoid(z)`` (``models/kimi_linear.py``). Under a mesh the kernels run
    per shard of the batch, as ``state_space``'s do."""
    from ray_tpu.ops.gated_norm import gated_norm as op
    return _over_batch_shards(
        partial(op, eps=eps, gate_first=gate_first, activation=activation,
                group=group),
        (x, z, scale), (True, True, False), out_rank=3)


def delta_rule(q, k, v, a, beta):
    """The gated delta rule with a decay a channel, ``S_t = Diag(exp(a_t))
    S_(t-1)``, ``S_t += beta_t k_t (v_t - S_t^T k_t)^T``, ``o_t = S_t^T
    q_t``, by ``ops/kda.py``'s chunked kernels, under the published kernel's
    contract: q and k come as the layer has them, and the rule brings every
    head's k to length 1 and q to length ``K ** -0.5`` itself. q, k, a: [B,
    S, H, K], v: [B, S, H, V], beta: [B, S, H] -> [B, S, H, V]. Under a mesh
    the kernels run per shard of the batch, as ``state_space``'s do."""
    from ray_tpu.ops.kda import kda
    return _over_batch_shards(kda, (q, k, v, a, beta), (True,) * 5)


# -- pieces two families share as they stand ------------------------------

def rmsnorm(x, scale, eps):
    """RMSNorm over the last axis, statistics in float32, in x's dtype."""
    x32 = x.astype(jnp.float32)
    y = x32 * jax.lax.rsqrt((x32 ** 2).mean(-1, keepdims=True) + eps)
    return (y * scale.astype(jnp.float32)).astype(x.dtype)


def layernorm(x, scale, bias, eps):
    """LayerNorm over the last axis (``nn.LayerNorm``: mean and biased
    variance, a scale and a bias), statistics in float32, in x's dtype."""
    x32 = x.astype(jnp.float32)
    mean = x32.mean(-1, keepdims=True)
    centred = x32 - mean
    y = centred * jax.lax.rsqrt((centred ** 2).mean(-1, keepdims=True) + eps)
    return (y * scale.astype(jnp.float32) + bias.astype(jnp.float32)
            ).astype(x.dtype)


#: The ``rope_type`` values ``rope_table`` makes a table for.
ROPE_TYPES = ("default", "yarn")


def rope_table(parameters, head_dim: int):
    """(inverse frequencies [head_dim / 2] float32, factor on cos and sin) of
    one kind of layer from what a config publishes for it
    (``rope_parameters``, or a bare theta): ``rope_type`` ``default`` is
    ``theta^(-2i/D)`` and a factor of 1; ``yarn`` (``factor`` over
    ``original_max_position_embeddings``, ``beta_fast`` 32, ``beta_slow`` 1,
    ``attention_factor`` ``0.1 ln(factor) + 1`` where not given) blends each
    pair's frequency with its ``factor``-th over a ramp of pairs::

        d(n)  = D ln(original / (2 pi n)) / (2 ln theta)
        low, high = floor(d(beta_fast)), ceil(d(beta_slow)), in [0, D - 1]
        r[i]  = clip((i - low) / (high - low), 0, 1)
        f[i]  = (1 - r[i]) theta^(-2i/D) + r[i] theta^(-2i/D) / factor

    as ``transformers``' ``_compute_yarn_parameters`` does. The other
    scalings a config may name (``linear``, ``dynamic``, ``llama3``,
    ``longrope``) are refused."""
    if not isinstance(parameters, Mapping):
        parameters = {"rope_theta": parameters}
    theta, half = parameters["rope_theta"], head_dim // 2
    freqs = 1.0 / (theta ** (jnp.arange(half, dtype=jnp.float32) / half))
    kind = parameters.get("rope_type", "default")
    if kind not in ROPE_TYPES:
        raise NotImplementedError(
            f"rope_type {kind!r}: the table is made for {ROPE_TYPES} only")
    if kind == "default":
        return freqs, 1.0
    factor = parameters["factor"]
    original = parameters["original_max_position_embeddings"]

    def pair_of(rotations):
        return head_dim * math.log(original / (rotations * 2 * math.pi)) \
            / (2 * math.log(theta))

    low = max(math.floor(pair_of(parameters.get("beta_fast") or 32)), 0)
    high = min(math.ceil(pair_of(parameters.get("beta_slow") or 1)),
               head_dim - 1)
    ramp = jnp.clip((jnp.arange(half, dtype=jnp.float32) - low)
                    / ((high - low) or 0.001), 0.0, 1.0)
    scaled = parameters.get("attention_factor")
    if scaled is None:
        scaled = 0.1 * math.log(factor) + 1.0 if factor > 1 else 1.0
    return freqs * (1.0 - ramp) + freqs / factor * ramp, float(scaled)


def _rope(x, positions, table, pairs):
    freqs, factor = table
    half = x.shape[-1] // 2
    angles = positions[..., None].astype(jnp.float32) * freqs  # [B, S, half]
    cos, sin = jnp.cos(angles)[:, :, None, :], jnp.sin(angles)[:, :, None, :]
    if factor != 1.0:
        cos, sin = cos * factor, sin * factor
    first, second = pairs(x.astype(jnp.float32), half)
    return jnp.concatenate([first * cos - second * sin,
                            second * cos + first * sin], -1).astype(x.dtype)


def rope(x, positions, theta):
    """Rotary embedding over the whole last axis of x [B, S, H, D], pairing
    the halves: dimension i with i + D / 2, as published for
    ``models/afmoe.py``, ``models/lfm2.py`` and ``models/mellum.py``.
    ``theta`` is a float (angle pos * theta^(-2i/D)) or a kind of layer's
    ``rope_parameters`` (``rope_table``: the angle from its table, cos and
    sin times its factor)."""
    return _rope(x, positions, rope_table(theta, x.shape[-1]),
                 lambda x, half: (x[..., :half], x[..., half:]))


def rope_interleaved(x, positions, theta: float):
    """Rotary embedding over the whole last axis of x [B, S, H, R], pairing
    dimension 2i with 2i+1 (angle pos * theta^(-2i/R)) as published for
    ``deepseek_v3``; the result holds all first members, then all second
    (q and k alike, so scores are unchanged)."""
    return _rope(x, positions, rope_table(theta, x.shape[-1]),
                 lambda x, half: (x[..., 0::2], x[..., 1::2]))


def swiglu(x, w_gate, w_up, w_down):
    """``(silu(x w_gate) * x w_up) w_down`` in x's dtype."""
    dt = x.dtype
    gate = jnp.einsum("...d,df->...f", x, w_gate.astype(dt))
    up = jnp.einsum("...d,df->...f", x, w_up.astype(dt))
    return jnp.einsum("...f,fd->...d", jax.nn.silu(gate) * up,
                      w_down.astype(dt))


def swiglu_leaves(d: int, width: int, prefix: str = "", gated: bool = True):
    """The leaves ``swiglu`` reads, as a family's table of leaves holds
    them (``Decoder``); without ``gated``, the two ``mlp`` reads."""
    gate = {prefix + "w_gate": ((d, width), ("embed", "mlp"), 0.02)}
    return {**(gate if gated else {}),
            prefix + "w_up": ((d, width), ("embed", "mlp"), 0.02),
            prefix + "w_down": ((width, d), ("mlp", "embed"), 0.02)}


def mlp(x, w_up, w_down, activation: str):
    """``act(x w_up) w_down`` in x's dtype: two matrices and no gate,
    ``activation`` as ``ops/moe.py`` names them (``"relu2"``: a squared
    ReLU)."""
    from ray_tpu.ops.moe import ACTIVATIONS
    dt = x.dtype
    up = jnp.einsum("...d,df->...f", x, w_up.astype(dt))
    return jnp.einsum("...f,fd->...d", ACTIVATIONS[activation](up),
                      w_down.astype(dt))


def mla_leaves(latent):
    """The leaves ``mla`` reads, of ``latent``: a family's config, or any
    view of one that carries a latent attention's geometry under the
    published plain names (``hidden_size``, ``num_attention_heads``,
    ``q_lora_rank``, ``kv_lora_rank``, ``qk_nope_head_dim``,
    ``qk_rope_head_dim``, ``v_head_dim``; a model with two geometries hands
    in one view a kind of layer, ``models/dots3_note.py``). With
    ``q_lora_rank`` the query is of low rank (``w_q_a``, ``q_norm_scale``,
    ``w_q_b``); null, or a config without the key, one matrix ``wq``."""
    d, h, rank = latent.hidden_size, latent.num_attention_heads, \
        latent.kv_lora_rank
    q_rank = getattr(latent, "q_lora_rank", None)
    heads = ("heads", "head_dim")
    qk = latent.qk_nope_head_dim + latent.qk_rope_head_dim
    queries = {"wq": ((d, h, qk), ("embed",) + heads, 0.02)} if not q_rank \
        else {"w_q_a": ((d, q_rank), ("embed", None), 0.02),
              "q_norm_scale": ((q_rank,), (None,), ones),
              "w_q_b": ((q_rank, h, qk), (None,) + heads, 0.02)}
    return {
        **queries,
        "w_kv_a": ((d, rank + latent.qk_rope_head_dim), ("embed", None),
                   0.02),
        "kv_norm_scale": ((rank,), (None,), ones),
        "w_kv_b": ((rank, h, latent.qk_nope_head_dim + latent.v_head_dim),
                   (None,) + heads, 0.02),
        "wo": ((h, latent.v_head_dim, d), heads + ("embed",), 0.02),
    }


def mla_qkv(latent, x, layer, positions):
    """``mla``'s q, k [B, S, H, nope + rope], v [B, S, H, v_head] and the
    normed low-rank query ``c_q`` [B, S, q_lora_rank] (None where the rank
    is null) of normed x [B, S, d]. ``latent`` is what ``mla_leaves`` takes,
    with ``rope_theta``, ``rms_norm_eps``, ``dtype`` and ``mla_use_nope``;
    where it has a ``q_lora_scale`` / ``kv_lora_scale`` that is not None,
    the normed latent is multiplied by it before its second matrix (the
    ``c_q`` handed back is the unscaled one)."""
    dt = latent.dtype
    nope, rank = latent.qk_nope_head_dim, latent.kv_lora_rank
    q_scale = getattr(latent, "q_lora_scale", None)
    kv_scale = getattr(latent, "kv_lora_scale", None)
    c_q = None
    if getattr(latent, "q_lora_rank", None):
        c_q = rmsnorm(jnp.einsum("bsd,dr->bsr", x, layer["w_q_a"].astype(dt)),
                      layer["q_norm_scale"], latent.rms_norm_eps)
        scaled = c_q if q_scale is None else c_q * jnp.asarray(q_scale, dt)
        q = jnp.einsum("bsr,rhk->bshk", scaled, layer["w_q_b"].astype(dt))
    else:
        q = jnp.einsum("bsd,dhk->bshk", x, layer["wq"].astype(dt))
    kv_a = jnp.einsum("bsd,dr->bsr", x, layer["w_kv_a"].astype(dt))
    c = rmsnorm(kv_a[..., :rank], layer["kv_norm_scale"],
                latent.rms_norm_eps)
    if kv_scale is not None:
        c = c * jnp.asarray(kv_scale, dt)
    kv = jnp.einsum("bsr,rhk->bshk", c, layer["w_kv_b"].astype(dt))
    rotated = (lambda x: x) if latent.mla_use_nope else partial(
        rope_interleaved, positions=positions, theta=latent.rope_theta)
    q_rope = rotated(q[..., nope:])
    k_rope = rotated(kv_a[..., None, rank:])
    q = jnp.concatenate([q[..., :nope], q_rope], -1)
    k = jnp.concatenate(
        [kv[..., :nope],
         jnp.broadcast_to(k_rope, q_rope.shape)], -1)
    return q, k, kv[..., nope:], c_q


def mla(cfg, x, layer, positions):
    """Multi-head latent attention (``deepseek_v3``'s) on normed x [B, S, d]
    -> [B, S, d], from a layer's ``mla_leaves``: the query one matrix
    ``wq`` where ``q_lora_rank`` is null, else ``RMSNorm(x W_qa) W_qb``.
    cfg is any config with the latent keys under their published names
    (``models/deepseek.py``, ``models/kimi_linear.py``;
    ``models/glm_moe_dsa.py`` and ``models/dots3_note.py`` attend over a
    selection, the latter also in a window and behind a gate, and call
    ``mla_qkv`` themselves); with ``cfg.mla_use_nope`` the "rope"
    dimensions of q and of the shared key go unrotated."""
    q, k, v, _ = mla_qkv(cfg, x, layer, positions)
    attn = attention(q, k, v, cfg)
    return jnp.einsum("bshk,hkd->bsd", attn, layer["wo"].astype(cfg.dtype))


# -- attention over a learned selection (ops/dsa.py) ------------------------
# What ``models/glm_moe_dsa.py`` and ``models/dots3_note.py`` share: a layer's
# indexer, the main attention over its selection and the indexers' loss. cfg
# is either family's config: the ``index_*`` keys, ``q_lora_rank``,
# ``qk_rope_head_dim`` and ``rope_theta`` (of the layers that own an
# indexer), ``index_norm_eps``, ``indexer_loss_coef``.

def indexer_leaves(cfg):
    """The leaves of a layer's indexer (``index_scores``)."""
    d, std = cfg.hidden_size, 0.02
    heads, width = cfg.index_n_heads, cfg.index_head_dim
    return {
        "w_iq": ((cfg.q_lora_rank, heads, width), (None, "heads", "head_dim"),
                 std),
        "w_ik": ((d, width), ("embed", None), std),
        "ik_norm_scale": ((width,), (None,), ones),
        "ik_norm_bias": ((width,), (None,), zeros),
        "w_iw": ((d, heads), ("embed", None), std),
    }


def partly_rotated(x, positions, cfg):
    """x [B, S, H, E] with its first ``qk_rope_head_dim`` dimensions
    rotated (pairs (2i, 2i+1)), the rest as they are."""
    r = cfg.qk_rope_head_dim
    return jnp.concatenate([rope_interleaved(
        x[..., :r], positions, cfg.rope_theta), x[..., r:]], -1)


def _selection_kernels(cfg):
    """Whether what attends over a selection runs ``ops/dsa.py``'s kernels
    (``cfg.attn_impl`` ``"flash"``) or their ``jax.numpy`` forms
    (``"dot"``); the kernels run whole sequences of one device."""
    if cfg.attn_impl == "dot":
        return False
    if cfg.attn_impl != "flash":
        raise NotImplementedError(
            f"attn_impl={cfg.attn_impl!r}: attention over a selection runs "
            "as 'dot' or 'flash' (ops/dsa.py)")
    from ray_tpu.parallel.mesh import current_mesh
    mesh = current_mesh()
    if mesh is not None and mesh.size > 1:
        raise NotImplementedError(
            "models/lm.py runs a selection's kernels on one device; the "
            f"mesh has {mesh.size}")
    return True


def index_scores(cfg, x, c_q, layer, positions, rotated=partly_rotated):
    """The indexer's scores I [B, S, S] float32 of normed x [B, S, d] and
    the normed low-rank query c_q [B, S, q_lora_rank], neither of which its
    gradient reaches; ``rotated`` is what rotates its q and k. By
    ``cfg.attn_impl``, ``ops/dsa.py``'s kernels (``index_scores``) or the
    sum written out (``dot_index_scores``)."""
    from ray_tpu.ops import dsa
    dt, f32 = cfg.dtype, jnp.float32
    x, c_q = jax.lax.stop_gradient(x), jax.lax.stop_gradient(c_q)
    q = jnp.einsum("bsr,rje->bsje", c_q, layer["w_iq"].astype(dt))
    k = layernorm(jnp.einsum("bsd,de->bse", x, layer["w_ik"].astype(dt)),
                  layer["ik_norm_scale"], layer["ik_norm_bias"],
                  cfg.index_norm_eps)
    w = jnp.einsum("bsd,dj->bsj", x, layer["w_iw"].astype(dt)).astype(f32) \
        * (cfg.index_n_heads ** -0.5 * cfg.index_head_dim ** -0.5)
    q = rotated(q, positions, cfg)
    k = rotated(k[:, :, None], positions, cfg)[:, :, 0]
    if not _selection_kernels(cfg):
        return dsa.dot_index_scores(q, k, w)
    return dsa.index_scores(q, k, w)


def selected_attention(cfg, q, k, v, selection):
    """(out, lse) of the main attention over the selection, by
    ``cfg.attn_impl``."""
    from ray_tpu.ops import dsa
    if not _selection_kernels(cfg):
        return dsa.dot_selected_attention(q, k, v, selection)
    return dsa.selected_attention(q, k, v, selection, cfg.attn_blk_q,
                                  cfg.attn_blk_k, None)


def selection_probs(cfg, q, k, lse, selection):
    """The main attention's probabilities summed over the heads, on the
    selection: the indexer's target, which no gradient reaches."""
    from ray_tpu.ops import dsa
    q, k, lse = (jax.lax.stop_gradient(a) for a in (q, k, lse))
    if not _selection_kernels(cfg):
        return dsa.dot_head_probs(q, k, lse, selection)
    return dsa.head_probs(q, k, lse, selection, cfg.attn_blk_q,
                          cfg.attn_blk_k)


def index_loss(cfg, aux, mask):
    """``indexer_loss_coef * L_I`` (a ``Decoder``'s ``extra_loss``): every
    indexer's KL (aux ``index_loss`` [indexers, B]), a mean over the rows of
    the sequences of which ``mask`` keeps a token (all, if None); zero where
    no layer with an indexer runs."""
    if "index_loss" not in aux:
        return jnp.float32(0.0)
    per_row = aux["index_loss"].sum(0)  # [B], over the layers with one
    if mask is None:
        return cfg.indexer_loss_coef * per_row.mean()
    rows = (mask.astype(jnp.float32).sum(-1) > 0).astype(jnp.float32)
    return cfg.indexer_loss_coef * (per_row * rows).sum() \
        / jnp.maximum(rows.sum(), 1.0)


def selection_metrics(aux, targets):
    """``dsa_selected_share`` (pairs the selections kept, aux ``selected``
    [indexers], over the causal pairs) and ``dsa_index_loss`` (``L_I`` over
    the whole batch); not numbers where no layer with an indexer runs."""
    if "selected" not in aux:
        return dict.fromkeys(("dsa_selected_share", "dsa_index_loss"),
                             jnp.float32(jnp.nan))
    B, S = targets.shape
    owners = aux["selected"].shape[0]
    return {"dsa_selected_share":
            aux["selected"].sum() / (owners * B * (S * (S + 1) // 2)),
            "dsa_index_loss": aux["index_loss"].sum(0).mean()}


#: What records ``selection_metrics`` in the registry (a family's
#: ``RECORDED_METRICS``).
SELECTION_RECORDED = {
    "dsa_selected_share": unless_nan(
        lambda value: builtin_metrics.train_dsa_selected_share().set(value)),
    "dsa_index_loss": unless_nan(
        lambda value: builtin_metrics.train_dsa_index_loss().set(value)),
}


# -- expert layers --------------------------------------------------------

def held_experts(held, experts: int):
    """A config's ``experts_held`` checked against its number of experts:
    (first, count) as a tuple, or None for all of them."""
    if held is None:
        return None
    first, count = held = tuple(held)
    if first < 0 or count < 1 or first + count > experts:
        raise ValueError(f"experts_held={held} of {experts} experts")
    return held


def expert_leaves(d: int, experts: int, held, width: int,
                  shared_width: int = 0, bias: bool = True,
                  gated: bool = True):
    """The leaves ``expert_ffn`` reads: the router over all ``experts`` and,
    with ``bias``, its correction bias (the published ``expert_bias`` /
    ``e_score_correction_bias``: a buffer of zeros that the gradient never
    moves), the SwiGLUs of ``width`` of the experts ``held`` (a config's
    ``experts_held``: (first, count), or None for all) and, with
    ``shared_width``, the shared experts' as one SwiGLU. Without ``gated``
    an expert, routed or shared, is two matrices (no ``w_gate``)."""
    count = experts if held is None else held[1]
    leaves = {"router": ((d, experts), ("embed", None), 0.02)}
    if bias:
        leaves["router_bias"] = ((experts,), (None,), zeros)
    if gated:
        leaves["w_gate"] = ((count, d, width), ("expert", "embed", "mlp"),
                            0.02)
    leaves.update({
        "w_up": ((count, d, width), ("expert", "embed", "mlp"), 0.02),
        "w_down": ((count, width, d), ("expert", "mlp", "embed"), 0.02),
    })
    if shared_width:
        leaves.update(swiglu_leaves(d, shared_width, "shared_", gated))
    return leaves


def expert_aux(aux, batch_shape):
    """What a block returns of an expert layer, from ``routed_experts``'
    aux over the flattened tokens: ``picked`` [B, S, K], ``group_sizes``
    [held experts], ``asked`` (assignments the router gave them),
    ``within_bound`` (1 where they fit ``ops/moe.py``'s one buffer),
    ``rows_summed`` (rows the way back to tokens read) and, of a softmax
    router, ``picked_mass``, of squared-ReLU experts ``relu2_zero_share``.
    With every expert held the router's
    assignments are all asked, the one buffer holds them and the weighted
    sum reads them all."""
    routed = jnp.int32(aux["picked"].size)
    out = {"picked": aux["picked"].reshape(*batch_shape, -1),
           "group_sizes": aux["group_sizes"],
           "asked": aux.get("asked", routed),
           "within_bound": aux.get("within_bound", jnp.int32(1)),
           "rows_summed": aux.get("rows_summed", routed)}
    out.update({name: aux[name]
                for name in ("picked_mass", "relu2_zero_share")
                if name in aux})
    return out


def expert_ffn(x, layer, *, top_k: int, scaling: float, normalize: bool,
               held, score: str = "sigmoid", activation: str = "silu"):
    """The expert layer on normed x [B, S, d] from a layer's leaves
    (``router``, ``router_bias`` where the family has one, the held experts'
    ``w_gate`` (where they have a gate) / ``w_up`` / ``w_down``,
    ``shared_*`` where it has shared experts, of the routed experts' form):
    (this chip's part of the routed sum, the shared experts or None,
    ``expert_aux``), the sums [B, S, d], for the caller to add in its own
    order. ``held``, ``score`` and ``activation`` are ``ops/moe.py``'s."""
    from ray_tpu.ops.moe import routed_experts
    B, S, d = x.shape
    routed, aux = routed_experts(
        x.reshape(B * S, d), layer["router"], layer.get("router_bias"),
        layer.get("w_gate"), layer["w_up"], layer["w_down"],
        top_k=top_k, scaling=scaling, normalize=normalize, held=held,
        score=score, activation=activation)
    shared = None
    if "shared_w_gate" in layer:
        with jax.named_scope("shared_expert"):
            shared = swiglu(x, layer["shared_w_gate"], layer["shared_w_up"],
                            layer["shared_w_down"])
    elif "shared_w_up" in layer:
        with jax.named_scope("shared_expert"):
            shared = mlp(x, layer["shared_w_up"], layer["shared_w_down"],
                         activation)
    aux = expert_aux(aux, (B, S))
    return routed.reshape(B, S, d), shared, aux


def no_expert_parallelism(family: str):
    """Raises on a mesh with ``ep`` > 1: no family here exchanges tokens."""
    from ray_tpu.parallel.mesh import current_mesh
    mesh = current_mesh()
    if mesh is not None and mesh.shape.get("ep", 1) > 1:
        raise NotImplementedError(
            f"models/{family}.py does not implement expert parallelism: the "
            "mesh has ep > 1, and the expert layer (ops/moe.py) computes "
            "the experts held here without an exchange. Use ep=1 (fsdp and "
            "tp shard the expert weights).")


#: Metrics of ``moe_metrics`` that count a batch: summed over accumulation
#: microbatches where the others are averaged (parallel/train_step.py reads
#: ``SUMMED_METRICS`` and ``RECORDED_METRICS`` off the module that defines
#: a config's type, so a family binds these there).
SUMMED_METRICS = ("moe_assignments", "moe_tokens", "moe_routed",
                  "moe_rows_summed", "moe_calls", "moe_calls_within_bound")

#: Metrics of ``moe_metrics`` that feed the registry, each with what records
#: its value there (parallel/train_step.py reads them without a sync).
RECORDED_METRICS = {
    "moe_assignments":
        lambda value: builtin_metrics.train_moe_assignments().inc(value),
    "moe_tokens":
        lambda value: builtin_metrics.train_moe_tokens().inc(value),
    "moe_routed":
        lambda value: builtin_metrics.train_moe_routed().inc(value),
    "moe_rows_summed":
        lambda value: builtin_metrics.train_moe_rows_summed().inc(value),
    "moe_calls":
        lambda value: builtin_metrics.train_moe_calls().inc(value),
    "moe_calls_within_bound":
        lambda value: builtin_metrics.train_moe_calls_within_bound().inc(
            value),
    "moe_load_max_over_mean":
        lambda value: builtin_metrics.train_moe_expert_load().set(value),
}


def moe_metrics(aux, routed_a_layer: int) -> Dict[str, jax.Array]:
    """What the expert layers did, from ``Decoder.hidden_states``' aux
    (``expert_aux`` stacked over the expert layers): ``moe_routed`` (every
    assignment the router made: ``routed_a_layer``, tokens x experts per
    token, times the expert layers), ``moe_tokens`` (those it gave to
    experts held here), ``moe_assignments`` (rows the grouped matmuls
    computed: equal to ``moe_tokens``, or something was dropped),
    ``moe_rows_summed`` (rows the way back to tokens read: ``moe_tokens``
    where ``ops/moe.py``'s kernel ran, ``moe_routed`` a buffer where its
    gathers did), ``moe_calls`` and ``moe_calls_within_bound`` (expert
    layers, and those whose share fit one buffer) and
    ``moe_load_max_over_mean`` (the busiest held expert's load over the held
    experts' mean, worst layer). {} of a model without an expert layer."""
    if "group_sizes" not in aux:
        return {}
    sizes = aux["group_sizes"].astype(jnp.float32)  # [L_moe, held]
    calls = sizes.shape[0]
    return {
        "moe_assignments": sizes.sum(),
        "moe_tokens": aux["asked"].astype(jnp.float32).sum(),
        "moe_routed": jnp.float32(routed_a_layer * calls),
        "moe_rows_summed": aux["rows_summed"].astype(jnp.float32).sum(),
        "moe_calls": jnp.float32(calls),
        "moe_calls_within_bound":
            aux["within_bound"].astype(jnp.float32).sum(),
        "moe_load_max_over_mean": (
            sizes.max(-1) / jnp.maximum(sizes.mean(-1), 1e-9)).max(),
    }


# -- head and loss --------------------------------------------------------

def ce_stats(logits: jax.Array, targets: jax.Array, mask: jax.Array,
             z_loss: float, per: int = 0) -> Tuple[jax.Array, jax.Array]:
    """fp32 CE pieces for one [..., vocab] logits slab → (Σ nll·m, Σ hit·m),
    the sums over all of targets' axes but the last ``per`` (prediction
    heads, each with a sum of its own)."""
    logits = logits.astype(jnp.float32)
    logz = jax.scipy.special.logsumexp(logits, axis=-1)
    tgt_logit = jnp.take_along_axis(
        logits, targets[..., None], axis=-1)[..., 0]
    nll = logz - tgt_logit
    if z_loss:
        nll = nll + z_loss * logz ** 2
    hits = (logits.argmax(-1) == targets).astype(jnp.float32)
    over = tuple(range(nll.ndim - per))
    return (nll * mask).sum(over), (hits * mask).sum(over)


def chunked_ce(head, x: jax.Array, targets: jax.Array, mask32: jax.Array,
               chunk: int, z_loss: float = 0.0,
               weight: Optional[jax.Array] = None
               ) -> Tuple[jax.Array, jax.Array]:
    """(weight · Σ nll·mask, Σ hit·mask) of ``head(x)`` against targets, in
    fp32; ``weight`` is a scalar no gradient moves (None: 1).

    ``head`` maps hidden [..., d] to logits [..., vocab]; x is [B, S, d].
    Where one hidden state feeds several prediction heads, ``head`` gives
    [..., heads, vocab], targets and mask32 are [B, S, heads] and ``weight``
    may be [heads]: the result is then (Σ over heads of weight · Σ nll·mask,
    (weight · Σ nll·mask, Σ hit·mask) a head, which move no gradient): one
    walk and one cotangent for all the heads (``_totals``).
    With ``chunk > 0`` the head matmul and the fp32 softmax run ``chunk``
    tokens at a time under a lax.scan, so the [tokens, vocab] fp32 logits
    never exist whole. A chunk is a slice of S across the whole
    batch, [B, S / n, d]: the scanned dimension is not the one the batch's
    sharding lies on, so every data shard walks its own tokens and no chip
    sees another's (chunks of whole rows put the sharding on the scanned
    dimension, and the partitioner then splits d and sums every chunk's
    logits instead).

    The chunked path is a ``custom_vjp`` (``_chunked_sums``) and nothing in
    it is rematerialised: the loss is the last thing the forward pass does
    and its cotangent on the logits is a function of the logits alone, so
    when a gradient is asked the one walk over the chunks takes each
    chunk's ``jax.vjp`` while its logits are there, sums the cotangents of
    what ``head`` reads and lays out x's, and the backward pass only scales
    them: three vocabulary-wide products a chunk (the head, d x, d W), where
    autodiff of a checkpointed scan runs the head twice. What ``head``
    closes over that a gradient can move (its parameters) is hoisted by
    ``jax.closure_convert`` into explicit arguments of the ``custom_vjp``,
    which cannot close over them: callers hand over a closure as before.
    Reverse mode only; forward mode takes ``chunk=0``."""
    with jax.named_scope("head_loss"):
        B, S = targets.shape[:2]
        per = targets.ndim - 2
        weight = jnp.asarray(1.0 if weight is None else weight, jnp.float32)
        if per:
            weight = jnp.broadcast_to(weight, targets.shape[2:])
        if not (chunk and B * S > chunk):
            nll_sum, hit_sum = ce_stats(head(x), targets, mask32, z_loss,
                                        per)
            return _totals(nll_sum, hit_sum, jax.lax.stop_gradient(weight))
        # The fewest slices of S that hold at most ``chunk`` tokens each:
        # where ``chunk`` does not divide, the largest slice under it that
        # does, never the whole logits (the feature's memory bound stands).
        n = next((n for n in range(2, S)
                  if S % n == 0 and B * S // n <= chunk), S)
        head, head_params = jax.closure_convert(head, jax.ShapeDtypeStruct(
            (B * S // n, *x.shape[2:]), x.dtype))
        return _chunked_sums(head, n, z_loss, x, head_params, targets,
                             mask32, weight)


def _slices(n: int, *arrays: jax.Array) -> Tuple[jax.Array, ...]:
    """Each [B, S, ...] as n slices of S, [n, B, S / n, ...]."""
    def slices(a):
        B, S = a.shape[:2]
        a = a.reshape(B, n, S // n, *a.shape[2:]).swapaxes(0, 1)
        return constrain(a, None, "batch", "sequence",
                         *[None] * (a.ndim - 3))
    return tuple(slices(a) for a in arrays)


def _totals(nll_sum, hit_sum, weight):
    """What ``chunked_ce`` returns of the sums: of one head (scalars),
    ``(nll_sum * weight, hit_sum)``; of several ([heads]), the weighted sum
    over the heads, which is what a gradient is taken of, and the pair a
    head beside it."""
    if not nll_sum.ndim:
        return nll_sum * weight, hit_sum
    weighted = nll_sum * weight
    return weighted.sum(), (jax.lax.stop_gradient(weighted), hit_sum)


def _chunk_stats(head, z_loss, head_params, xtm):
    """``ce_stats`` of one slice as ``_slices`` cuts them, on one row of
    tokens [B * S / n, ...], as head and loss see it on one device too."""
    x_c, t_c, m_c = (a.reshape(-1, *a.shape[2:]) for a in xtm)
    return ce_stats(head(x_c, *head_params), t_c, m_c, z_loss, t_c.ndim - 1)


@partial(jax.custom_vjp, nondiff_argnums=(0, 1, 2))
def _chunked_sums(head, n, z_loss, x, head_params, targets, mask32, weight):
    """``chunked_ce``'s chunked path with no gradient asked (evaluation):
    the walk over the chunks, nothing saved."""
    def chunk_stats(carry, xtm):
        nll_sum, hit_sum = _chunk_stats(head, z_loss, head_params, xtm)
        return (carry[0] + nll_sum, carry[1] + hit_sum), None

    (nll_sum, hit_sum), _ = jax.lax.scan(
        chunk_stats, (jnp.zeros(targets.shape[2:], jnp.float32),) * 2,
        _slices(n, x, targets, mask32))
    return _totals(nll_sum, hit_sum, weight)


def _chunked_sums_fwd(head, n, z_loss, x, head_params, targets, mask32,
                      weight):
    """The same walk with a gradient asked: each chunk's ``jax.vjp`` is
    applied to ``weight`` (the cotangent ``weight · Σ nll`` leaves on a
    chunk's Σ nll; Σ hit moves nothing) while its logits are there. The
    carry sums d ``head_params`` in their own dtypes, the scan lays out d x;
    they are all the backward pass needs."""
    def chunk_stats(carry, xtm):
        nll_sum, hit_sum, d_params = carry
        (nll_c, hit_c), vjp = jax.vjp(
            lambda x_c, p: _chunk_stats(head, z_loss, p, (x_c, *xtm[1:])),
            xtm[0], head_params)
        d_x, d_params_c = vjp((weight, jnp.zeros(weight.shape, jnp.float32)))
        d_params = jax.tree.map(jnp.add, d_params, d_params_c)
        return (nll_sum + nll_c, hit_sum + hit_c, d_params), d_x

    zero = jnp.zeros(targets.shape[2:], jnp.float32)
    (nll_sum, hit_sum, d_params), d_x = jax.lax.scan(
        chunk_stats, (zero, zero, jax.tree.map(jnp.zeros_like, head_params)),
        _slices(n, x, targets, mask32))
    d_x = constrain(d_x.swapaxes(0, 1).reshape(x.shape),
                    "batch", "sequence", *[None] * (x.ndim - 2))
    # The barrier costs nothing on the device and says what is true: the
    # backward pass starts from these, whole. Without it the compiler
    # orders GPT-J's layer scan backward differently behind the walk and
    # that step's arena is 0.2 GB larger than it was (off the chip and on).
    return _totals(nll_sum, hit_sum, weight), jax.lax.optimization_barrier(
        (d_x, d_params))


def _chunked_sums_bwd(head, n, z_loss, cotangents, g):
    """g[0] (the constant 1 under ``value_and_grad`` of the loss) times what
    the forward walk formed: no product, no scan."""
    return (*jax.tree.map(lambda d: g[0].astype(d.dtype) * d, cotangents),
            None, None, None)


_chunked_sums.defvjp(_chunked_sums_fwd, _chunked_sums_bwd)


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

    ``head`` maps hidden [..., d] to logits [..., vocab], a closure over
    ``head_gathered``'s params; x is the final hidden states [B, S, d];
    ``chunk`` is ``chunked_ce``'s. The mean's 1 / tokens goes into
    ``chunked_ce`` as its ``weight``, so the cotangents its forward walk
    forms are the loss's own and the backward pass multiplies them by 1."""
    mask32 = jnp.ones(targets.shape, jnp.float32) if mask is None \
        else mask.astype(jnp.float32)
    denom = jnp.maximum(mask32.sum(), 1.0)
    loss, hit_sum = chunked_ce(head, x, targets, mask32, chunk, z_loss,
                               1.0 / denom)
    return loss, {"loss": loss, "accuracy": hit_sum / denom,
                  "perplexity": jnp.exp(jnp.minimum(loss, 20.0))}


def shifted_targets(targets: jax.Array, mask: Optional[jax.Array],
                    heads: int) -> Tuple[jax.Array, jax.Array]:
    """(targets [B, S, heads] int32, mask [B, S, heads] float32) of
    ``heads`` prediction heads from next-token targets [B, S] (``targets[:,
    t]`` is token t + 1): head i at position t predicts token t + 1 + i,
    ``targets[:, t + i]``, under that target's own mask, and nothing where t
    + i is past the sequence (mask 0, target 0)."""
    B, S = targets.shape
    mask32 = jnp.ones((B, S), jnp.float32) if mask is None \
        else mask.astype(jnp.float32)

    def shifted(a, i):
        return jnp.pad(a[:, i:], ((0, 0), (0, i)))

    return (jnp.stack([shifted(targets, i) for i in range(heads)], -1),
            jnp.stack([shifted(mask32, i) for i in range(heads)], -1))


def multi_token_loss(head, x: jax.Array, targets: jax.Array,
                     mask: Optional[jax.Array], chunk: int, heads: int
                     ) -> Tuple[jax.Array, Dict[str, jax.Array]]:
    """The loss of ``heads`` prediction heads on one hidden state: the mean,
    with equal weights, of every head's cross-entropy over the positions
    whose target ``shifted_targets`` keeps -> (loss, {"loss", "accuracy",
    "perplexity": head 0's, the next token's, as ``next_token_loss`` gives
    them; "total_loss": the mean; "mbp_loss_<i>": head i's}).

    ``head`` maps hidden [..., d] to logits [..., heads, vocab]. One walk of
    ``chunked_ce`` over (position, head) rows, each head's 1 / (heads x its
    tokens) as its weight; with one head this is ``next_token_loss``, bit
    for bit."""
    with jax.named_scope("mbp_head"):
        targets, mask32 = shifted_targets(targets, mask, heads)
        denom = jnp.maximum(mask32.sum((0, 1)), 1.0)  # [heads]
        total, (weighted, hit_sum) = chunked_ce(
            head, x, targets, mask32, chunk, 0.0, 1.0 / (heads * denom))
    head_losses = weighted * heads
    return total, {"loss": head_losses[0], "accuracy": hit_sum[0] / denom[0],
                   "perplexity": jnp.exp(jnp.minimum(head_losses[0], 20.0)),
                   "total_loss": total,
                   **{f"mbp_loss_{i}": head_losses[i] for i in range(heads)}}


# -- the decoder's shell --------------------------------------------------

def ones(key, shape):
    return jnp.ones(shape, jnp.float32)


def log_arange(key, shape):
    """log(1..n) along the last axis: a state-space layer's ``A_log`` as
    Mamba publishes it."""
    return jnp.broadcast_to(jnp.log(jnp.arange(
        1, shape[-1] + 1, dtype=jnp.float32)), shape)


def zeros(key, shape):
    return jnp.zeros(shape, jnp.float32)


def _drawn(key, shape, init, dtype):
    """One leaf of a table: ``init`` is the std of a normal draw, or a
    callable ``(key, shape) -> float32 array`` for anything else."""
    if callable(init):
        return init(key, shape).astype(dtype)
    return (jax.random.normal(key, shape, jnp.float32) * init).astype(dtype)


def merged_aux(auxes) -> Dict[str, jax.Array]:
    """One aux for the model from ``scan_blocks``' list of every run's
    (each stacked over the run's layers, or None): under every name any run
    returns (sorted, as a scan hands a dict back), the concatenation over
    the runs that return it, in layer order."""
    auxes = [aux for aux in auxes if aux]
    return {name: jnp.concatenate([aux[name] for aux in auxes if name in aux])
            for name in sorted({name for aux in auxes for name in aux})}


@dataclass(frozen=True)
class Decoder:
    """What a family hands this module to be a language model (the module
    text, "A family"); the methods are then the same code for all of them,
    bound in the family's module under the names the benchmark reads."""
    #: The family's module under ``models/``, for messages.
    name: str
    #: cfg -> the table of leaves, in any grouping ``leaves_of`` reads.
    shapes: Callable
    #: (cfg, kind, h, layer, positions) -> (h, aux or None). A family hands
    #: it over as ``lambda *args: _block(*args)``: the ``_block`` its module
    #: holds when the model is traced, which tests and the benchmark's
    #: fault checks replace.
    block: Callable
    #: (table, kind) -> {leaf: (shape without the layers axis, logical
    #: axes, init)} of one layer of ``kind``, in the order they are drawn:
    #: the table's entry of that name, unless a layer is made of several.
    leaves_of: Callable = lambda table, kind: table[kind]
    #: cfg -> ((name in the parameter tree, kind, layers) of every run).
    runs_of: Callable = lambda cfg: runs(cfg.layers)
    #: The final norm's leaf, and the config key of every norm's epsilon.
    final_norm: str = "lnf_scale"
    eps: str = "rms_norm_eps"
    #: The final norm's bias leaf: the family's norms are LayerNorms
    #: (``layernorm``) and its blocks call that; None: RMSNorms.
    final_norm_bias: Optional[str] = None
    #: The family's blocks hand values on to the runs behind them and take
    #: ``shared`` as their last argument (``scan_blocks``, ``shares``).
    shares: bool = False
    #: (cfg, a run's name in the parameter tree) -> {name: [layers, ...]}:
    #: what the run's blocks read beside their leaves, under those names,
    #: and no gradient moves (a function of where a layer lies in the
    #: published model, say). None: nothing.
    constants: Optional[Callable] = None
    #: The head is ``wte``'s rows again, else a matrix ``lm_head``.
    tied: bool = False
    #: cfg -> what the looked-up rows are multiplied by (or None), and what
    #: the final hidden states are divided by before the head.
    embed_scale: Optional[Callable] = None
    logits_divisor: Optional[Callable] = None
    #: The family has expert layers: a mesh with ``ep`` > 1 is refused.
    experts: bool = False
    #: (cfg, aux, targets) -> the metrics ``loss_fn`` adds to the loss's.
    metrics: Optional[Callable] = None
    #: (cfg, aux, mask) -> a term the blocks' aux carries, added to the
    #: cross-entropy: ``loss_fn`` then returns and differentiates the sum
    #: and reports it as ``total_loss``; ``loss`` stays the cross-entropy,
    #: which ``perplexity`` is of. None: no second term.
    extra_loss: Optional[Callable] = None
    #: cfg -> how many prediction heads read one hidden state (head i
    #: predicts token t + 1 + i): ``lm_head`` is then [d, heads x vocab],
    #: ``head`` returns [..., heads, vocab] and the loss is
    #: ``multi_token_loss``. None: one head, the shapes without the axis.
    pred_heads: Optional[Callable] = None
    #: The final norm's leaf is an offset from one (drawn zero): its scale
    #: is ``1 + leaf``, as the family's blocks read their own.
    unit_offset: bool = False
    #: The head's product comes out in float32, not in ``cfg.dtype``.
    fp32_logits: bool = False
    #: The std ``wte`` and ``lm_head`` are drawn at: cfg -> float.
    top_std: Callable = lambda cfg: 0.02
    #: The family's block is a unit of several layers and rematerialises
    #: each of them itself (``rematerialised``): the scan does not wrap it.
    remat_in_block: bool = False

    def _top(self, cfg):
        """The leaves outside the layer stacks, as a table."""
        v, d, std = cfg.vocab_size, cfg.hidden_size, self.top_std(cfg)
        top = {"wte": ((v, d), ("vocab", "embed"), std),
               self.final_norm: ((d,), ("embed",),
                                 zeros if self.unit_offset else ones)}
        if self.final_norm_bias:
            top[self.final_norm_bias] = ((d,), ("embed",), zeros)
        if not self.tied:
            heads = self.pred_heads(cfg) if self.pred_heads else 1
            top["lm_head"] = ((d, heads * v), ("embed", "vocab"), std)
        return top

    def _stacks(self, cfg):
        """[(name in the parameter tree, layers, one layer's leaves)] of
        every run."""
        table = self.shapes(cfg)
        return [(run, depth, self.leaves_of(table, kind))
                for run, kind, depth in self.runs_of(cfg)]

    def init(self, cfg, key: jax.Array) -> Dict[str, Any]:
        """Parameters in ``cfg.param_dtype`` as the tables say. ``key`` is
        split once a drawn leaf outside the stacks (``wte``, then
        ``lm_head`` where there is one) and once for the stacks; run
        ``index``'s key is ``fold_in`` of that by ``index``, split once a
        leaf in the table's order. Every run of one kind of layer is a
        stack of its own, over a leading layers axis. A seed's parameters
        are a contract (tests/test_init_pinned.py)."""
        top = self._top(cfg)
        drawn = [name for name in top
                 if name not in (self.final_norm, self.final_norm_bias)]
        *k_top, k_layers = jax.random.split(key, len(drawn) + 1)
        keys = dict(zip(drawn, k_top))
        params = {name: _drawn(keys.get(name), shape, init, cfg.param_dtype)
                  for name, (shape, _, init) in top.items()}
        for index, (run, depth, leaves) in enumerate(self._stacks(cfg)):
            keys = jax.random.split(jax.random.fold_in(k_layers, index),
                                    len(leaves))
            params[run] = {
                name: _drawn(k, (depth,) + shape, init, cfg.param_dtype)
                for k, (name, (shape, _, init)) in zip(keys, leaves.items())}
        return params

    def param_specs(self, cfg, rules: ShardingRules) -> Dict[str, Any]:
        """PartitionSpec pytree matching init()'s structure."""
        specs = {name: rules.spec(*axes)
                 for name, (_, axes, _) in self._top(cfg).items()}
        for run, _, leaves in self._stacks(cfg):
            specs[run] = {name: rules.spec("layers", *axes)
                          for name, (_, axes, _) in leaves.items()}
        return specs

    def hidden_states(self, params: Dict[str, Any], cfg, tokens: jax.Array,
                      positions: Optional[jax.Array] = None):
        """tokens [B, S] int32 -> (final-normed hidden [B, S, d], aux): what
        the blocks return (``merged_aux``), each name stacked over the
        layers that return it, in layer order."""
        if self.experts:
            no_expert_parallelism(self.name)
        if positions is None:
            positions = positions_of(tokens)
        x = embed(params["wte"], tokens, cfg.dtype)  # batch-split
        scale = self.embed_scale(cfg) if self.embed_scale else None
        if scale is not None:
            x = x * jnp.asarray(scale, cfg.dtype)
        # A stack without layers (a model with no leading dense layer)
        # is in the tree and not in the scan.
        stacks = [run for run in self.runs_of(cfg) if run[2]]
        layers = [params[run] for run, _, _ in stacks]
        if self.constants:
            layers = [dict(stack, **self.constants(cfg, run))
                      for stack, (run, _, _) in zip(layers, stacks)]
        x, auxes = scan_blocks(
            cfg, {kind: partial(self.block, cfg, kind)
                  for _, kind, _ in stacks}, x, layers, positions,
            runs=[(kind, depth) for _, kind, depth in stacks],
            shares=self.shares, remat_in_block=self.remat_in_block)
        x = constrain(x, "batch", "sequence", None)
        aux = merged_aux(auxes)
        eps = getattr(cfg, self.eps)
        if self.final_norm_bias:
            return layernorm(x, params[self.final_norm],
                             params[self.final_norm_bias], eps), aux
        scale = params[self.final_norm]
        if self.unit_offset:
            scale = 1.0 + scale.astype(jnp.float32)
        return rmsnorm(x, scale, eps), aux

    def head(self, params: Dict[str, Any], cfg, x: jax.Array):
        """Logits [..., vocab] of final-normed hidden states x [..., d].
        Where the logits are divided the hidden states are, not the
        logits: the same numbers, and no second pass over a [tokens, vocab]
        array."""
        if self.logits_divisor:
            x = x / jnp.asarray(self.logits_divisor(cfg), x.dtype)
        if self.tied:
            return jnp.einsum("...d,vd->...v", x,
                              params["wte"].astype(cfg.dtype))
        if not (self.pred_heads or self.fp32_logits):
            return jnp.einsum("...d,dv->...v", x,
                              params["lm_head"].astype(cfg.dtype))
        logits = jnp.einsum(
            "...d,dv->...v", x, params["lm_head"].astype(cfg.dtype),
            preferred_element_type=jnp.float32 if self.fp32_logits else None)
        if self.pred_heads:
            logits = logits.reshape(*logits.shape[:-1], self.pred_heads(cfg),
                                    cfg.vocab_size)
        return logits

    def forward_with_aux(self, params: Dict[str, Any], cfg,
                         tokens: jax.Array,
                         positions: Optional[jax.Array] = None):
        """tokens [B, S] -> (logits [B, S, vocab], aux of
        ``hidden_states``)."""
        x, aux = self.hidden_states(params, cfg, tokens, positions)
        return self.head(params, cfg, x), aux

    def forward(self, params: Dict[str, Any], cfg, tokens: jax.Array,
                positions: Optional[jax.Array] = None) -> jax.Array:
        return self.forward_with_aux(params, cfg, tokens, positions)[0]

    def loss_of_hidden(self, params: Dict[str, Any], cfg, x: jax.Array, aux,
                       targets: jax.Array, mask: Optional[jax.Array] = None
                       ) -> Tuple[jax.Array, Dict[str, jax.Array]]:
        """``loss_fn`` from ``hidden_states``' result (x [B, S, d], aux)."""
        head = partial(self.head, head_gathered(params, self.tied), cfg)
        if self.pred_heads:
            loss, metrics = multi_token_loss(
                head, x, targets, mask, cfg.loss_chunk, self.pred_heads(cfg))
        else:
            loss, metrics = next_token_loss(head, x, targets, mask,
                                            cfg.loss_chunk, 0.0)
        if self.extra_loss:
            loss = loss + self.extra_loss(cfg, aux, mask)
            metrics = {**metrics, "total_loss": loss}
        if self.metrics:
            metrics = {**metrics, **self.metrics(cfg, aux, targets)}
        return loss, metrics

    def loss_fn(self, params: Dict[str, Any], cfg, tokens: jax.Array,
                targets: jax.Array, mask: Optional[jax.Array] = None
                ) -> Tuple[jax.Array, Dict[str, jax.Array]]:
        """Next-token cross-entropy in fp32 (chunked by ``cfg.loss_chunk``),
        no balance term, plus the family's ``extra_loss`` where it has one,
        with the family's ``metrics``."""
        x, aux = self.hidden_states(params, cfg, tokens)
        return self.loss_of_hidden(params, cfg, x, aux, targets, mask)
