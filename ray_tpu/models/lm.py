"""What a causal language model of this package is made of, and no model
owns: the embedding lookup, the layer scan with rematerialisation, the
attention dispatch (which attention runs, and how it is laid over a mesh),
the state-space scan's, and the chunked head and loss. ``models/gpt.py``,
``models/deepseek.py``, ``models/granite.py`` and ``models/afmoe.py`` are
built from these; a new family brings its config, parameters, block and head
and is written against this module, not against another model.

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

from ray_tpu.parallel.sharding import ambient_spec, constrain


# -- lookup and layer scan ------------------------------------------------

def positions_of(tokens: jax.Array) -> jax.Array:
    """Positions 0..S-1 for every row of tokens [B, S]."""
    B, S = tokens.shape
    return jnp.broadcast_to(jnp.arange(S, dtype=jnp.int32), (B, S))


def embed(wte, tokens, dtype):
    """wte[tokens] in ``dtype``, batch-split: the residual stream is split
    by batch (and sequence, under context parallelism) from the lookup to
    the loss and says so at both ends of the layer scan; derived from the
    weights it would be d over fsdp, resharded wherever an operation wants
    the batch split. The lookup itself leaves d split as the table has it.
    Stated so first, rows are cut where they lie, and the change that
    follows is one all-to-all over those axes (from the lookup's own
    layout the partitioner can only replicate x whole when dp and fsdp
    are both above 1)."""
    x = jnp.take(wte, tokens, axis=0).astype(dtype)
    x = constrain(x, "batch", "sequence", "embed")
    return constrain(x, "batch", "sequence", None)


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
        from ray_tpu._private.jax_compat import shard_map
        q_spec, kv_spec = attention_specs(
            mesh, q.shape[2], k.shape[2], seq_axis=None)
        if kv_spec[2] is None:
            q_spec = kv_spec
        return shard_map(fn, mesh=mesh, in_specs=(q_spec, kv_spec, kv_spec),
                         out_specs=q_spec, check_vma=False)(q, k, v)
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


# -- state-space scan -----------------------------------------------------

def state_space(u, dt, A, B, C, D, chunk: int):
    """The Mamba-2 recurrence ``S_t = exp(dt_t A) S_(t-1) + dt_t u_t B_t^T``,
    ``y_t = S_t C_t + D u_t`` by ``ops/ssd.py``'s chunked scan. u: [B, S, H,
    P], dt: [B, S, H] (positive), A, D: [H], B, C: [B, S, N] -> [B, S, H,
    P]. Under a mesh the kernels run per shard, as the flash kernels do:
    the recurrence is independent per batch row, so each shard scans its
    own rows whole, with every head."""
    from ray_tpu.ops.ssd import ssd
    from ray_tpu.parallel.mesh import current_mesh
    fn = partial(ssd, chunk=chunk)
    mesh = current_mesh()
    if mesh is None or mesh.size == 1:
        return fn(u, dt, A, B, C, D)
    from ray_tpu._private.jax_compat import shard_map
    batch = ambient_spec(mesh, "batch")[0]
    rows = lambda rank: PartitionSpec(batch, *[None] * (rank - 1))
    head = PartitionSpec(None)
    return shard_map(fn, mesh=mesh,
                     in_specs=(rows(4), rows(3), head, rows(3), rows(3), head),
                     out_specs=rows(4), check_vma=False)(u, dt, A, B, C, D)


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
