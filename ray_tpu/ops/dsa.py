"""Learned sparse attention (DeepSeek Sparse Attention, ``glm_moe_dsa``): an
indexer scores every causal pair, each query keeps its ``topk`` best keys,
and the main attention runs over those alone.

Four pieces; the three that make products have a ``jax.numpy`` form beside
the kernels the chip runs (``dot_index_scores``, ``dot_selected_attention``,
``dot_head_probs``):

* ``index_scores``: ``I[t, s] = sum_j w[t, j] ReLU(q[t, j] . k[s])`` for
  ``s <= t``, float32, ``-inf`` above the diagonal. Differentiable in q, k
  and w: the indexer's loss trains it. Two kernels under a ``custom_vjp``
  whose residuals are q, k and w alone. ``dsa_index_fwd`` has a step for
  every ``[blk_q, blk_k]`` tile of the scores: it holds the tile's queries
  of every head, and a device loop over the heads adds ``ReLU(k q_j^T)
  w_j`` into a float32 sum in registers, a piece of the tile's keys at a
  time; a piece above the diagonal is filled with ``-inf`` and makes no
  product. ``dsa_index_bwd`` walks the causal tiles alone, Q-major: a head
  of a tile makes its product again and from it ``dP_j = (P_j > 0) dI
  w_j``, ``dq_j += dP_j k``, ``dk += dP_j^T q_j`` and ``dw_j += sum_s dI
  ReLU(P_j)``; the sums for dq and dw stay in VMEM for a row of tiles, dk's
  ``[S, E]`` for the whole sequence. A head's ``[blk_q, blk_k]`` products
  exist in VMEM and nowhere else, forward or backward; the ``jax.numpy``
  form writes a block of query rows' ``[rows, heads, S]`` to memory and
  rematerialises it in the backward pass.
* ``select``: the selection ``[B, S, S]`` int8, 1 where ``I[t, s]`` is among
  the ``topk`` largest of row t (every causal key while ``t < topk``). No
  sort: a row's ``topk``-th largest score is found exactly by 32 passes of
  compare-and-count over the scores' bits (one bit of the answer a pass,
  from the top), and the selection is ``I >= that`` under the causal edge.
  Float32 scores do not tie, so a row keeps exactly ``min(t + 1, topk)``.
  Nothing differentiates it.
* ``selected_attention``: softmax attention of q over the selected keys,
  ``(out, lse)``. On the chip, ``ops/flash_attention.py``'s three kernels
  with one more operand, the selection's ``[blk_q, blk_k]`` tile, where
  those build the causal mask from positions: the same table of causal
  tile pairs (the selection is causal), the same online softmax, the same
  two backward kernels, under names of their own (``dsa_fwd``,
  ``dsa_bwd_dq``, ``dsa_bwd_dkv``). A tile in which nothing is selected
  keeps its grid step and does nothing in it (a scalar-prefetched flag a
  pair, counted from the selection in the step). ``lse`` goes out for
  ``head_probs``; no cotangent comes back through it.
* ``head_probs``: the main attention's probabilities summed over the heads,
  ``p[t, s] = sum_h exp(q_h[t] . k_h[s] * scale - lse_h[t])`` on the
  selection, which the forward kernel never forms: one more kernel
  (``dsa_probs``), the heads the inner grid axis and a ``[blk_q, blk_k]``
  float32 tile of p resident across them. ``index_loss`` is then the
  indexer's own term, ``mean_t KL(p[t] / sum p[t] || softmax_{S_t} I[t])``,
  under a rule of its own: its forward pass makes the gradient by the
  scores, [B, S, S] float32, and names it (``LOSS_GRADIENT_NAME``), so that
  a rematerialised block (``models/lm.py`` ``scan_blocks``) keeps 4 S^2
  bytes a layer and its second forward pass holds neither this kernel nor
  ``dsa_index_fwd``: ``H x D / 8`` of this kernel's products a kept byte
  whatever S (85 ms of step a GB at 128 heads of 192, where
  ``flash_attention.worth_keeping``'s line is 21).

The kernels run whole sequences of one device; under a mesh of more than
one the caller splits the batch itself (``models/glm_moe_dsa.py`` refuses).
"""

from __future__ import annotations

import functools
import math
import sys

import jax
import jax.numpy as jnp
import numpy as np
from jax.ad_checkpoint import checkpoint_name
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ray_tpu.ops.flash_attention import (
    _NEG_INF, _STAT_LANES, RESIDUAL_NAMES, _from_bh, _lanes, _pick_block,
    _row_ends, _score_scale, _tile_pairs, _to_bh, worth_keeping)

#: The name the selection carries among a block's values: a rematerialisation
#: policy that keeps it (``models/lm.py`` ``scan_blocks``) does not search the
#: thresholds a second time in the backward pass, and the backward pass
#: attends over the very keys the forward pass did.
SELECTION_NAME = "dsa_selection"

#: The name of ``index_loss``'s gradient by the scores, the one array its
#: backward pass reads: a rematerialisation policy that keeps it runs neither
#: ``head_probs`` nor the scores' forward kernel a second time.
LOSS_GRADIENT_NAME = "dsa_index_loss_gradient"

#: Query rows a block of ``dot_index_scores``.
INDEX_ROWS = 256


# -- the indexer ----------------------------------------------------------

def dot_index_scores(q, k, w, rows: int = INDEX_ROWS):
    """``index_scores`` written out: a block of ``rows`` query rows at a
    time (``lax.map``, each block rematerialised in the backward pass), so
    the ``[rows, heads, S]`` products of one block exist and never those of
    a sequence."""
    B, S, J, E = q.shape
    rows = min(rows, S)
    while S % rows:
        rows //= 2
    n = S // rows
    q_blocks = q.reshape(B, n, rows, J, E).swapaxes(0, 1)
    w_blocks = w.reshape(B, n, rows, J).swapaxes(0, 1)

    @jax.checkpoint
    def block(at):
        start, q_b, w_b = at
        dots = jnp.einsum("bqje,bke->bqjk", q_b, k,
                          preferred_element_type=jnp.float32)
        scores = (jax.nn.relu(dots) * w_b[..., None]).sum(2)
        t = start + jax.lax.broadcasted_iota(jnp.int32, (rows, S), 0)
        s = jax.lax.broadcasted_iota(jnp.int32, (rows, S), 1)
        return jnp.where(s <= t, scores, -jnp.inf)

    out = jax.lax.map(block, (jnp.arange(n, dtype=jnp.int32) * rows,
                              q_blocks, w_blocks))
    return out.swapaxes(0, 1).reshape(B, S, S)


def _ordered_bits(x):
    """Float32 as uint32 in the same order: a larger float, a larger
    integer (negatives have every bit turned, the others their sign)."""
    bits = jax.lax.bitcast_convert_type(x, jnp.uint32)
    return bits ^ jnp.where(bits >> 31 == 1, jnp.uint32(0xFFFFFFFF),
                            jnp.uint32(0x80000000))


def select(scores, topk: int):
    """scores [B, S, S] float32 (``index_scores``) -> the selection [B, S,
    S] int8: 1 at the ``topk`` largest causal scores of every row, all of
    them where a row has no more."""
    B, S, _ = scores.shape
    t = jax.lax.broadcasted_iota(jnp.int32, (S, S), 0)
    s = jax.lax.broadcasted_iota(jnp.int32, (S, S), 1)
    causal = s <= t
    bits = jnp.where(causal, _ordered_bits(scores), jnp.uint32(0))
    if topk >= S:
        return jnp.broadcast_to(causal, scores.shape).astype(jnp.int8)
    # Rows before topk - 1 keep every causal key: only the others search.
    late = bits[:, topk - 1:]

    def one_bit(i, floor):
        raised = floor | (jnp.uint32(1) << jnp.asarray(31 - i, jnp.uint32))
        enough = (late >= raised[..., None]).sum(-1) >= topk
        return jnp.where(enough, raised, floor)

    floor = jax.lax.fori_loop(
        0, 32, one_bit, jnp.zeros(late.shape[:2], jnp.uint32))
    floor = jnp.concatenate(
        [jnp.zeros((B, topk - 1), jnp.uint32), floor], axis=1)
    return ((bits >= floor[..., None]) & causal).astype(jnp.int8)


def _kl(scores, probs, selection):
    """``index_loss`` as autodiff reads it."""
    chosen = selection != 0
    masked = jnp.where(chosen, scores, -jnp.inf)
    log_q = masked - jax.scipy.special.logsumexp(masked, -1, keepdims=True)
    p = probs / jnp.maximum(probs.sum(-1, keepdims=True), 1e-30)
    terms = jnp.where(chosen & (p > 0),
                      p * (jnp.log(jnp.maximum(p, 1e-38))
                           - jnp.where(chosen, log_q, 0.0)), 0.0)
    return terms.sum(-1).mean(-1)


@jax.custom_vjp
def index_loss(scores, probs, selection):
    """``KL(p / sum p || softmax over the selection of I)`` a row, its mean
    over a sequence's rows: [B]. scores, probs [B, S, S] float32 (probs:
    ``head_probs``, no gradient), selection [B, S, S]. Differentiable in
    the scores, by a rule that makes the gradient in the forward pass:
    ``(softmax over the selection of I - p / sum p) / S`` on the selection
    and zero off it, as autodiff writes it, under ``LOSS_GRADIENT_NAME``.
    The backward pass is that array times the loss's cotangent a batch row
    and reads nothing else of the block."""
    return _kl(scores, probs, selection)


def _index_loss_fwd(scores, probs, selection):
    loss, pulled = jax.vjp(lambda s: _kl(s, probs, selection), scores)
    # A batch row's loss reads its own scores alone.
    by_scores, = pulled(jnp.ones_like(loss))
    return loss, checkpoint_name(by_scores, LOSS_GRADIENT_NAME)


def _index_loss_bwd(by_scores, g):
    return by_scores * g[:, None, None], None, None


index_loss.defvjp(_index_loss_fwd, _index_loss_bwd)


# -- the main attention over the selection: jax.numpy ---------------------

def dot_selected_attention(q, k, v, selection, scale=None):
    """q, k [B, S, H, D], v [B, S, H, Dv], selection [B, S, S] -> (out [B,
    S, H, Dv], lse [B, H, S]); fp32 softmax over the selected keys."""
    scale = _score_scale(scale, q.shape[-1])
    logits = (jnp.einsum("bqhd,bkhd->bhqk", q, k) * scale).astype(jnp.float32)
    logits = jnp.where(selection[:, None] != 0, logits, _NEG_INF)
    lse = jax.scipy.special.logsumexp(logits, axis=-1)
    probs = jnp.exp(logits - lse[..., None]).astype(q.dtype)
    return jnp.einsum("bhqk,bkhd->bqhd", probs, v), lse


def dot_head_probs(q, k, lse, selection, scale=None):
    """``head_probs`` written out."""
    scale = _score_scale(scale, q.shape[-1])
    logits = (jnp.einsum("bqhd,bkhd->bhqk", q, k) * scale).astype(jnp.float32)
    probs = jnp.exp(logits - lse[..., None]).sum(1)
    return jnp.where(selection != 0, probs, 0.0)


# -- the kernels ----------------------------------------------------------

def _interpret() -> bool:
    """As the flash kernels decide it, asked of their module at every call
    (``ray_tpu.ops`` binds the function ``flash_attention`` over the
    module's name): what steers them to the chip's compiler off the chip
    (``tests/test_chip_compile.py``, ``benchmark/rehearse.py``) steers
    these."""
    return sys.modules["ray_tpu.ops.flash_attention"]._interpret()


def _q_tile(b, t, qi_tab, ki_tab, live_tab):
    return b, qi_tab[t], 0


def _kv_tile(b, t, qi_tab, ki_tab, live_tab):
    return b, ki_tab[t], 0


def _q_row(b, t, qi_tab, ki_tab, live_tab):
    return b, 0, qi_tab[t]


def _chosen(sel_ref):
    """The selection's tile as a mask over a float32 tile."""
    return sel_ref[...].astype(jnp.int32) != 0


def _fwd_kernel(qi_tab, ki_tab, live_tab, q_ref, k_ref, v_ref, sel_ref,
                o_ref, lse_ref, m_scr, l_scr, o_scr, *, blk_k: int,
                scale: float):
    """``flash_attention._flash_fwd_kernel`` with the selection's tile for
    the causal mask. A row of tiles may open on tiles in which some query
    row has nothing selected: its m is then still -1e30, p is 1 and l and o
    gather what they should not, until the row's first selected key (every
    row has one) makes corr = exp(-1e30 - m_new) = 0.0 exactly and wipes
    both, as under a window there."""
    t = pl.program_id(1)
    first, last = _row_ends(qi_tab)
    Dv = o_scr.shape[1]

    @pl.when(first)
    def _():
        m_scr[...] = jnp.full(m_scr.shape, _NEG_INF, jnp.float32)
        l_scr[...] = jnp.zeros(l_scr.shape, jnp.float32)
        o_scr[...] = jnp.zeros(o_scr.shape, jnp.float32)

    @pl.when(live_tab[t] > 0)
    def _():
        q = q_ref[...].astype(jnp.float32) * scale
        k_blk = k_ref[...].astype(jnp.float32)
        v_blk = v_ref[...].astype(jnp.float32)
        logits = jax.lax.dot_general(
            q, k_blk, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32)
        logits = jnp.where(_chosen(sel_ref), logits, _NEG_INF)
        m = m_scr[...]
        m_new = jnp.maximum(m, logits.max(-1, keepdims=True))
        corr = jnp.exp(m - m_new)
        p = jnp.exp(logits - _lanes(m_new, blk_k))
        l_scr[...] = l_scr[...] * corr + p.sum(-1, keepdims=True)
        o_scr[...] = o_scr[...] * _lanes(corr, Dv) + jax.lax.dot_general(
            p, v_blk, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        m_scr[...] = m_new

    @pl.when(last)
    def _():
        l_safe = jnp.maximum(l_scr[...], 1e-30)
        o_ref[...] = (o_scr[...] / _lanes(l_safe, Dv)).astype(o_ref.dtype)
        lse_ref[...] = (m_scr[...] + jnp.log(l_safe))[:, 0][None, :]


def _probabilities(q_ref, k_ref, lse_ref, sel_ref, scale):
    """(p [blk_q, blk_k] on the selection, the scaled q tile, the k tile)."""
    q = q_ref[...].astype(jnp.float32) * scale
    k_blk = k_ref[...].astype(jnp.float32)
    logits = jax.lax.dot_general(
        q, k_blk, (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32)
    p = jnp.exp(logits - lse_ref[0, :][:, None])
    return jnp.where(_chosen(sel_ref), p, 0.0), q, k_blk


def _bwd_dq_kernel(qi_tab, ki_tab, live_tab, q_ref, k_ref, v_ref, g_ref,
                   lse_ref, delta_ref, sel_ref, dq_ref, dq_scr, *,
                   scale: float):
    t = pl.program_id(1)
    first, last = _row_ends(qi_tab)

    @pl.when(first)
    def _():
        dq_scr[...] = jnp.zeros(dq_scr.shape, jnp.float32)

    @pl.when(live_tab[t] > 0)
    def _():
        p, _, k_blk = _probabilities(q_ref, k_ref, lse_ref, sel_ref, scale)
        dp = jax.lax.dot_general(
            g_ref[...].astype(jnp.float32), v_ref[...].astype(jnp.float32),
            (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32)
        ds = p * (dp - delta_ref[0, :][:, None])
        dq_scr[...] += jax.lax.dot_general(
            ds, k_blk, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)

    @pl.when(last)
    def _():
        dq_ref[...] = (dq_scr[...] * scale).astype(dq_ref.dtype)


def _bwd_dkv_kernel(qi_tab, ki_tab, live_tab, q_ref, k_ref, v_ref, g_ref,
                    lse_ref, delta_ref, sel_ref, dk_ref, dv_ref, dk_scr,
                    dv_scr, *, scale: float):
    t = pl.program_id(1)
    first, last = _row_ends(ki_tab)

    @pl.when(first)
    def _():
        dk_scr[...] = jnp.zeros(dk_scr.shape, jnp.float32)
        dv_scr[...] = jnp.zeros(dv_scr.shape, jnp.float32)

    @pl.when(live_tab[t] > 0)
    def _():
        p, q_blk, _ = _probabilities(q_ref, k_ref, lse_ref, sel_ref, scale)
        g_blk = g_ref[...].astype(jnp.float32)
        dv_scr[...] += jax.lax.dot_general(
            p, g_blk, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        dp = jax.lax.dot_general(
            g_blk, v_ref[...].astype(jnp.float32), (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32)
        ds = p * (dp - delta_ref[0, :][:, None])
        # q_blk carries one factor of scale: scale * ds^T @ q is dk.
        dk_scr[...] += jax.lax.dot_general(
            ds, q_blk, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)

    @pl.when(last)
    def _():
        dk_ref[...] = dk_scr[...].astype(dk_ref.dtype)
        dv_ref[...] = dv_scr[...].astype(dv_ref.dtype)


def _probs_kernel(qi_tab, ki_tab, live_tab, q_ref, k_ref, lse_ref, sel_ref,
                  p_ref, *, scale: float):
    """Grid (batch, pairs, heads), the heads innermost: p's tile stays where
    it is while the heads' q, k and lse tiles pass under it."""
    t, h = pl.program_id(1), pl.program_id(2)

    @pl.when(h == 0)
    def _():
        p_ref[...] = jnp.zeros(p_ref.shape, jnp.float32)

    @pl.when(live_tab[t] > 0)
    def _():
        p_ref[...] += _probabilities(q_ref, k_ref, lse_ref, sel_ref,
                                     scale)[0]


def _live(selection, pairs, blk_q: int, blk_k: int):
    """1 for every pair of the table whose tile selects anything (in any
    row of the batch), int32."""
    B, S, _ = selection.shape
    tiles = (selection != 0).reshape(
        B, S // blk_q, blk_q, S // blk_k, blk_k).any((0, 2, 4))
    return tiles[pairs[0], pairs[1]].astype(jnp.int32)


def _selection_tile(heads: int):
    """The index map of the selection's tile under a grid whose first axis
    walks batch * heads: every head of a batch row reads that row's."""
    return lambda b, t, qi_tab, ki_tab, live_tab: (
        b // heads, qi_tab[t], ki_tab[t])


def _call(kernel, name, grid_lead, pairs, third, in_specs, out_specs,
          out_shape, scratch_shapes, semantics, vmem_limit=None):
    """``kernel`` over ``grid_lead`` with the pairs' two tables scalar-
    prefetched, and a third an entry a pair where a kernel reads one (a
    selection's kernels whether the tile selects anything, ``_live``)."""
    tables = [jnp.asarray(table) for table in pairs]
    tables += [] if third is None else [third]
    call = pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=len(tables), grid=grid_lead,
            in_specs=in_specs, out_specs=out_specs,
            scratch_shapes=scratch_shapes),
        out_shape=out_shape,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=semantics, vmem_limit_bytes=vmem_limit),
        interpret=_interpret(), name=name)
    return functools.partial(call, *tables)


def _blocks(S: int, blk_q: int, blk_k: int):
    blk_q, blk_k = _pick_block(S, blk_q), _pick_block(S, blk_k)
    if blk_q < 128 or blk_k < 128:
        raise ValueError(
            f"the selection's kernels need a sequence length that is a "
            f"multiple of 128, got S={S}: pad it or use attn_impl='dot'")
    return blk_q, blk_k


def _forward(q, k, v, selection, blk_q, blk_k, scale):
    B, S, H, D = q.shape
    Dv = v.shape[-1]
    scale = _score_scale(scale, D)
    blk_q, blk_k = _blocks(S, blk_q, blk_k)
    pairs = _tile_pairs(S, blk_q, blk_k, True, False)
    out, lse = _call(
        functools.partial(_fwd_kernel, blk_k=blk_k, scale=scale), "dsa_fwd",
        (B * H, len(pairs[0])), pairs, _live(selection, pairs, blk_q, blk_k),
        in_specs=[
            pl.BlockSpec((None, blk_q, D), _q_tile),
            pl.BlockSpec((None, blk_k, D), _kv_tile),
            pl.BlockSpec((None, blk_k, Dv), _kv_tile),
            pl.BlockSpec((None, blk_q, blk_k), _selection_tile(H)),
        ],
        out_specs=[
            pl.BlockSpec((None, blk_q, Dv), _q_tile),
            pl.BlockSpec((None, 1, blk_q), _q_row),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((B * H, S, Dv), q.dtype),
            jax.ShapeDtypeStruct((B * H, 1, S), jnp.float32),
        ],
        scratch_shapes=[pltpu.VMEM((blk_q, _STAT_LANES), jnp.float32),
                        pltpu.VMEM((blk_q, _STAT_LANES), jnp.float32),
                        pltpu.VMEM((blk_q, Dv), jnp.float32)],
        semantics=("parallel", "arbitrary"),
    )(_to_bh(q), _to_bh(k), _to_bh(v), selection)
    return _from_bh(out, B, H), lse


def _backward(q, k, v, selection, out, lse, g, blk_q, blk_k, scale):
    B, S, H, D = q.shape
    Dv = v.shape[-1]
    scale = _score_scale(scale, D)
    blk_q, blk_k = _blocks(S, blk_q, blk_k)
    qf, kf, vf, gf, of = (_to_bh(a) for a in (q, k, v, g, out))
    delta = jnp.sum(gf.astype(jnp.float32) * of.astype(jnp.float32),
                    axis=-1)[:, None, :]  # [BH, 1, S]
    operands = (qf, kf, vf, gf, lse, delta, selection)
    in_specs = [
        pl.BlockSpec((None, blk_q, D), _q_tile),
        pl.BlockSpec((None, blk_k, D), _kv_tile),
        pl.BlockSpec((None, blk_k, Dv), _kv_tile),
        pl.BlockSpec((None, blk_q, Dv), _q_tile),
        pl.BlockSpec((None, 1, blk_q), _q_row),
        pl.BlockSpec((None, 1, blk_q), _q_row),
        pl.BlockSpec((None, blk_q, blk_k), _selection_tile(H)),
    ]
    pairs = _tile_pairs(S, blk_q, blk_k, True, False)
    dq = _call(
        functools.partial(_bwd_dq_kernel, scale=scale), "dsa_bwd_dq",
        (B * H, len(pairs[0])), pairs, _live(selection, pairs, blk_q, blk_k),
        in_specs=in_specs,
        out_specs=pl.BlockSpec((None, blk_q, D), _q_tile),
        out_shape=jax.ShapeDtypeStruct((B * H, S, D), q.dtype),
        scratch_shapes=[pltpu.VMEM((blk_q, D), jnp.float32)],
        semantics=("parallel", "arbitrary"),
    )(*operands)
    pairs = _tile_pairs(S, blk_q, blk_k, True, True)
    dk, dv = _call(
        functools.partial(_bwd_dkv_kernel, scale=scale), "dsa_bwd_dkv",
        (B * H, len(pairs[0])), pairs, _live(selection, pairs, blk_q, blk_k),
        in_specs=in_specs,
        out_specs=[
            pl.BlockSpec((None, blk_k, D), _kv_tile),
            pl.BlockSpec((None, blk_k, Dv), _kv_tile),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((B * H, S, D), k.dtype),
            jax.ShapeDtypeStruct((B * H, S, Dv), v.dtype),
        ],
        scratch_shapes=[pltpu.VMEM((blk_k, D), jnp.float32),
                        pltpu.VMEM((blk_k, Dv), jnp.float32)],
        semantics=("parallel", "arbitrary"),
    )(*operands)
    return _from_bh(dq, B, H), _from_bh(dk, B, H), _from_bh(dv, B, H)


@functools.partial(jax.custom_vjp, nondiff_argnums=(4, 5, 6))
def selected_attention(q, k, v, selection, blk_q: int = 512,
                       blk_k: int = 512, scale=None):
    """q, k: [B, S, H, D], v: [B, S, H, Dv], selection: [B, S, S] int8
    (causal, at least one key a row) -> (out [B, S, H, Dv], lse [B, H, S]
    float32), the softmax over the selected keys, scores times ``scale``
    (1/sqrt(D) if None). Differentiable in q, k and v through ``out``;
    ``lse`` is for ``head_probs`` and carries no cotangent back."""
    out, lse = _forward(q, k, v, selection, blk_q, blk_k, scale)
    return out, lse.reshape(q.shape[0], q.shape[2], q.shape[1])


def _fwd(q, k, v, selection, blk_q, blk_k, scale):
    out, lse = _forward(q, k, v, selection, blk_q, blk_k, scale)
    if worth_keeping(q.shape[1], v.shape[-1]):
        out = checkpoint_name(out, RESIDUAL_NAMES[0])
        lse = checkpoint_name(lse, RESIDUAL_NAMES[1])
    B, S, H, _ = q.shape
    return (out, lse.reshape(B, H, S)), (q, k, v, selection, out, lse)


def _bwd(blk_q, blk_k, scale, residuals, cotangents):
    q, k, v, selection, out, lse = residuals
    dq, dk, dv = _backward(q, k, v, selection, out, lse, cotangents[0],
                           blk_q, blk_k, scale)
    return dq, dk, dv, np.zeros(selection.shape, jax.dtypes.float0)


selected_attention.defvjp(_fwd, _bwd)


def head_probs(q, k, lse, selection, blk_q: int = 512, blk_k: int = 512,
               scale=None):
    """q, k [B, S, H, D], lse [B, H, S] (``selected_attention``'s),
    selection [B, S, S] -> p [B, S, S] float32: the probabilities of the
    main attention summed over the heads, 0 off the selection (a row sums
    to H). Not differentiable: the indexer's target."""
    B, S, H, D = q.shape
    scale = _score_scale(scale, D)
    blk_q, blk_k = _blocks(S, blk_q, blk_k)
    pairs = _tile_pairs(S, blk_q, blk_k, True, False)
    sel_tile = lambda b, t, h, qi_tab, ki_tab, live_tab: (
        b, qi_tab[t], ki_tab[t])
    probs = _call(
        functools.partial(_probs_kernel, scale=scale), "dsa_probs",
        (B, len(pairs[0]), H), pairs, _live(selection, pairs, blk_q, blk_k),
        in_specs=[
            pl.BlockSpec((None, blk_q, D), lambda b, t, h, qi_tab, ki_tab,
                         live_tab: (b * H + h, qi_tab[t], 0)),
            pl.BlockSpec((None, blk_k, D), lambda b, t, h, qi_tab, ki_tab,
                         live_tab: (b * H + h, ki_tab[t], 0)),
            pl.BlockSpec((None, 1, blk_q), lambda b, t, h, qi_tab, ki_tab,
                         live_tab: (b * H + h, 0, qi_tab[t])),
            pl.BlockSpec((None, blk_q, blk_k), sel_tile),
        ],
        out_specs=pl.BlockSpec((None, blk_q, blk_k), sel_tile),
        out_shape=jax.ShapeDtypeStruct((B, S, S), jnp.float32),
        scratch_shapes=[],
        semantics=("parallel", "arbitrary", "arbitrary"),
    )(_to_bh(q), _to_bh(k), lse.reshape(B * H, 1, S), selection)
    # Tiles above the diagonal are in no grid step and hold nothing.
    return jnp.where(selection != 0, probs, 0.0)


# -- the indexer's kernels ------------------------------------------------
# A head's products of a tile are made keys down, queries across ([keys,
# queries], the transpose of what is stored): the head's weights are then a
# row, which spreads over the keys for nothing, and dw a sum down the
# columns. q arrives head-major ([B, J, S, E]: a head of a tile is a leading
# index) and w as [B, J, S]; ``index_scores`` turns them.

#: Tiles (queries, keys) of the forward and of the backward kernel, where S
#: has them; the forward works its tile ``_INDEX_PIECE`` keys at a time, and
#: ``_INDEX_TURN`` heads a turn of its loop (fewer where J has no such
#: divisor). By measurement at [8192, 64, 128] and [4096, 32, 128] (my chip
#: runs, PR 65; PERF.md section 6).
_INDEX_FWD_TILE, _INDEX_PIECE, _INDEX_TURN = (128, 1024), 512, 16
_INDEX_BWD_TILE = (512, 512)
#: What a backward step's heads may take of VMEM (their queries and the
#: float32 sums for dq, two buffers each), and the limit the compiler is
#: given for either kernel (a v5e core has 128 MiB).
_INDEX_HEADS_BYTES = 40 * 1024 * 1024
_INDEX_VMEM_LIMIT = 96 * 1024 * 1024


def _index_tile(S: int, E: int, want):
    """``want``'s (blk_q, blk_k) as S has them, or what ``_blocks`` raises;
    the chip's compiler takes heads of whole lanes alone."""
    blk_q, blk_k = _blocks(S, *want)
    if E % 128 and not _interpret():
        raise ValueError(
            f"the indexer's kernels need a head width that is a multiple "
            f"of 128 on the chip, got E={E}: use attn_impl='dot'")
    return blk_q, blk_k


def _index_fwd_blocks(S: int, J: int, E: int):
    """(blk_q, blk_k, keys a piece, heads a turn of the loop)."""
    blk_q, blk_k = _index_tile(S, E, _INDEX_FWD_TILE)
    return blk_q, blk_k, _pick_block(blk_k, _INDEX_PIECE), \
        math.gcd(J, _INDEX_TURN)


def _index_bwd_blocks(S: int, J: int, E: int, itemsize: int):
    """(blk_q, blk_k, heads a grid step): the largest divisor of J whose
    queries and float32 dq sums, twice each, fit ``_INDEX_HEADS_BYTES``."""
    blk_q, blk_k = _index_tile(S, E, _INDEX_BWD_TILE)
    a_head = 2 * blk_q * E * (itemsize + 4)
    heads = max(1, min(J, _INDEX_HEADS_BYTES // a_head))
    while J % heads:
        heads -= 1
    return blk_q, blk_k, heads


def _nt(a, b):
    """a [m, e] . b [n, e]^T -> [m, n] float32."""
    return jax.lax.dot_general(a, b, (((1,), (1,)), ((), ())),
                               preferred_element_type=jnp.float32)


def _is_causal(query, key, rows: int, cols: int):
    """[rows, cols]: whether key + column <= query + row."""
    t = query + jax.lax.broadcasted_iota(jnp.int32, (rows, cols), 0)
    s = key + jax.lax.broadcasted_iota(jnp.int32, (rows, cols), 1)
    return s <= t


def _index_fwd_kernel(qi_tab, ki_tab, load_tab, q_ref, k_ref, w_ref, o_ref,
                      *, blk_q: int, blk_k: int, piece: int, turn: int):
    """A step of grid (batch, tiles): q_ref [J, blk_q, E], k_ref [blk_k, E],
    w_ref [J, blk_q] float32, o_ref [blk_q, blk_k]. Every tile of the
    scores has a step, Q-major. The tile is worked ``piece`` keys at a
    time, ``turn`` heads a turn of a device loop whose carry is the piece's
    float32 sum over the heads; a piece above the diagonal is filled with
    ``-inf`` and makes no product."""
    t = pl.program_id(1)
    query = qi_tab[t] * blk_q
    for at in range(0, blk_k, piece):
        cols = slice(at, at + piece)
        key = ki_tab[t] * blk_k + at
        live = key < query + blk_q

        @pl.when(live)
        def _(cols=cols, key=key):
            k_blk = k_ref[cols, :]

            def heads(i, acc):
                for j in range(turn):
                    j += i * turn
                    acc = acc + jax.nn.relu(_nt(k_blk, q_ref[j])) \
                        * w_ref[pl.ds(j, 1), :]
                return acc

            acc = jax.lax.fori_loop(
                0, q_ref.shape[0] // turn, heads,
                jnp.zeros((piece, blk_q), jnp.float32))
            o_ref[:, cols] = jnp.where(
                _is_causal(query, key, blk_q, piece), acc.T, -jnp.inf)

        @pl.when(jnp.logical_not(live))
        def _(cols=cols):
            o_ref[:, cols] = jnp.full((blk_q, piece), -jnp.inf, jnp.float32)


def _index_bwd_kernel(qi_tab, ki_tab, q_ref, k_ref, w_ref, g_ref, dq_ref,
                      dk_ref, dw_ref, *, blk_q: int, blk_k: int,
                      chunks: int):
    """A step of grid (batch * chunks of heads, causal tiles), Q-major:
    q_ref [heads, blk_q, E], k_ref [blk_k, E], w_ref [heads, blk_q], g_ref
    [blk_q, blk_k] (the scores' cotangent). The three sums are the outputs'
    float32 blocks: dq_ref [heads, blk_q, E] and dw_ref [heads, blk_q] stay
    for a row of tiles, dk_ref [S, E] for a batch row's every step. A head
    of a tile makes ``P = k q^T`` again, ``dP = (P > 0) g^T w`` in the
    operands' dtype, and from it ``dk += dP q`` and ``dq += dP^T k``."""
    t = pl.program_id(1)
    qi, ki = qi_tab[t], ki_tab[t]
    first, _ = _row_ends(qi_tab)

    @pl.when(first)
    def _():
        dq_ref[...] = jnp.zeros(dq_ref.shape, jnp.float32)
        dw_ref[...] = jnp.zeros(dw_ref.shape, jnp.float32)

    @pl.when(jnp.logical_and(t == 0, pl.program_id(0) % chunks == 0))
    def _():
        dk_ref[...] = jnp.zeros(dk_ref.shape, jnp.float32)

    k_blk = k_ref[...]
    # No cotangent belongs to a -inf: whatever came for it is dropped.
    g_t = jnp.where(_is_causal(qi * blk_q, ki * blk_k, blk_q, blk_k),
                    g_ref[...], 0.0).T

    def head(j, dk):
        q_j = q_ref[j]
        p_t = _nt(k_blk, q_j)
        dw_ref[pl.ds(j, 1), :] += (g_t * jnp.maximum(p_t, 0.0)).sum(
            0, keepdims=True)
        dp_t = jnp.where(p_t > 0, g_t * w_ref[pl.ds(j, 1), :],
                         0.0).astype(k_blk.dtype)
        dq_ref[j] += jax.lax.dot_general(
            dp_t, k_blk, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        return dk + jax.lax.dot_general(
            dp_t, q_j, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)

    dk_ref[pl.ds(pl.multiple_of(ki * blk_k, blk_k), blk_k), :] += \
        jax.lax.fori_loop(0, q_ref.shape[0], head,
                          jnp.zeros(k_blk.shape, jnp.float32))


def _index_forward(q, k, w):
    """``index_scores``: the forward kernel on q and w head-major."""
    B, S, J, E = q.shape
    blk_q, blk_k, piece, turn = _index_fwd_blocks(S, J, E)
    qi, ki = _tile_pairs(S, blk_q, blk_k, False, False)
    # A step above the diagonal keeps the row's last tile of keys that has
    # a causal pair where it is: the third table is the tile to load.
    load = np.minimum(ki, ((qi + 1) * blk_q - 1) // blk_k)
    return _call(
        functools.partial(_index_fwd_kernel, blk_q=blk_q, blk_k=blk_k,
                          piece=piece, turn=turn),
        "dsa_index_fwd", (B, len(qi)), (qi, ki), jnp.asarray(load),
        in_specs=[
            pl.BlockSpec((None, J, blk_q, E), lambda b, t, qi_tab, ki_tab,
                         load_tab: (b, 0, qi_tab[t], 0)),
            pl.BlockSpec((None, blk_k, E), lambda b, t, qi_tab, ki_tab,
                         load_tab: (b, load_tab[t], 0)),
            pl.BlockSpec((None, J, blk_q), _q_row),
        ],
        out_specs=pl.BlockSpec((None, blk_q, blk_k), lambda b, t, qi_tab,
                               ki_tab, load_tab: (b, qi_tab[t], ki_tab[t])),
        out_shape=jax.ShapeDtypeStruct((B, S, S), jnp.float32),
        scratch_shapes=[], semantics=("parallel", "arbitrary"),
        vmem_limit=_INDEX_VMEM_LIMIT,
    )(q.transpose(0, 2, 1, 3), k, w.transpose(0, 2, 1))


def _index_backward(q, k, w, g):
    """(dq, dk, dw) of the scores' cotangent g [B, S, S]: the backward
    kernel on q and w head-major, its float32 sums turned back and cast."""
    B, S, J, E = q.shape
    blk_q, blk_k, heads = _index_bwd_blocks(S, J, E, q.dtype.itemsize)
    chunks = J // heads
    pairs = _tile_pairs(S, blk_q, blk_k, True, False)

    def q_tile(i, t, qi_tab, ki_tab):
        return i // chunks, i % chunks, qi_tab[t], 0

    def q_row(i, t, qi_tab, ki_tab):
        return i // chunks, i % chunks, qi_tab[t]

    dq_t, dk, dw_t = _call(
        functools.partial(_index_bwd_kernel, blk_q=blk_q, blk_k=blk_k,
                          chunks=chunks),
        "dsa_index_bwd", (B * chunks, len(pairs[0])), pairs, None,
        in_specs=[
            pl.BlockSpec((None, heads, blk_q, E), q_tile),
            pl.BlockSpec((None, blk_k, E), lambda i, t, qi_tab, ki_tab: (
                i // chunks, ki_tab[t], 0)),
            pl.BlockSpec((None, heads, blk_q), q_row),
            pl.BlockSpec((None, blk_q, blk_k), lambda i, t, qi_tab, ki_tab: (
                i // chunks, qi_tab[t], ki_tab[t])),
        ],
        out_specs=[
            pl.BlockSpec((None, heads, blk_q, E), q_tile),
            pl.BlockSpec((None, S, E), lambda i, t, qi_tab, ki_tab: (
                i // chunks, 0, 0)),
            pl.BlockSpec((None, heads, blk_q), q_row),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((B, J, S, E), jnp.float32),
            jax.ShapeDtypeStruct((B, S, E), jnp.float32),
            jax.ShapeDtypeStruct((B, J, S), jnp.float32),
        ],
        scratch_shapes=[], semantics=("arbitrary", "arbitrary"),
        vmem_limit=_INDEX_VMEM_LIMIT,
    )(q.transpose(0, 2, 1, 3), k, w.transpose(0, 2, 1), g)
    return (dq_t.transpose(0, 2, 1, 3).astype(q.dtype), dk.astype(k.dtype),
            dw_t.transpose(0, 2, 1).astype(w.dtype))


@jax.custom_vjp
def index_scores(q, k, w):
    """q [B, S, J, E], k [B, S, E], w [B, S, J] (float32) -> I [B, S, S]
    float32: ``sum_j w[t, j] ReLU(q[t, j] . k[s])`` where ``s <= t``, else
    ``-inf``. Products in the operands' dtype, everything after them in
    float32. Differentiable in q, k and w: the residuals are the three."""
    return _index_forward(q, k, w)


def _index_fwd(q, k, w):
    return _index_forward(q, k, w), (q, k, w)


def _index_bwd(residuals, g):
    return _index_backward(*residuals, g)


index_scores.defvjp(_index_fwd, _index_bwd)
