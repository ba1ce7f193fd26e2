"""Block-sparse attention by the model's own scores (InfLLM-V2,
arXiv:2509.24663, as MiniCPM4 and ``minicpm_sala``'s ``minicpm4`` mixer run
it): every query keeps ``topk`` blocks of ``block_size`` keys for each KV
group, chosen with no parameter of their own, and attends over those alone.

Five pieces, each ``jax.numpy`` but the last, which has a ``jax.numpy`` form
beside what the chip runs:

* ``compress``: ``Kc[j] = mean(k[stride j : stride j + kernel])`` for every
  whole kernel of the sequence (``S / stride - kernel / stride + 1`` of
  them), float32 sums, in k's dtype.
* ``block_scores``: ``p_h[t, :] = softmax_j(q_h[t] . Kc_g[j] * scale)`` over
  the kernels that lie wholly at or before t, float32; summed over the heads
  of a KV group; max-pooled to blocks (block b takes the kernels that
  overlap it and are visible). A block of query rows at a time (``lax.map``,
  as ``dsa.index_scores``), so the ``[rows, H, S / stride]`` softmax of one
  block exists and never a sequence's. No gradient: q and k enter under
  ``stop_gradient``.
* ``select``: the selection ``[B, G, S, S / block]`` int8. Forced in: the
  first ``init_blocks`` blocks and the ``window / block`` blocks that end at
  the query's own; then the largest scores up to ``topk`` blocks in all
  (every causal block while a row has no more). No sort: ``dsa.select``'s 32
  passes of compare-and-count find the ``topk``-th largest; **a tie at that
  place falls to the lowest block index** (a running count of the tied).
* ``free_mass``: over a stride of query rows, the share of a query's softmax
  sum on blocks that were chosen by score and not forced (a gauge: 0 would
  say the selection does nothing).
* ``selected_attention``: the softmax of q over the keys ``s <= t`` of the
  selected blocks, ``(out, lse)``, the 16 query heads of a group against the
  group's one K/V head where it lies (the index maps send a head to its
  group: no repeated copy of k and v). On the chip, ``ops/dsa.py``'s three
  kernel bodies under names of their own (``sala_fwd``, ``sala_bwd_dq``,
  ``sala_bwd_dkv``) on the causal pair table, with a flag a tile pair for
  tiles in which no query of any group selected a key. **The kernels'
  selection operand is token-level**: the ``[B, G, S, S]`` int8 mask that
  ``token_mask`` widens from the block-level selection, made by XLA in each
  pass that needs it (268 MB a group at 16384, never kept: what remat keeps
  under ``SELECTION_NAME`` is the block-level 8 MB). ``dk`` and ``dv`` come
  out a query head and are summed over a group's heads outside the kernel.
  ``dot_selected_attention`` is the oracle.

The kernels run whole sequences of one device.
"""

from __future__ import annotations

import functools
from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.ad_checkpoint import checkpoint_name
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ray_tpu.ops import dsa
from ray_tpu.ops.dsa import SELECTION_NAME  # noqa: F401 - kept by remat
from ray_tpu.ops.flash_attention import (
    _NEG_INF, _STAT_LANES, RESIDUAL_NAMES, _from_bh, _score_scale,
    _tile_pairs, _to_bh, worth_keeping)

F32 = jnp.float32

#: Query rows a block of ``block_scores``.
SCORE_ROWS = 256
#: Query rows ``free_mass`` samples, evenly spaced with the last.
MASS_ROWS = 64


class Sizes(NamedTuple):
    """A ``sparse_config``: keys a compressed kernel and its stride, keys a
    block, blocks a query keeps, blocks forced at the start, keys of the
    local window (whole blocks, ending at the query's own)."""
    kernel: int = 32
    stride: int = 16
    block: int = 64
    topk: int = 64
    init_blocks: int = 1
    window: int = 2048

    def check(self, S: int) -> None:
        if self.kernel % self.stride or self.block % self.stride \
                or self.window % self.block or S % self.block:
            raise ValueError(
                f"{self} over {S} positions: the stride has to divide the "
                "kernel and the block, the block the window and the "
                "sequence")
        if self.init_blocks + self.window // self.block > self.topk:
            raise ValueError(f"{self}: more forced blocks than topk")


# -- the selection ----------------------------------------------------------

def compress(k, sizes: Sizes):
    """k [B, S, G, D] -> Kc [B, S / stride - kernel / stride + 1, G, D]: the
    mean of every whole kernel."""
    B, S, G, D = k.shape
    pieces = k.astype(F32).reshape(B, S // sizes.stride, sizes.stride, G,
                                   D).sum(2)
    per = sizes.kernel // sizes.stride
    n = pieces.shape[1] - per + 1
    total = sum(pieces[:, i:i + n] for i in range(per))
    return (total / sizes.kernel).astype(k.dtype)


def _pooled(summed, sizes: Sizes, blocks: int):
    """Kernels' scores [..., n] (-1 where invisible) max-pooled to blocks
    [..., blocks]: block b takes the kernels that overlap it, ``block /
    stride`` that start in it and the ``kernel / stride - 1`` before."""
    per, ratio = sizes.kernel // sizes.stride, sizes.block // sizes.stride
    padded = jnp.pad(summed, ((0, 0),) * (summed.ndim - 1)
                     + ((per - 1, per - 1),), constant_values=-1.0)
    return functools.reduce(jnp.maximum, (
        padded[..., o::ratio][..., :blocks]
        for o in range(ratio + per - 1)))


def block_scores(q, kc, sizes: Sizes, scale=None, rows: int = SCORE_ROWS):
    """q [B, S, H, D], kc [B, n, G, D] (``compress``) -> [B, G, S, S /
    block] float32: a block's score for a query and KV group, -1 where none
    of the block's kernels is visible to the query (a visible block scores
    0 or more)."""
    B, S, H, D = q.shape
    n, G = kc.shape[1], kc.shape[2]
    scale = _score_scale(scale, D)
    rows = min(rows, S)
    while S % rows:
        rows //= 2
    blocks = S // sizes.block
    q, kc = jax.lax.stop_gradient((q, kc))
    q_blocks = q.reshape(B, S // rows, rows, G, H // G, D).swapaxes(0, 1)
    last_key = jnp.arange(n, dtype=jnp.int32) * sizes.stride \
        + sizes.kernel - 1

    @jax.checkpoint
    def some_rows(at):
        start, q_b = at
        dots = jnp.einsum("brghd,bjgd->brghj", q_b, kc,
                          preferred_element_type=F32) * scale
        t = start + jnp.arange(rows, dtype=jnp.int32)
        visible = (last_key[None, :] <= t[:, None])[None, :, None, :]
        masked = jnp.where(visible[:, :, :, None], dots, -jnp.inf)
        top = jnp.maximum(masked.max(-1, keepdims=True), _NEG_INF)
        p = jnp.exp(masked - top)
        p = p / jnp.maximum(p.sum(-1, keepdims=True), 1e-30)
        summed = jnp.where(visible, p.sum(3), -1.0)       # [B, rows, G, n]
        return _pooled(summed, sizes, blocks).transpose(0, 2, 1, 3)

    out = jax.lax.map(some_rows, (
        jnp.arange(S // rows, dtype=jnp.int32) * rows, q_blocks))
    return out.transpose(1, 2, 0, 3, 4).reshape(B, G, S, blocks)


def forced(S: int, sizes: Sizes):
    """(causal, forced) [S, S / block] bool: the blocks at or before a
    query's own, and of those the first ``init_blocks`` and the ``window /
    block`` that end at its own."""
    own = (jnp.arange(S, dtype=jnp.int32) // sizes.block)[:, None]
    b = jnp.arange(S // sizes.block, dtype=jnp.int32)[None, :]
    causal = b <= own
    return causal, causal & ((b < sizes.init_blocks)
                             | (b > own - sizes.window // sizes.block))


def select(scores, sizes: Sizes):
    """scores [B, G, S, S / block] float32 (``block_scores``) -> the
    selection, int8 of the same shape, named ``SELECTION_NAME``: module
    text."""
    B, G, S, blocks = scores.shape
    sizes.check(S)
    causal, must = forced(S, sizes)
    topk = sizes.topk
    if topk >= blocks:
        chosen = jnp.broadcast_to(causal, scores.shape)
        return checkpoint_name(chosen.astype(jnp.int8), SELECTION_NAME)
    bits = jnp.where(must, jnp.uint32(0xFFFFFFFF), dsa._ordered_bits(scores))
    bits = jnp.where(causal, bits, jnp.uint32(0))
    # Rows of fewer than topk blocks keep every causal one: the others
    # search (a causal block's bits are above 0, so a floor of 0 keeps all).
    first = (topk - 1) * sizes.block
    late = bits[:, :, first:]

    def one_bit(i, floor):
        raised = floor | (jnp.uint32(1) << jnp.asarray(31 - i, jnp.uint32))
        enough = (late >= raised[..., None]).sum(-1) >= topk
        return jnp.where(enough, raised, floor)

    floor = jax.lax.fori_loop(
        0, 32, one_bit, jnp.zeros(late.shape[:3], jnp.uint32))
    floor = jnp.concatenate(
        [jnp.zeros((B, G, first), jnp.uint32), floor], axis=2)[..., None]
    above = bits > floor
    tied = bits == floor
    room = topk - above.sum(-1, keepdims=True)
    chosen = above | (tied & (jnp.cumsum(tied, axis=-1) <= room))
    return checkpoint_name((chosen & causal).astype(jnp.int8),
                           SELECTION_NAME)


def token_mask(selection, block: int):
    """The selection [B, G, S, S / block] widened to keys: [B, G, S, S]
    int8, 1 where ``s <= t`` and s's block is selected."""
    S = selection.shape[2]
    t = jax.lax.broadcasted_iota(jnp.int32, (S, S), 0)
    s = jax.lax.broadcasted_iota(jnp.int32, (S, S), 1)
    wide = jnp.repeat(selection, block, axis=-1)
    return jnp.where(s <= t, wide, jnp.int8(0))


def selected_pairs_share(selection, block: int):
    """(query, key) pairs the selection attends over the causal pairs: a
    selected block before the query's own is ``block`` keys, its own ``t %
    block + 1``. The mean over batch and groups."""
    S = selection.shape[2]
    t = jnp.arange(S, dtype=jnp.int32)
    own = jnp.take_along_axis(
        selection, jnp.broadcast_to((t // block)[None, None, :, None],
                                    selection.shape[:3] + (1,)), axis=-1)
    keys = (selection.astype(jnp.int32).sum(-1) - own[..., 0]) * block \
        + own[..., 0] * (t % block + 1)
    return keys.astype(F32).sum(-1).mean() / (S * (S + 1) / 2)


def free_mass(q, k, selection, sizes: Sizes, scale=None,
              rows: int = MASS_ROWS):
    """The mean over ``rows`` evenly spaced query rows (the last among
    them), every head and batch row, of the share of the softmax sum over
    the selected keys that lies on blocks not forced. No gradient."""
    B, S, H, D = q.shape
    G = k.shape[2]
    scale = _score_scale(scale, D)
    q, k = jax.lax.stop_gradient((q, k))
    at = S - 1 - jnp.arange(min(rows, S), dtype=jnp.int32) \
        * (S // min(rows, S))
    q_r = jnp.take(q, at, axis=1).reshape(B, -1, G, H // G, D)
    logits = jnp.einsum("brghd,bsgd->bgrhs", q_r, k,
                        preferred_element_type=F32) * scale
    s = jnp.arange(S, dtype=jnp.int32)
    chosen = jnp.repeat(jnp.take(selection, at, axis=2), sizes.block, -1) \
        .astype(bool) & (s[None, :] <= at[:, None])     # [B, G, R, S]
    must = jnp.repeat(jnp.take(forced(S, sizes)[1], at, axis=0),
                      sizes.block, -1)                   # [R, S]
    logits = jnp.where(chosen[:, :, :, None], logits, -jnp.inf)
    probs = jax.nn.softmax(logits, axis=-1)
    return jnp.where(must[:, None, :], 0.0, probs).sum(-1).mean()


# -- the attention over the selection: jax.numpy ------------------------------

def dot_selected_attention(q, k, v, selection, block: int, scale=None):
    """q [B, S, H, D], k [B, S, G, D], v [B, S, G, Dv], selection [B, G, S,
    S / block] -> (out [B, S, H, Dv], lse [B, H, S]); fp32 softmax over the
    keys ``s <= t`` of the selected blocks."""
    B, S, H, D = q.shape
    G = k.shape[2]
    scale = _score_scale(scale, D)
    logits = (jnp.einsum("bqghd,bkgd->bghqk",
                         q.reshape(B, S, G, H // G, D), k) * scale
              ).astype(F32)
    mask = token_mask(selection, block)[:, :, None] != 0
    logits = jnp.where(mask, logits, _NEG_INF)
    lse = jax.scipy.special.logsumexp(logits, axis=-1)
    probs = jnp.exp(logits - lse[..., None]).astype(q.dtype)
    out = jnp.einsum("bghqk,bkgd->bqghd", probs, v)
    return out.reshape(B, S, H, -1), lse.reshape(B, H, S)


# -- the kernels: ops/dsa.py's bodies, a group's heads on one K/V head --------

def _kv_of(heads: int):
    """The index map of a K/V tile under a grid whose first axis walks
    batch * query heads: ``heads`` of them read one KV head."""
    return lambda b, t, qi_tab, ki_tab, live_tab: (b // heads, ki_tab[t], 0)


def live_tiles(selection, block: int, blk_q: int, blk_k: int):
    """[S / blk_q, S / blk_k] bool: the tiles in which any query of any
    group and batch row selected a block."""
    B, G, S, blocks = selection.shape
    wide = blk_k // block
    return (selection != 0).reshape(
        B, G, S // blk_q, blk_q, blocks // wide, wide).any((0, 1, 3, 5))


def whole_tile(S: int, block: int, want: int = 512) -> int:
    """The largest tile of whole blocks, ``want`` keys at most, that divides
    S: what the gauges count tiles by where no kernel sets one."""
    tile = max(min(S, want) // block, 1) * block
    while S % tile:
        tile -= block
    return tile


def _live(selection, block, pairs, blk_q, blk_k):
    """The kernels' flag a pair of the table: 1 where its tile is live."""
    return live_tiles(selection, block, blk_q, blk_k)[
        pairs[0], pairs[1]].astype(jnp.int32)


def live_tile_share(selection, block: int, blk_q: int, blk_k: int):
    """Live tiles over the tiles of the causal table."""
    pairs = _tile_pairs(selection.shape[2], blk_q, blk_k, True, False)
    return _live(selection, block, pairs, blk_q, blk_k).astype(F32).mean()


def tiles(q, k, block: int, blk_q: int, blk_k: int):
    """The kernels' (Q tile, KV tile) for these shapes, or why not."""
    S = q.shape[1]
    blk_q, blk_k = dsa._blocks(S, blk_q, blk_k)
    if blk_k % block or q.shape[2] % k.shape[2]:
        raise ValueError(
            f"KV tiles of {blk_k} in blocks of {block}, {q.shape[2]} heads "
            f"over {k.shape[2]}: neither may leave a remainder")
    return blk_q, blk_k


def _forward(q, k, v, selection, block, blk_q, blk_k, scale):
    B, S, H, D = q.shape
    G, Dv = k.shape[2], v.shape[-1]
    scale = _score_scale(scale, D)
    blk_q, blk_k = tiles(q, k, block, blk_q, blk_k)
    pairs = _tile_pairs(S, blk_q, blk_k, True, False)
    mask = token_mask(selection, block).reshape(B * G, S, S)
    out, lse = dsa._call(
        functools.partial(dsa._fwd_kernel, blk_k=blk_k, scale=scale),
        "sala_fwd", (B * H, len(pairs[0])), pairs,
        _live(selection, block, pairs, blk_q, blk_k),
        in_specs=[
            pl.BlockSpec((None, blk_q, D), dsa._q_tile),
            pl.BlockSpec((None, blk_k, D), _kv_of(H // G)),
            pl.BlockSpec((None, blk_k, Dv), _kv_of(H // G)),
            pl.BlockSpec((None, blk_q, blk_k), dsa._selection_tile(H // G)),
        ],
        out_specs=[
            pl.BlockSpec((None, blk_q, Dv), dsa._q_tile),
            pl.BlockSpec((None, 1, blk_q), dsa._q_row),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((B * H, S, Dv), q.dtype),
            jax.ShapeDtypeStruct((B * H, 1, S), F32),
        ],
        scratch_shapes=[pltpu.VMEM((blk_q, _STAT_LANES), F32),
                        pltpu.VMEM((blk_q, _STAT_LANES), F32),
                        pltpu.VMEM((blk_q, Dv), F32)],
        semantics=("parallel", "arbitrary"),
    )(_to_bh(q), _to_bh(k), _to_bh(v), mask)
    return _from_bh(out, B, H), lse


def _backward(q, k, v, selection, out, lse, g, block, blk_q, blk_k, scale):
    B, S, H, D = q.shape
    G, Dv = k.shape[2], v.shape[-1]
    scale = _score_scale(scale, D)
    blk_q, blk_k = tiles(q, k, block, blk_q, blk_k)
    qf, kf, vf, gf, of = (_to_bh(a) for a in (q, k, v, g, out))
    delta = jnp.sum(gf.astype(F32) * of.astype(F32), axis=-1)[:, None, :]
    mask = token_mask(selection, block).reshape(B * G, S, S)
    operands = (qf, kf, vf, gf, lse, delta, mask)
    in_specs = [
        pl.BlockSpec((None, blk_q, D), dsa._q_tile),
        pl.BlockSpec((None, blk_k, D), _kv_of(H // G)),
        pl.BlockSpec((None, blk_k, Dv), _kv_of(H // G)),
        pl.BlockSpec((None, blk_q, Dv), dsa._q_tile),
        pl.BlockSpec((None, 1, blk_q), dsa._q_row),
        pl.BlockSpec((None, 1, blk_q), dsa._q_row),
        pl.BlockSpec((None, blk_q, blk_k), dsa._selection_tile(H // G)),
    ]
    pairs = _tile_pairs(S, blk_q, blk_k, True, False)
    dq = dsa._call(
        functools.partial(dsa._bwd_dq_kernel, scale=scale), "sala_bwd_dq",
        (B * H, len(pairs[0])), pairs,
        _live(selection, block, pairs, blk_q, blk_k),
        in_specs=in_specs,
        out_specs=pl.BlockSpec((None, blk_q, D), dsa._q_tile),
        out_shape=jax.ShapeDtypeStruct((B * H, S, D), q.dtype),
        scratch_shapes=[pltpu.VMEM((blk_q, D), F32)],
        semantics=("parallel", "arbitrary"),
    )(*operands)
    pairs = _tile_pairs(S, blk_q, blk_k, True, True)
    # A query head's own dk and dv (the kernel's K/V tile is its group's):
    # float32, summed over the group's heads below.
    dk, dv = dsa._call(
        functools.partial(dsa._bwd_dkv_kernel, scale=scale), "sala_bwd_dkv",
        (B * H, len(pairs[0])), pairs,
        _live(selection, block, pairs, blk_q, blk_k),
        in_specs=in_specs,
        out_specs=[
            pl.BlockSpec((None, blk_k, D), dsa._kv_tile),
            pl.BlockSpec((None, blk_k, Dv), dsa._kv_tile),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((B * H, S, D), F32),
            jax.ShapeDtypeStruct((B * H, S, Dv), F32),
        ],
        scratch_shapes=[pltpu.VMEM((blk_k, D), F32),
                        pltpu.VMEM((blk_k, Dv), F32)],
        semantics=("parallel", "arbitrary"),
    )(*operands)

    def over_group(d, like):
        d = d.reshape(B, G, H // G, S, -1).sum(2).astype(like.dtype)
        return d.transpose(0, 2, 1, 3)

    return _from_bh(dq, B, H), over_group(dk, k), over_group(dv, v)


def keeps_forward(S: int, Dv: int, sizes: Sizes) -> bool:
    """``flash_attention.worth_keeping`` asked with the most keys a query
    sees here, ``topk`` blocks: whether ``out`` and ``lse`` survive remat."""
    return worth_keeping(S, Dv, sizes.topk * sizes.block)


@functools.partial(jax.custom_vjp, nondiff_argnums=(4, 5, 6, 7, 8))
def selected_attention(q, k, v, selection, block: int, blk_q: int = 512,
                       blk_k: int = 512, scale=None, keep: bool = False):
    """q [B, S, H, D], k [B, S, G, D], v [B, S, G, Dv], selection [B, G, S,
    S / block] int8 (causal, a query's own block always in it) -> (out [B,
    S, H, Dv], lse [B, H, S] float32). Differentiable in q, k and v through
    ``out``. With ``keep`` the forward's outputs carry the names a
    rematerialisation policy keeps (``keeps_forward``)."""
    out, lse = _forward(q, k, v, selection, block, blk_q, blk_k, scale)
    return out, lse.reshape(q.shape[0], q.shape[2], q.shape[1])


def _fwd(q, k, v, selection, block, blk_q, blk_k, scale, keep):
    out, lse = _forward(q, k, v, selection, block, blk_q, blk_k, scale)
    if keep:
        out = checkpoint_name(out, RESIDUAL_NAMES[0])
        lse = checkpoint_name(lse, RESIDUAL_NAMES[1])
    B, S, H, _ = q.shape
    return (out, lse.reshape(B, H, S)), (q, k, v, selection, out, lse)


def _bwd(block, blk_q, blk_k, scale, keep, residuals, cotangents):
    q, k, v, selection, out, lse = residuals
    dq, dk, dv = _backward(q, k, v, selection, out, lse, cotangents[0],
                           block, blk_q, blk_k, scale)
    return dq, dk, dv, np.zeros(selection.shape, jax.dtypes.float0)


selected_attention.defvjp(_fwd, _bwd)
