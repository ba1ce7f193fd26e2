"""EVA attention (Zheng, Yuan, Wang, Kong: "Efficient Attention via Control
Variates", ICLR 2023) in the deterministic form the EvaByte release trains
(``attention_class: eva``): an exact softmax over the keys of a query's own
window and, **under the same softmax**, one learned summary of every chunk
of keys in the windows before it.

With chunks ``C_j = {chunk j, .., chunk j + chunk - 1}``, windows of
``window`` keys (whole chunks), per head ``phi`` and ``mu`` in R^D::

    a[m]   = softmax_{m in C_j}(k[m] . phi)             the chunk's pooling weights
    kc[j]  = sum_{m in C_j} a[m] k[m] + mu              pooled key
    vc[j]  = sum_{m in C_j} a[m] v[m]                   pooled value
    query t, window w = t // window:
      L_t  = {m : window w <= m <= t}                   its own window, exact
      R_t  = {j : C_j lies in a window before w}        (window / chunk) w summaries
      o[t] = softmax over L_t and R_t together of (q[t] . k[m] | q[t] . kc[j]) * scale
             applied to (v[m] | vc[j])

A query in window 0 sees no summary; the chunks of a query's own window are
never pooled for it, so nothing is counted twice. The summaries carry
gradient: the attention's cotangents on kc and vc go through the pooling
into k, v, ``phi`` and ``mu``.

Three pieces:

* ``pool``: the pooling, ``jax.numpy`` (a reshape to chunks, a softmax over
  a chunk, two weighted sums, in float32), differentiated by JAX.
* ``eva_attention``: the attention on the flash kernels
  (``ops/flash_attention.py``): the summaries are stacked in front of k and
  v (``stacked``: one K and one V of ``rows + S`` rows, ``rows`` = S / chunk
  up to a whole KV tile) and the three kernel bodies run over
  ``flash_attention.Summaries``' table and mask under names of their own
  (``eva_fwd``, ``eva_bwd_dq``, ``eva_bwd_dkv``). One online-softmax state
  walks a row's summary tiles and then its window's key tiles. The backward
  kernels return the cotangents of the stacked rows, whose first ``rows``
  are the summaries'. The forward kernel also gives, at no further pass,
  the share of every query's softmax sum that lies on summaries
  (``mass``).
* ``dot_eva_attention``: the same attention over the explicit ``[S, S /
  chunk + S]`` mask, for ``attn_impl="dot"`` and the tests.

The kernels run whole sequences of one device; under a mesh the caller runs
them per shard (``models/lm.py`` ``eva_attention``).
"""

from __future__ import annotations

import functools
import sys

import jax
import jax.numpy as jnp
from jax.ad_checkpoint import checkpoint_name
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ray_tpu.ops.flash_attention import (
    _NEG_INF, _STAT_LANES, RESIDUAL_NAMES, Summaries, _flash_bwd_dkv_kernel,
    _flash_bwd_dq_kernel, _flash_fwd_kernel, _from_bh, _kv_tile, _pick_block,
    _q_row, _q_tile, _score_scale, _tile_pairs, _tiled_call, _to_bh,
    summary_rows, worth_keeping)


def keys_seen(S: int, window: int, chunk: int) -> int:
    """The most keys and summaries one query sees: a whole window and every
    chunk before the last window. What ``worth_keeping`` is asked with."""
    return min(S, window) + max(S - window, 0) // chunk


@functools.lru_cache(maxsize=None)
def pairs_share(S: int, window: int, chunk: int, blk_q=None, blk_k=None
                ) -> float:
    """Pairs the attention covers over the causal pairs: counted from the
    kernels' table and mask at tiles of ``blk_q`` x ``blk_k``
    (``flash_attention.eva_tile_census``), or with no tiles from the
    explicit mask."""
    if blk_q is None:
        with jax.ensure_compile_time_eval():
            return int(allowed(S, window, chunk).sum()) / (S * (S + 1) // 2)
    blk_q, blk_k = _blocks(S, blk_q, blk_k)
    census = sys.modules["ray_tpu.ops.flash_attention"].eva_tile_census(
        S, window, chunk, blk_q, blk_k)
    return census["counted_pairs"] / census["causal_pairs"]


def pool(k, v, phi, mu, chunk: int):
    """k [B, S, H, D], v [B, S, H, Dv], phi, mu [H, D] -> (kc [B, S /
    chunk, H, D], vc [B, S / chunk, H, Dv]): every chunk's keys and values
    under the softmax of ``k . phi`` over the chunk, ``mu`` added to the
    pooled key. Sums in float32, results in k's and v's dtypes."""
    B, S, H, D = k.shape
    f32 = jnp.float32
    k_c = k.reshape(B, S // chunk, chunk, H, D).astype(f32)
    v_c = v.reshape(B, S // chunk, chunk, H, v.shape[-1]).astype(f32)
    a = jax.nn.softmax(
        jnp.einsum("bjchd,hd->bjch", k_c, phi.astype(f32)), axis=2)
    kc = jnp.einsum("bjch,bjchd->bjhd", a, k_c) + mu.astype(f32)
    vc = jnp.einsum("bjch,bjchd->bjhd", a, v_c)
    return kc.astype(k.dtype), vc.astype(v.dtype)


def stacked(kc, k, rows: int):
    """The summaries in front of the keys (or values): [B, rows + S, H, D],
    zeros where ``rows`` is more than the summaries."""
    pad = rows - kc.shape[1]
    if pad:
        kc = jnp.pad(kc, ((0, 0), (0, pad), (0, 0), (0, 0)))
    return jnp.concatenate([kc, k], axis=1)


def allowed(S: int, window: int, chunk: int):
    """The explicit mask [S, S / chunk + S] bool over summaries, then
    keys."""
    t = jnp.arange(S)[:, None]
    start = t - t % window
    j, m = jnp.arange(S // chunk)[None, :], jnp.arange(S)[None, :]
    return jnp.concatenate([j * chunk < start, (start <= m) & (m <= t)], 1)


def dot_eva_attention(q, k, v, kc, vc, window: int, chunk: int, scale=None):
    """(out [B, S, H, Dv], mass [B, H, S]) over the explicit mask; fp32
    softmax."""
    S = q.shape[1]
    scale = _score_scale(scale, q.shape[-1])
    keys, values = stacked(kc, k, kc.shape[1]), stacked(vc, v, vc.shape[1])
    logits = (jnp.einsum("bqhd,bkhd->bhqk", q, keys) * scale
              ).astype(jnp.float32)
    logits = jnp.where(allowed(S, window, chunk)[None, None], logits,
                       _NEG_INF)
    probs = jax.nn.softmax(logits, axis=-1)
    mass = jax.lax.stop_gradient(probs[..., :kc.shape[1]].sum(-1))
    return jnp.einsum("bhqk,bkhd->bqhd", probs.astype(q.dtype), values), mass


# -- the kernels' calls ---------------------------------------------------

def _blocks(S: int, blk_q: int, blk_k: int):
    blk_q, blk_k = _pick_block(S, blk_q), _pick_block(S, blk_k)
    if blk_q < 128 or blk_k < 128:
        raise ValueError(
            f"eva_attention needs a sequence length that is a multiple of "
            f"128, got S={S}: pad the sequence or use attn_impl='dot'")
    return blk_q, blk_k


def _mask_of(S: int, window: int, chunk: int, blk_k: int) -> Summaries:
    if S % chunk:
        raise ValueError(f"S={S} is not whole chunks of {chunk}")
    return Summaries(window, chunk, summary_rows(S, chunk, blk_k))


def _eva_fwd_kernel(qi_tab, ki_tab, q_ref, k_ref, v_ref, o_ref, lse_ref,
                    mass_ref, m_scr, l_scr, o_scr, s_scr, **static):
    """``_flash_fwd_kernel`` with the summaries' share of l as a third
    output and a fourth statistic."""
    _flash_fwd_kernel(qi_tab, ki_tab, q_ref, k_ref, v_ref, o_ref, lse_ref,
                      m_scr, l_scr, o_scr, mass=(mass_ref, s_scr), **static)


def _eva_forward(q, keys, values, eva: Summaries, blk_q: int, blk_k: int,
                 scale: float):
    """q [B, S, H, D], keys [B, rows + S, H, D], values [B, rows + S, H,
    Dv] -> (out [B, S, H, Dv], lse [B H, 1, S], mass [B H, 1, S])."""
    B, S, H, D = q.shape
    Dv = values.shape[-1]
    row = jax.ShapeDtypeStruct((B * H, 1, S), jnp.float32)
    out, lse, mass = _tiled_call(
        functools.partial(_eva_fwd_kernel, blk_q=blk_q, blk_k=blk_k,
                          causal=True, scale=scale, window=eva),
        "eva_fwd", B * H, _tile_pairs(S, blk_q, blk_k, True, False, eva),
        in_specs=[pl.BlockSpec((None, blk_q, D), _q_tile),
                  pl.BlockSpec((None, blk_k, D), _kv_tile),
                  pl.BlockSpec((None, blk_k, Dv), _kv_tile)],
        out_specs=[pl.BlockSpec((None, blk_q, Dv), _q_tile),
                   pl.BlockSpec((None, 1, blk_q), _q_row),
                   pl.BlockSpec((None, 1, blk_q), _q_row)],
        out_shape=[jax.ShapeDtypeStruct((B * H, S, Dv), q.dtype), row, row],
        scratch_shapes=[pltpu.VMEM((blk_q, _STAT_LANES), jnp.float32),
                        pltpu.VMEM((blk_q, _STAT_LANES), jnp.float32),
                        pltpu.VMEM((blk_q, Dv), jnp.float32),
                        pltpu.VMEM((blk_q, _STAT_LANES), jnp.float32)],
    )(_to_bh(q), _to_bh(keys), _to_bh(values))
    return _from_bh(out, B, H), lse, mass


def _eva_backward(q, keys, values, out, lse, g, eva: Summaries, blk_q: int,
                  blk_k: int, scale: float):
    """(dq, d keys, d values): ``flash_attention._flash_backward`` over the
    stacked rows and ``Summaries``' tables."""
    B, S, H, D = q.shape
    Dv, rows = values.shape[-1], keys.shape[1]
    gf, of = _to_bh(g), _to_bh(out)
    delta = jnp.sum(gf.astype(jnp.float32) * of.astype(jnp.float32),
                    axis=-1)[:, None, :]
    common = dict(blk_q=blk_q, blk_k=blk_k, causal=True, scale=scale,
                  window=eva)
    operands = (_to_bh(q), _to_bh(keys), _to_bh(values), gf, lse, delta)
    in_specs = [pl.BlockSpec((None, blk_q, D), _q_tile),
                pl.BlockSpec((None, blk_k, D), _kv_tile),
                pl.BlockSpec((None, blk_k, Dv), _kv_tile),
                pl.BlockSpec((None, blk_q, Dv), _q_tile),
                pl.BlockSpec((None, 1, blk_q), _q_row),
                pl.BlockSpec((None, 1, blk_q), _q_row)]
    dq = _tiled_call(
        functools.partial(_flash_bwd_dq_kernel, **common), "eva_bwd_dq",
        B * H, _tile_pairs(S, blk_q, blk_k, True, False, eva),
        in_specs=in_specs,
        out_specs=pl.BlockSpec((None, blk_q, D), _q_tile),
        out_shape=jax.ShapeDtypeStruct((B * H, S, D), q.dtype),
        scratch_shapes=[pltpu.VMEM((blk_q, D), jnp.float32)],
    )(*operands)
    dk, dv = _tiled_call(
        functools.partial(_flash_bwd_dkv_kernel, **common), "eva_bwd_dkv",
        B * H, _tile_pairs(S, blk_q, blk_k, True, True, eva),
        in_specs=in_specs,
        out_specs=[pl.BlockSpec((None, blk_k, D), _kv_tile),
                   pl.BlockSpec((None, blk_k, Dv), _kv_tile)],
        out_shape=[jax.ShapeDtypeStruct((B * H, rows, D), keys.dtype),
                   jax.ShapeDtypeStruct((B * H, rows, Dv), values.dtype)],
        scratch_shapes=[pltpu.VMEM((blk_k, D), jnp.float32),
                        pltpu.VMEM((blk_k, Dv), jnp.float32)],
    )(*operands)
    return _from_bh(dq, B, H), _from_bh(dk, B, H), _from_bh(dv, B, H)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6, 7))
def _stacked_attention(q, keys, values, window, chunk, blk_q, blk_k, scale):
    """(out, mass) of q [B, S, H, D] over the stacked rows ``keys`` [B,
    rows + S, H, D] and ``values``; mass [B H, 1, S] takes no cotangent."""
    return _stacked_fwd(q, keys, values, window, chunk, blk_q, blk_k,
                        scale)[0]


def _stacked_fwd(q, keys, values, window, chunk, blk_q, blk_k, scale):
    S = q.shape[1]
    blk_q, blk_k = _blocks(S, blk_q, blk_k)
    out, lse, mass = _eva_forward(
        q, keys, values, _mask_of(S, window, chunk, blk_k), blk_q, blk_k,
        _score_scale(scale, q.shape[-1]))
    if worth_keeping(S, values.shape[-1], keys_seen(S, window, chunk)):
        out = checkpoint_name(out, RESIDUAL_NAMES[0])
        lse = checkpoint_name(lse, RESIDUAL_NAMES[1])
    return (out, mass), (q, keys, values, out, lse)


def _stacked_bwd(window, chunk, blk_q, blk_k, scale, residuals, g):
    q, keys, values, out, lse = residuals
    S = q.shape[1]
    blk_q, blk_k = _blocks(S, blk_q, blk_k)
    return _eva_backward(
        q, keys, values, out, lse, g[0], _mask_of(S, window, chunk, blk_k),
        blk_q, blk_k, _score_scale(scale, q.shape[-1]))


_stacked_attention.defvjp(_stacked_fwd, _stacked_bwd)


def eva_attention(q, k, v, kc, vc, window: int, chunk: int,
                  blk_q: int = 512, blk_k: int = 512, scale=None):
    """q, k [B, S, H, D], v [B, S, H, Dv], the summaries kc [B, S / chunk,
    H, D] and vc [B, S / chunk, H, Dv] (``pool``) -> (out [B, S, H, Dv],
    mass [B, H, S] float32: the share of each query's softmax sum on
    summaries; no gradient). Differentiable in all five."""
    B, S, H, _ = q.shape
    rows = summary_rows(S, chunk, _blocks(S, blk_q, blk_k)[1])
    out, mass = _stacked_attention(
        q, stacked(kc, k, rows), stacked(vc, v, rows), window, chunk,
        blk_q, blk_k, scale)
    return out, jax.lax.stop_gradient(mass).reshape(B, H, S)
