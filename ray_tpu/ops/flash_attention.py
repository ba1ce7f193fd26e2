"""Flash attention as Pallas TPU kernels (forward + backward).

Forward tiles Q over the grid and streams KV blocks through VMEM with the
online-softmax recurrence, keeping the MXU fed with [blk_q, D] x [D, blk_k]
matmuls (pallas_guide.md: grid/BlockSpec + fori_loop pattern), and emits the
per-row logsumexp needed by the backward pass.

Backward is the standard two-kernel FlashAttention scheme: a dQ kernel
(grid over Q blocks, streaming KV) and a dK/dV kernel (grid over KV blocks,
streaming Q), both recomputing probabilities from q, k and the saved
logsumexp — O(S) memory, no S x S tensor ever materializes in HBM. This is
what lets the GPT train step run "selective" rematerialisation instead of
full-block recompute (models/gpt.py GPTConfig.remat_policy).

On non-TPU backends the kernels run in interpreter mode so the same code
path is testable on the CPU mesh (SURVEY.md §4: fake-TPU strategy), and
sequences the kernels cannot tile (not a multiple of 128) take the jnp
blockwise path there. On a TPU such a sequence is an error: the chip never
runs anything but the kernels under this name.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from ray_tpu.ops.blockwise_attention import blockwise_attention

_NEG_INF = -1e30


def _interpret() -> bool:
    return jax.default_backend() != "tpu"


def _flash_fwd_kernel(q_ref, k_ref, v_ref, o_ref, lse_ref, *, blk_q: int,
                      blk_k: int, seq_len: int, causal: bool, scale: float):
    """Grid: (batch*heads, num_q_blocks). q_ref: [blk_q, D] tile;
    k_ref/v_ref: [S, D] for this (b, h); o_ref: [blk_q, D];
    lse_ref: [1, blk_q]."""
    qi = pl.program_id(1)
    q = q_ref[...].astype(jnp.float32) * scale
    D = q.shape[-1]

    q_pos = qi * blk_q + jax.lax.broadcasted_iota(
        jnp.int32, (blk_q, blk_k), 0)

    n_k = seq_len // blk_k

    def body(kb, carry):
        m, l, o = carry
        k_blk = k_ref[pl.ds(kb * blk_k, blk_k), :].astype(jnp.float32)
        v_blk = v_ref[pl.ds(kb * blk_k, blk_k), :].astype(jnp.float32)
        logits = jax.lax.dot_general(
            q, k_blk, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32)
        if causal:
            k_pos = kb * blk_k + jax.lax.broadcasted_iota(
                jnp.int32, (blk_q, blk_k), 1)
            mask = q_pos >= k_pos
            logits = jnp.where(mask, logits, _NEG_INF)
        m_new = jnp.maximum(m, logits.max(-1))
        corr = jnp.exp(m - m_new)
        p = jnp.exp(logits - m_new[:, None])
        if causal:
            p = jnp.where(mask, p, 0.0)
        l_new = l * corr + p.sum(-1)
        o_new = o * corr[:, None] + jax.lax.dot_general(
            p, v_blk, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        return m_new, l_new, o_new

    m0 = jnp.full((blk_q,), _NEG_INF, jnp.float32)
    l0 = jnp.zeros((blk_q,), jnp.float32)
    o0 = jnp.zeros((blk_q, D), jnp.float32)
    if causal:
        # Only KV blocks at or before this Q block's last row contribute.
        n_iter = jnp.minimum(pl.cdiv((qi + 1) * blk_q, blk_k), n_k)
    else:
        n_iter = n_k
    m, l, o = jax.lax.fori_loop(0, n_iter, body, (m0, l0, o0))
    l_safe = jnp.maximum(l, 1e-30)
    o_ref[...] = (o / l_safe[:, None]).astype(o_ref.dtype)
    lse_ref[...] = (m + jnp.log(l_safe))[None, :]


def _flash_bwd_dq_kernel(q_ref, k_ref, v_ref, g_ref, lse_ref, delta_ref,
                         dq_ref, *, blk_q: int, blk_k: int, seq_len: int,
                         causal: bool, scale: float):
    """Grid: (batch*heads, num_q_blocks). dq for one Q tile, streaming KV."""
    qi = pl.program_id(1)
    q = q_ref[...].astype(jnp.float32) * scale
    g = g_ref[...].astype(jnp.float32)
    lse = lse_ref[0, :]
    delta = delta_ref[0, :]
    D = q.shape[-1]

    q_pos = qi * blk_q + jax.lax.broadcasted_iota(
        jnp.int32, (blk_q, blk_k), 0)
    n_k = seq_len // blk_k

    def body(kb, dq):
        k_blk = k_ref[pl.ds(kb * blk_k, blk_k), :].astype(jnp.float32)
        v_blk = v_ref[pl.ds(kb * blk_k, blk_k), :].astype(jnp.float32)
        logits = jax.lax.dot_general(
            q, k_blk, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32)
        p = jnp.exp(logits - lse[:, None])
        if causal:
            k_pos = kb * blk_k + jax.lax.broadcasted_iota(
                jnp.int32, (blk_q, blk_k), 1)
            p = jnp.where(q_pos >= k_pos, p, 0.0)
        dp = jax.lax.dot_general(
            g, v_blk, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32)
        ds = p * (dp - delta[:, None])
        return dq + jax.lax.dot_general(
            ds, k_blk, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)

    if causal:
        n_iter = jnp.minimum(pl.cdiv((qi + 1) * blk_q, blk_k), n_k)
    else:
        n_iter = n_k
    dq = jax.lax.fori_loop(
        0, n_iter, body, jnp.zeros((blk_q, D), jnp.float32))
    dq_ref[...] = (dq * scale).astype(dq_ref.dtype)


def _flash_bwd_dkv_kernel(q_ref, k_ref, v_ref, g_ref, lse_ref, delta_ref,
                          dk_ref, dv_ref, *, blk_q: int, blk_k: int,
                          seq_len: int, causal: bool, scale: float):
    """Grid: (batch*heads, num_k_blocks). dk/dv for one KV tile, streaming
    Q blocks (only those at or after the diagonal when causal)."""
    ki = pl.program_id(1)
    k = k_ref[...].astype(jnp.float32)
    v = v_ref[...].astype(jnp.float32)
    D = k.shape[-1]

    k_pos = ki * blk_k + jax.lax.broadcasted_iota(
        jnp.int32, (blk_q, blk_k), 1)
    n_q = seq_len // blk_q

    def body(qb, carry):
        dk, dv = carry
        q_blk = q_ref[pl.ds(qb * blk_q, blk_q), :].astype(
            jnp.float32) * scale
        g_blk = g_ref[pl.ds(qb * blk_q, blk_q), :].astype(jnp.float32)
        lse = lse_ref[0, pl.ds(qb * blk_q, blk_q)]
        delta = delta_ref[0, pl.ds(qb * blk_q, blk_q)]
        logits = jax.lax.dot_general(
            q_blk, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32)
        p = jnp.exp(logits - lse[:, None])
        if causal:
            q_pos = qb * blk_q + jax.lax.broadcasted_iota(
                jnp.int32, (blk_q, blk_k), 0)
            p = jnp.where(q_pos >= k_pos, p, 0.0)
        dv = dv + jax.lax.dot_general(
            p, g_blk, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        dp = jax.lax.dot_general(
            g_blk, v, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32)
        ds = p * (dp - delta[:, None])
        dk = dk + jax.lax.dot_general(
            ds, q_blk, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        return dk, dv

    if causal:
        # Q blocks strictly before this KV block's first row see none of it.
        qb_start = (ki * blk_k) // blk_q
    else:
        qb_start = 0
    dk, dv = jax.lax.fori_loop(
        qb_start, n_q, body,
        (jnp.zeros((blk_k, D), jnp.float32),
         jnp.zeros((blk_k, D), jnp.float32)))
    # dk already includes one factor of scale via q_blk; that IS d(logits)^T
    # @ q * scale, which equals scale * ds^T @ q — correct as accumulated.
    dk_ref[...] = dk.astype(dk_ref.dtype)
    dv_ref[...] = dv.astype(dv_ref.dtype)


def _repeat_heads(k, v, n_heads):
    kvh = k.shape[2]
    if kvh != n_heads:
        rep = n_heads // kvh
        k = jnp.repeat(k, rep, axis=2)
        v = jnp.repeat(v, rep, axis=2)
    return k, v


def _to_bh(x):
    B, S, H, D = x.shape
    return x.transpose(0, 2, 1, 3).reshape(B * H, S, D)


def _from_bh(x, B, H):
    BH, S, D = x.shape
    return x.reshape(B, H, S, D).transpose(0, 2, 1, 3)


def _pick_block(S: int, want: int) -> int:
    """Largest lane-aligned block <= want that divides S (0 if none)."""
    b = min(want, S)
    b -= b % 128
    while b >= 128 and S % b:
        b -= 128
    return b


def _flash_forward(q, k, v, causal: bool, blk_q: int, blk_k: int):
    B, S, H, D = q.shape
    k, v = _repeat_heads(k, v, H)
    scale = 1.0 / math.sqrt(D)
    blk_q = _pick_block(S, blk_q)
    blk_k = _pick_block(S, blk_k)
    if blk_q < 128 or blk_k < 128:
        if not _interpret():
            raise ValueError(
                f"flash_attention on TPU needs a sequence length that is a "
                f"multiple of 128, got S={S}: pad the sequence or use "
                "attn_impl='dot'")
        # Short or ragged sequence on the CPU test backend, where there is
        # no kernel to lose: the jnp blockwise path (no lse output — the
        # custom VJP then differentiates the blockwise recurrence instead
        # of running the Pallas backward).
        return blockwise_attention(q, k, v, causal=causal), None
    qf, kf, vf = _to_bh(q), _to_bh(k), _to_bh(v)

    kernel = functools.partial(
        _flash_fwd_kernel, blk_q=blk_q, blk_k=blk_k, seq_len=S,
        causal=causal, scale=scale)
    out, lse = pl.pallas_call(
        kernel,
        grid=(B * H, S // blk_q),
        in_specs=[
            pl.BlockSpec((None, blk_q, D), lambda i, j: (i, j, 0)),
            pl.BlockSpec((None, S, D), lambda i, j: (i, 0, 0)),
            pl.BlockSpec((None, S, D), lambda i, j: (i, 0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((None, blk_q, D), lambda i, j: (i, j, 0)),
            pl.BlockSpec((None, 1, blk_q), lambda i, j: (i, 0, j)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((B * H, S, D), q.dtype),
            jax.ShapeDtypeStruct((B * H, 1, S), jnp.float32),
        ],
        interpret=_interpret(),
        name="flash_fwd",
    )(qf, kf, vf)
    return _from_bh(out, B, H), lse


def _flash_backward(q, k, v, out, lse, g, causal: bool, blk_q: int,
                    blk_k: int):
    B, S, H, D = q.shape
    kvh = k.shape[2]
    k_rep, v_rep = _repeat_heads(k, v, H)
    scale = 1.0 / math.sqrt(D)
    qf, kf, vf = _to_bh(q), _to_bh(k_rep), _to_bh(v_rep)
    gf, of = _to_bh(g), _to_bh(out)
    delta = jnp.sum(gf.astype(jnp.float32) * of.astype(jnp.float32),
                    axis=-1)[:, None, :]  # [BH, 1, S]

    common = dict(blk_q=blk_q, blk_k=blk_k, seq_len=S, causal=causal,
                  scale=scale)
    dq = pl.pallas_call(
        functools.partial(_flash_bwd_dq_kernel, **common),
        grid=(B * H, S // blk_q),
        in_specs=[
            pl.BlockSpec((None, blk_q, D), lambda i, j: (i, j, 0)),
            pl.BlockSpec((None, S, D), lambda i, j: (i, 0, 0)),
            pl.BlockSpec((None, S, D), lambda i, j: (i, 0, 0)),
            pl.BlockSpec((None, blk_q, D), lambda i, j: (i, j, 0)),
            pl.BlockSpec((None, 1, blk_q), lambda i, j: (i, 0, j)),
            pl.BlockSpec((None, 1, blk_q), lambda i, j: (i, 0, j)),
        ],
        out_specs=pl.BlockSpec((None, blk_q, D), lambda i, j: (i, j, 0)),
        out_shape=jax.ShapeDtypeStruct((B * H, S, D), q.dtype),
        interpret=_interpret(),
        name="flash_bwd_dq",
    )(qf, kf, vf, gf, lse, delta)

    dk, dv = pl.pallas_call(
        functools.partial(_flash_bwd_dkv_kernel, **common),
        grid=(B * H, S // blk_k),
        in_specs=[
            pl.BlockSpec((None, S, D), lambda i, j: (i, 0, 0)),
            pl.BlockSpec((None, blk_k, D), lambda i, j: (i, j, 0)),
            pl.BlockSpec((None, blk_k, D), lambda i, j: (i, j, 0)),
            pl.BlockSpec((None, S, D), lambda i, j: (i, 0, 0)),
            pl.BlockSpec((None, 1, S), lambda i, j: (i, 0, 0)),
            pl.BlockSpec((None, 1, S), lambda i, j: (i, 0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((None, blk_k, D), lambda i, j: (i, j, 0)),
            pl.BlockSpec((None, blk_k, D), lambda i, j: (i, j, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((B * H, S, D), k.dtype),
            jax.ShapeDtypeStruct((B * H, S, D), v.dtype),
        ],
        interpret=_interpret(),
        name="flash_bwd_dkv",
    )(qf, kf, vf, gf, lse, delta)

    dq = _from_bh(dq, B, H)
    dk = _from_bh(dk, B, H)
    dv = _from_bh(dv, B, H)
    if kvh != H:
        # GQA: fold gradients of the repeated heads back onto the KV heads.
        rep = H // kvh
        dk = dk.reshape(B, S, kvh, rep, D).sum(axis=3)
        dv = dv.reshape(B, S, kvh, rep, D).sum(axis=3)
    return dq, dk, dv


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5))
def flash_attention(q, k, v, causal: bool = True, blk_q: int = 1024,
                    blk_k: int = 1024):
    """q: [B, S, H, D], k/v: [B, S, KVH, D] → [B, S, H, D]."""
    return _flash_forward(q, k, v, causal, blk_q, blk_k)[0]


def _fwd(q, k, v, causal, blk_q, blk_k):
    out, lse = _flash_forward(q, k, v, causal, blk_q, blk_k)
    if lse is None:
        # Ragged fallback: differentiate the jnp blockwise recurrence.
        return out, (q, k, v, None, None)
    return out, (q, k, v, out, lse)


def _bwd(causal, blk_q, blk_k, residuals, g):
    q, k, v, out, lse = residuals
    if lse is None:
        _, vjp = jax.vjp(
            lambda q_, k_, v_: blockwise_attention(q_, k_, v_, causal=causal),
            q, k, v)
        return vjp(g)
    S = q.shape[1]
    return _flash_backward(q, k, v, out, lse, g, causal,
                           _pick_block(S, blk_q), _pick_block(S, blk_k))


flash_attention.defvjp(_fwd, _bwd)
