"""Blockwise (online-softmax) attention in pure jnp.

The memory-efficient attention recurrence (Rabe & Staats / FlashAttention):
iterate over KV chunks with running (max, sum, out) accumulators so the full
[S, S] score matrix never materializes. O(S) memory instead of O(S^2), fully
differentiable through `lax.scan`, runs on any backend — it is both the
fallback for the Pallas kernel's backward pass and the per-step compute of
ring attention (ring_attention.py).
"""

from __future__ import annotations

import math
from functools import partial
from typing import Optional

import jax
import jax.numpy as jnp

_NEG_INF = -1e30


def _repeat_kv(k, v, n_heads):
    kvh = k.shape[2]
    if kvh != n_heads:
        rep = n_heads // kvh
        k = jnp.repeat(k, rep, axis=2)
        v = jnp.repeat(v, rep, axis=2)
    return k, v


def attention_chunk(q, k, v, m, l, o, q_pos, k_pos, causal: bool,
                    scale: float):
    """One online-softmax update. q: [B,H,Sq,D]; k,v: [B,H,Sk,D];
    m,l: [B,H,Sq]; o: [B,H,Sq,D] (fp32 accumulators). Returns updated
    (m, l, o)."""
    logits = jnp.einsum("bhqd,bhkd->bhqk", q, k,
                        preferred_element_type=jnp.float32) * scale
    if causal:
        mask = q_pos[:, None] >= k_pos[None, :]
        logits = jnp.where(mask[None, None], logits, _NEG_INF)
    m_new = jnp.maximum(m, logits.max(-1))
    # Rows with every key masked keep m == _NEG_INF; correction stays finite.
    correction = jnp.exp(m - m_new)
    p = jnp.exp(logits - m_new[..., None])
    if causal:
        p = jnp.where(mask[None, None], p, 0.0)
    l_new = l * correction + p.sum(-1)
    o_new = o * correction[..., None] + jnp.einsum(
        "bhqk,bhkd->bhqd", p, v, preferred_element_type=jnp.float32)
    return m_new, l_new, o_new


@partial(jax.jit, static_argnames=("causal", "chunk_size", "scale"))
def blockwise_attention(q, k, v, causal: bool = True,
                        chunk_size: int = 512,
                        q_offset: int = 0, kv_offset: int = 0,
                        scale: Optional[float] = None) -> jax.Array:
    """Causal attention over KV chunks. q,k,v: [B, S, H|KVH, D] →
    [B, S, H, D]. ``q_offset``/``kv_offset`` shift global positions (used by
    ring attention when q and kv live on different sequence shards);
    ``scale`` multiplies the scores (1/sqrt(D) if None)."""
    B, Sq, H, D = q.shape
    Sk = k.shape[1]
    k, v = _repeat_kv(k, v, H)
    if scale is None:
        scale = 1.0 / math.sqrt(D)
    qt = q.transpose(0, 2, 1, 3)
    kt = k.transpose(0, 2, 1, 3)
    vt = v.transpose(0, 2, 1, 3)
    q_pos = q_offset + jnp.arange(Sq)
    chunk = min(chunk_size, Sk)
    n_chunks = (Sk + chunk - 1) // chunk
    pad = n_chunks * chunk - Sk
    if pad:
        kt = jnp.pad(kt, ((0, 0), (0, 0), (0, pad), (0, 0)))
        vt = jnp.pad(vt, ((0, 0), (0, 0), (0, pad), (0, 0)))
    kt = kt.reshape(B, H, n_chunks, chunk, D).transpose(2, 0, 1, 3, 4)
    vt = vt.reshape(B, H, n_chunks, chunk, D).transpose(2, 0, 1, 3, 4)

    m0 = jnp.full((B, H, Sq), _NEG_INF, jnp.float32)
    l0 = jnp.zeros((B, H, Sq), jnp.float32)
    o0 = jnp.zeros((B, H, Sq, D), jnp.float32)

    def body(carry, inputs):
        m, l, o = carry
        idx, kc, vc = inputs
        k_pos = kv_offset + idx * chunk + jnp.arange(chunk)
        # Padded keys sit past the real sequence; mask them via position.
        valid = (idx * chunk + jnp.arange(chunk)) < Sk
        k_pos = jnp.where(valid, k_pos, q_offset + Sq + 10**9)
        m, l, o = attention_chunk(qt, kc, vc, m, l, o, q_pos, k_pos,
                                  True, scale)
        return (m, l, o), None

    if causal:
        (m, l, o), _ = jax.lax.scan(
            body, (m0, l0, o0), (jnp.arange(n_chunks), kt, vt))
    else:
        # Non-causal: same loop, mask only padding.
        def body_nc(carry, inputs):
            m, l, o = carry
            idx, kc, vc = inputs
            k_pos = jnp.where(
                (idx * chunk + jnp.arange(chunk)) < Sk,
                jnp.zeros((chunk,), jnp.int32), q_offset + Sq + 10**9)
            q_pos_nc = jnp.full((Sq,), 10**9)  # q >= k always (no mask)
            m, l, o = attention_chunk(qt, kc, vc, m, l, o, q_pos_nc, k_pos,
                                      True, scale)
            return (m, l, o), None

        (m, l, o), _ = jax.lax.scan(
            body_nc, (m0, l0, o0), (jnp.arange(n_chunks), kt, vt))
    out = o / jnp.maximum(l[..., None], 1e-30)
    return out.transpose(0, 2, 1, 3).astype(q.dtype)
