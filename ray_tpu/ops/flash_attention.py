"""Flash attention as Pallas TPU kernels (forward + backward).

Forward tiles Q and KV over the grid: one Q tile meets one KV tile per
step, the KV axis last and sequential, with the online-softmax state in
VMEM scratch, keeping the MXU fed with [blk_q, D] x [D, blk_k] matmuls
(pallas_guide.md: grid/BlockSpec + scratch accumulators), and emits the
per-row logsumexp needed by the backward pass. Nothing of length S is ever
resident in VMEM, so the sequence length is bounded by HBM alone, and q/k
may have another head size (D) than v and the output (Dv): latent
attention trains with D = 192, Dv = 128.

Backward is the standard two-kernel FlashAttention scheme: a dQ kernel
(grid over Q tiles, KV tiles streamed) and a dK/dV kernel (grid over KV
tiles; Q, dO, lse and delta tiles streamed), both recomputing probabilities
from q, k and the saved logsumexp — O(S) memory, no S x S tensor ever
materializes in HBM. This is
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
from jax.experimental.pallas import tpu as pltpu

from ray_tpu.ops.blockwise_attention import blockwise_attention

_NEG_INF = -1e30


def _interpret() -> bool:
    return jax.default_backend() != "tpu"


def _causal_mask(qi, ki, blk_q: int, blk_k: int):
    """[blk_q, blk_k] bool: query row >= key column, for tiles qi and ki."""
    q_pos = qi * blk_q + jax.lax.broadcasted_iota(
        jnp.int32, (blk_q, blk_k), 0)
    k_pos = ki * blk_k + jax.lax.broadcasted_iota(
        jnp.int32, (blk_q, blk_k), 1)
    return q_pos >= k_pos


def _last_k_block(qi, blk_q: int, blk_k: int):
    """The last KV tile a causal Q tile sees (its last row's column)."""
    return ((qi + 1) * blk_q - 1) // blk_k


def _first_q_block(ki, blk_q: int, blk_k: int):
    """The first Q tile that sees a causal KV tile (its first column)."""
    return (ki * blk_k) // blk_q


def _flash_fwd_kernel(q_ref, k_ref, v_ref, o_ref, lse_ref, m_scr, l_scr,
                      o_scr, *, blk_q: int, blk_k: int, causal: bool,
                      scale: float):
    """Grid: (batch*heads, num_q_blocks, num_k_blocks), the last axis
    sequential. One [blk_q, D] Q tile against one [blk_k, D] / [blk_k, Dv]
    KV tile per step, the online-softmax state (m, l, o) carried in VMEM
    scratch across the KV axis; o_ref [blk_q, Dv] and lse_ref [1, blk_q]
    are written at the last KV step. KV tiles past the diagonal of a
    causal Q tile are skipped (their index map repeats the last one seen,
    so nothing is fetched for them either)."""
    qi, ki = pl.program_id(1), pl.program_id(2)

    @pl.when(ki == 0)
    def _():
        m_scr[...] = jnp.full(m_scr.shape, _NEG_INF, jnp.float32)
        l_scr[...] = jnp.zeros(l_scr.shape, jnp.float32)
        o_scr[...] = jnp.zeros(o_scr.shape, jnp.float32)

    def accumulate():
        q = q_ref[...].astype(jnp.float32) * scale
        k_blk = k_ref[...].astype(jnp.float32)
        v_blk = v_ref[...].astype(jnp.float32)
        logits = jax.lax.dot_general(
            q, k_blk, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32)
        if causal:
            mask = _causal_mask(qi, ki, blk_q, blk_k)
            logits = jnp.where(mask, logits, _NEG_INF)
        m = m_scr[...]
        m_new = jnp.maximum(m, logits.max(-1, keepdims=True))
        corr = jnp.exp(m - m_new)
        p = jnp.exp(logits - m_new)
        if causal:
            p = jnp.where(mask, p, 0.0)
        l_scr[...] = l_scr[...] * corr + p.sum(-1, keepdims=True)
        o_scr[...] = o_scr[...] * corr + jax.lax.dot_general(
            p, v_blk, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        m_scr[...] = m_new

    if causal:
        pl.when(ki <= _last_k_block(qi, blk_q, blk_k))(accumulate)
    else:
        accumulate()

    @pl.when(ki == pl.num_programs(2) - 1)
    def _():
        l_safe = jnp.maximum(l_scr[...], 1e-30)
        o_ref[...] = (o_scr[...] / l_safe).astype(o_ref.dtype)
        lse_ref[...] = (m_scr[...] + jnp.log(l_safe))[:, 0][None, :]


def _flash_bwd_dq_kernel(q_ref, k_ref, v_ref, g_ref, lse_ref, delta_ref,
                         dq_ref, dq_scr, *, blk_q: int, blk_k: int,
                         causal: bool, scale: float):
    """Grid: (batch*heads, num_q_blocks, num_k_blocks), the last axis
    sequential: dq for one Q tile, accumulated over streamed KV tiles."""
    qi, ki = pl.program_id(1), pl.program_id(2)

    @pl.when(ki == 0)
    def _():
        dq_scr[...] = jnp.zeros(dq_scr.shape, jnp.float32)

    def accumulate():
        q = q_ref[...].astype(jnp.float32) * scale
        g = g_ref[...].astype(jnp.float32)
        k_blk = k_ref[...].astype(jnp.float32)
        v_blk = v_ref[...].astype(jnp.float32)
        logits = jax.lax.dot_general(
            q, k_blk, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32)
        p = jnp.exp(logits - lse_ref[0, :][:, None])
        if causal:
            p = jnp.where(_causal_mask(qi, ki, blk_q, blk_k), p, 0.0)
        dp = jax.lax.dot_general(
            g, v_blk, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32)
        ds = p * (dp - delta_ref[0, :][:, None])
        dq_scr[...] += jax.lax.dot_general(
            ds, k_blk, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)

    if causal:
        pl.when(ki <= _last_k_block(qi, blk_q, blk_k))(accumulate)
    else:
        accumulate()

    @pl.when(ki == pl.num_programs(2) - 1)
    def _():
        dq_ref[...] = (dq_scr[...] * scale).astype(dq_ref.dtype)


def _flash_bwd_dkv_kernel(q_ref, k_ref, v_ref, g_ref, lse_ref, delta_ref,
                          dk_ref, dv_ref, dk_scr, dv_scr, *, blk_q: int,
                          blk_k: int, causal: bool, scale: float):
    """Grid: (batch*heads, num_k_blocks, num_q_blocks), the last axis
    sequential: dk/dv for one KV tile, accumulated over streamed Q, dO, lse
    and delta tiles (only those at or after the diagonal when causal)."""
    ki, qi = pl.program_id(1), pl.program_id(2)

    @pl.when(qi == 0)
    def _():
        dk_scr[...] = jnp.zeros(dk_scr.shape, jnp.float32)
        dv_scr[...] = jnp.zeros(dv_scr.shape, jnp.float32)

    def accumulate():
        k = k_ref[...].astype(jnp.float32)
        v = v_ref[...].astype(jnp.float32)
        q_blk = q_ref[...].astype(jnp.float32) * scale
        g_blk = g_ref[...].astype(jnp.float32)
        logits = jax.lax.dot_general(
            q_blk, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32)
        p = jnp.exp(logits - lse_ref[0, :][:, None])
        if causal:
            p = jnp.where(_causal_mask(qi, ki, blk_q, blk_k), p, 0.0)
        dv_scr[...] += jax.lax.dot_general(
            p, g_blk, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        dp = jax.lax.dot_general(
            g_blk, v, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32)
        ds = p * (dp - delta_ref[0, :][:, None])
        # q_blk carries one factor of scale: scale * ds^T @ q is dk.
        dk_scr[...] += jax.lax.dot_general(
            ds, q_blk, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)

    if causal:
        pl.when(qi >= _first_q_block(ki, blk_q, blk_k))(accumulate)
    else:
        accumulate()

    @pl.when(qi == pl.num_programs(2) - 1)
    def _():
        dk_ref[...] = dk_scr[...].astype(dk_ref.dtype)
        dv_ref[...] = dv_scr[...].astype(dv_ref.dtype)


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


def _streamed(causal: bool, blk_q: int, blk_k: int):
    """Index maps of a (batch*heads, Q tile, KV tile) grid and of its
    transpose. A tile that a causal step skips maps to the nearest one it
    does not, so consecutive skipped steps fetch nothing new."""
    if causal:
        def kv_of(b, i, j):
            return b, jnp.minimum(j, _last_k_block(i, blk_q, blk_k)), 0

        def q_of(b, j, i):
            return b, jnp.maximum(i, _first_q_block(j, blk_q, blk_k)), 0

        def row_of(b, j, i):
            return b, 0, jnp.maximum(i, _first_q_block(j, blk_q, blk_k))
    else:
        def kv_of(b, i, j):
            return b, j, 0

        def q_of(b, j, i):
            return b, i, 0

        def row_of(b, j, i):
            return b, 0, i
    return kv_of, q_of, row_of


def _compiler_params():
    return pltpu.CompilerParams(
        dimension_semantics=("parallel", "parallel", "arbitrary"))


def _flash_forward(q, k, v, causal: bool, blk_q: int, blk_k: int):
    B, S, H, D = q.shape
    Dv = v.shape[-1]
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
    kv_of, _, _ = _streamed(causal, blk_q, blk_k)

    kernel = functools.partial(
        _flash_fwd_kernel, blk_q=blk_q, blk_k=blk_k, causal=causal,
        scale=scale)
    out, lse = pl.pallas_call(
        kernel,
        grid=(B * H, S // blk_q, S // blk_k),
        in_specs=[
            pl.BlockSpec((None, blk_q, D), lambda b, i, j: (b, i, 0)),
            pl.BlockSpec((None, blk_k, D), kv_of),
            pl.BlockSpec((None, blk_k, Dv), kv_of),
        ],
        out_specs=[
            pl.BlockSpec((None, blk_q, Dv), lambda b, i, j: (b, i, 0)),
            pl.BlockSpec((None, 1, blk_q), lambda b, i, j: (b, 0, i)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((B * H, S, Dv), q.dtype),
            jax.ShapeDtypeStruct((B * H, 1, S), jnp.float32),
        ],
        scratch_shapes=[pltpu.VMEM((blk_q, 1), jnp.float32),
                        pltpu.VMEM((blk_q, 1), jnp.float32),
                        pltpu.VMEM((blk_q, Dv), jnp.float32)],
        compiler_params=_compiler_params(),
        interpret=_interpret(),
        name="flash_fwd",
    )(qf, kf, vf)
    return _from_bh(out, B, H), lse


def _flash_backward(q, k, v, out, lse, g, causal: bool, blk_q: int,
                    blk_k: int):
    B, S, H, D = q.shape
    Dv = v.shape[-1]
    kvh = k.shape[2]
    k_rep, v_rep = _repeat_heads(k, v, H)
    scale = 1.0 / math.sqrt(D)
    qf, kf, vf = _to_bh(q), _to_bh(k_rep), _to_bh(v_rep)
    gf, of = _to_bh(g), _to_bh(out)
    delta = jnp.sum(gf.astype(jnp.float32) * of.astype(jnp.float32),
                    axis=-1)[:, None, :]  # [BH, 1, S]
    kv_of, q_of, row_of = _streamed(causal, blk_q, blk_k)

    common = dict(blk_q=blk_q, blk_k=blk_k, causal=causal, scale=scale)
    dq = pl.pallas_call(
        functools.partial(_flash_bwd_dq_kernel, **common),
        grid=(B * H, S // blk_q, S // blk_k),
        in_specs=[
            pl.BlockSpec((None, blk_q, D), lambda b, i, j: (b, i, 0)),
            pl.BlockSpec((None, blk_k, D), kv_of),
            pl.BlockSpec((None, blk_k, Dv), kv_of),
            pl.BlockSpec((None, blk_q, Dv), lambda b, i, j: (b, i, 0)),
            pl.BlockSpec((None, 1, blk_q), lambda b, i, j: (b, 0, i)),
            pl.BlockSpec((None, 1, blk_q), lambda b, i, j: (b, 0, i)),
        ],
        out_specs=pl.BlockSpec((None, blk_q, D), lambda b, i, j: (b, i, 0)),
        out_shape=jax.ShapeDtypeStruct((B * H, S, D), q.dtype),
        scratch_shapes=[pltpu.VMEM((blk_q, D), jnp.float32)],
        compiler_params=_compiler_params(),
        interpret=_interpret(),
        name="flash_bwd_dq",
    )(qf, kf, vf, gf, lse, delta)

    dk, dv = pl.pallas_call(
        functools.partial(_flash_bwd_dkv_kernel, **common),
        grid=(B * H, S // blk_k, S // blk_q),
        in_specs=[
            pl.BlockSpec((None, blk_q, D), q_of),
            pl.BlockSpec((None, blk_k, D), lambda b, j, i: (b, j, 0)),
            pl.BlockSpec((None, blk_k, Dv), lambda b, j, i: (b, j, 0)),
            pl.BlockSpec((None, blk_q, Dv), q_of),
            pl.BlockSpec((None, 1, blk_q), row_of),
            pl.BlockSpec((None, 1, blk_q), row_of),
        ],
        out_specs=[
            pl.BlockSpec((None, blk_k, D), lambda b, j, i: (b, j, 0)),
            pl.BlockSpec((None, blk_k, Dv), lambda b, j, i: (b, j, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((B * H, S, D), k.dtype),
            jax.ShapeDtypeStruct((B * H, S, Dv), v.dtype),
        ],
        scratch_shapes=[pltpu.VMEM((blk_k, D), jnp.float32),
                        pltpu.VMEM((blk_k, Dv), jnp.float32)],
        compiler_params=_compiler_params(),
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
        dv = dv.reshape(B, S, kvh, rep, Dv).sum(axis=3)
    return dq, dk, dv


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5))
def flash_attention(q, k, v, causal: bool = True, blk_q: int = 1024,
                    blk_k: int = 1024):
    """q: [B, S, H, D], k: [B, S, KVH, D], v: [B, S, KVH, Dv] →
    [B, S, H, Dv]; scores are scaled by 1/sqrt(D)."""
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
