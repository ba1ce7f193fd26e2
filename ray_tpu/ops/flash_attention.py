"""Flash attention as Pallas TPU kernels (forward + backward).

One grid step is one tile: a [blk_q, D] Q tile against a [blk_k, D] /
[blk_k, Dv] KV tile. The grid is ``(batch*heads, pairs)``: its second axis
walks a table of the (Q tile, KV tile) pairs that have work, built with
numpy at trace time from S, the two tile sizes and ``causal``, and handed
to the kernel as two scalar-prefetched int32 arrays that the index maps
and the kernel body read. A tile above the causal diagonal is not in the
table, so it costs no step and no fetch (a step of a rectangular grid
that ``pl.when`` skips costs 0.36-0.38 us on a v5e, PERF.md §6, PR 28);
``causal=False`` is the same code over the table of all pairs. Every causal
tile in the table builds and applies the mask, though only a *diagonal* one
has anything to mask and a *full* one (its last column at or before its
first row) has not: leaving the mask out of full tiles was measured and
bought nothing, the backward kernels being bound by their products and the
forward by its row maximum. ``causal_tile_census`` counts the classes.

A ``window`` (sliding-window attention: key j is seen by query i where
``0 <= i - j < window``) is the same table with one more edge: the tiles
wholly behind the window's trailing edge are left out as those above the
diagonal are, and a tile the trailing edge cuts is masked as one the
diagonal cuts (``window_tile_census``). With ``window=None``, or a window
the sequence does not reach, tables, kernels and their names are the causal
ones; a call with a window that cuts something carries names of its own
(``flash_fwd_win``, ``flash_bwd_dq_win``, ``flash_bwd_dkv_win``), so that a
trace tells a window layer's kernels from a full layer's.

The forward kernel walks the table Q-major, KV tiles ascending, with the
online-softmax state in VMEM scratch, keeping the MXU fed with
[blk_q, D] x [D, blk_k] matmuls (pallas_guide.md: grid/BlockSpec + scratch
accumulators), and emits the per-row logsumexp needed by the backward
pass. Nothing of length S is ever resident in VMEM, so the sequence length
is bounded by HBM alone, and q/k may have another head size (D) than v and
the output (Dv): latent attention trains with D = 192, Dv = 128.

Backward is the standard two-kernel FlashAttention scheme: a dQ kernel
(the forward's table) and a dK/dV kernel (the table KV-major, Q tiles
ascending from the first that sees the KV tile), both recomputing
probabilities from q, k and the saved logsumexp: O(S) memory, no S x S
tensor ever materializes in HBM. The one dispatch is models/lm.py
``attention``; every model reaches the kernels through it.

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
import numpy as np
from jax.ad_checkpoint import checkpoint_name
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ray_tpu.ops.blockwise_attention import blockwise_attention

_NEG_INF = -1e30

# The names of the forward kernel's two outputs among the custom VJP's
# residuals. A rematerialisation policy that keeps them
# (``save_only_these_names(*RESIDUAL_NAMES)``, as models/lm.py
# ``scan_blocks`` does) recomputes q, k and v in the backward pass and not
# the kernel: O(S^2) work for O(S) bytes, ``out`` [B, S, H, Dv] in the
# activations' dtype and ``lse`` [B*H, 1, S] in f32. The outputs carry the
# names only where ``worth_keeping`` says a kept byte buys enough.
RESIDUAL_NAMES = ("flash_out", "flash_lse")


def _interpret() -> bool:
    return jax.default_backend() != "tpu"


def _causal_mask(qi, ki, blk_q: int, blk_k: int, window=None):
    """[blk_q, blk_k] bool: query row >= key column, for tiles qi and ki;
    with a ``window`` also query row - key column < window."""
    q_pos = qi * blk_q + jax.lax.broadcasted_iota(
        jnp.int32, (blk_q, blk_k), 0)
    k_pos = ki * blk_k + jax.lax.broadcasted_iota(
        jnp.int32, (blk_q, blk_k), 1)
    if window is None:
        return q_pos >= k_pos
    return (q_pos >= k_pos) & (q_pos - k_pos < window)


def _is_empty(qi, ki, blk_q: int, blk_k: int, window=None):
    """Causal tile (qi, ki) allows no pair: its first column is past its
    last row, or (with a ``window``) its last column is ``window`` or more
    behind its first row."""
    above = ki * blk_k > (qi + 1) * blk_q - 1
    if window is None:
        return above
    return above | ((ki + 1) * blk_k - 1 <= qi * blk_q - window)


def _is_full(qi, ki, blk_q: int, blk_k: int, window=None):
    """Causal tile (qi, ki) allows every pair: its last column is at or
    before its first row, and (with a ``window``) its first column is less
    than ``window`` behind its last row."""
    under = (ki + 1) * blk_k - 1 <= qi * blk_q
    if window is None:
        return under
    return under & ((qi + 1) * blk_q - 1 - ki * blk_k < window)


def _tile_grid(S: int, blk_q: int, blk_k: int):
    return np.meshgrid(np.arange(S // blk_q, dtype=np.int32),
                       np.arange(S // blk_k, dtype=np.int32), indexing="ij")


def _tile_pairs(S: int, blk_q: int, blk_k: int, causal: bool,
                kv_major: bool, window=None):
    """The tiles with work as two int32 tables (qi_tab, ki_tab), one entry
    a grid step: Q-major with KV tiles ascending, or KV-major with Q tiles
    ascending, so every sum a kernel carries keeps its order."""
    qi, ki = _tile_grid(S, blk_q, blk_k)
    keep = ~_is_empty(qi, ki, blk_q, blk_k, window) if causal \
        else np.ones_like(qi, bool)
    if kv_major:
        qi, ki, keep = qi.T, ki.T, keep.T
    return qi[keep], ki[keep]


def causal_tile_census(S: int, blk_q: int, blk_k: int) -> dict:
    """How many of a causal S x S attention's tiles are of each class:
    ``executed`` (= ``diagonal`` + ``full``) is the length of the kernels'
    table, ``empty`` the tiles that get no grid step."""
    return window_tile_census(S, None, blk_q, blk_k)


def window_tile_census(S: int, window, blk_q: int, blk_k: int) -> dict:
    """``causal_tile_census`` under a ``window``: ``diagonal`` counts the
    tiles either edge cuts (the diagonal or the window's trailing edge),
    ``empty`` those above the diagonal or wholly behind the window. At
    tiles of 512 x 512 a window of 4096 executes 252 of a 16384-token
    head's tiles (causal: 528) and 540 of a 32768-token head's (2,080)."""
    qi, ki = _tile_grid(S, blk_q, blk_k)
    empty = int(_is_empty(qi, ki, blk_q, blk_k, window).sum())
    full = int(_is_full(qi, ki, blk_q, blk_k, window).sum())
    executed = qi.size - empty
    return {"executed": executed, "diagonal": executed - full,
            "full": full, "empty": empty}


def _cutting(window, S: int):
    """``window`` where it cuts something of a causal S x S attention, else
    None: a window the sequence does not reach is causal attention, by the
    causal kernels under their own names."""
    if window is None:
        return None
    if window < 1:
        raise ValueError(f"window must be at least 1, got {window}")
    return int(window) if window < S else None


def worth_keeping(S: int, Dv: int) -> bool:
    """Whether the forward kernel's outputs are worth their bytes to a
    backward pass that could run the kernel again instead: from
    S / Dv = 32. A head's causal forward is S^2 / (2 blk^2) tiles for
    2 S Dv bytes of output, so a kept byte buys kernel time in proportion
    to S / Dv, about 2 ms a GB for each unit of it at 512 x 512 tiles of
    1.9-2.3 us. Measured on a v5e, step time saved over bytes kept
    (PERF.md §6, PR 30): 887 ms a GB at S / Dv = 512 (S 32768, heads of
    64), 220 at 64 (S 8192, Dv 128), but 12-16 at 8 (S 2048, heads of
    256), less than the 21 ms a GB that the output of a 4096-wide matmul
    buys, and there the 1.35 GB a chip crowded a checkpoint's transfers
    (a save stalled the loop 10.3 s, not 8.9). Nothing was measured
    between 8 and 64; at 32 the estimate is three times a matmul's."""
    return S >= 32 * Dv


def _row_ends(row_tab):
    """(first, last): whether this grid step opens / closes its row of
    tiles, a run of equal entries in ``row_tab``."""
    t, last_t = pl.program_id(1), pl.num_programs(1) - 1
    row = row_tab[t]
    first = jnp.logical_or(
        t == 0, row_tab[jnp.maximum(t - 1, 0)] != row)
    last = jnp.logical_or(
        t == last_t, row_tab[jnp.minimum(t + 1, last_t)] != row)
    return first, last


def _flash_fwd_kernel(qi_tab, ki_tab, q_ref, k_ref, v_ref, o_ref, lse_ref,
                      m_scr, l_scr, o_scr, *, blk_q: int, blk_k: int,
                      causal: bool, scale: float, window=None):
    """Grid: (batch*heads, pairs), Q-major, the pair axis sequential. One
    [blk_q, D] Q tile against one [blk_k, D] / [blk_k, Dv] KV tile per
    step, the online-softmax state (m, l, o) carried in VMEM scratch along
    a row of tiles; o_ref [blk_q, Dv] and lse_ref [1, blk_q] are written at
    the row's last step."""
    t = pl.program_id(1)
    qi, ki = qi_tab[t], ki_tab[t]
    first, last = _row_ends(qi_tab)

    @pl.when(first)
    def _():
        m_scr[...] = jnp.full(m_scr.shape, _NEG_INF, jnp.float32)
        l_scr[...] = jnp.zeros(l_scr.shape, jnp.float32)
        o_scr[...] = jnp.zeros(o_scr.shape, jnp.float32)

    q = q_ref[...].astype(jnp.float32) * scale
    k_blk = k_ref[...].astype(jnp.float32)
    v_blk = v_ref[...].astype(jnp.float32)
    logits = jax.lax.dot_general(
        q, k_blk, (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32)
    if causal:
        logits = jnp.where(_causal_mask(qi, ki, blk_q, blk_k, window),
                           logits, _NEG_INF)
    m = m_scr[...]
    m_new = jnp.maximum(m, logits.max(-1, keepdims=True))
    corr = jnp.exp(m - m_new)
    # A masked logit is -1e30 and every row has seen column 0 by now (a
    # row of tiles starts at KV tile 0), so m_new is a real logit and
    # exp(-1e30 - m_new) is 0.0 exactly: p needs no select of its own.
    # Under a window a row of tiles starts at the first tile the window
    # reaches, of which the later query rows may see nothing: m_new is
    # then still -1e30, p is 1 and l and o gather what they should not,
    # until the row's first real logit (every row sees itself) makes
    # corr = exp(-1e30 - m_new) = 0.0 exactly and wipes both.
    p = jnp.exp(logits - m_new)
    l_scr[...] = l_scr[...] * corr + p.sum(-1, keepdims=True)
    o_scr[...] = o_scr[...] * corr + jax.lax.dot_general(
        p, v_blk, (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)
    m_scr[...] = m_new

    @pl.when(last)
    def _():
        l_safe = jnp.maximum(l_scr[...], 1e-30)
        o_ref[...] = (o_scr[...] / l_safe).astype(o_ref.dtype)
        lse_ref[...] = (m_scr[...] + jnp.log(l_safe))[:, 0][None, :]


def _flash_bwd_dq_kernel(qi_tab, ki_tab, q_ref, k_ref, v_ref, g_ref,
                         lse_ref, delta_ref, dq_ref, dq_scr, *, blk_q: int,
                         blk_k: int, causal: bool, scale: float,
                         window=None):
    """Grid: (batch*heads, pairs), Q-major, the pair axis sequential: dq
    for one Q tile, accumulated over its row of KV tiles."""
    t = pl.program_id(1)
    qi, ki = qi_tab[t], ki_tab[t]
    first, last = _row_ends(qi_tab)

    @pl.when(first)
    def _():
        dq_scr[...] = jnp.zeros(dq_scr.shape, jnp.float32)

    q = q_ref[...].astype(jnp.float32) * scale
    g = g_ref[...].astype(jnp.float32)
    k_blk = k_ref[...].astype(jnp.float32)
    v_blk = v_ref[...].astype(jnp.float32)
    logits = jax.lax.dot_general(
        q, k_blk, (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32)
    p = jnp.exp(logits - lse_ref[0, :][:, None])
    if causal:
        p = jnp.where(_causal_mask(qi, ki, blk_q, blk_k, window), p, 0.0)
    dp = jax.lax.dot_general(
        g, v_blk, (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32)
    ds = p * (dp - delta_ref[0, :][:, None])
    dq_scr[...] += jax.lax.dot_general(
        ds, k_blk, (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)

    @pl.when(last)
    def _():
        dq_ref[...] = (dq_scr[...] * scale).astype(dq_ref.dtype)


def _flash_bwd_dkv_kernel(qi_tab, ki_tab, q_ref, k_ref, v_ref, g_ref,
                          lse_ref, delta_ref, dk_ref, dv_ref, dk_scr,
                          dv_scr, *, blk_q: int, blk_k: int, causal: bool,
                          scale: float, window=None):
    """Grid: (batch*heads, pairs), KV-major, the pair axis sequential:
    dk/dv for one KV tile, accumulated over the Q, dO, lse and delta tiles
    of its row (those at or after the diagonal when causal)."""
    t = pl.program_id(1)
    qi, ki = qi_tab[t], ki_tab[t]
    first, last = _row_ends(ki_tab)

    @pl.when(first)
    def _():
        dk_scr[...] = jnp.zeros(dk_scr.shape, jnp.float32)
        dv_scr[...] = jnp.zeros(dv_scr.shape, jnp.float32)

    k = k_ref[...].astype(jnp.float32)
    v = v_ref[...].astype(jnp.float32)
    q_blk = q_ref[...].astype(jnp.float32) * scale
    g_blk = g_ref[...].astype(jnp.float32)
    logits = jax.lax.dot_general(
        q_blk, k, (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32)
    p = jnp.exp(logits - lse_ref[0, :][:, None])
    if causal:
        p = jnp.where(_causal_mask(qi, ki, blk_q, blk_k, window), p, 0.0)
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

    @pl.when(last)
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


def _q_tile(b, t, qi_tab, ki_tab):
    return b, qi_tab[t], 0


def _kv_tile(b, t, qi_tab, ki_tab):
    return b, ki_tab[t], 0


def _q_row(b, t, qi_tab, ki_tab):
    return b, 0, qi_tab[t]


def _tiled_call(kernel, name: str, batch_heads: int, pairs, in_specs,
                out_specs, out_shape, scratch_shapes):
    """One kernel over the grid (batch*heads, pairs), to be called on its
    operands: the first axis parallel, the pair axis sequential, the two
    tables scalar-prefetched."""
    qi_tab, ki_tab = pairs
    call = pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2, grid=(batch_heads, len(qi_tab)),
            in_specs=in_specs, out_specs=out_specs,
            scratch_shapes=scratch_shapes),
        out_shape=out_shape,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=_interpret(),
        name=name,
    )
    return functools.partial(call, jnp.asarray(qi_tab), jnp.asarray(ki_tab))


def _score_scale(scale, D: int) -> float:
    return 1.0 / math.sqrt(D) if scale is None else float(scale)


def _named(name: str, window) -> str:
    return name + "_win" if window is not None else name


def _flash_forward(q, k, v, causal: bool, blk_q: int, blk_k: int,
                   scale=None, window=None):
    B, S, H, D = q.shape
    Dv = v.shape[-1]
    k, v = _repeat_heads(k, v, H)
    scale = _score_scale(scale, D)
    blk_q = _pick_block(S, blk_q)
    blk_k = _pick_block(S, blk_k)
    window = _cutting(window, S)
    if window is not None and not causal:
        raise ValueError("a window is the causal mask's trailing edge: "
                         "window needs causal=True")
    if blk_q < 128 or blk_k < 128:
        if not _interpret():
            raise ValueError(
                f"flash_attention on TPU needs a sequence length that is a "
                f"multiple of 128, got S={S}: pad the sequence or use "
                "attn_impl='dot'")
        if window is not None:
            raise NotImplementedError(
                f"flash_attention with window={window}: the blockwise path "
                f"that a ragged sequence (S={S}) takes has no window; use "
                "attn_impl='dot'")
        # Short or ragged sequence on the CPU test backend, where there is
        # no kernel to lose: the jnp blockwise path (no lse output — the
        # custom VJP then differentiates the blockwise recurrence instead
        # of running the Pallas backward).
        return blockwise_attention(q, k, v, causal=causal, scale=scale), None
    qf, kf, vf = _to_bh(q), _to_bh(k), _to_bh(v)

    kernel = functools.partial(
        _flash_fwd_kernel, blk_q=blk_q, blk_k=blk_k, causal=causal,
        scale=scale, window=window)
    out, lse = _tiled_call(
        kernel, _named("flash_fwd", window), B * H,
        _tile_pairs(S, blk_q, blk_k, causal, False, window),
        in_specs=[
            pl.BlockSpec((None, blk_q, D), _q_tile),
            pl.BlockSpec((None, blk_k, D), _kv_tile),
            pl.BlockSpec((None, blk_k, Dv), _kv_tile),
        ],
        out_specs=[
            pl.BlockSpec((None, blk_q, Dv), _q_tile),
            pl.BlockSpec((None, 1, blk_q), _q_row),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((B * H, S, Dv), q.dtype),
            jax.ShapeDtypeStruct((B * H, 1, S), jnp.float32),
        ],
        scratch_shapes=[pltpu.VMEM((blk_q, 1), jnp.float32),
                        pltpu.VMEM((blk_q, 1), jnp.float32),
                        pltpu.VMEM((blk_q, Dv), jnp.float32)],
    )(qf, kf, vf)
    return _from_bh(out, B, H), lse


def _flash_backward(q, k, v, out, lse, g, causal: bool, blk_q: int,
                    blk_k: int, scale=None, window=None):
    B, S, H, D = q.shape
    window = _cutting(window, S)
    Dv = v.shape[-1]
    kvh = k.shape[2]
    k_rep, v_rep = _repeat_heads(k, v, H)
    scale = _score_scale(scale, D)
    qf, kf, vf = _to_bh(q), _to_bh(k_rep), _to_bh(v_rep)
    gf, of = _to_bh(g), _to_bh(out)
    delta = jnp.sum(gf.astype(jnp.float32) * of.astype(jnp.float32),
                    axis=-1)[:, None, :]  # [BH, 1, S]
    common = dict(blk_q=blk_q, blk_k=blk_k, causal=causal, scale=scale,
                  window=window)
    # The six operands of both backward kernels, tiled alike in both.
    operands = (qf, kf, vf, gf, lse, delta)
    in_specs = [
        pl.BlockSpec((None, blk_q, D), _q_tile),
        pl.BlockSpec((None, blk_k, D), _kv_tile),
        pl.BlockSpec((None, blk_k, Dv), _kv_tile),
        pl.BlockSpec((None, blk_q, Dv), _q_tile),
        pl.BlockSpec((None, 1, blk_q), _q_row),
        pl.BlockSpec((None, 1, blk_q), _q_row),
    ]
    dq = _tiled_call(
        functools.partial(_flash_bwd_dq_kernel, **common),
        _named("flash_bwd_dq", window), B * H,
        _tile_pairs(S, blk_q, blk_k, causal, False, window),
        in_specs=in_specs,
        out_specs=pl.BlockSpec((None, blk_q, D), _q_tile),
        out_shape=jax.ShapeDtypeStruct((B * H, S, D), q.dtype),
        scratch_shapes=[pltpu.VMEM((blk_q, D), jnp.float32)],
    )(*operands)

    dk, dv = _tiled_call(
        functools.partial(_flash_bwd_dkv_kernel, **common),
        _named("flash_bwd_dkv", window), B * H,
        _tile_pairs(S, blk_q, blk_k, causal, True, window),
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
    )(*operands)

    dq = _from_bh(dq, B, H)
    dk = _from_bh(dk, B, H)
    dv = _from_bh(dv, B, H)
    if kvh != H:
        # GQA: fold gradients of the repeated heads back onto the KV heads.
        rep = H // kvh
        dk = dk.reshape(B, S, kvh, rep, D).sum(axis=3)
        dv = dv.reshape(B, S, kvh, rep, Dv).sum(axis=3)
    return dq, dk, dv


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6, 7))
def flash_attention(q, k, v, causal: bool = True, blk_q: int = 1024,
                    blk_k: int = 1024, scale=None, window=None):
    """q: [B, S, H, D], k: [B, S, KVH, D], v: [B, S, KVH, Dv] →
    [B, S, H, Dv]. Query head i reads KV head i // (H // KVH). Scores are
    multiplied by ``scale``: the model's own (a Python float), or
    1/sqrt(D) where it is None. With ``window`` (an int; causal only) query
    i sees the keys j with ``0 <= i - j < window``: itself and the
    ``window - 1`` before it."""
    return _flash_forward(q, k, v, causal, blk_q, blk_k, scale, window)[0]


def _fwd(q, k, v, causal, blk_q, blk_k, scale, window):
    out, lse = _flash_forward(q, k, v, causal, blk_q, blk_k, scale, window)
    if lse is None:
        # Ragged fallback: differentiate the jnp blockwise recurrence.
        return out, (q, k, v, None, None)
    if worth_keeping(q.shape[1], v.shape[-1]):
        # Named once, before ``out`` goes out both as the primal and as a
        # residual, so that both are the one kept value.
        out = checkpoint_name(out, RESIDUAL_NAMES[0])
        lse = checkpoint_name(lse, RESIDUAL_NAMES[1])
    return out, (q, k, v, out, lse)


def _bwd(causal, blk_q, blk_k, scale, window, residuals, g):
    q, k, v, out, lse = residuals
    if lse is None:
        _, vjp = jax.vjp(
            lambda q_, k_, v_: blockwise_attention(
                q_, k_, v_, causal=causal, scale=scale), q, k, v)
        return vjp(g)
    S = q.shape[1]
    return _flash_backward(q, k, v, out, lse, g, causal,
                           _pick_block(S, blk_q), _pick_block(S, blk_k),
                           scale, window)


flash_attention.defvjp(_fwd, _bwd)
