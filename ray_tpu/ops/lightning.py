"""Lightning linear attention (Lightning Attention-2, arXiv:2401.04658, as
MiniMax-01 and ``minicpm_sala`` run it): a linear-attention recurrence with
one fixed decay a head, as a pair of Pallas TPU kernels (forward, backward)
and two ``jax.numpy`` forms of the same sums.

Per head, with ``lambda = exp(-slope)`` (``slope`` [H] positive, a constant
of the layer that no gradient moves) and a state ``S`` [K, V] float32 that is
zero before the first token::

    S_t = lambda S_(t-1) + k_t v_t^T
    o_t = scale q_t S_t          = scale sum_{s<=t} lambda^(t-s) (q_t . k_s) v_s

The literal recurrence (``lightning_recurrent``, the tests' oracle) is S
sequential steps. The chunked form does a chunk of L steps as matrix
products: inside a chunk, with ``D[i, j] = lambda^(i-j)`` for ``j <= i`` and
0 above the diagonal,

    o     = ((scale Q K^T) * D) V + scale diag(lambda^(i+1)) Q S_in
    S_out = lambda^L S_in + (K * lambda^(L-1-i))^T V

and only ``S_in -> S_out`` runs along the sequence, once a chunk
(``lightning_chunked``: any length, float32, the kernels' oracle).

The kernels follow ``ops/ssd.py``'s plan: the grid is ``(batch, head blocks,
chunks)``, the last sequential; a grid step is one chunk of one block of
heads, whose state stays in VMEM scratch from chunk to chunk. The ``[L, L]``
decay matrix is made in VMEM from the head's slope (a scalar a head: there
is no ``cum`` operand and no float32 ``[S, H]`` array in HBM; ``lambda^(i -
j)`` is ``exp(-slope (i - j))`` of one iota difference, never a quotient of
two powers, which under- and overflows at the steep heads). The forward
writes each chunk's entry state, which the backward reads: it walks the
chunks in reverse with the state's cotangent carried the same way. Products
take operands in q's dtype and accumulate in float32; states and decays are
float32.

``lightning`` is the one entry (``models/lm.py`` ``linear_attention``): the
kernels where the shapes tile (S a multiple of the chunk, heads of a
multiple of 128), else ``lightning_chunked``. Off the TPU the kernels run in
interpreter mode, as the flash kernels decide it.
"""

from __future__ import annotations

import functools
import importlib
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

F32 = jnp.float32

#: Positions a chunk of the kernels.
CHUNK = 256


def _interpret() -> bool:
    """The flash kernels' answer, asked of that module each time so that one
    switch steers every kernel of ``ops/``."""
    return importlib.import_module(
        "ray_tpu.ops.flash_attention")._interpret()


def _scale(scale, width: int) -> float:
    return 1.0 / math.sqrt(width) if scale is None else float(scale)


# -- jax.numpy ---------------------------------------------------------------

def lightning_recurrent(q, k, v, slope, scale=None):
    """The recurrence token by token, float32. q, k [B, S, H, K], v [B, S,
    H, V], slope [H] -> [B, S, H, V] in q's dtype."""
    B, S, H, K = q.shape
    lam = jnp.exp(-slope.astype(F32))[None, :, None, None]

    def step(state, qkv):
        q_t, k_t, v_t = qkv
        state = lam * state + k_t[..., :, None] * v_t[..., None, :]
        return state, jnp.einsum("bhk,bhkv->bhv", q_t, state)

    _, out = jax.lax.scan(
        step, jnp.zeros((B, H, K, v.shape[-1]), F32),
        tuple(a.astype(F32).swapaxes(0, 1) for a in (q, k, v)))
    return (out.swapaxes(0, 1) * _scale(scale, K)).astype(q.dtype)


def lightning_chunked(q, k, v, slope, scale=None, chunk: int = CHUNK):
    """The chunked form as einsums, any length (the tail is padded with
    zero keys and values, which add nothing to a state): float32
    throughout."""
    B, S, H, K = q.shape
    V = v.shape[-1]
    pad = -S % chunk
    if pad:
        q, k, v = (jnp.pad(a, ((0, 0), (0, pad), (0, 0), (0, 0)))
                   for a in (q, k, v))
    n = (S + pad) // chunk
    q_c, k_c, v_c = (a.astype(F32).reshape(B, n, chunk, H, -1)
                     for a in (q, k, v))
    slope = slope.astype(F32)
    at = jnp.arange(chunk, dtype=F32)
    gap = at[:, None] - at[None, :]
    decay = jnp.where(gap >= 0, jnp.exp(-slope[:, None, None]
                                        * jnp.maximum(gap, 0.0)), 0.0)
    scores = jnp.einsum("bcihk,bcjhk->bchij", q_c, k_c) * decay
    out = jnp.einsum("bchij,bcjhv->bcihv", scores, v_c)
    to_end = jnp.exp(-slope[None, :] * (chunk - 1 - at)[:, None])  # [L, H]
    own = jnp.einsum("bcjhk,jh,bcjhv->bchkv", k_c, to_end, v_c)
    whole = jnp.exp(-slope * chunk)[None, :, None, None]

    def carry(state, own_c):
        return whole * state + own_c, state

    _, entry = jax.lax.scan(carry, jnp.zeros((B, H, K, V), F32),
                            own.swapaxes(0, 1))
    from_start = jnp.exp(-slope[None, :] * (at + 1.0)[:, None])   # [L, H]
    out = out + jnp.einsum("bcihk,ih,cbhkv->bcihv", q_c, from_start, entry)
    out = out.reshape(B, S + pad, H, V)[:, :S] * _scale(scale, K)
    return out.astype(q.dtype)


# -- the kernels -------------------------------------------------------------

def _mm(a, b, contract_a: int, contract_b: int):
    return jax.lax.dot_general(
        a, b, (((contract_a,), (contract_b,)), ((), ())),
        preferred_element_type=F32)


def _decays(slope, chunk: int):
    """(D [L, L], lambda^(i+1) [L, 1], lambda^(L-1-i) [L, 1], lambda^L [1,
    1]) of one head from its slope [1, 1]."""
    rows = jax.lax.broadcasted_iota(jnp.int32, (chunk, chunk), 0)
    cols = jax.lax.broadcasted_iota(jnp.int32, (chunk, chunk), 1)
    gap = (rows - cols).astype(F32)
    within = jnp.where(rows >= cols, jnp.exp(-slope * jnp.maximum(gap, 0.0)),
                       0.0)
    at = jax.lax.broadcasted_iota(jnp.int32, (chunk, 1), 0).astype(F32)
    return (within, jnp.exp(-slope * (at + 1.0)),
            jnp.exp(-slope * (chunk - 1.0 - at)), jnp.exp(-slope * chunk))


def _fwd_kernel(q_ref, k_ref, v_ref, slope_ref, o_ref, entry_ref, state_scr,
                *, heads: int, width: int, v_width: int, scale: float):
    """One chunk of one block of ``heads`` heads. q/k [L, heads * width],
    v/o [L, heads * v_width], slope [1, heads * width] (a head's slope on
    each of its lanes), entry [heads, width, v_width] the block's states on
    entry."""
    chunk, dtype = q_ref.shape[0], q_ref.dtype

    @pl.when(pl.program_id(2) == 0)
    def _():
        state_scr[...] = jnp.zeros(state_scr.shape, F32)

    for h in range(heads):
        lanes = slice(h * width, (h + 1) * width)
        v_lanes = slice(h * v_width, (h + 1) * v_width)
        q, k, v = q_ref[:, lanes], k_ref[:, lanes], v_ref[:, v_lanes]
        within, from_start, to_end, whole = _decays(
            slope_ref[:, h * width:h * width + 1], chunk)
        state = state_scr[h]
        entry_ref[h] = state
        scores = (_mm(q, k, 1, 1) * within).astype(dtype)
        out = _mm(scores, v, 1, 0) \
            + from_start * _mm(q, state.astype(dtype), 1, 0)
        o_ref[:, v_lanes] = (out * scale).astype(o_ref.dtype)
        k_end = (k.astype(F32) * to_end).astype(dtype)
        state_scr[h] = whole * state + _mm(k_end, v, 0, 0)


def _bwd_kernel(q_ref, k_ref, v_ref, slope_ref, entry_ref, do_ref, dq_ref,
                dk_ref, dv_ref, dstate_scr, *, heads: int, width: int,
                v_width: int, scale: float):
    """The forward's grid step with the chunks in reverse (the index maps
    turn them round): ``dstate_scr`` carries the cotangent of the block's
    exit states."""
    chunk, dtype = q_ref.shape[0], q_ref.dtype

    @pl.when(pl.program_id(2) == 0)
    def _():
        dstate_scr[...] = jnp.zeros(dstate_scr.shape, F32)

    for h in range(heads):
        lanes = slice(h * width, (h + 1) * width)
        v_lanes = slice(h * v_width, (h + 1) * v_width)
        q, k, v = q_ref[:, lanes], k_ref[:, lanes], v_ref[:, v_lanes]
        do = do_ref[:, v_lanes]
        within, from_start, to_end, whole = _decays(
            slope_ref[:, h * width:h * width + 1], chunk)
        state, dstate = entry_ref[h].astype(dtype), dstate_scr[h]
        dstate_lo = dstate.astype(dtype)
        scores = (_mm(q, k, 1, 1) * within).astype(dtype)
        dscores = (_mm(do, v, 1, 1) * within).astype(dtype)
        q_start = (q.astype(F32) * from_start).astype(dtype)
        k_end = (k.astype(F32) * to_end).astype(dtype)
        dq = _mm(dscores, k, 1, 0) + from_start * _mm(do, state, 1, 1)
        dk = _mm(dscores, q, 0, 0) * scale \
            + to_end * _mm(v, dstate_lo, 1, 1)
        dv = _mm(scores, do, 0, 0) * scale + _mm(k_end, dstate_lo, 1, 0)
        dq_ref[:, lanes] = (dq * scale).astype(dq_ref.dtype)
        dk_ref[:, lanes] = dk.astype(dk_ref.dtype)
        dv_ref[:, v_lanes] = dv.astype(dv_ref.dtype)
        dstate_scr[h] = whole * dstate + scale * _mm(q_start, do, 0, 0)


def heads_per_block(heads: int, width: int, v_width: int) -> int:
    """Heads a grid step takes: the most of 4, 2, 1 that divide the head
    count, at heads of whole 128-lane tiles (0 if the widths are not)."""
    if width % 128 or v_width % 128:
        return 0
    return next(n for n in (4, 2, 1) if heads % n == 0)


def _specs(chunk: int, block: int, width: int, v_width: int, n_chunks: int,
           reverse: bool):
    def at(t):
        return n_chunks - 1 - t if reverse else t

    return {
        "key": pl.BlockSpec((None, chunk, block * width),
                            lambda b, j, t: (b, at(t), j)),
        "value": pl.BlockSpec((None, chunk, block * v_width),
                              lambda b, j, t: (b, at(t), j)),
        "slope": pl.BlockSpec((None, 1, block * width),
                              lambda b, j, t: (j, 0, 0)),
        "state": pl.BlockSpec((None, None, block, width, v_width),
                              lambda b, j, t: (b, at(t), j, 0, 0)),
    }


def _call(kernel, name: str, reverse: bool, operands, out_kinds, out_shape,
          dims, scale: float, chunk: int):
    """``pl.pallas_call`` of one of the two kernels over the grid (batch,
    head blocks, chunks): ``operands`` as (array, kind of ``_specs``)."""
    batch, seq, heads, width, v_width = dims
    block = heads_per_block(heads, width, v_width)
    spec = _specs(chunk, block, width, v_width, seq // chunk, reverse)
    return pl.pallas_call(
        functools.partial(kernel, heads=block, width=width, v_width=v_width,
                          scale=scale),
        grid=(batch, heads // block, seq // chunk),
        in_specs=[spec[kind] for _, kind in operands],
        out_specs=[spec[kind] for kind in out_kinds],
        out_shape=out_shape,
        scratch_shapes=[pltpu.VMEM((block, width, v_width), F32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=_interpret(),
        name=name,
    )(*(x for x, _ in operands))


def _slope_lanes(slope, heads: int, width: int, v_width: int):
    """slope [H] as the kernels read it: [head blocks, 1, block * width], a
    head's slope on each of its lanes."""
    block = heads_per_block(heads, width, v_width)
    return jnp.repeat(slope.astype(F32), width).reshape(
        heads // block, 1, block * width)


def lightning_fwd(q, k, v, slope, dims, scale: float, chunk: int):
    """(o [B, S, H * V], entry states [B, chunks, H, K, V] float32) by the
    forward kernel; q, k [B, S, H * K], v [B, S, H * V]."""
    batch, seq, heads, width, v_width = dims
    return _call(
        _fwd_kernel, "lightning_fwd", False,
        [(q, "key"), (k, "key"), (v, "value"),
         (_slope_lanes(slope, heads, width, v_width), "slope")],
        ["value", "state"],
        [jax.ShapeDtypeStruct(v.shape, q.dtype),
         jax.ShapeDtypeStruct((batch, seq // chunk, heads, width, v_width),
                              F32)],
        dims, scale, chunk)


def lightning_bwd(q, k, v, slope, entry, do, dims, scale: float, chunk: int):
    """(dq, dk, dv) by the backward kernel."""
    heads, width, v_width = dims[2:]
    return _call(
        _bwd_kernel, "lightning_bwd", True,
        [(q, "key"), (k, "key"), (v, "value"),
         (_slope_lanes(slope, heads, width, v_width), "slope"),
         (entry, "state"), (do, "value")],
        ["key", "key", "value"],
        [jax.ShapeDtypeStruct(q.shape, q.dtype),
         jax.ShapeDtypeStruct(k.shape, k.dtype),
         jax.ShapeDtypeStruct(v.shape, v.dtype)],
        dims, scale, chunk)


@functools.partial(jax.custom_vjp, nondiff_argnums=(4, 5, 6))
def _kernels(q, k, v, slope, dims, scale, chunk):
    return lightning_fwd(q, k, v, slope, dims, scale, chunk)[0]


def _kernels_fwd(q, k, v, slope, dims, scale, chunk):
    out, entry = lightning_fwd(q, k, v, slope, dims, scale, chunk)
    return out, (q, k, v, slope, entry)


def _kernels_bwd(dims, scale, chunk, residuals, do):
    q, k, v, slope, entry = residuals
    dq, dk, dv = lightning_bwd(q, k, v, slope, entry, do, dims, scale, chunk)
    return dq, dk, dv, jnp.zeros_like(slope)


_kernels.defvjp(_kernels_fwd, _kernels_bwd)


def lightning(q, k, v, slope, scale=None, chunk: int = CHUNK):
    """o [B, S, H, V] of the recurrence at the top of this file. q, k [B, S,
    H, K], v [B, S, H, V], slope [H] positive (``lambda = exp(-slope)``; a
    constant: its cotangent is zero), scores times ``scale`` (1/sqrt(K) if
    None). The kernels where the shapes tile, else ``lightning_chunked``."""
    B, S, H, K = q.shape
    V = v.shape[-1]
    if S % chunk or chunk % 128 or not heads_per_block(H, K, V):
        return lightning_chunked(q, k, v, jax.lax.stop_gradient(slope), scale,
                                 chunk)
    out = _kernels(q.reshape(B, S, H * K), k.reshape(B, S, H * K),
                   v.reshape(B, S, H * V), slope, (B, S, H, K, V),
                   _scale(scale, K), chunk)
    return out.reshape(B, S, H, V)
