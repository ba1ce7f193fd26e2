"""Kimi Delta Attention: a gated delta rule with a decay a channel, in its
chunked form, as a pair of Pallas TPU kernels (forward, backward), the same
algorithm in ``jax.numpy``, and the literal recurrence.

Per head, with a state ``S`` [K keys, V values] that is zero before the
first token::

    S_t = Diag(exp(a_t)) S_(t-1)                           the decay, a channel of the key
    S_t = S_t + beta_t k_t (v_t - S_t^T k_t)^T             the delta rule: what k_t reads is replaced
    o_t = S_t^T q_t

``q``, ``k`` [batch, S, H, K] and ``v`` [batch, S, H, V] hold H heads, ``a``
[batch, S, H, K] the log-decays (<= 0, float32), ``beta`` [batch, S, H] the
write strengths (0..1, float32). **q and k come as the layer has them, not
normalised**: every entry here first brings each head's row of k to length 1
and of q to length ``K ** -0.5`` (a row's own length held above 1e-6, in
float32, rounded to the dtype it came in: ``_unit_rows``), as the published
kernel does, and the ``q_t``, ``k_t`` above are those. The literal
recurrence (``kda_recurrent``) is S sequential steps; the chunked form does
a chunk of L steps as matrix products. With ``cum_t`` the sum of ``a`` from
the chunk's first token to t (a vector of K) and ``S_0`` the state on entry,
the state inside the chunk is

    S_t = Diag(exp(cum_t)) S_0 + sum_{s<=t} Diag(exp(cum_t - cum_s)) k_s u_s^T

for pseudo-values ``u`` [L, V] that the chunk's keys fix among themselves:

    (I + A) U = Diag(beta) (V - (K * exp(cum)) S_0)
    A_ts      = beta_t sum_c k_t[c] k_s[c] exp(cum_t[c] - cum_s[c])     s < t, else 0
    O         = (Q * exp(cum)) S_0 + B U
    B_ts      = sum_c q_t[c] k_s[c] exp(cum_t[c] - cum_s[c])            s <= t, else 0
    S_L       = Diag(exp(cum_L)) S_0 + (K * exp(cum_L - cum))^T U

and only ``S_0 -> S_L`` runs along the sequence, once a chunk.

**Every decay factor is exp of a difference ``cum_t - cum_s`` with s <= t.**
``exp(-cum)`` overflows float32 inside one chunk at the published
initialisation (log-decays down to -1.6 a step, -100 over 64 steps), so A
and B are not formed as ``(k exp(cum)) (k exp(-cum))^T``. A pair (t, s),
s < t, is in exactly one level h = 1, 2, 4, ..., L / 2: the one at which t is
in the second half and s in the first half of the same block of 2h rows.
There the row r at the end of the first half lies between them, and
``exp(cum_t - cum_s) = exp(cum_t - cum_r) exp(cum_r - cum_s)``: two factors
<= 1, both ``exp(-|cum - cum_r|)`` of their own row, so a level is one
scaling of the rows and one matrix product, masked to its pairs
(``_pair_products``).

**The unit lower-triangular ``(I + A)`` is inverted once a chunk and never
differentiated through** (``_unit_lower_inverse``). Its diagonal blocks of
16 rows are inverted by substitution on the vector unit in float32
(``_block_inverses``: all the blocks of a chunk side by side, fifteen
multiply-subtracts of two registers), and the same doubling goes on from
there (``_inverse_by_levels``: the inverse of a block from those of its
halves, a level of 16, 32 and 64 rows two whole [L, L] products) in float32
products: at the highest precision for float32 inputs, and for bfloat16
inputs at sixteen bits (each operand as two bfloat16 pieces, three products
of pieces: half the passes, and still 256 times finer than the operands
around it). Its cotangent is the inverse's own identity (``_inverse_of``:
``T = (I + A)^-1`` gives ``dT = -T dA T``, so ``A``'s cotangent is ``-(T^T
ct T^T)`` on the strict lower triangle): two products of the same precision
on the ``T`` already made, where differentiating the levels ran them again
with two transposed products each. The other products run in the dtype of
``q`` with float32 accumulation; ``a``, ``cum``, ``beta`` and the states are
float32.

The kernels' grid is ``(batch, heads, chunks)``, the last sequential: a grid
step is one chunk of one head. The head's state (kept transposed, [V, K],
so that its decay is a value a lane) stays in VMEM scratch from chunk to
chunk as ``ops/ssd.py`` and the flash kernels carry theirs. The forward also
writes each chunk's entry state and its inverse ([L, L] float32: 268 MB a
call at 16k tokens of 32 heads, 0.3 ms to move against 5 ms to make again),
which the backward reads: it walks the chunks in reverse with the cotangent
of the state carried the same way, and differentiates the chunk's own
function (``_chunk``) where it stands, given that inverse.
The kernels take what the layer has: q and k as the convolutions leave them
and ``a`` itself. The chunk's function normalises its [L, K] tiles of q and
k and sums ``a`` over its rows where they lie in VMEM (``_running_sum``:
seven shifts of the rows and adds, its cotangent the same sum from the last
row upwards), so the backward kernel returns the cotangents of the raw q, k
and of ``a``, and XLA runs nothing over [S, H K] around the pair. In a trace
the kernels are ``kda_fwd`` and ``kda_bwd``, under the scope ``kda``.

``kda`` is the one entry: the kernels where the shapes tile (S a multiple of
the chunk, K and V multiples of 128 lanes), else ``kda_chunked``, the same
chunk function under ``vmap`` and a ``lax.scan`` over chunks, which is also
the kernels' oracle in the tests. On backends other than the TPU the kernels
run in interpreter mode.
"""

from __future__ import annotations

import functools
import importlib

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

F32 = jnp.float32
_HIGHEST = jax.lax.Precision.HIGHEST
#: Rows of a float32 sublane tile: blocks of whole tiles are reshaped, the
#: rows inside one are picked by a mask.
_TILE = 8
#: Positions a chunk. The published kernels use 64; on the v5e a call at
#: 16k tokens of 32 heads of 128 takes 8.45 ms forward and 20.3 ms forward
#: and backward at 128 against 12.9 and 28.0 at 64: half the grid steps,
#: state updates, entry states and inverses for one more level of doubling.
CHUNK = 128
#: Rows of the diagonal blocks of ``I + A`` that the vector unit inverts;
#: the levels of doubling, whole [L, L] products each, go on from there.
_BLOCK = 16


def _interpret() -> bool:
    """The flash kernels' answer (interpreter mode off the TPU), asked of
    that module each time so that one switch steers every kernel of
    ``ops/``."""
    return importlib.import_module(
        "ray_tpu.ops.flash_attention")._interpret()


# -- the literal recurrence -------------------------------------------------

def kda_recurrent(q, k, v, a, beta):
    """The recurrence at the top of this file, token by token, in float32:
    o [batch, S, H, V]. The chunked forms' oracle."""
    q, k, v, a, beta = (x.astype(F32) for x in (q, k, v, a, beta))
    batch, _, heads, width = q.shape

    def unit(x):
        return x * jax.lax.rsqrt(
            jnp.maximum((x * x).sum(-1, keepdims=True), 1e-12))

    q, k = unit(q) * width ** -0.5, unit(k)

    def step(state, token):
        q_t, k_t, v_t, a_t, beta_t = token
        state = jnp.exp(a_t)[..., None] * state
        read = jnp.einsum("bhkv,bhk->bhv", state, k_t)
        state = state + (beta_t[..., None] * k_t)[..., None] \
            * (v_t - read)[..., None, :]
        return state, jnp.einsum("bhkv,bhk->bhv", state, q_t)

    _, out = jax.lax.scan(
        step, jnp.zeros((batch, heads, width, v.shape[-1]), F32),
        tuple(x.swapaxes(0, 1) for x in (q, k, v, a, beta)))
    return out.swapaxes(0, 1)


# -- one chunk of one head --------------------------------------------------

def _mm(a, b, contract_a: int, contract_b: int, precision=None):
    return jax.lax.dot_general(
        a, b, (((contract_a,), (contract_b,)), ((), ())),
        preferred_element_type=F32, precision=precision)


def _row_between(cum, half: int):
    """For every row t of cum [L, K], the row at the end of the first half
    of t's block of ``2 half`` rows: ``cum[(t // 2 half) 2 half + half -
    1]``, the row that lies between the pairs of this level."""
    length, width = cum.shape
    block = 2 * half
    if block >= _TILE:
        blocks = cum.reshape(length // block, block, width)
        pos = jax.lax.broadcasted_iota(jnp.int32, blocks.shape, 1)
        row = jnp.where(pos == half - 1, blocks, 0.0).sum(1, keepdims=True)
        return jnp.broadcast_to(row, blocks.shape).reshape(length, width)
    tiles = cum.reshape(length // _TILE, _TILE, width)
    pos = jax.lax.broadcasted_iota(jnp.int32, tiles.shape, 1)
    wanted = (pos & ~(block - 1)) + (half - 1)
    out = jnp.zeros_like(tiles)
    for r in range(half - 1, _TILE, block):
        row = jnp.where(pos == r, tiles, 0.0).sum(1, keepdims=True)
        out = jnp.where(wanted == r, row, out)
    return out.reshape(length, width)


def _levels(length: int):
    """(half, mask [L, L] of the pairs (t, s) of that level) for half = 1,
    2, ..., L / 2: t in the second half, s in the first half of the same
    block of ``2 half`` rows. Every pair s < t is in exactly one."""
    rows = jax.lax.broadcasted_iota(jnp.int32, (length, length), 0)
    cols = jax.lax.broadcasted_iota(jnp.int32, (length, length), 1)
    out, half, shift = [], 1, 0
    while half < length:
        # Neighbouring blocks of ``half`` rows, the column's an even one.
        row_block, col_block = rows >> shift, cols >> shift
        out.append((half, (row_block == col_block + 1)
                    & ((col_block & 1) == 0)))
        half, shift = 2 * half, shift + 1
    return rows, cols, out


def _pair_products(q32, k32, cum, dtype, rows, cols, levels):
    """(B [L, L] lower with its diagonal, A / beta [L, L] strictly lower) of
    the module text: the decayed q.k and k.k products of every pair s <= t,
    a level a product."""
    length = q32.shape[0]
    qk = jnp.where(rows == cols, (q32 * k32).sum(-1, keepdims=True), 0.0)
    kk = jnp.zeros((length, length), F32)
    for half, mask in levels:
        # A row of a second half looks back to the row between, a row of a
        # first half ahead to it: each by exp(-|cum - between|), one factor
        # for both (the other half's is masked).
        near = jnp.exp(-jnp.abs(cum - _row_between(cum, half)))
        k_near = (k32 * near).astype(dtype)
        qk = qk + jnp.where(
            mask, _mm((q32 * near).astype(dtype), k_near, 1, 1), 0.0)
        kk = kk + jnp.where(mask, _mm(k_near, k_near, 1, 1), 0.0)
    return qk, kk


def _two_pieces(x):
    """x [.., ..] float32 as two bfloat16 arrays that sum to it within
    2^-16 of its size."""
    high = x.astype(jnp.bfloat16)
    return high, (x - high.astype(F32)).astype(jnp.bfloat16)


@jax.custom_vjp
def _mm_16_bits(a, b):
    """a [m, k] times b [k, n], float32, carried as two bfloat16 pieces
    each: three of the four products of pieces (the low ones' product is
    under 2^-16), float32 accumulation. Half the passes of the highest
    precision for sixteen bits of its twenty-four; the cotangents go the
    same way."""
    (a_hi, a_lo), (b_hi, b_lo) = _two_pieces(a), _two_pieces(b)
    return _mm(a_hi, b_hi, 1, 0) + (_mm(a_hi, b_lo, 1, 0)
                                    + _mm(a_lo, b_hi, 1, 0))


def _mm_16_bits_fwd(a, b):
    return _mm_16_bits(a, b), (a, b)


def _mm_16_bits_bwd(operands, ct):
    a, b = operands
    return _mm_16_bits(ct, b.T), _mm_16_bits(a.T, ct)


_mm_16_bits.defvjp(_mm_16_bits_fwd, _mm_16_bits_bwd)


def _times(exact: bool):
    """The inverse's matrix product: float32 at the highest precision if
    ``exact``, else at sixteen bits (``_mm_16_bits``)."""
    return (lambda a, b: _mm(a, b, 1, 0, _HIGHEST)) if exact else _mm_16_bits


def _block_inverses(lower, block: int, exact: bool):
    """The inverses of the diagonal blocks of ``block`` rows of ``I +
    lower``, block-diagonal [L, L], by substitution on the vector unit in
    float32. The blocks side by side are one [block, L] array ``x`` (block b
    in lanes ``block b`` onwards), which starts as their identities; the
    inverse of ``I + N`` is that of its columns' elementary matrices in
    turn, ``x <- x - N[:, j] x[j, :]`` for j = 0 .. block - 2, each a
    multiply and subtract of the whole of ``x``. Column j of every block
    spread over the block's lanes comes from one product with the blocks'
    ones, for all j at once and ahead of the substitution (it reads only
    ``lower``): exact at the highest precision, else two bfloat16 pieces."""
    length = lower.shape[0]
    shift = block.bit_length() - 1
    rows = jax.lax.broadcasted_iota(jnp.int32, (length, length), 0)
    cols = jax.lax.broadcasted_iota(jnp.int32, (length, length), 1)
    same = (rows >> shift) == (cols >> shift)
    side_by_side = jnp.where(same & (rows > cols), lower, 0.0).reshape(
        length // block, block, length).sum(0)
    column = jax.lax.broadcasted_iota(
        jnp.int32, (block - 1, block, length), 0)
    lane = jax.lax.broadcasted_iota(
        jnp.int32, (block - 1, block, length), 2) & (block - 1)
    picked = jnp.where(lane == column, side_by_side[None], 0.0).reshape(
        (block - 1) * block, length)
    if exact:
        spread = _mm(picked, same.astype(F32), 1, 0, _HIGHEST)
    else:
        ones = same.astype(jnp.bfloat16)
        spread = sum(_mm(piece, ones, 1, 0) for piece in _two_pieces(picked))
    row = jax.lax.broadcasted_iota(jnp.int32, (block, length), 0)
    in_block = jax.lax.broadcasted_iota(
        jnp.int32, (block, length), 1) & (block - 1)
    x = jnp.where(row == in_block, 1.0, 0.0).astype(F32)
    for j in range(block - 1):
        x = x - spread[j * block:(j + 1) * block] \
            * jnp.where(row == j, x, 0.0).sum(0, keepdims=True)
    return jnp.where(same, jnp.broadcast_to(
        x[None], (length // block, block, length)).reshape(length, length),
        0.0)


def _inverse_by_levels(lower, exact: bool, block: int):
    """(I + lower)^-1 for a strictly lower-triangular ``lower`` [L, L]: the
    inverse of a block ``[[M1, 0], [M21, M2]]`` is ``[[T1, 0], [-T2 M21 T1,
    T2]]``, for all blocks of a level at once as ``T - T M_off T``, from the
    inverses of the diagonal blocks of ``block`` rows upwards (1: from the
    identity, every level a product)."""
    times = _times(exact)
    rows, cols, levels = _levels(lower.shape[0])
    inverse = jnp.where(rows == cols, 1.0, 0.0).astype(F32) if block == 1 \
        else _block_inverses(lower, block, exact)
    for half, mask in levels:
        if half >= block:
            inverse = inverse - times(
                times(inverse, jnp.where(mask, lower, 0.0)), inverse)
    return inverse


@functools.partial(jax.custom_vjp, nondiff_argnums=(2,))
def _inverse_of(lower, inverse, exact: bool):
    """``inverse``, which is T = (I + lower)^-1, as a function of ``lower``:
    ``dT = -T dlower T``, so the cotangent of ``lower`` is ``-(T^T ct T^T)``
    on the strictly lower triangle, two products at the precision of the
    levels' on the T they made, and the levels are never differentiated."""
    return inverse


def _inverse_of_fwd(lower, inverse, exact):
    return inverse, inverse


def _inverse_of_bwd(exact, inverse, ct):
    times = _times(exact)
    return (jnp.tril(-times(times(inverse.T, ct), inverse.T), -1),
            jnp.zeros_like(inverse))


_inverse_of.defvjp(_inverse_of_fwd, _inverse_of_bwd)


def _unit_lower_inverse(lower, exact: bool, made=None):
    """(I + lower)^-1 with its cotangent by ``_inverse_of``: ``made`` where
    an earlier call on the same ``lower`` returned it, else by blocks of
    ``_BLOCK`` rows and the levels from there."""
    if made is None:
        made = _inverse_by_levels(jax.lax.stop_gradient(lower), exact,
                                  min(_BLOCK, lower.shape[0]))
    return _inverse_of(lower, made, exact)


def _unit_rows(y, scale: float = 1.0):
    """y [L, K] with every row brought to length ``scale`` (its own length
    held above 1e-6), in float32, in the dtype it came in. The floor is on
    the sum of squares, under the root: a row of zeros stays zeros and its
    cotangent is the output's times ``scale / 1e-6``, where a floor on the
    root itself would hand the root's slope at 0 a 0 / 0."""
    y32 = y.astype(F32)
    length = jnp.sqrt(jnp.maximum((y32 * y32).sum(-1, keepdims=True), 1e-12))
    return (y32 * (scale / length)).astype(y.dtype)


def _sum_rows(x, upwards: bool):
    """x [L, K] float32 -> [L, K]: row t the sum of rows 0 .. t (of rows t ..
    L - 1 if ``upwards``), by doubling: log2 L times every row takes in the
    row 1, 2, 4, ... before it. A sum is a tree of pairs, its rounding
    log2 L deep where ``jnp.cumsum``'s row after row is L deep."""
    length = x.shape[0]
    rows = jax.lax.broadcasted_iota(jnp.int32, x.shape, 0)
    by = 1
    while by < length:
        arrives = rows < length - by if upwards else rows >= by
        x = x + jnp.where(arrives, jnp.roll(x, -by if upwards else by, 0),
                          0.0)
        by *= 2
    return x


@jax.custom_vjp
def _running_sum(a):
    """cum [L, K] float32: the running sum of ``a`` [L, K] down the chunk's
    rows. Its cotangent is the same sum from the last row upwards."""
    return _sum_rows(a, False)


_running_sum.defvjp(lambda a: (_running_sum(a), None),
                    lambda _, ct: (_sum_rows(ct, True),))


def _chunk(q, k, v, a, beta, state, inverse=None):
    """One chunk of one head: q, k [L, K] as the layer has them, v [L, V],
    a [L, K] float32 (the log-decays), beta [L, 1] float32, state [V, K]
    float32 (the entry state, transposed) -> (o [L, V] float32, the exit
    state [V, K], the inverse of ``I + A`` [L, L] float32). ``inverse`` is
    that inverse where an earlier call on the same chunk returned it."""
    length = q.shape[0]
    dtype = q.dtype
    q, k = _unit_rows(q, q.shape[1] ** -0.5), _unit_rows(k)
    cum = _running_sum(a)
    q32, k32, v32 = q.astype(F32), k.astype(F32), v.astype(F32)
    rows, cols, levels = _levels(length)
    qk, kk = _pair_products(q32, k32, cum, dtype, rows, cols, levels)
    # Sixteen bits for the inverse where everything around it has eight.
    inverse = _unit_lower_inverse(kk * beta, dtype != jnp.bfloat16, inverse)
    from_start = jnp.exp(cum)
    state_d = state.astype(dtype)
    rhs = beta * (v32 - _mm((k32 * from_start).astype(dtype), state_d, 1, 1))
    u = _mm(inverse.astype(dtype), rhs.astype(dtype), 1, 0).astype(dtype)
    out = _mm((q32 * from_start).astype(dtype), state_d, 1, 1) \
        + _mm(qk.astype(dtype), u, 1, 0)
    last = jax.lax.broadcasted_iota(jnp.int32, cum.shape, 0) == length - 1
    total = jnp.where(last, cum, 0.0).sum(0, keepdims=True)       # [1, K]
    to_end = (k32 * jnp.exp(jnp.minimum(total - cum, 0.0))).astype(dtype)
    return out, jnp.exp(total) * state + _mm(u, to_end, 0, 0), inverse


# -- the chunked form in jax.numpy -----------------------------------------

def decay_floor(a, chunk: int = CHUNK):
    """The most negative ``cum`` any chunk reaches: the least of the chunks'
    sums of ``a`` (a <= 0, so a chunk's last row holds its least). How close
    the decay products come to underflow: float32's exp is 0 below -103."""
    batch, seq = a.shape[:2]
    pad = -seq % chunk
    a = jnp.pad(a.astype(F32), ((0, 0), (0, pad)) + ((0, 0),) * (a.ndim - 2))
    return a.reshape((batch, (seq + pad) // chunk, chunk)
                     + a.shape[2:]).sum(2).min()


def kda_chunked(q, k, v, a, beta, chunk: int = CHUNK):
    """The chunked algorithm outside a kernel, any length (the tail is
    padded with steps that decay nothing and write nothing): ``_chunk``
    under ``vmap`` over batch and heads and a ``lax.scan`` over the chunks.
    The kernels' oracle and the path for shapes they cannot tile."""
    if chunk < _TILE or chunk & (chunk - 1):
        raise ValueError(f"chunk={chunk}: a power of two, {_TILE} at least")
    batch, seq, heads, width = q.shape
    pad = -seq % chunk
    if pad:
        q, k, v, a, beta = (
            jnp.pad(x, ((0, 0), (0, pad)) + ((0, 0),) * (x.ndim - 2))
            for x in (q, k, v, a, beta))
    n = (seq + pad) // chunk

    def by_chunk(x):
        """[batch, S, H, ...] -> [chunks, batch, H, L, ...]"""
        x = x.reshape((batch, n, chunk) + x.shape[2:])
        return jnp.moveaxis(x, (1, 3), (0, 2))

    one = jax.vmap(jax.vmap(_chunk))

    def carry(state, xs):
        out, state, _ = one(*xs, state)
        return state, out

    _, out = jax.lax.scan(
        carry, jnp.zeros((batch, heads, v.shape[-1], width), F32),
        tuple(by_chunk(x) for x in (
            q, k, v, a.astype(F32), beta.astype(F32)[..., None])))
    out = jnp.moveaxis(out, (0, 2), (1, 3)).reshape(
        batch, seq + pad, heads, v.shape[-1])
    return out[:, :seq].astype(v.dtype)


# -- the kernels -------------------------------------------------------------

def _kda_fwd_kernel(q_ref, k_ref, v_ref, a_ref, beta_ref, o_ref, entry_ref,
                    inverse_ref, state_scr):
    """One chunk of one head: q/k/a [L, K], v/o [L, V], beta [L, 1];
    entry [V, K] is the head's state on entry, transposed, inverse [L, L]
    the chunk's ``(I + A)^-1``."""
    @pl.when(pl.program_id(2) == 0)
    def _():
        state_scr[...] = jnp.zeros(state_scr.shape, F32)

    state = state_scr[...]
    entry_ref[...] = state
    out, state_scr[...], inverse_ref[...] = _chunk(
        q_ref[...], k_ref[...], v_ref[...], a_ref[...], beta_ref[...],
        state)
    o_ref[...] = out.astype(o_ref.dtype)


def _kda_bwd_kernel(q_ref, k_ref, v_ref, a_ref, beta_ref, entry_ref,
                    inverse_ref, do_ref, dq_ref, dk_ref, dv_ref, da_ref,
                    dbeta_ref, dstate_scr):
    """The forward's grid step with the chunks in reverse (the index maps
    turn them round): the chunk's function is differentiated where it
    stands (the rows' normalisation and the running sum with it: the
    cotangents are the raw q's, k's and ``a``'s), from its inputs and the
    entry state and the inverse the forward wrote, and the cotangent of the
    head's state is carried in ``dstate_scr``."""
    @pl.when(pl.program_id(2) == 0)
    def _():
        dstate_scr[...] = jnp.zeros(dstate_scr.shape, F32)

    inverse = inverse_ref[...]
    _, pullback = jax.vjp(lambda *xs: _chunk(*xs, inverse)[:2],
                          q_ref[...], k_ref[...], v_ref[...], a_ref[...],
                          beta_ref[...], entry_ref[...])
    dq, dk, dv, da, dbeta, dstate_scr[...] = pullback(
        (do_ref[...].astype(F32), dstate_scr[...]))
    dq_ref[...] = dq
    dk_ref[...] = dk
    dv_ref[...] = dv
    da_ref[...] = da
    dbeta_ref[...] = dbeta


def _specs(chunk: int, width: int, v_width: int, n_chunks: int,
           reverse: bool):
    """BlockSpecs over the grid (batch, heads, chunks), by operand kind;
    ``reverse`` walks the chunks from the last."""
    def at(t):
        return n_chunks - 1 - t if reverse else t

    return {
        "key": pl.BlockSpec((None, chunk, width),
                            lambda b, h, t: (b, at(t), h)),
        "value": pl.BlockSpec((None, chunk, v_width),
                              lambda b, h, t: (b, at(t), h)),
        "beta": pl.BlockSpec((None, None, chunk, 1),
                             lambda b, h, t: (b, h, at(t), 0)),
        "state": pl.BlockSpec((None, None, None, v_width, width),
                              lambda b, h, t: (b, h, at(t), 0, 0)),
        "inverse": pl.BlockSpec((None, None, None, chunk, chunk),
                                lambda b, h, t: (b, h, at(t), 0, 0)),
    }


def _call(kernel, name: str, reverse: bool, operands, out_kinds, out_shape,
          chunk: int):
    """``pl.pallas_call`` of one of the two kernels over the grid (batch,
    heads, chunks): ``operands`` as (array, kind of ``_specs``) pairs; q
    first, v third, beta fifth."""
    (q, _), _, (v, _), _, (beta, _) = operands[:5]
    batch, seq = q.shape[:2]
    heads = beta.shape[1]
    width, v_width = q.shape[-1] // heads, v.shape[-1] // heads
    n = seq // chunk
    spec = _specs(chunk, width, v_width, n, reverse)
    return pl.pallas_call(
        kernel,
        grid=(batch, heads, n),
        in_specs=[spec[kind] for _, kind in operands],
        out_specs=[spec[kind] for kind in out_kinds],
        out_shape=out_shape(batch, heads, n, v_width, width),
        scratch_shapes=[pltpu.VMEM((v_width, width), F32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=_interpret(),
        name=name,
    )(*(x for x, _ in operands))


_INPUTS = ("key", "key", "value", "key", "beta")


def _forward(q, k, v, a, beta, chunk: int):
    """(o [batch, S, H * V], entry states [batch, H, chunks, V, K], inverses
    [batch, H, chunks, L, L]) by the forward kernel; q, k, a are [batch,
    S, H * K], v [batch, S, H * V], beta [batch, H, S, 1]."""
    return _call(
        _kda_fwd_kernel, "kda_fwd", False,
        list(zip((q, k, v, a, beta), _INPUTS)),
        ("value", "state", "inverse"),
        lambda *state: [jax.ShapeDtypeStruct(v.shape, v.dtype),
                        jax.ShapeDtypeStruct(state, F32),
                        jax.ShapeDtypeStruct(state[:3] + (chunk, chunk), F32)],
        chunk)


def _backward(q, k, v, a, beta, entry, inverse, do, chunk: int):
    """Cotangents (dq, dk, dv, da, dbeta) by the backward kernel."""
    inputs = (q, k, v, a, beta)
    return _call(
        _kda_bwd_kernel, "kda_bwd", True,
        list(zip(inputs + (entry, inverse, do),
                 _INPUTS + ("state", "inverse", "value"))),
        _INPUTS, lambda *_: [jax.ShapeDtypeStruct(x.shape, x.dtype)
                             for x in inputs], chunk)


@functools.partial(jax.custom_vjp, nondiff_argnums=(5,))
def _kda_kernels(q, k, v, a, beta, chunk: int):
    return _forward(q, k, v, a, beta, chunk)[0]


def _kda_kernels_fwd(q, k, v, a, beta, chunk):
    out, entry, inverse = _forward(q, k, v, a, beta, chunk)
    return out, (q, k, v, a, beta, entry, inverse)


def _kda_kernels_bwd(chunk, residuals, do):
    return _backward(*residuals, do, chunk)


_kda_kernels.defvjp(_kda_kernels_fwd, _kda_kernels_bwd)


def kda(q, k, v, a, beta, chunk: int = CHUNK):
    """o [batch, S, H, V] of the recurrence at the top of this file, by
    chunks of ``chunk`` positions. q, k [batch, S, H, K] as the layer has
    them (normalised here, q scaled by ``K ** -0.5``); v [batch, S, H, V];
    a [batch, S, H, K] <= 0; beta [batch, S, H]. The kernels where the
    shapes tile, else ``kda_chunked``."""
    batch, seq, heads, width = q.shape
    v_width = v.shape[-1]
    if seq % chunk or width % 128 or v_width % 128:
        return kda_chunked(q, k, v, a, beta, chunk)
    if chunk < _TILE or chunk & (chunk - 1):
        raise ValueError(f"chunk={chunk}: a power of two, {_TILE} at least")
    with jax.named_scope("kda"):
        out = _kda_kernels(
            q.reshape(batch, seq, heads * width),
            k.astype(q.dtype).reshape(batch, seq, heads * width),
            v.reshape(batch, seq, heads * v_width),
            a.astype(F32).reshape(batch, seq, heads * width),
            beta.astype(F32).swapaxes(1, 2)[..., None], chunk)
        return out.reshape(v.shape)
