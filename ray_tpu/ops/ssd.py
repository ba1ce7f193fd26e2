"""The Mamba-2 state-space recurrence in its chunked ("state-space dual")
form, as a pair of Pallas TPU kernels (forward, backward) and a plain
``jax.numpy`` form of the same algorithm.

Per head, with a state ``S`` [P, N] that is zero before the first token::

    S_t = exp(dt_t A) S_(t-1) + dt_t u_t B_t^T
    y_t = S_t C_t + D u_t

``u`` [batch, S, H, P] holds H heads of P channels, ``dt`` [batch, S, H] the
step sizes (positive: after the softplus), ``A`` [H] a negative scalar a
head, ``B`` and ``C`` [batch, S, G, N] G groups, head i reading group ``i //
(H / G)`` (or [batch, S, N]: one group shared by every head), ``D`` [H] the
skip. The literal recurrence is S sequential steps; the chunked form
does a chunk of L steps as matrix products. With ``cum_t`` the sum of
``dt A`` from the chunk's first position to t, inside a chunk

    y_t = sum_{s<=t} exp(cum_t - cum_s) (C_t . B_s) dt_s u_s      quadratic in L
          + exp(cum_t) C_t S_in + D u_t                            the carried state
    S_out = exp(cum_L) S_in + sum_s exp(cum_L - cum_s) dt_s u_s B_s^T

and only ``S_in -> S_out`` runs along the sequence, once a chunk.

The kernels' grid is ``(batch, chunks, head blocks)``, the last two
sequential: a grid step is one chunk of one block of ``heads_per_block``
heads, whose [L, L] decay matrices live in VMEM only (as einsums they are a
float32 [heads, chunks, L, L] array in HBM). The states of all heads stay in
VMEM scratch from chunk to chunk as the flash kernels carry their sums. **A
head block's group**: a block never straddles two groups
(``heads_per_block`` divides the heads of a group), so block j reads the B
and C of group ``j // (blocks a group)`` as its operand block; ``C B^T`` is
computed once a chunk and group, at the group's first head block, and kept
in scratch for the group's others (with one group: once a chunk; with as many
heads a group as a block holds, 64 heads of 64 in 8 groups, once a grid
step). The forward also writes each chunk's entry state, which the backward
reads: it walks the chunks in reverse with the cotangent of the state carried
the same way, and sums the cotangents of ``B`` and ``C`` over a group's head
blocks in that group's output block. ``dt A`` and its running sum are made
outside the kernels, by XLA, which differentiates them too: the kernels take
``dt`` and ``cum`` and return their cotangents. Products run in the dtype of
``u`` with float32 accumulation; decays, states and sums are float32.

**The backward's head loop** makes one [L, L] array a head, the decay (128
rows at a time against the columns up to theirs: the tiles above the
diagonal are never made), and three products with it. ``dt`` rides the
operand a head wide: ``mixed = scores * decay * dt_s`` is ``decayed =
scores * decay`` on ``dt u``, so the cotangent of ``decayed`` is ``dY (dt
u)^T`` out of the matrix unit, and that of ``dt u`` is ``decayed^T dY``. What
``dt`` and ``cum`` are owed is read off products [L, P], not off squares:
with ``g = decayed^T dY`` and ``y = decayed (dt u)`` (the forward's product
again), ``d dt_s = u_s . g_s``, ``d cum_t = dY_t . y_t`` and ``d cum_s = -(dt
u)_s . g_s``. The last two cancel over a chunk (the decay reads ``cum_t -
cum_s``) and ``dA`` sums what is left over every position, so both take
their operands as the products rounded them (``dt u`` in the dtype of
``u``, not ``dt`` times the float32 ``u``): rounded two ways they leave a
tenth of ``dA`` behind. Everything else of a grid step runs once over the
block's [L, heads * P] lanes, before and after the loop; a head's ``dt``,
``exp(cum)`` and ``exp(cum_L - cum)`` reach its lanes, and its lanes' sums
come back as rows [heads, L], through products with 0/1 matrices, float32
moved through them in three bfloat16 pieces (``_pieces``).

``ssd`` is the one entry: the kernels where the shapes tile (S a multiple of
the chunk, the chunk and N multiples of 128, a block of a group's heads a
multiple of 128 lanes), else ``ssd_chunked``, the ``jax.numpy`` form, which
is also the kernels' oracle in the tests. On backends other than the TPU the
kernels run in interpreter mode.
"""

from __future__ import annotations

import functools
import importlib

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

F32 = jnp.float32
_NEG = -1e30


def _interpret() -> bool:
    """The flash kernels' answer (interpreter mode off the TPU), asked of
    that module each time so that one switch steers every kernel of
    ``ops/``."""
    return importlib.import_module(
        "ray_tpu.ops.flash_attention")._interpret()


# -- the chunked form in jax.numpy -----------------------------------------

def _chunk_sums(dt, A, chunk: int):
    """(dt, cum) [batch, chunks, L, H] float32: the step sizes by chunk and
    the running sum of ``dt A`` inside each chunk."""
    batch, seq, heads = dt.shape
    dt = dt.astype(F32).reshape(batch, seq // chunk, chunk, heads)
    return dt, jnp.cumsum(dt * A.astype(F32), axis=2)


def _grouped(B, heads: int):
    """B or C as [batch, S, G, N] (one group where the axis is not given),
    with G checked against the heads it is shared among."""
    if B.ndim == 3:
        B = B[:, :, None, :]
    if heads % B.shape[2]:
        raise ValueError(f"{heads} heads over {B.shape[2]} groups of B and C")
    return B


def ssd_chunked(u, dt, A, B, C, D, chunk: int = 256):
    """The chunked algorithm as einsums, any length (the tail is padded with
    steps of size 0, which leave the state as it is): float32 throughout,
    the decay matrix [batch, chunks, L, L, H] whole, the heads as [G, H / G]
    beside their group's B and C. The kernels' oracle and the path for
    shapes they cannot tile."""
    batch, seq, heads, width = u.shape
    B, C = _grouped(B, heads), _grouped(C, heads)
    groups = B.shape[2]
    pad = -seq % chunk
    if pad:
        u, dt, B, C = (jnp.pad(a, ((0, 0), (0, pad)) + ((0, 0),) * (a.ndim - 2))
                       for a in (u, dt, B, C))
    n = (seq + pad) // chunk
    by_group = (batch, n, chunk, groups, heads // groups)
    dt_c, cum = (a.reshape(by_group) for a in _chunk_sums(dt, A, chunk))
    u_c = u.astype(F32).reshape(by_group + (width,))
    B_c, C_c = (a.astype(F32).reshape(batch, n, chunk, groups, -1)
                for a in (B, C))
    causal = jnp.tril(jnp.ones((chunk, chunk), bool))[:, :, None, None]
    decay = jnp.exp(jnp.where(
        causal, cum[:, :, :, None] - cum[:, :, None], _NEG))
    scores = jnp.einsum("bclgn,bcsgn->bclsg", C_c, B_c)
    mixed = scores[..., None] * decay * dt_c[:, :, None]
    y = jnp.einsum("bclsgr,bcsgrp->bclgrp", mixed, u_c)
    # Each chunk's own contribution to its exit state, then the entry
    # states by the recurrence over chunks.
    total = cum[:, :, -1]
    own = jnp.einsum("bclgr,bclgrp,bclgn->bcgrpn",
                     jnp.exp(total[:, :, None] - cum) * dt_c, u_c, B_c)

    def carry(state, chunk_terms):
        decay_c, own_c = chunk_terms
        return decay_c[..., None, None] * state + own_c, state

    _, entry = jax.lax.scan(
        carry, jnp.zeros(own.shape[:1] + own.shape[2:], F32),
        (jnp.exp(total).swapaxes(0, 1), own.swapaxes(0, 1)))
    y = y + jnp.exp(cum)[..., None] * jnp.einsum(
        "bclgn,cbgrpn->bclgrp", C_c, entry)
    y = y + D.astype(F32).reshape(groups, -1, 1) * u_c
    return y.reshape(batch, seq + pad, heads, width)[:, :seq].astype(u.dtype)


# -- the kernels -------------------------------------------------------------

def _mm(a, b, contract_a: int, contract_b: int):
    return jax.lax.dot_general(
        a, b, (((contract_a,), (contract_b,)), ((), ())),
        preferred_element_type=F32)


def _causal(chunk: int):
    rows = jax.lax.broadcasted_iota(jnp.int32, (chunk, chunk), 0)
    cols = jax.lax.broadcasted_iota(jnp.int32, (chunk, chunk), 1)
    return rows >= cols


def _ssd_fwd_kernel(u_ref, b_ref, c_ref, cols_ref, rows_ref, d_ref, etot_ref,
                    y_ref, entry_ref, scores_scr, state_scr, scaled_scr, *,
                    heads: int, width: int, per_group: int):
    """One chunk of one block of ``heads`` heads, of the ``per_group`` blocks
    that share a group's B and C. u/y [L, heads * width]; b/c [L, N], the
    block's group's; cols [3, L, heads] holds (dt, cum, cum_L - cum) as
    columns and rows [2, heads, L] (dt, cum) as rows; d [1, heads * width] is
    D and etot [1, heads * width] exp(cum_L), a value a lane; entry [N, heads
    * width] is the block's state on entry, transposed (the state of head h
    is ``[:, h * width:(h + 1) * width]``)."""
    ci, hi = pl.program_id(1), pl.program_id(2)
    chunk = u_ref.shape[0]
    dtype = u_ref.dtype

    @pl.when(ci == 0)
    def _():
        state_scr[hi] = jnp.zeros(state_scr.shape[1:], F32)

    @pl.when(hi % per_group == 0)
    def _():
        scores_scr[...] = _mm(c_ref[...], b_ref[...], 1, 1)

    state = state_scr[hi]
    entry_ref[...] = state
    from_state = _mm(c_ref[...], state.astype(dtype), 1, 0)  # [L, W]
    scores, causal = scores_scr[...], _causal(chunk)
    for h in range(heads):
        lanes = slice(h * width, (h + 1) * width)
        u = u_ref[:, lanes]
        dt_col, cum_col = cols_ref[0, :, h:h + 1], cols_ref[1, :, h:h + 1]
        dt_row, cum_row = rows_ref[0, h:h + 1, :], rows_ref[1, h:h + 1, :]
        decay = jnp.exp(jnp.where(causal, cum_col - cum_row, _NEG))
        mixed = (scores * decay * dt_row).astype(dtype)
        y = (_mm(mixed, u, 1, 0) + jnp.exp(cum_col) * from_state[:, lanes]
             + d_ref[:, lanes] * u.astype(F32))
        y_ref[:, lanes] = y.astype(y_ref.dtype)
        scaled_scr[:, lanes] = (
            u.astype(F32) * (jnp.exp(cols_ref[2, :, h:h + 1]) * dt_col)
        ).astype(dtype)
    state_scr[hi] = etot_ref[...] * state + _mm(
        b_ref[...], scaled_scr[...], 0, 0)


def _pieces(x):
    """x float32 as three bfloat16 arrays that sum to it (to 2 ** -24 of
    it): what moves float32 whole through a product with a 0/1 matrix."""
    out = []
    for _ in range(3):
        out.append(x.astype(jnp.bfloat16))
        x = x - out[-1].astype(F32)
    return out


def _of_head(rows: int, heads: int, width: int):
    """The 0/1 matrix [rows, heads * width] that is 1 where row ``r`` (of
    ``rows`` / ``heads`` stacks of the heads) and the lane are one head's."""
    shape = (rows, heads * width)
    same = (jax.lax.broadcasted_iota(jnp.int32, shape, 0) % heads
            == jax.lax.broadcasted_iota(jnp.int32, shape, 1) // width)
    return same.astype(jnp.bfloat16)


def _by_lane(rows, width: int):
    """rows [heads, R] float32, a value a head and position, as a value a
    lane [R, heads * width]: the pieces one under the other against the 0/1
    matrix of the lanes' heads, one product, which adds them up."""
    heads = rows.shape[0]
    pieces = jnp.concatenate([p.astype(F32) for p in _pieces(rows)])
    return _mm(pieces.astype(jnp.bfloat16), _of_head(3 * heads, heads, width),
               0, 0)


def _head_sums(x, width: int):
    """x [R, heads * width] float32 summed over each head's lanes, as rows
    [heads, R]: the 0/1 matrix of the lanes' heads on x's pieces."""
    heads = x.shape[1] // width
    of_head = _of_head(heads, heads, width)
    return sum(_mm(of_head, piece, 1, 1) for piece in _pieces(x))


def _ssd_bwd_kernel(u_ref, b_ref, c_ref, cols_ref, rows_ref, d_ref, etot_ref,
                    entry_ref, dy_ref, du_ref, db_ref, dc_ref, drows_ref,
                    dd_ref, scores_scr, dscores_scr, dstate_scr, dt_u_scr,
                    du_scr, y_scr, *, heads: int, width: int,
                    per_group: int):
    """The forward's grid step with the chunks in reverse (the index maps
    turn them round): the cotangent of the block's exit state is carried in
    ``dstate_scr``. db/dc [L, N] float32, the group's, are summed over its
    ``per_group`` head blocks in place; drows [2, heads, L] are the
    cotangents of dt and cum, dd [1, heads * width] what each lane adds to
    that of D.

    A head's own work is its [L, L] decay and three products with it: dt
    rides ``dt u`` [L, width], so the cotangent of ``scores * decay`` is ``dY
    (dt u)^T``, that of ``dt u`` its transpose on dY, and the forward's
    product made again says what cum is owed as y's exponent. Everything
    else runs once over the block's lanes, before and after that loop: what
    dt and cum are owed are sums over a head's lanes of products of those
    [L, width] results."""
    ti, hi = pl.program_id(1), pl.program_id(2)
    chunk = u_ref.shape[0]
    dtype = u_ref.dtype

    @pl.when(ti == 0)
    def _():
        dstate_scr[hi] = jnp.zeros(dstate_scr.shape[1:], F32)

    @pl.when(hi % per_group == 0)
    def _():
        scores_scr[...] = _mm(c_ref[...], b_ref[...], 1, 1)
        dscores_scr[...] = jnp.zeros(dscores_scr.shape, F32)
        db_ref[...] = jnp.zeros(db_ref.shape, F32)
        dc_ref[...] = jnp.zeros(dc_ref.shape, F32)

    dt = _by_lane(rows_ref[0], width)
    dt_u_scr[...] = (u_ref[...].astype(F32) * dt).astype(dtype)
    causal = _causal(chunk)
    tiles = [slice(first, first + 128) for first in range(0, chunk, 128)]
    for h in range(heads):
        lanes = slice(h * width, (h + 1) * width)
        decayed = []
        # 128 positions t at a time, against the positions s up to theirs:
        # the tiles above the diagonal are zeros nobody makes.
        for t in tiles:
            upto = slice(0, t.stop)
            dy, dt_u = dy_ref[t, lanes], dt_u_scr[upto, lanes]
            decay = jnp.exp(jnp.where(
                causal[t, upto],
                cols_ref[1, t, h:h + 1] - rows_ref[1, h:h + 1, upto], _NEG))
            decayed.append((scores_scr[t, upto] * decay).astype(dtype))
            dscores_scr[t, upto] += _mm(dy, dt_u, 1, 1) * decay
            y_scr[t, lanes] = _mm(decayed[-1], dt_u, 1, 0)  # y, of the chunk
        for k, s in enumerate(tiles):  # d (dt u), in the chunk
            du_scr[s, lanes] = sum(
                _mm(decayed[i][:, s], dy_ref[tiles[i], lanes], 0, 0)
                for i in range(k, len(tiles)))

    state, dstate = entry_ref[...], dstate_scr[hi]
    from_state = _mm(c_ref[...], state.astype(dtype), 1, 0)    # [L, W]
    from_dstate = _mm(b_ref[...], dstate.astype(dtype), 1, 0)  # [L, W]
    u, dy = u_ref[...].astype(F32), dy_ref[...].astype(F32)
    # The weight of u_s B_s^T in the exit state is w_s dt_s; exp(cum_t) is
    # the entry state's in y_t.
    cum_rows = rows_ref[1]
    w = _by_lane(jnp.exp(cum_rows[:, chunk - 1:] - cum_rows), width)
    into_y = _by_lane(jnp.exp(cum_rows), width)
    scaled, dys = (u * (dt * w)).astype(dtype), (dy * into_y).astype(dtype)
    d_dt_u = du_scr[...] + w * from_dstate
    du_ref[...] = (dt * d_dt_u + d_ref[...] * dy).astype(du_ref.dtype)
    dd_ref[...] = (dy * u).sum(0, keepdims=True)
    dc_ref[...] += _mm(dys, state.astype(dtype), 1, 1)
    db_ref[...] += _mm(scaled, dstate.astype(dtype), 1, 1)
    dstate_scr[hi] = etot_ref[...] * dstate + _mm(c_ref[...], dys, 0, 0)
    # cum: y_t's exponent, less what position s lent every later t and the
    # exit state, and at the last position the exponent of the whole exit
    # state. What one position is owed another owes: the operands as the
    # products above rounded them, so that the two cancel to float32's bits.
    lent = scaled.astype(F32) * from_dstate
    owed = (dy * (y_scr[...] + into_y * from_state)
            - dt_u_scr[...].astype(F32) * du_scr[...] - lent)
    dtotal = (lent.sum(0, keepdims=True)
              + (etot_ref[...] * state * dstate).sum(0, keepdims=True))
    last = jax.lax.broadcasted_iota(jnp.int32, (chunk, 1), 0) == chunk - 1
    drows_ref[0] = _head_sums(u * d_dt_u, width)
    drows_ref[1] = _head_sums(owed + jnp.where(last, dtotal, 0.0), width)

    @pl.when(hi % per_group == per_group - 1)
    def _():
        dscores = dscores_scr[...].astype(dtype)
        dc_ref[...] += _mm(dscores, b_ref[...], 1, 0)
        db_ref[...] += _mm(dscores, c_ref[...], 0, 0)


def heads_per_block(heads: int, width: int, groups: int = 1) -> int:
    """Heads a grid step takes: the most of 8, 4, 2, 1 that divide the heads
    of one group of B and C into blocks of whole 128-lane tiles (0 if none
    does)."""
    return next((n for n in (8, 4, 2, 1)
                 if heads // groups % n == 0 and n * width % 128 == 0), 0)


def _layouts(dt_c, cum, D, block: int, width: int):
    """The per-head vectors as the kernels read them: cols [batch, chunks,
    blocks, 3, L, block] of (dt, cum, cum_L - cum), rows [.., 2, block, L]
    of (dt, cum), D and exp(cum_L) a value a lane ([blocks, 1, block *
    width] and [batch, chunks, blocks, 1, block * width])."""
    batch, n, chunk, heads = dt_c.shape
    blocks = heads // block
    stacked = jnp.stack([dt_c, cum, cum[:, :, -1:, :] - cum], axis=2).reshape(
        batch, n, 3, chunk, blocks, block)
    cols = stacked.transpose(0, 1, 4, 2, 3, 5)
    rows = stacked[:, :, :2].transpose(0, 1, 4, 2, 5, 3)
    d_lanes = jnp.repeat(D.astype(F32), width).reshape(blocks, 1, -1)
    etot = jnp.repeat(jnp.exp(cum[:, :, -1, :]), width, axis=-1).reshape(
        batch, n, blocks, 1, -1)
    return cols, rows, d_lanes, etot


def _specs(chunk: int, lanes: int, state: int, block: int, n_chunks: int,
           per_group: int, reverse: bool):
    """BlockSpecs over the grid (batch, chunks, head blocks), by operand
    kind; ``reverse`` walks the chunks from the last. B and C are [batch, S,
    G * N], head block j reading the N columns of group ``j //
    per_group``."""
    def at(t):
        return n_chunks - 1 - t if reverse else t

    return {
        "wide": pl.BlockSpec((None, chunk, lanes),
                             lambda b, t, j: (b, at(t), j)),
        "bc": pl.BlockSpec((None, chunk, state),
                           lambda b, t, j: (b, at(t), j // per_group)),
        "cols": lambda k: pl.BlockSpec(
            (None, None, None, k, chunk, block),
            lambda b, t, j: (b, at(t), j, 0, 0, 0)),
        "rows": lambda k: pl.BlockSpec(
            (None, None, None, k, block, chunk),
            lambda b, t, j: (b, at(t), j, 0, 0, 0)),
        "d": pl.BlockSpec((None, 1, lanes), lambda b, t, j: (j, 0, 0)),
        "etot": pl.BlockSpec((None, None, None, 1, lanes),
                             lambda b, t, j: (b, at(t), j, 0, 0)),
        "state": pl.BlockSpec((None, None, None, state, lanes),
                              lambda b, t, j: (b, at(t), j, 0, 0)),
    }


_PARAMS = dict(dimension_semantics=("parallel", "arbitrary", "arbitrary"))


def _flat_groups(B):
    """B or C [batch, S, G, N] as the kernels read it, [batch, S, G * N]."""
    return B.reshape(B.shape[:2] + (-1,))


def _forward(u, dt_c, cum, B, C, D, block: int):
    """(y [batch, S, H * P], entry states [batch, chunks, blocks, N, block *
    P]) by the forward kernel; u is [batch, S, H * P], B and C [batch, S, G,
    N]."""
    batch, n, chunk, heads = dt_c.shape
    groups, state = B.shape[2:]
    width = u.shape[-1] // heads
    lanes, blocks = block * width, heads // block
    per_group = blocks // groups
    spec = _specs(chunk, lanes, state, block, n, per_group, reverse=False)
    return pl.pallas_call(
        functools.partial(_ssd_fwd_kernel, heads=block, width=width,
                          per_group=per_group),
        grid=(batch, n, blocks),
        in_specs=[spec["wide"], spec["bc"], spec["bc"], spec["cols"](3),
                  spec["rows"](2), spec["d"], spec["etot"]],
        out_specs=[spec["wide"], spec["state"]],
        out_shape=[jax.ShapeDtypeStruct(u.shape, u.dtype),
                   jax.ShapeDtypeStruct((batch, n, blocks, state, lanes),
                                        F32)],
        scratch_shapes=[pltpu.VMEM((chunk, chunk), F32),
                        pltpu.VMEM((blocks, state, lanes), F32),
                        pltpu.VMEM((chunk, lanes), u.dtype)],
        compiler_params=pltpu.CompilerParams(**_PARAMS),
        interpret=_interpret(),
        name="ssd_fwd",
    )(u, _flat_groups(B), _flat_groups(C),
      *_layouts(dt_c, cum, D, block, width))


def _backward(u, dt_c, cum, B, C, D, entry, dy, block: int):
    """Cotangents (du, ddt, dcum [batch, chunks, L, H], dB, dC, dD) by the
    backward kernel."""
    batch, n, chunk, heads = dt_c.shape
    groups, state = B.shape[2:]
    width = u.shape[-1] // heads
    lanes, blocks = block * width, heads // block
    per_group = blocks // groups
    spec = _specs(chunk, lanes, state, block, n, per_group, reverse=True)
    flat = jax.ShapeDtypeStruct(B.shape[:2] + (groups * state,), F32)
    du, dB, dC, drows, dD = pl.pallas_call(
        functools.partial(_ssd_bwd_kernel, heads=block, width=width,
                          per_group=per_group),
        grid=(batch, n, blocks),
        in_specs=[spec["wide"], spec["bc"], spec["bc"], spec["cols"](3),
                  spec["rows"](2), spec["d"], spec["etot"], spec["state"],
                  spec["wide"]],
        out_specs=[spec["wide"], spec["bc"], spec["bc"], spec["rows"](2),
                   spec["etot"]],
        out_shape=[jax.ShapeDtypeStruct(u.shape, u.dtype), flat, flat,
                   jax.ShapeDtypeStruct((batch, n, blocks, 2, block, chunk),
                                        F32),
                   jax.ShapeDtypeStruct((batch, n, blocks, 1, lanes), F32)],
        scratch_shapes=[pltpu.VMEM((chunk, chunk), F32),
                        pltpu.VMEM((chunk, chunk), F32),
                        pltpu.VMEM((blocks, state, lanes), F32),
                        pltpu.VMEM((chunk, lanes), u.dtype),
                        pltpu.VMEM((chunk, lanes), F32),
                        pltpu.VMEM((chunk, lanes), F32)],
        compiler_params=pltpu.CompilerParams(**_PARAMS),
        interpret=_interpret(),
        name="ssd_bwd",
    )(u, _flat_groups(B), _flat_groups(C),
      *_layouts(dt_c, cum, D, block, width), entry, dy)
    # [batch, chunks, blocks, k, block, L] -> k x [batch, chunks, L, H]
    ddt, dcum = drows.transpose(3, 0, 1, 5, 2, 4).reshape(
        2, batch, n, chunk, heads)
    return (du, ddt, dcum, dB.reshape(B.shape).astype(B.dtype),
            dC.reshape(C.shape).astype(C.dtype),
            dD.sum((0, 1)).reshape(heads, width).sum(1).astype(D.dtype))


@functools.partial(jax.custom_vjp, nondiff_argnums=(6,))
def _ssd_kernels(u, dt_c, cum, B, C, D, block: int):
    return _forward(u, dt_c, cum, B, C, D, block)[0]


def _ssd_kernels_fwd(u, dt_c, cum, B, C, D, block):
    y, entry = _forward(u, dt_c, cum, B, C, D, block)
    return y, (u, dt_c, cum, B, C, D, entry)


def _ssd_kernels_bwd(block, residuals, dy):
    return _backward(*residuals, dy, block)


_ssd_kernels.defvjp(_ssd_kernels_fwd, _ssd_kernels_bwd)


def ssd(u, dt, A, B, C, D, chunk: int = 256):
    """y [batch, S, H, P] of the recurrence at the top of this file, by
    chunks of ``chunk`` positions. u [batch, S, H, P]; dt [batch, S, H]
    positive; A [H] negative; B, C [batch, S, G, N] (or [batch, S, N]: one
    group), head i reading group ``i // (H / G)``; D [H]. The kernels where
    the shapes tile, else ``ssd_chunked``."""
    batch, seq, heads, width = u.shape
    B, C = _grouped(B, heads), _grouped(C, heads)
    block = heads_per_block(heads, width, B.shape[2])
    if seq % chunk or chunk % 128 or B.shape[-1] % 128 or not block:
        return ssd_chunked(u, dt, A, B, C, D, chunk)
    with jax.named_scope("ssd"):
        dt_c, cum = _chunk_sums(dt, A, chunk)
        y = _ssd_kernels(u.reshape(batch, seq, heads * width), dt_c, cum,
                         B.astype(u.dtype), C.astype(u.dtype), D, block)
        return y.reshape(u.shape)
