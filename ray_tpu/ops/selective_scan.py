"""Mamba-1's selective scan: a recurrence with a decay for every (channel,
state) pair, as a pair of Pallas TPU kernels (forward, backward) and a plain
``jax.numpy`` form.

Per channel c, with a state ``H`` [C channels, N states] that is zero before
the first token::

    H_t = exp(delta_t (x) A) * H_(t-1) + (delta_t * xs_t) (x) B_t
    y_t = H_t C_t + D * xs_t

``xs`` and ``delta`` [batch, S, C] hold the channels' inputs and their step
sizes (positive: after the softplus), ``A`` [C, N] the decay rates (negative:
``-exp(A_log)``), ``B`` and ``C`` [batch, S, N] one input and one output
vector a token, shared by every channel, ``D`` [C] the skip. ``ops/ssd.py``'s
recurrence (Mamba-2) has one scalar decay a head, so that a chunk is a few
matrix products shared between channels; here ``exp(delta_t[c] A[c, n])``
differs for every pair, no product is shared, and the recurrence is walked
token by token on the vector unit: N x C state elements a token, about nine
operations and one exponential each. ``exp`` is taken of ``delta_t A``
itself (at most 0), never of a running sum: nothing overflows, whatever the
length.

The kernels' grid is ``(batch, channel blocks, chunks)``, the last
sequential: a grid step is one chunk of L tokens of one block of channels.
The block's state lives transposed, [N, channels]: the channels on lanes and
the states on sublanes, so that ``delta_t`` and ``xs_t`` are rows spread over
the sublanes and ``B_t`` / ``C_t`` columns spread over the lanes. It stays in
VMEM scratch from chunk to chunk, as ``ops/ssd.py`` carries its states.
``B`` and ``C`` come transposed ([batch, N, S], made outside by XLA: 16 rows)
so that a token's vector is a column; a group of ``GROUP`` tokens' columns
is brought to the first lanes of a register by one rotation, and the group
is unrolled. The forward writes each chunk's entry state. The backward walks
the chunks in reverse with the state's cotangent carried the same way: it
forms the chunk's states again from the entry state (kept in VMEM, [L, N,
channels]), then walks the chunk's tokens in reverse and returns the
cotangents of ``xs``, ``delta``, ``A``, ``B``, ``C`` and ``D`` (those of
``A`` and ``D`` a chunk, of ``B`` and ``C`` a channel block: summed outside).
``xs``, ``delta``, ``y`` and the cotangents of the first two cross HBM in the
dtype of ``xs``; the state and every product are float32. In a trace the
kernels are ``selective_scan_fwd`` and ``selective_scan_bwd``, under the
scope ``selective_scan``.

``selective_scan`` is the one entry: the kernels where the shapes tile (S a
multiple of the chunk of 256, C of 128, N of 8), else
``selective_scan_xla``, a ``lax.scan`` over time in float32, which is also the
kernels' oracle in the tests: a length the chunk does not divide is not
padded, it takes that form. On backends other than the TPU the kernels run
in interpreter mode.
"""

from __future__ import annotations

import importlib

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

F32 = jnp.float32

#: Tokens a grid step takes.
CHUNK = 256
#: Tokens unrolled together: their columns of B and C sit side by side in
#: one 128-lane register.
GROUP = 16
_LANE = 128
#: Channels a grid step takes, forward and backward: the widest that
#: divides them. Wider is faster on a v5e, at [1, 16384, 5120] (PERF.md §6,
#: PR 42): forward 7.06 / 4.85 / 4.15 ms a call at 256 / 512 / 1024,
#: backward 74.0 / 37.5 / 22.6 ms at 128 / 256 / 512 (the chunk's states
#: [256, 16, 512] float32 are 8 MB of its VMEM).
_FWD_LANES = (1024, 512, 256, 128)
_BWD_LANES = (512, 256, 128)
_VMEM_BYTES = 64 * 1024 * 1024


def _interpret() -> bool:
    """The flash kernels' answer (interpreter mode off the TPU), asked of
    that module each time so that one switch steers every kernel of
    ``ops/``."""
    return importlib.import_module(
        "ray_tpu.ops.flash_attention")._interpret()


# -- the recurrence in jax.numpy -------------------------------------------

def selective_scan_xla(xs, delta, A, B, C, D):
    """The recurrence at the top of this file as a ``lax.scan`` over time,
    float32 throughout, any length; y in ``xs``'s dtype. The kernels' oracle
    and the path for shapes they cannot tile."""
    A32, D32 = A.astype(F32), D.astype(F32)

    def step(h, at_t):
        x_t, dt_t, b_t, c_t = at_t                     # [batch, C | N]
        h = jnp.exp(dt_t[..., None] * A32) * h \
            + (dt_t * x_t)[..., None] * b_t[:, None, :]
        return h, (h * c_t[:, None, :]).sum(-1) + D32 * x_t

    over_time = [a.astype(F32).swapaxes(0, 1) for a in (xs, delta, B, C)]
    h0 = jnp.zeros((xs.shape[0],) + A.shape, F32)
    _, y = jax.lax.scan(step, h0, over_time)
    return y.swapaxes(0, 1).astype(xs.dtype)


def decay_floor(delta, A):
    """The most negative ``delta_t A`` any (token, channel, state) has:
    where a state forgets within a token. ``delta`` is positive and ``A``
    negative, so a channel's least is its largest step times its fastest
    rate."""
    steps = delta.astype(F32).max(tuple(range(delta.ndim - 1)))
    return (steps * A.astype(F32).min(-1)).min()


# -- the kernels -------------------------------------------------------------

def _columns(ref, g):
    """[N, 128] of ``ref`` [N, L] with group ``g``'s ``GROUP`` columns
    brought to the first lanes: token i of the group is column i."""
    per_tile = _LANE // GROUP
    tile = ref[:, pl.ds(pl.multiple_of(g // per_tile * _LANE, _LANE), _LANE)]
    offset = g % per_tile * GROUP
    return pltpu.roll(tile, jax.lax.rem(_LANE - offset, _LANE), axis=1)


def _fwd_kernel(xs_ref, dt_ref, at_ref, bt_ref, ct_ref, d_ref, y_ref,
                entry_ref, h_scr, dt_scr, dtx_scr, y_scr):
    """One chunk of one block of channels. xs/dt/y [L, channels]; at [N,
    channels] is A transposed; bt/ct [N, L] are B and C transposed; d [1,
    channels]; entry [N, channels] the block's state on entry."""
    chunk = xs_ref.shape[0]

    @pl.when(pl.program_id(2) == 0)
    def _():
        h_scr[...] = jnp.zeros(h_scr.shape, F32)

    entry_ref[...] = h_scr[...]
    dt_scr[...] = dt_ref[...].astype(F32)
    dtx_scr[...] = dt_scr[...] * xs_ref[...].astype(F32)
    a = at_ref[...]

    def group(g, h):
        bt, ct = _columns(bt_ref, g), _columns(ct_ref, g)
        base = pl.multiple_of(g * GROUP, GROUP)
        for i in range(GROUP):
            row = pl.ds(base + i, 1)
            h = jnp.exp(dt_scr[row, :] * a) * h \
                + dtx_scr[row, :] * bt[:, i:i + 1]
            y_scr[row, :] = jnp.sum(h * ct[:, i:i + 1], axis=0, keepdims=True)
        return h

    h_scr[...] = jax.lax.fori_loop(0, chunk // GROUP, group, h_scr[...])
    y_ref[...] = (y_scr[...] + d_ref[...] * xs_ref[...].astype(F32)
                  ).astype(y_ref.dtype)


def _bwd_kernel(xs_ref, dt_ref, at_ref, bt_ref, ct_ref, d_ref, entry_ref,
                dy_ref, dxs_ref, ddt_ref, da_ref, dbt_ref, dct_ref, dd_ref,
                g_scr, hs_scr, dt_scr, dtx_scr, dy_scr, ddt_scr, ddtx_scr):
    """The forward's grid step with the chunks in reverse (the index maps
    turn them round): the cotangent of the block's exit state is carried in
    ``g_scr``. da [N, channels] and dd [1, channels] are this chunk's part
    of A's and D's cotangents, dbt/dct [N, L] this channel block's part of
    B's and C's."""
    chunk = xs_ref.shape[0]
    groups = chunk // GROUP

    @pl.when(pl.program_id(2) == 0)
    def _():
        g_scr[...] = jnp.zeros(g_scr.shape, F32)

    dt_scr[...] = dt_ref[...].astype(F32)
    dtx_scr[...] = dt_scr[...] * xs_ref[...].astype(F32)
    dy_scr[...] = dy_ref[...].astype(F32)
    a = at_ref[...]

    # The chunk's states again: hs[t] is the state before token t.
    def states(g, h):
        bt = _columns(bt_ref, g)
        base = pl.multiple_of(g * GROUP, GROUP)
        for i in range(GROUP):
            row = pl.ds(base + i, 1)
            hs_scr[base + i] = h
            h = jnp.exp(dt_scr[row, :] * a) * h \
                + dtx_scr[row, :] * bt[:, i:i + 1]
        return h

    jax.lax.fori_loop(0, groups, states, entry_ref[...])

    dbt_ref[...] = jnp.zeros(dbt_ref.shape, F32)
    dct_ref[...] = jnp.zeros(dct_ref.shape, F32)
    lane = jax.lax.broadcasted_iota(jnp.int32, (a.shape[0], _LANE), 1)
    per_tile = _LANE // GROUP

    def group(r, carry):
        ghat, da = carry     # a_(t+1) * G_(t+1); A's cotangent so far
        g = groups - 1 - r
        bt, ct = _columns(bt_ref, g), _columns(ct_ref, g)
        base = pl.multiple_of(g * GROUP, GROUP)
        db = jnp.zeros(lane.shape, F32)
        dc = jnp.zeros(lane.shape, F32)
        for i in reversed(range(GROUP)):
            row = pl.ds(base + i, 1)
            dt, dtx, dy = dt_scr[row, :], dtx_scr[row, :], dy_scr[row, :]
            before = hs_scr[base + i]
            decay = jnp.exp(dt * a)
            b_col = bt[:, i:i + 1]
            h = decay * before + dtx * b_col
            gh = dy * ct[:, i:i + 1] + ghat       # the state's cotangent
            dc = jnp.where(lane == i, jnp.sum(dy * h, axis=1, keepdims=True),
                           dc)
            db = jnp.where(lane == i, jnp.sum(gh * dtx, axis=1,
                                              keepdims=True), db)
            ddtx_scr[row, :] = jnp.sum(gh * b_col, axis=0, keepdims=True)
            ghat = decay * gh
            by_rate = ghat * before               # d / d (delta_t A)
            ddt_scr[row, :] = jnp.sum(by_rate * a, axis=0, keepdims=True)
            da = da + by_rate * dt
        tile = pl.ds(pl.multiple_of(g // per_tile * _LANE, _LANE), _LANE)
        offset = g % per_tile * GROUP
        dbt_ref[:, tile] += pltpu.roll(db, offset, axis=1)
        dct_ref[:, tile] += pltpu.roll(dc, offset, axis=1)
        return ghat, da

    ghat, da = jax.lax.fori_loop(
        0, groups, group, (g_scr[...], jnp.zeros(a.shape, F32)))
    g_scr[...] = ghat
    da_ref[...] = da
    xs = xs_ref[...].astype(F32)
    dxs_ref[...] = (ddtx_scr[...] * dt_scr[...] + d_ref[...] * dy_scr[...]
                    ).astype(dxs_ref.dtype)
    ddt_ref[...] = (ddt_scr[...] + ddtx_scr[...] * xs).astype(ddt_ref.dtype)
    dd_ref[...] = jnp.sum(dy_scr[...] * xs, axis=0, keepdims=True)


def _specs(lanes: int, state: int, n_chunks: int, reverse: bool):
    """BlockSpecs over the grid (batch, channel blocks, chunks), by operand
    kind; ``reverse`` walks the chunks from the last."""
    chunk = CHUNK

    def at(t):
        return n_chunks - 1 - t if reverse else t

    return {
        "wide": pl.BlockSpec((None, chunk, lanes),
                             lambda b, j, t: (b, at(t), j)),
        "rates": pl.BlockSpec((state, lanes), lambda b, j, t: (0, j)),
        "bc": pl.BlockSpec((None, state, chunk),
                           lambda b, j, t: (b, 0, at(t))),
        "d": pl.BlockSpec((1, lanes), lambda b, j, t: (0, j)),
        "state": pl.BlockSpec((None, None, state, lanes),
                              lambda b, j, t: (b, at(t), 0, j)),
        "row": pl.BlockSpec((None, None, 1, lanes),
                            lambda b, j, t: (b, at(t), 0, j)),
        "dbc": pl.BlockSpec((None, None, state, chunk),
                            lambda b, j, t: (b, j, 0, at(t))),
    }


def _params():
    return pltpu.CompilerParams(
        dimension_semantics=("parallel", "parallel", "arbitrary"),
        vmem_limit_bytes=_VMEM_BYTES)


def _block_lanes(channels: int, wanted) -> int:
    """Channels a grid step takes: the widest of ``wanted`` that divides
    them (0 if none does)."""
    return next((n for n in wanted if channels % n == 0), 0)


def _transposed(A, B, C, D):
    """(A^T [N, C], B^T and C^T [batch, N, S], D [1, C]), float32: the small
    operands as the kernels read them."""
    return (A.astype(F32).T, B.astype(F32).swapaxes(1, 2),
            C.astype(F32).swapaxes(1, 2), D.astype(F32)[None])


def _forward(xs, delta, A, B, C, D):
    """(y [batch, S, C], entry states [batch, chunks, N, C]) by the forward
    kernel."""
    batch, seq, channels = xs.shape
    state, n = A.shape[1], seq // CHUNK
    lanes = _block_lanes(channels, _FWD_LANES)
    spec = _specs(lanes, state, n, reverse=False)
    at, bt, ct, d = _transposed(A, B, C, D)
    return pl.pallas_call(
        _fwd_kernel,
        grid=(batch, channels // lanes, n),
        in_specs=[spec["wide"], spec["wide"], spec["rates"], spec["bc"],
                  spec["bc"], spec["d"]],
        out_specs=[spec["wide"], spec["state"]],
        out_shape=[jax.ShapeDtypeStruct(xs.shape, xs.dtype),
                   jax.ShapeDtypeStruct((batch, n, state, channels), F32)],
        scratch_shapes=[pltpu.VMEM((state, lanes), F32)]
        + [pltpu.VMEM((CHUNK, lanes), F32)] * 3,
        compiler_params=_params(),
        interpret=_interpret(),
        name="selective_scan_fwd",
    )(xs, delta.astype(xs.dtype), at, bt, ct, d)


def _backward(xs, delta, A, B, C, D, entry, dy):
    """Cotangents of (xs, delta, A, B, C, D) by the backward kernel."""
    batch, seq, channels = xs.shape
    state, n = A.shape[1], seq // CHUNK
    lanes = _block_lanes(channels, _BWD_LANES)
    blocks = channels // lanes
    spec = _specs(lanes, state, n, reverse=True)
    at, bt, ct, d = _transposed(A, B, C, D)
    wide = jax.ShapeDtypeStruct(xs.shape, xs.dtype)
    small = jax.ShapeDtypeStruct((batch, blocks, state, seq), F32)
    dxs, ddt, da, dbt, dct, dd = pl.pallas_call(
        _bwd_kernel,
        grid=(batch, blocks, n),
        in_specs=[spec["wide"], spec["wide"], spec["rates"], spec["bc"],
                  spec["bc"], spec["d"], spec["state"], spec["wide"]],
        out_specs=[spec["wide"], spec["wide"], spec["state"], spec["dbc"],
                   spec["dbc"], spec["row"]],
        out_shape=[wide, wide,
                   jax.ShapeDtypeStruct((batch, n, state, channels), F32),
                   small, small,
                   jax.ShapeDtypeStruct((batch, n, 1, channels), F32)],
        scratch_shapes=[pltpu.VMEM((state, lanes), F32),
                        pltpu.VMEM((CHUNK, state, lanes), F32)]
        + [pltpu.VMEM((CHUNK, lanes), F32)] * 5,
        compiler_params=_params(),
        interpret=_interpret(),
        name="selective_scan_bwd",
    )(xs, delta.astype(xs.dtype), at, bt, ct, d, entry, dy.astype(xs.dtype))
    return (dxs, ddt.astype(delta.dtype), da.sum((0, 1)).T.astype(A.dtype),
            dbt.sum(1).swapaxes(1, 2).astype(B.dtype),
            dct.sum(1).swapaxes(1, 2).astype(C.dtype),
            dd.sum((0, 1, 2)).astype(D.dtype))


@jax.custom_vjp
def _kernels(xs, delta, A, B, C, D):
    return _forward(xs, delta, A, B, C, D)[0]


def _kernels_fwd(xs, delta, A, B, C, D):
    y, entry = _forward(xs, delta, A, B, C, D)
    return y, (xs, delta, A, B, C, D, entry)


def _kernels_bwd(residuals, dy):
    return _backward(*residuals, dy)


_kernels.defvjp(_kernels_fwd, _kernels_bwd)


def selective_scan(xs, delta, A, B, C, D):
    """y [batch, S, C] of the recurrence at the top of this file. xs, delta
    [batch, S, C] (delta positive); A [C, N] negative; B, C [batch, S, N]; D
    [C]. The kernels where the shapes tile, else ``selective_scan_xla``."""
    seq, channels = xs.shape[1:]
    if seq % CHUNK or channels % _LANE or A.shape[1] % 8:
        return selective_scan_xla(xs, delta, A, B, C, D)
    with jax.named_scope("selective_scan"):
        return _kernels(xs, delta, A, B, C, D)
