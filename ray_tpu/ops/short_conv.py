"""Short causal convolutions, depthwise along S, as one fused Pallas TPU pass
each way and a plain ``jax.numpy`` form of the same function. Two
functions over one set of seam helpers:

``short_conv``, the double-gated convolution that is a layer's sequence
mixer (LFM2's ``Lfm2ShortConv``). One projection gives three chunks of d
channels a token, ``bcx = B | C | x``; with K taps ``w`` [K, d] and zeros
before the first token::

    z_t = sum_{k=0..K-1} w_k (B * x)_(t-K+1+k)
    y_t = C_t * z_t

No activation, no bias. ``conv_silu``, the convolution in front of a
recurrence (a Kimi delta-rule layer's q, k, v; a granite state-space layer's
xBC): no gates, a bias ``b`` [d] or none, a SiLU on the way out::

    y_t = silu(b + sum_k w_k x_(t-K+1+k))

Everything is elementwise but the K-term sum along S, so the floor is bytes:
the gated forward reads three chunks and writes one, ``conv_silu``'s reads
one and writes one. As XLA operations (``short_conv_xla``, ``conv_silu_xla``
on ``causal_conv``) either is float32 copies, a pad and a pass a tap.

The gated kernels read ``bcx`` [batch, S, 3 d] where the projection left it
(no split copies): a grid step is ``ROWS`` whole rows of one sequence, all 3 d
lanes, worked through ``_lanes(d)`` channels at a time in float32. The K - 1
rows a tile needs from before it are the last of a second, small block of the
same array (``HALO`` rows: one sublane tile of bfloat16), zeros at a
sequence's first tile; no padded copy exists and no grid step waits for
another. The backward takes ``dy`` and ``bcx``, forms ``z`` again, and
writes **one** [batch, S, 3 d] cotangent for the projection's backward to
read as it lies::

    dC   = dy * z
    g_t  = sum_k w_k (dy * C)_(t+K-1-k)          the anti-causal side
    dB   = g * x ;  dx = g * B
    dw_k = sum_{b,t} (dy * C)_t (B * x)_(t-K+1+k)

``g`` looks K - 1 rows ahead: a small block of ``dy`` and of ``C`` from
after the tile, zeros at a sequence's last. ``dw`` leaves the kernel as one
float32 [8, d] partial sum a grid step and is added up outside.

``conv_silu``'s kernels take the same tiles, halos and partial sums over
columns ``start .. start + width`` of an ``x`` [batch, S, W] that may be
wider (granite's xBC lies between z and dt in one projection's output): the
blocks are whole rows of ``x`` and the kernel takes its columns out of the
tile, ``_SILU_LANES`` at a time in a loop. The taps and the bias go in as one float32 [K + 1, width] block and
their gradients come back as the rows of one partial sum. The backward
forms the pre-activation again, in the tile and on the halo after it::

    dz   = dy * silu'(pre)
    dx_t = sum_k w_k dz_(t+K-1-k)
    dw_k = sum_{b,t} dz_t x_(t-K+1+k) ;  db = sum_{b,t} dz_t

``dz`` of the rows after the tile needs their ``pre``, whose taps reach back
into the tile: that halo carries ``x`` (joined to the tile's last rows), not
only ``dy``.

``short_conv`` and ``conv_silu`` are the entries: the kernels where the
shapes tile (S a multiple of ``ROWS``, the channels of 128, the taps (and
bias) at most 8 rows), else the ``jax.numpy`` form, which is also the
kernels' oracle in the tests. On backends other than the TPU the kernels run
in interpreter mode.
"""

from __future__ import annotations

import functools
import importlib

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

F32 = jnp.float32
#: Rows of a sequence a grid step takes.
ROWS = 256
#: Rows of the neighbouring tile a grid step reads for the taps that reach
#: across its edge: a whole sublane tile of bfloat16.
HALO = 16
#: Partial sums of ``dw`` a grid step writes: K rows of them, a sublane
#: tile of float32.
_DW_ROWS = 8
#: Blocks of whole rows double-buffered (3 MB each way at d = 2048) do not
#: fit the 16 MB the compiler scopes a kernel by default.
_VMEM_BYTES = 64 * 1024 * 1024
#: Channels ``conv_silu``'s kernels work through at a time: a tile of
#: float32 values and its K shifted copies then stay near the register file
#: (the forward at [16384, 4096]: 0.68 / 0.59 / 0.49 ms at 512 / 256 / 128).
_SILU_LANES = 128


def _interpret() -> bool:
    """The flash kernels' answer (interpreter mode off the TPU), asked of
    that module each time so that one switch steers every kernel of
    ``ops/``."""
    return importlib.import_module(
        "ray_tpu.ops.flash_attention")._interpret()


# -- the same function in jax.numpy ----------------------------------------

def causal_conv(x, w, b=None):
    """Depthwise causal convolution along S of x [B, S, C] with taps w [K,
    C] and, if given, bias b [C], in float32: y_t = b + sum_k w_k x_(t - K +
    1 + k), zeros before the first token. ``lm.causal_conv`` is this."""
    taps, seq = w.shape[0], x.shape[1]
    padded = jnp.pad(x.astype(F32), ((0, 0), (taps - 1, 0), (0, 0)))
    w = w.astype(F32)
    bias = 0.0 if b is None else b.astype(F32)
    return bias + sum(w[k] * padded[:, k:k + seq] for k in range(taps))


def conv_silu_xla(x, w, b=None):
    """``silu(b + conv(x))`` as XLA operations, any shape: ``causal_conv``
    and the SiLU in float32, the result in ``x``'s dtype. ``conv_silu``'s
    kernels' oracle and their fallback."""
    return jax.nn.silu(causal_conv(x, w, b)).astype(x.dtype)


def short_conv_xla(bcx, w):
    """``C * conv(B * x)`` as XLA operations, any shape: products and the
    K-term sum in float32 (``causal_conv``), the result in ``bcx``'s dtype.
    The kernels' oracle and their fallback."""
    gate_b, gate_c, x = jnp.split(bcx, 3, axis=-1)
    z = causal_conv(gate_b.astype(F32) * x.astype(F32), w)
    return (gate_c.astype(F32) * z).astype(bcx.dtype)


# -- kernels ----------------------------------------------------------------

def _lanes(d: int) -> int:
    """Channels a kernel works through at a time: whole 128-lane tiles."""
    return next(c for c in (512, 256, 128) if d % c == 0)


def _rows_before(cur, before, j: int):
    """a_t = cur_(t-j) over one tile ``cur`` [T, L], its first j rows from
    ``before`` [HALO, L], the rows that precede it. The roll wraps the
    tile's own last rows into its first; those come again from a roll over
    the seam, and the pieces meet on a sublane tile's edge."""
    if j == 0:
        return cur
    body = pltpu.roll(cur, j, 0)
    seam = pltpu.roll(jnp.concatenate([before, cur[:HALO]], axis=0), j, 0)
    return jnp.concatenate([seam[HALO:], body[HALO:]], axis=0)


def _rows_after(cur, after, j: int):
    """a_t = cur_(t+j), its last j rows from ``after`` [HALO, L], the rows
    that follow the tile."""
    if j == 0:
        return cur
    rows = cur.shape[0]
    body = pltpu.roll(cur, rows - j, 0)
    seam = pltpu.roll(jnp.concatenate([cur[rows - HALO:], after], axis=0),
                      2 * HALO - j, 0)
    return jnp.concatenate([body[:rows - HALO], seam[:HALO]], axis=0)


def _fwd_kernel(bcx_ref, before_ref, w_ref, y_ref, *, d: int, taps: int):
    first = pl.program_id(1) == 0
    step = _lanes(d)
    for c in range(d // step):
        at = lambda chunk: pl.ds(chunk * d + c * step, step)  # noqa: E731
        bx = bcx_ref[:, at(0)].astype(F32) * bcx_ref[:, at(2)].astype(F32)
        before = jnp.where(first, 0.0, before_ref[:, at(0)].astype(F32)
                           * before_ref[:, at(2)].astype(F32))
        w = w_ref[:, pl.ds(c * step, step)]
        z = sum(w[k:k + 1] * _rows_before(bx, before, taps - 1 - k)
                for k in range(taps))
        y_ref[:, pl.ds(c * step, step)] = (
            bcx_ref[:, at(1)].astype(F32) * z).astype(y_ref.dtype)


def _bwd_kernel(bcx_ref, before_ref, c_after_ref, dy_ref, dy_after_ref,
                w_ref, dbcx_ref, dw_ref, *, d: int, taps: int):
    first = pl.program_id(1) == 0
    last = pl.program_id(1) == pl.num_programs(1) - 1
    step = _lanes(d)
    for c in range(d // step):
        own = pl.ds(c * step, step)
        at = lambda chunk: pl.ds(chunk * d + c * step, step)  # noqa: E731
        gate_b, gate_c, x = (bcx_ref[:, at(i)].astype(F32) for i in range(3))
        dy = dy_ref[:, own].astype(F32)
        before = jnp.where(first, 0.0, before_ref[:, at(0)].astype(F32)
                           * before_ref[:, at(2)].astype(F32))
        w = w_ref[:, own]
        # (B * x)_(t-j) for every reach j of a tap: tap k reaches K - 1 - k.
        reached = [_rows_before(gate_b * x, before, j) for j in range(taps)]
        z = sum(w[k:k + 1] * reached[taps - 1 - k] for k in range(taps))
        dbcx_ref[:, at(1)] = (dy * z).astype(dbcx_ref.dtype)
        dz = dy * gate_c
        after = jnp.where(last, 0.0, dy_after_ref[:, own].astype(F32)
                          * c_after_ref[:, own].astype(F32))
        back = sum(w[k:k + 1] * _rows_after(dz, after, taps - 1 - k)
                   for k in range(taps))
        dbcx_ref[:, at(0)] = (back * x).astype(dbcx_ref.dtype)
        dbcx_ref[:, at(2)] = (back * gate_b).astype(dbcx_ref.dtype)
        # Row k of the block is tap k's partial sum; the others stay zero.
        row = jax.lax.broadcasted_iota(jnp.int32, (_DW_ROWS, step), 0)
        dw_ref[:, own] = sum(
            jnp.where(row == k, (dz * reached[taps - 1 - k]).sum(
                0, keepdims=True), 0.0) for k in range(taps))


def _params():
    return pltpu.CompilerParams(
        dimension_semantics=("parallel", "parallel"),
        vmem_limit_bytes=_VMEM_BYTES)


def _specs(seq: int, d: int, taps: int, wide: int | None = None):
    """BlockSpecs over the grid (batch, tiles of ``ROWS`` rows) of arrays
    ``wide`` (3 d, if not given) and d channels wide, and of ``taps`` rows of
    weights."""
    ratio, halos = ROWS // HALO, seq // HALO
    wide = 3 * d if wide is None else wide
    return {
        "wide": pl.BlockSpec((None, ROWS, wide), lambda b, s: (b, s, 0)),
        "own": pl.BlockSpec((None, ROWS, d), lambda b, s: (b, s, 0)),
        # The HALO rows before the tile (at a sequence's first tile its own
        # first rows: masked in the kernel) and, of chunk ``lane`` or of
        # every column, after it.
        "before": pl.BlockSpec(
            (None, HALO, wide),
            lambda b, s: (b, jnp.maximum(s * ratio - 1, 0), 0)),
        "after": lambda lane: pl.BlockSpec(
            (None, HALO, d),
            lambda b, s: (b, jnp.minimum((s + 1) * ratio, halos - 1), lane)),
        "wide_after": pl.BlockSpec(
            (None, HALO, wide),
            lambda b, s: (b, jnp.minimum((s + 1) * ratio, halos - 1), 0)),
        "taps": pl.BlockSpec((taps, d), lambda b, s: (0, 0)),
        "dw": pl.BlockSpec((None, None, _DW_ROWS, d),
                           lambda b, s: (b, s, 0, 0)),
    }


def _forward(bcx, w):
    batch, seq, wide = bcx.shape
    d, taps = wide // 3, w.shape[0]
    spec = _specs(seq, d, taps)
    return pl.pallas_call(
        functools.partial(_fwd_kernel, d=d, taps=taps),
        grid=(batch, seq // ROWS),
        in_specs=[spec["wide"], spec["before"], spec["taps"]],
        out_specs=spec["own"],
        out_shape=jax.ShapeDtypeStruct((batch, seq, d), bcx.dtype),
        compiler_params=_params(),
        interpret=_interpret(),
        name="short_conv_fwd",
    )(bcx, bcx, w.astype(F32))


def _backward(bcx, w, dy):
    """(dbcx [batch, S, 3 d] in ``bcx``'s dtype, dw [K, d] float32)."""
    batch, seq, wide = bcx.shape
    d, taps = wide // 3, w.shape[0]
    spec = _specs(seq, d, taps)
    dbcx, dw = pl.pallas_call(
        functools.partial(_bwd_kernel, d=d, taps=taps),
        grid=(batch, seq // ROWS),
        in_specs=[spec["wide"], spec["before"], spec["after"](1),
                  spec["own"], spec["after"](0), spec["taps"]],
        out_specs=[spec["wide"], spec["dw"]],
        out_shape=[jax.ShapeDtypeStruct(bcx.shape, bcx.dtype),
                   jax.ShapeDtypeStruct(
                       (batch, seq // ROWS, _DW_ROWS, d), F32)],
        compiler_params=_params(),
        interpret=_interpret(),
        name="short_conv_bwd",
    )(bcx, bcx, bcx, dy, dy, w.astype(F32))
    return dbcx, dw.sum((0, 1))[:taps]


@jax.custom_vjp
def _kernels(bcx, w):
    return _forward(bcx, w)


def _kernels_fwd(bcx, w):
    return _forward(bcx, w), (bcx, w)


def _kernels_bwd(residuals, dy):
    bcx, w = residuals
    dbcx, dw = _backward(bcx, w, dy)
    return dbcx, dw.astype(w.dtype)


_kernels.defvjp(_kernels_fwd, _kernels_bwd)


def short_conv(bcx, w):
    """y [batch, S, d] = ``C * conv(B * x)`` of the function at the top of
    this file: ``bcx`` [batch, S, 3 d] (one projection's output, the chunks
    B, C, x in that order), ``w`` [K, d]; y in ``bcx``'s dtype, products and
    the K-term sum in float32. The kernels where the shapes tile, else
    ``short_conv_xla``."""
    seq, d, taps = bcx.shape[1], bcx.shape[2] // 3, w.shape[0]
    if seq % ROWS or d % 128 or not 1 <= taps <= _DW_ROWS:
        return short_conv_xla(bcx, w)
    with jax.named_scope("short_conv_kernels"):
        return _kernels(bcx, w)


# -- silu(b + conv(x)): the short convolution in front of a recurrence ------

def _pre(w, reached, taps: int):
    """b + sum_k w_k x_(t-K+1+k) from ``reached[j]`` = x_(t-j); ``w`` holds
    the taps and, if it has a row more, the bias under them."""
    z = sum(w[k:k + 1] * reached[taps - 1 - k] for k in range(taps))
    return z + w[taps:taps + 1] if w.shape[0] > taps else z


def _chunks(ref, start: int, body):
    """``body(own, at)`` for every ``_SILU_LANES`` of ``ref``'s columns in
    turn, as a loop (one copy of the body in the kernel and in the trace,
    not thirty-two): ``own`` the columns in ``ref``, ``at`` the same ones
    ``start`` further on."""
    step = _SILU_LANES

    def one(c, carry):
        body(pl.ds(pl.multiple_of(c * step, step), step),
             pl.ds(pl.multiple_of(start + c * step, step), step))
        return carry

    jax.lax.fori_loop(0, ref.shape[1] // step, one, None)


def _silu_fwd_kernel(x_ref, before_ref, w_ref, y_ref, *, start: int,
                     taps: int):
    first = pl.program_id(1) == 0

    def chunk(own, at):
        x = x_ref[:, at].astype(F32)
        before = jnp.where(first, 0.0, before_ref[:, at].astype(F32))
        pre = _pre(w_ref[:, own],
                   [_rows_before(x, before, j) for j in range(taps)], taps)
        y_ref[:, own] = (pre * jax.nn.sigmoid(pre)).astype(y_ref.dtype)

    _chunks(y_ref, start, chunk)


def _silu_bwd_kernel(x_ref, before_ref, after_ref, dy_ref, dy_after_ref,
                     w_ref, dx_ref, dw_ref, *, start: int, taps: int):
    first = pl.program_id(1) == 0
    last = pl.program_id(1) == pl.num_programs(1) - 1
    rows = x_ref.shape[0]

    def chunk(own, at):
        x, w = x_ref[:, at].astype(F32), w_ref[:, own]

        def dz_of(reached, dy):
            """dy * silu'(pre), pre formed again."""
            pre = _pre(w, reached, taps)
            s = jax.nn.sigmoid(pre)
            return dy.astype(F32) * s * (1.0 + pre * (1.0 - s))

        before = jnp.where(first, 0.0, before_ref[:, at].astype(F32))
        reached = [_rows_before(x, before, j) for j in range(taps)]
        dz = dz_of(reached, dy_ref[:, own])
        # dz of the rows after the tile needs their pre, whose taps reach
        # back into the tile: the seam of its last rows and the halo's x.
        seam = jnp.concatenate(
            [x[rows - HALO:], after_ref[:, at].astype(F32)], axis=0)
        after = jnp.where(last, 0.0, dz_of(
            [seam[HALO:]] + [pltpu.roll(seam, j, 0)[HALO:]
                             for j in range(1, taps)], dy_after_ref[:, own]))
        dx_ref[:, own] = sum(
            w[k:k + 1] * _rows_after(dz, after, taps - 1 - k)
            for k in range(taps)).astype(dx_ref.dtype)
        # Row k of the block is tap k's partial sum, row K the bias's.
        row = jax.lax.broadcasted_iota(
            jnp.int32, (_DW_ROWS, _SILU_LANES), 0)
        sums = [dz * reached[taps - 1 - k] for k in range(taps)] + [dz]
        dw_ref[:, own] = sum(
            jnp.where(row == k, sums[k].sum(0, keepdims=True), 0.0)
            for k in range(w.shape[0]))

    _chunks(dx_ref, start, chunk)


@functools.partial(jax.custom_vjp, nondiff_argnums=(2, 3, 4))
def _silu_kernels(x, wb, start, width, taps):
    batch, seq, wide = x.shape
    spec = _specs(seq, width, wb.shape[0], wide)
    return pl.pallas_call(
        functools.partial(_silu_fwd_kernel, start=start, taps=taps),
        grid=(batch, seq // ROWS),
        in_specs=[spec["wide"], spec["before"], spec["taps"]],
        out_specs=spec["own"],
        out_shape=jax.ShapeDtypeStruct(x.shape[:2] + (width,), x.dtype),
        compiler_params=_params(),
        interpret=_interpret(),
        name="conv_silu_fwd",
    )(x, x, wb)


def _silu_kernels_fwd(x, wb, start, width, taps):
    return _silu_kernels(x, wb, start, width, taps), (x, wb)


def _silu_kernels_bwd(start, width, taps, residuals, dy):
    """(dx over all of ``x``'s columns, zeros outside the pass's own; the
    taps' and the bias's gradient as they lie in ``wb``)."""
    x, wb = residuals
    batch, seq, wide = x.shape
    spec = _specs(seq, width, wb.shape[0], wide)
    dx, dw = pl.pallas_call(
        functools.partial(_silu_bwd_kernel, start=start, taps=taps),
        grid=(batch, seq // ROWS),
        in_specs=[spec["wide"], spec["before"], spec["wide_after"],
                  spec["own"], spec["after"](0), spec["taps"]],
        out_specs=[spec["own"], spec["dw"]],
        out_shape=[jax.ShapeDtypeStruct(dy.shape, x.dtype),
                   jax.ShapeDtypeStruct(
                       (batch, seq // ROWS, _DW_ROWS, width), F32)],
        compiler_params=_params(),
        interpret=_interpret(),
        name="conv_silu_bwd",
    )(x, x, x, dy, dy, wb)
    beside = (start, x.shape[2] - start - width)
    return (jnp.pad(dx, ((0, 0), (0, 0), beside)) if any(beside) else dx,
            dw.sum((0, 1))[:wb.shape[0]])


_silu_kernels.defvjp(_silu_kernels_fwd, _silu_kernels_bwd)


def conv_silu(x, w, b=None, start: int = 0, width: int | None = None):
    """y [batch, S, width] = ``silu(b + conv(x))`` over columns ``start ..
    start + width`` (all, if not given) of ``x`` [batch, S, W], read where
    they lie: taps ``w`` [K, width], bias ``b`` [width] or None; y in ``x``'s
    dtype, products, the K-term sum and the SiLU in float32. The kernels
    where the shapes tile (S a multiple of ``ROWS``, ``start`` and ``width``
    of 128, the taps and the bias at most ``_DW_ROWS`` rows), else
    ``conv_silu_xla`` on the slice."""
    width = x.shape[2] - start if width is None else width
    wb = w if b is None else jnp.concatenate([w, b[None]])
    if x.shape[1] % ROWS or start % 128 or width % 128 \
            or wb.shape[0] > _DW_ROWS:
        return conv_silu_xla(x[..., start:start + width], w, b)
    with jax.named_scope("conv_silu_kernels"):
        return _silu_kernels(x, wb.astype(F32), start, width, w.shape[0])
