"""The double-gated short convolution that is a layer's sequence mixer
(LFM2's ``Lfm2ShortConv``), as one fused Pallas TPU pass each way and a
plain ``jax.numpy`` form of the same function.

One projection gives three chunks of d channels a token, ``bcx = B | C |
x``; depthwise over the d channels, causal, with K taps ``w`` [K, d] and
zeros before the first token::

    z_t = sum_{k=0..K-1} w_k (B * x)_(t-K+1+k)
    y_t = C_t * z_t

No activation, no bias. Everything is elementwise but the K-term sum along
S, so the floor is bytes: forward three chunks read and one written. As XLA
operations (``short_conv_xla`` on ``causal_conv``, which ``lm.causal_conv``
hands to granite's and Kimi's layers too) it is float32 copies of the
chunks, a pad and a pass a tap.

The kernels read ``bcx`` [batch, S, 3 d] where the projection left it (no
split copies): a grid step is ``ROWS`` whole rows of one sequence, all 3 d
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

``short_conv`` is the one entry: the kernels where the shapes tile (S a
multiple of ``ROWS``, d of 128, K at most 8), else ``short_conv_xla``, which
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


def _specs(seq: int, d: int, taps: int):
    """BlockSpecs over the grid (batch, tiles of ``ROWS`` rows)."""
    ratio, halos = ROWS // HALO, seq // HALO
    return {
        "wide": pl.BlockSpec((None, ROWS, 3 * d), lambda b, s: (b, s, 0)),
        "own": pl.BlockSpec((None, ROWS, d), lambda b, s: (b, s, 0)),
        # The HALO rows before the tile (at a sequence's first tile its own
        # first rows: masked in the kernel) and, of chunk ``lane``, after it.
        "before": pl.BlockSpec(
            (None, HALO, 3 * d),
            lambda b, s: (b, jnp.maximum(s * ratio - 1, 0), 0)),
        "after": lambda lane: pl.BlockSpec(
            (None, HALO, d),
            lambda b, s: (b, jnp.minimum((s + 1) * ratio, halos - 1), lane)),
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
