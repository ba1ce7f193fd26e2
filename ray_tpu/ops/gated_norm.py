"""The gate and the RMSNorm behind a recurrence as one fused Pallas TPU pass
each way, and a plain ``jax.numpy`` form of the same function.

A state-space or delta-rule layer ends with its recurrence's output ``x``
gated by a second projection ``z`` and brought to unit mean square over a
group of its channels, with a learned ``scale``: [group], one vector that
every group shares, or [width], a value a channel of the row. Three published
layers, two orders of the same three steps::

    gate first, a SiLU, one group of the whole row (Mamba-2's
    ``MambaRMSNormGated``, granite's):
        pre = x * silu(z) ;  out = pre * rsqrt(mean(pre^2) + eps) * scale
    gate first, a SiLU, statistics over each of the row's groups and a scale
    as wide as the row (Mamba-2's own ``RMSNormGated(group_size = d_inner /
    n_groups)``, Nemotron-H's: 8 groups of 512 of 4096):
        pre = x * silu(z) ;  out = pre * rsqrt(mean_group(pre^2) + eps) * scale
    norm first, a sigmoid, a group a head (Kimi Delta Attention's
    ``FusedRMSNormGated``):
        out = x * rsqrt(mean_group(x^2) + eps) * scale * sigmoid(z)

``gate_first`` and ``activation`` say which; the group is ``scale``'s length
unless ``group`` says otherwise, and ``scale`` is then as long as the group
or as the row. Everything is elementwise but a sum over a group's lanes, so
the floor is bytes: the forward reads two arrays and writes one, the backward
reads three and writes two. As XLA operations (``gated_norm_xla``) the chain
runs in float32 through HBM several times each way.

A grid step is ``ROWS`` whole rows of one sequence, worked through a group at
a time and, within a group, ``_LANES`` channels at a time in float32: a
first walk over the group forms ``pre`` (kept in VMEM) and its sum of
squares, a second writes the result. ``z`` may be the first ``width``
columns of a wider array (granite's z lies before xBC and dt in one
projection's output) and is read where it lies. Nothing but ``out`` is
written: the backward takes the operands and ``d out``, forms ``pre`` and
``rstd`` again, and with ``t`` the cotangent of the normed value (``d out``,
times the gate if it comes after), ``s = t * scale``::

    d pre   = rstd * (s - prehat * mean(s * prehat))     prehat = pre * rstd
    d scale = sum over rows (and groups) of t * prehat
    gate first:  dx = d pre * act(z) ;  dz = d pre * x * act'(z)
    norm first:  dx = d pre ;           dz = d out * prehat * scale * act'(z)

``d scale`` leaves the kernel as one float32 [8, width] partial sum a grid
step and is added up outside.

``gated_norm`` is the entry: the kernels where the shapes tile (S a multiple
of ``ROWS``, the group a multiple of 128 that divides the width), else the
``jax.numpy`` form, which is also the
kernels' oracle in the tests. On backends other than the TPU the kernels run
in interpreter mode.
"""

from __future__ import annotations

import functools
import importlib
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

F32 = jnp.float32
#: Rows of a sequence a grid step takes: a float32 value of [ROWS, _LANES]
#: is sixteen registers, and the backward's two running sums leave half the
#: register file to its body (the backward at [32768, 4096]: 2.41 ms at 256
#: rows, 2.1 at 128; a delta-rule layer's group a head reads the same at
#: both).
ROWS = 128
#: Channels the kernels work through at a time: a lane tile.
_LANES = 128
#: Partial sums of ``d scale`` a grid step writes: a sublane tile of float32.
_DSCALE_ROWS = 8
#: Five blocks of whole rows double-buffered (1 MB each at 4096 channels)
#: and the backward's two float32 ones of a group (2 MB each) are 14 MB of
#: the 16 MB the compiler scopes a kernel by default, before the values a
#: loop's body holds.
_VMEM_BYTES = 32 * 1024 * 1024


def _interpret() -> bool:
    """The flash kernels' answer (interpreter mode off the TPU), asked of
    that module each time so that one switch steers every kernel of
    ``ops/``."""
    return importlib.import_module(
        "ray_tpu.ops.flash_attention")._interpret()


# -- the same function in jax.numpy ----------------------------------------

def gated_norm_xla(x, z, scale, eps, gate_first: bool, activation: str,
                   group: Optional[int] = None):
    """The function at the top of this file as XLA operations, any shape:
    ``x``, ``z`` [..., width], the groups of ``group`` channels (``scale``'s
    length if not given) side by side along the last axis, ``scale``
    [group] or [width]; gate, statistics and products in float32, the
    result in ``x``'s dtype. The kernels' oracle and their fallback."""
    gate = getattr(jax.nn, activation)(z.astype(F32))
    pre = x.astype(F32)
    if gate_first:
        pre = pre * gate
    group = group or scale.shape[0]
    groups = pre.reshape(pre.shape[:-1] + (-1, group))
    out = (groups * jax.lax.rsqrt((groups ** 2).mean(-1, keepdims=True) + eps)
           * scale.astype(F32).reshape(-1, group)).reshape(pre.shape)
    return (out if gate_first else out * gate).astype(x.dtype)


# -- kernels ----------------------------------------------------------------

def _gate(z, silu: bool):
    """(act(z), act'(z)) in float32: a SiLU or a sigmoid. The sigmoid as a
    tanh: one transcendental and no divide (the backward at [32768, 4096]:
    2.41 ms where ``jax.nn.sigmoid`` read 2.92)."""
    sig = 0.5 * jnp.tanh(0.5 * z) + 0.5
    if silu:
        return z * sig, sig * (1.0 + z * (1.0 - sig))
    return sig, sig * (1.0 - sig)


def _mean(sums, group: int):
    """[ROWS, 1]: the mean over a group of ``group`` channels from its sums
    a lane, ``sums`` [ROWS, _LANES]."""
    return sums.sum(-1, keepdims=True) * (1.0 / group)


def _walk(groups: int, chunks: int, sums: int, first, between, second):
    """For every group of a tile in turn: ``acc = first(at, own, acc)`` over
    its chunks of ``_LANES`` channels (``at`` the chunk's lanes in the row,
    ``own`` in the group; ``acc`` starts as ``sums`` [ROWS, _LANES] zeros),
    ``stats = between(acc)``, then ``second(at, own, stats)`` over the
    chunks again. Loops, so that the kernel holds one copy of each body."""
    def lanes(group, chunk):
        own = pl.multiple_of(chunk * _LANES, _LANES)
        at = pl.multiple_of((group * chunks + chunk) * _LANES, _LANES)
        return pl.ds(at, _LANES), pl.ds(own, _LANES)

    def one_group(group, carry):
        acc = jax.lax.fori_loop(
            0, chunks, lambda c, acc: first(*lanes(group, c), acc),
            (jnp.zeros((ROWS, _LANES), F32),) * sums)
        stats = between(acc)
        jax.lax.fori_loop(
            0, chunks,
            lambda c, carry: second(*lanes(group, c), stats), None)
        return carry

    jax.lax.fori_loop(0, groups, one_group, None)


def _scale_lanes(scale_ref, group: int):
    """(at, own) -> which of the two a chunk's scale is read at: its lanes
    in the row where ``scale_ref`` [1, width] holds a value a channel, those
    in its group where [1, group] is one vector for every group."""
    shared = scale_ref.shape[1] == group
    return lambda at, own: own if shared else at


def _fwd_kernel(x_ref, z_ref, scale_ref, out_ref, pre_ref, *, eps: float,
                gate_first: bool, silu: bool):
    group = pre_ref.shape[1]
    scale_at = _scale_lanes(scale_ref, group)

    def first(at, own, acc):
        pre = x_ref[:, at].astype(F32)
        if gate_first:
            pre = pre * _gate(z_ref[:, at].astype(F32), silu)[0]
        pre_ref[:, own] = pre
        return (acc[0] + pre * pre,)

    def rstd_of(acc):
        return jax.lax.rsqrt(_mean(acc[0], group) + eps)

    def second(at, own, rstd):
        out = pre_ref[:, own] * rstd * scale_ref[:, scale_at(at, own)]
        if not gate_first:
            out = out * _gate(z_ref[:, at].astype(F32), silu)[0]
        out_ref[:, at] = out.astype(out_ref.dtype)

    _walk(x_ref.shape[1] // group, group // _LANES, 1, first, rstd_of,
          second)


def _bwd_kernel(x_ref, z_ref, scale_ref, dout_ref, dx_ref, dz_ref,
                dscale_ref, pre_ref, s_ref, *, eps: float, gate_first: bool,
                silu: bool):
    group = pre_ref.shape[1]
    scale_at = _scale_lanes(scale_ref, group)

    def first(at, own, acc):
        pre, t = x_ref[:, at].astype(F32), dout_ref[:, at].astype(F32)
        gate = _gate(z_ref[:, at].astype(F32), silu)[0]
        if gate_first:
            pre = pre * gate
        else:
            t = t * gate
        s = t * scale_ref[:, scale_at(at, own)]
        pre_ref[:, own], s_ref[:, own] = pre, s
        return acc[0] + pre * pre, acc[1] + s * pre

    def stats(acc):
        """(a, b) with d pre = a * s - b * pre: rstd and rstd^3 mean(s
        pre)."""
        rstd = jax.lax.rsqrt(_mean(acc[0], group) + eps)
        return rstd, rstd * rstd * rstd * _mean(acc[1], group)

    def second(at, own, ab):
        rstd, b = ab
        pre, scale = pre_ref[:, own], scale_ref[:, scale_at(at, own)]
        dpre = rstd * s_ref[:, own] - b * pre
        gate, slope = _gate(z_ref[:, at].astype(F32), silu)
        dout = dout_ref[:, at].astype(F32)
        prehat = pre * rstd
        if gate_first:
            dx = dpre * gate
            dz = dpre * x_ref[:, at].astype(F32) * slope
            t = dout
        else:
            dx = dpre
            dz = dout * prehat * scale * slope
            t = dout * gate
        dx_ref[:, at] = dx.astype(dx_ref.dtype)
        dz_ref[:, at] = dz.astype(dz_ref.dtype)
        # Row 0 of the block is the tile's partial sum; the others stay zero.
        row = jax.lax.broadcasted_iota(jnp.int32, (_DSCALE_ROWS, _LANES), 0)
        dscale_ref[:, at] = jnp.where(
            row == 0, (t * prehat).sum(0, keepdims=True), 0.0)

    _walk(x_ref.shape[1] // group, group // _LANES, 2, first, stats, second)


def _call(kernel, name, operands, outs, scratch: int, group: int, **static):
    """``kernel`` over the grid (batch, tiles of ``ROWS`` rows): ``operands``
    are ``x``, ``z`` (its first columns read), ``scale`` [1, group] or [1,
    width] and any number of arrays shaped like ``x``; ``outs`` names the
    outputs, "rows" such an array and "dscale" the partial sums; ``scratch``
    float32 copies of a group's rows in VMEM."""
    x, scale = operands[0], operands[2]
    batch, seq, width = x.shape
    rows = pl.BlockSpec((None, ROWS, width), lambda b, s: (b, s, 0))
    kinds = {
        "rows": (rows, jax.ShapeDtypeStruct(x.shape, x.dtype)),
        "dscale": (pl.BlockSpec((None, None, _DSCALE_ROWS, width),
                                lambda b, s: (b, s, 0, 0)),
                   jax.ShapeDtypeStruct(
                       (batch, seq // ROWS, _DSCALE_ROWS, width), F32)),
    }
    return pl.pallas_call(
        functools.partial(kernel, **static),
        grid=(batch, seq // ROWS),
        in_specs=[rows, rows, pl.BlockSpec(scale.shape, lambda b, s: (0, 0))]
        + [rows] * (len(operands) - 3),
        out_specs=[kinds[out][0] for out in outs],
        out_shape=[kinds[out][1] for out in outs],
        scratch_shapes=[pltpu.VMEM((ROWS, group), F32)] * scratch,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel"),
            vmem_limit_bytes=_VMEM_BYTES),
        interpret=_interpret(),
        name=name,
    )(*operands)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6))
def _kernels(x, z, scale, eps, gate_first, silu, group):
    return _call(_fwd_kernel, "gated_norm_fwd",
                 (x, z, scale.astype(F32)[None]), ["rows"], 1, group,
                 eps=eps, gate_first=gate_first, silu=silu)[0]


def _kernels_fwd(x, z, scale, eps, gate_first, silu, group):
    return _kernels(x, z, scale, eps, gate_first, silu, group), (x, z, scale)


def _kernels_bwd(eps, gate_first, silu, group, operands, dout):
    """(dx, dz over all of ``z``'s columns, zeros beside the gate's own,
    d scale)."""
    x, z, scale = operands
    dx, dz, dscale = _call(
        _bwd_kernel, "gated_norm_bwd", (x, z, scale.astype(F32)[None], dout),
        ["rows", "rows", "dscale"], 2, group,
        eps=eps, gate_first=gate_first, silu=silu)
    beside = z.shape[2] - x.shape[2]
    return (dx,
            jnp.pad(dz, ((0, 0), (0, 0), (0, beside))) if beside else dz,
            dscale.sum((0, 1, 2)).reshape(-1, scale.shape[0]).sum(0).astype(
                scale.dtype))


_kernels.defvjp(_kernels_fwd, _kernels_bwd)


def gated_norm(x, z, scale, eps: float, *, gate_first: bool,
               activation: str, group: Optional[int] = None):
    """out [batch, S, width] of the function at the top of this file: ``x``
    [batch, S, width], the gate's argument the first ``width`` columns of
    ``z`` [batch, S, >= width], read where they lie, the groups of ``group``
    channels (``scale``'s length if not given) side by side along the
    width, ``scale`` [group] (every group's) or [width] (a value a channel);
    ``activation`` is ``"silu"`` or ``"sigmoid"``, and ``gate_first`` says
    whether the gate comes before the norm or after it. out in ``x``'s
    dtype, ``dz`` in ``z``'s (which the kernels take to be the same), gate,
    statistics and products in float32. The kernels where the shapes tile
    (S a multiple of ``ROWS``, the group of 128), else ``gated_norm_xla`` on
    the slice."""
    width, group = x.shape[2], group or scale.shape[0]
    if activation not in ("silu", "sigmoid"):
        raise ValueError(f"activation {activation!r}: silu or sigmoid")
    if width % group or scale.shape[0] not in (group, width):
        raise ValueError(f"a scale of {scale.shape[0]} for groups of {group} "
                         f"of {width} channels")
    if x.shape[1] % ROWS or group % _LANES or z.dtype != x.dtype:
        return gated_norm_xla(x, z[..., :width], scale, eps, gate_first,
                              activation, group)
    with jax.named_scope("gated_norm_kernels"):
        return _kernels(x, z, scale, float(eps), gate_first,
                        activation == "silu", group)
