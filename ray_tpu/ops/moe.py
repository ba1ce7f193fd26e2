"""A dropless routed-expert layer: sort, group sizes, ragged matmuls.

``routed_experts`` is the routed half of a DeepSeek-V3-style expert layer
(``lm.expert_ffn`` adds the shared expert). An expert is a SwiGLU, or, with
no gate matrix, two matrices with the activation between them
(``activation``: Nemotron-H's squared ReLU, ``"relu2"``). Every token's
``top_k`` assignments are computed: there is no capacity and nothing is
dropped, and no tensor of ``tokens x experts x capacity`` exists at any size.
The ``tokens x top_k`` assignments are sorted by expert (a stable sort), each
expert multiplies its own contiguous group of rows (``grouped_matmul``), and
the rows go back to token order for the weighted sum.

The grouped matmul is JAX's Pallas kernel set ``megablox`` (``gmm`` and, for
the weights' gradient, ``tgmm``) wherever the rows are a multiple of 128 and
both widths multiples of 64 of at least a lane tile, and
``jax.lax.ragged_dot`` elsewhere (tiny test widths). Both cost the groups'
FLOPs, not the dense ``experts x`` product. On the v5e, at Moonlight's widths
(98,304 rows, 2048 x 1408, 64 groups), the three products of an expert SwiGLU
forward and backward took 95.9 ms with ``ragged_dot`` (XLA's own grouped
kernel, 53 TFLOP/s) and 52.8 ms with ``megablox`` at tiles of 512 x 512 x
1408 (97 TFLOP/s): PERF.md, PR 25. **Which widths take which path.** A width
that is a multiple of 128 is tiled by its widest divisor up to
``_TILE_N_MAX`` (its contraction by ``_TILE_K``, the last tile masked where
512 does not divide it: 2304, 2688). A width that is no multiple of 128
(Nemotron-H's experts of 1856 = 14.5 x 128) takes the same kernels with an
irregular last tile (``_tile_n``: an output of 1856 as two tiles of 1024,
the second cut at 832 columns; a contraction of 1856 as four of 512, the
last masked past 320). Measured on the v5e at that cell's rows (a buffer of
98,304 rows, 49,152 of them in 32 held groups; an expert's two products at
2688 x 1856 with the squared ReLU between them, forward and backward):
26.4 ms so (27.3 with tiles of 640, 28.4 with 1408), 25.3 ms with the
weights laid out 1920 wide behind 64 zero columns and rows (exact, 3.4 %
more product, and another parameter shape than the published one), 180.4 ms
with ``ragged_dot``; forward alone 11.1, 10.2 and 65.3 ms (88, 96 and 15
TFLOP/s of the 0.98 TFLOP the held rows need): PERF.md, PR 62.

**The whole layer's row passes** (every expert held: ``tokens x top_k``
rows, all of them some expert's). No pass scatters and none selects. The
sort gives ``order``, a second sort its inverse ``place``. Every row pass
is a gather that promises its indices in bounds (``_rows``): ``jnp.take``'s
default compares each index with the bounds and selects over the whole
``[tokens x top_k, d]`` result.
The backward pass of both ways is written out. ``_dispatch``'s is the
gather by ``place`` and a float32 sum over the ``top_k`` slabs, where
autodiff would emit a scatter-add. The way back to tokens,
``_all_to_tokens`` (the un-sort and the weighted sum, a token's terms in
float32 in the order of k), keeps the *sorted* rows ``out`` and never the
un-sorted ones: its backward gathers ``g`` [tokens, d] by ``order %
tokens`` and multiplies by the sorted weights on the gather's result (``d
out``, in sorted order, with no ``[top_k, tokens, d]`` slabs of ``w * g``
to build and then permute), and takes ``d weights`` from ``(out *
g_rows).sum(-1)`` back to ``[top_k, tokens]``. So under a block's ``remat``
the second forward stops at the down product: nothing asks it for the
un-sort or the sum. It is the share's backward (``_buffer_backward``) in
form, and the two differ where their rows do. The whole layer holds every
row, so its ways to and from tokens are XLA's gathers, whose cost is that
of ``tokens x top_k`` rows either way; the share holds a fraction of them,
and its way back is the kernel ``moe_rows_to_tokens``, which costs by the
held row (0.1 us each: 13 ms at Mellum's 131,072 rows where the gather and
the sum take 6.7) and wins by skipping the rest. The whole layer's three
products stay under autodiff as ``megablox``'s own ``custom_vjp`` (the share
runs them under ``jax.vjp`` in a loop over buffers that autodiff must not
see; here there is one buffer and no loop to hide). What a gather costs is
decided by where its table lies: rows of a ``[tokens, d]`` table that the
compiler has placed in the chip's fast memory (``x`` for the dispatch,
``g`` for the way back's backward) come at the speed of the write, 0.9 ms
for 131,072 rows of 2304, rows of a ``[tokens x top_k, d]`` table come from
HBM one at a time, 4.9 ms; a ``while`` between a table's producer and its
gather (``jnp.searchsorted``'s default) sends the table back to HBM, which
is why the sizes are not searched for here. Measured on the v5e: PERF.md,
PR 59.

**The scalars ride sorts and comparisons**, in the whole layer and in a
share alike: none is gathered, searched for or scattered (a TPU serialises
a scatter, gathers 131,072 scalars in 0.9-1.7 ms and sorts as many pairs in
0.1; PERF.md, PRs 59 and 61). The groups' sizes are ``[experts, tokens x
top_k]`` comparisons summed in one fusion (``_group_sizes``). What follows
a permutation, the weights into expert order and ``d weights`` out of it,
rides a sort keyed by the inverse permutation (``_permuted``); a share does
both once a call, outside its loop over buffers, a buffer taking its slice
of the one and laying its rows into the other (``_laid``). ``route`` picks
its ``top_k`` scores as a sum over E of one score and zeros, whose
transpose is a sum over K where ``take_along_axis``'s was the one scatter
of an expert layer.

**Held experts.** A chip that shares a layer's experts with others holds a
contiguous run of them, ``held = (first, count)``, and gives
``routed_experts`` the weights of those alone. The router keeps its whole
width: scores, ``top_k`` and the normalisation are over all the experts.
Only the assignments to held experts are multiplied (the grouped matmul
starts at group ``first`` and computes ``count`` groups; the other rows
come out zero), and the result is ``sum_{i picked and held} w_i
Expert_i(x)``: this chip's part of the layer's sum. What the absent
experts would have added is left out, and nothing stands in for the other
chips or for an exchange with them: the share is not expert parallelism,
which a mesh with ``ep`` > 1 would ask for and no model here implements.
Dropless on the share: every assignment to a held expert is computed, and
``aux["group_sizes"]`` counts the rows that were placed and multiplied.

**The share moves only its own rows.** The sort's key is the expert if it is
held and one past the last held expert if not, so the assignments to held
experts are the first rows of the order, and a buffer is a slice of it (and
of whatever else lies in that order: the weights, for the backward pass).
The buffer has ``_held_bound`` rows: twice what even routing would give the
held experts, ``2 * tokens * top_k * count / experts`` rounded up to the row
tile, computed from what ``routed_experts`` sees and set nowhere. The
gather, the grouped matmuls (three of a SwiGLU, two of an expert without a
gate; the rows of the buffer past the held groups are one more group that
no weights multiply: exact zeros), the activation and the way back run over
the buffer, not over ``tokens x top_k``.
The first buffer is computed on every routing; one that gives the held
experts more rows than it has takes the next rows in a second buffer, and
so on, ``ceil(asked / bound)`` in all: a loop that does not run on a
routing within the bound, and nothing is cut on any routing.

**The way back to tokens reads only the rows the buffer holds.**
``out[t] = sum_k [w[k, t] *] rows[at[k * T + t]]`` in float32, a token's
terms in the order ``_all_to_tokens`` sums them and an assignment not in
the buffer adding nothing, is the Pallas kernel ``moe_rows_to_tokens``
wherever the shapes tile (``_token_tile``: rows of bfloat16 or float32 whose
width is a multiple of 128, tokens a multiple of 128), in the forward's
weighted sum and in the backward's ``d x``. A grid step is a tile of tokens
whose float32 rows are the output block; the buffer stays in HBM, and a
held row is fetched by an asynchronous copy as the HBM tile of 8 rows it
lies in (what a DMA may slice; bfloat16 read as the 32-bit words that pack
two rows), 64 fetches ahead of the sum. Which rows a tile needs it reads
off one bit an assignment, made outside by one elementwise pass over
``at``, so the walk costs a loop step a held row and one a word of 32
assignments: a tile with nothing held writes zeros and fetches nothing.
Elsewhere (the tiny presets' widths) it is ``_to_tokens_xla``, ``top_k``
gathers of ``tokens`` rows each, of which all but the held share are exact
zeros: the kernel's oracle, to the bit. Measured on the v5e on the call
alone at LFM2's shapes (32,768 x 4 assignments, 16,000 held, rows of 2048):
6.8 ms the gathers, 1.6 ms the kernel; 3.5 ms with the tile's ``at`` walked
entry by entry, and as fast with the held rows sorted by token in XLA, at 7
to 11 s of compile a sort: PERF.md, PR 43. (PR 36 had measured the form
XLA allows, a second sort of the buffer's rows by token with a within-run
sum and one ``[tokens, d]`` gather, slower than the gathers: 44.4 against
38.4 ms a layer forward and backward at 16,384 x 8 rows of 2304 with 32 of
256 held, where all ``tokens x top_k`` rows took 51.1: PERF.md, PR 36.)

Forward and backward are each those buffers (``_held_experts`` is a
``custom_vjp``: autodiff sees neither the loop nor its trip count, so the
kernel needs no derivative of its own, and the backward pass multiplies a
buffer's rows again where storing them would keep every buffer's residuals
alive: under a block's ``remat`` that is the second forward the block would
run anyway), and no pass scatters.
"""

from __future__ import annotations

import importlib
from functools import partial
from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

# The module, not the function ``ray_tpu.ops`` re-exports under its name:
# one ``_interpret`` says for every kernel of this package whether a TPU
# is there.
_flash = importlib.import_module("ray_tpu.ops.flash_attention")

#: Rows and contraction depth of a ``megablox`` tile, and the widest output
#: tile: measured on the v5e at 98,304 x 2048 x 1408 (see the module text);
#: 1024 rows, or 1024 deep at this width, do not fit the scoped VMEM.
_TILE_M, _TILE_K, _TILE_N_MAX = 512, 512, 1408
#: What a width of the grouped product must be a multiple of to take the
#: kernels: half a lane tile, so that 1856 = 29 x 64 does.
_IRREGULAR = 64


def _picked_scores(scores, picked):
    """scores [T, E] at picked [T, K], as a sum over E of one score and
    zeros: ``take_along_axis``'s bits, and a transpose that is a sum over K
    where a gather's scatters. Behind a barrier, or XLA makes one sum over K
    and E of this and ``route``'s normalising sum: a token's K terms in
    another order, and the [T, K, E] pass twice."""
    hot = picked[..., None] == jnp.arange(scores.shape[-1])
    return jax.lax.optimization_barrier(
        jnp.where(hot, scores[:, None, :], 0).sum(-1))


def _tile_n(n: int) -> int:
    """The output tile of a grouped product ``n`` wide: the widest multiple
    of 128 up to ``_TILE_N_MAX`` that divides ``n``; of a width none divides
    (1856 = 14.5 x 128), the one that covers ``n`` in the fewest tiles with
    the least past the edge (1024: two tiles, the second cut at 832 columns,
    which ``megablox`` neither reads into a sum nor writes)."""
    tiles = range(min(n // 128 * 128, _TILE_N_MAX), 0, -128)
    return next((t for t in tiles if n % t == 0), None) or min(
        tiles, key=lambda t: (-(-n // t), -(-n // t) * t))


def grouped_matmul(rows, weights, group_sizes):
    """rows [M, k], sorted into ``len(group_sizes)`` contiguous groups, times
    each group's own weights [E, k, n] -> [M, n] in rows' dtype. Where there
    are more groups than weights, the weights are the first groups': only
    their rows are multiplied, the others come out zero. ``megablox`` where
    M is a multiple of 128 and both widths of ``_IRREGULAR`` and at least a
    lane tile (its last tile of k masked, its last tile of n cut at the
    edge), else ``jax.lax.ragged_dot``."""
    (m, k), n = rows.shape, weights.shape[-1]
    given = weights.shape[0]
    if m % 128 or min(k, n) < 128 or k % _IRREGULAR or n % _IRREGULAR:
        if group_sizes.shape[0] > given:
            # One stretch of rows after the last weights, against zeros.
            weights = jnp.concatenate(
                [weights, jnp.zeros((1, k, n), weights.dtype)])
            group_sizes = jnp.concatenate(
                [group_sizes[:given], group_sizes[given:].sum()[None]])
        return jax.lax.ragged_dot(rows, weights, group_sizes)
    from jax.experimental.pallas.ops.tpu.megablox import ops as megablox
    tile_m = next(t for t in (_TILE_M, 256, 128) if m % t == 0)
    return megablox.gmm(rows, weights, group_sizes, rows.dtype,
                        (tile_m, min(k // 128 * 128, _TILE_K), _tile_n(n)),
                        interpret=_flash._interpret())


def route(x, router, bias, top_k: int, scaling: float, normalize: bool,
          score: str = "sigmoid"
          ) -> Tuple[jax.Array, jax.Array, Optional[jax.Array]]:
    """Scores in float32 and the ``top_k`` experts of each token.

    x [T, d], router [d, E], bias [E] or None -> (picked [T, K] int32,
    weights [T, K] float32, picked mass [T] or None). ``score`` is the
    family's: ``"sigmoid"`` scores every expert by itself, and the experts
    are picked by ``score + bias`` (the correction bias of ``topk_method:
    noaux_tc``: selection only, no gradient); ``"softmax"`` scores them by a
    softmax over all E and picks by that alone (there is no bias term), and
    the third result is what of a token's probability its picked experts
    hold before any normalising (a gauge: no gradient). The weights are the
    picked scores themselves, normalised to sum to one when ``normalize``,
    times ``scaling``."""
    logits = jnp.dot(x.astype(jnp.float32), router.astype(jnp.float32),
                     precision=jax.lax.Precision.HIGHEST)
    mass = None
    if score == "softmax":
        if bias is not None:
            raise ValueError("a softmax router picks by its probabilities "
                             "alone: no bias")
        scores = jax.nn.softmax(logits, axis=-1)
        top, picked = jax.lax.top_k(scores, top_k)
        mass = jax.lax.stop_gradient(top.sum(-1))
    elif score == "sigmoid":
        scores = jax.nn.sigmoid(logits)
        _, picked = jax.lax.top_k(
            scores + jax.lax.stop_gradient(bias.astype(jnp.float32)), top_k)
    else:
        raise ValueError(f"score {score!r}: 'sigmoid' or 'softmax'")
    weights = _picked_scores(scores, picked)
    if normalize and top_k > 1:
        weights = weights / (weights.sum(-1, keepdims=True) + 1e-20)
    return picked, weights * scaling, mass


# Assignments are numbered choice-major: a = k * T + t. The [K * T, d] rows
# in that order are K whole [T, d] slabs, so going between the flat rows and
# the per-choice view is free; token-major [T, K, d] would pad K to the
# tile (6 -> 8) and turn every reshape into a copy.


def _rows(table, at):
    """table[at] by rows, every index promised in bounds (a gather with no
    select over its result)."""
    return table.at[at].get(mode="promise_in_bounds")


def _permuted(values, to):
    """out[to[i]] = values[i] for a permutation ``to``: a sort by ``to`` that
    carries the values. What a gather by the inverse permutation gives, and
    a TPU sorts 131,072 pairs in 0.1 ms where it gathers as many scalars in
    0.9-1.7 (PERF.md, PR 59)."""
    return jax.lax.sort((to, values), num_keys=1)[1]


def _group_sizes(key, count: int):
    """How many of ``key`` [K * T] are 0, 1, ... ``count - 1``, by [count,
    K * T] comparisons summed in one fusion (the module text)."""
    return (key == jnp.arange(count)[:, None]).sum(-1, dtype=jnp.int32)


@jax.custom_vjp
def _dispatch(x, order, place):
    """Rows of x [T, d] in expert order: x[order % T]."""
    return _rows(x, order % x.shape[0])


def _dispatch_fwd(x, order, place):
    return _dispatch(x, order, place), (place, x.shape[0])


def _dispatch_bwd(residuals, g):
    place, tokens = residuals
    back = _rows(g, place).reshape(-1, tokens, g.shape[-1])
    return back.astype(jnp.float32).sum(0).astype(g.dtype), None, None


_dispatch.defvjp(_dispatch_fwd, _dispatch_bwd)


@jax.custom_vjp
def _all_to_tokens(out, weights, order, place):
    """sum_k weights[k, t] * out[place[k * T + t]] in float32, a token's
    terms in the order of k -> [T, d] in out's dtype: every assignment's
    row, sorted by expert, back at its token. The K slabs of [T, d] are
    summed straight from the stored dtype, so no float32 copy of the
    [K * T, d] rows is stored; the backward pass reads the sorted rows and
    gathers ``g`` by token (the module text)."""
    tokens = weights.shape[1]
    flat = _rows(out, place)
    acc = sum(flat[k * tokens:(k + 1) * tokens].astype(jnp.float32)
              * weights[k][:, None] for k in range(weights.shape[0]))
    return acc.astype(out.dtype)


def _all_to_tokens_fwd(out, weights, order, place):
    return _all_to_tokens(out, weights, order, place), (
        out, weights, order, place)


def _all_to_tokens_bwd(residuals, g):
    out, weights, order, place = residuals
    g_rows = _rows(g, order % weights.shape[1]).astype(jnp.float32)
    w_rows = _permuted(weights.reshape(-1), place)
    d_out = (g_rows * w_rows[:, None]).astype(out.dtype)
    d_w_rows = (out.astype(jnp.float32) * g_rows).sum(-1)
    return d_out, _permuted(d_w_rows, order).reshape(weights.shape), None, None


_all_to_tokens.defvjp(_all_to_tokens_fwd, _all_to_tokens_bwd)


#: ``activation`` -> the function between an expert's two products.
ACTIVATIONS = {"silu": jax.nn.silu,
               "relu2": lambda x: jnp.square(jax.nn.relu(x))}


def _expert_hidden(rows, w_gate, w_up, group_sizes, activation: str):
    """Every expert's hidden activations on its own group of the rows:
    ``act(rows w_gate) * rows w_up`` (with a SiLU, a SwiGLU's), or, of an
    expert without a gate (``w_gate`` None), ``act(rows w_up)``."""
    act = ACTIVATIONS[activation]
    if w_gate is None:
        return act(grouped_matmul(rows, w_up, group_sizes))
    gate = grouped_matmul(rows, w_gate, group_sizes)
    up = grouped_matmul(rows, w_up, group_sizes)
    return act(gate) * up


def _expert_groups(rows, w_gate, w_up, w_down, group_sizes, activation: str):
    """Every expert on its own group of the rows; the rows of groups past
    the last weights come out exact zeros (``act(0)`` is 0 for both
    activations)."""
    return grouped_matmul(
        _expert_hidden(rows, w_gate, w_up, group_sizes, activation), w_down,
        group_sizes)


def _gauges(hidden, groups, activation: str):
    """What a forward counts beside its sum, of the rows of ``hidden`` that
    ``groups`` [held experts + 1] gives the held experts (all, if None).
    Of squared-ReLU experts ``computed``, those rows' hidden activations,
    and ``zeroed``, how many of them are exact zeros (what the ReLU leaves
    of every negative pre-activation), in one reduction; nothing of
    others."""
    if activation != "relu2":
        return {}
    held_rows = jnp.int32(hidden.shape[0]) if groups is None \
        else groups[:-1].sum()
    held = jnp.arange(hidden.shape[0])[:, None] < held_rows
    return {"zeroed": ((hidden == 0) & held).sum(dtype=jnp.int32),
            "computed": held_rows * hidden.shape[1]}


# -- a share of the experts: the held rows alone --------------------------


def _held_bound(tokens: int, top_k: int, count: int, n_experts: int) -> int:
    """Rows of the buffer the assignments to ``count`` held experts of
    ``n_experts`` are compacted into: twice their even share, in whole row
    tiles, and no more than all the assignments."""
    def tiles(rows):
        return -(-rows // _TILE_M) * _TILE_M
    return min(tiles(-(-2 * tokens * top_k * count // n_experts)),
               tiles(tokens * top_k))


def _buffers_needed(asked, bound: int):
    """Buffers of ``bound`` rows that hold ``asked`` rows: where both of
    ``_held_experts``' loops end."""
    return (asked + bound - 1) // bound


def _buffer(i, bound: int, sizes, *in_order):
    """Buffer ``i`` of the held rows: (its groups [count + 1]: the held
    experts' rows that lie in it, then the rows past them, which no expert
    is given; its ``bound`` rows of each of ``in_order``, arrays in expert
    order of whole buffers: ``order`` itself for the rows' assignments)."""
    lo = i * bound
    ends = jnp.cumsum(sizes)
    here = (jnp.clip(ends, lo, lo + bound)
            - jnp.clip(ends - sizes, lo, lo + bound))
    return (jnp.concatenate([here, (bound - here.sum())[None]]),
            *(jax.lax.dynamic_slice(a, (lo,), (bound,)) for a in in_order))


def _rows_or_zero(table, at):
    """table[at] by rows, and an exact zero where ``at`` is the table's
    length: no row, whatever row the gather read in its place."""
    length = table.shape[0]
    there = (at < length).reshape(at.shape + (1,) * (table.ndim - 1))
    return jnp.where(there, _rows(table, jnp.minimum(at, length - 1)), 0)


def _at(place, i, bound: int, groups):
    """Where in buffer ``i`` each of the K * T assignments sits, and ``bound``
    for one that is not among its held rows."""
    at = place - i * bound
    return jnp.where((at >= 0) & (at < groups[:-1].sum()), at, bound)


def _to_tokens_xla(rows, at, tokens: int, weights=None):
    """sum_k [weights[k, t] *] rows[at[k * T + t]] in float32 -> [T, d]: a
    buffer's rows [bound, d] summed into their tokens by gathers alone, a
    slab of T rows a choice and in the order ``_all_to_tokens`` sums them; an
    assignment that is not in the buffer adds an exact zero. The form of
    shapes that do not tile, and the kernel's oracle."""
    slabs = at.reshape(-1, tokens)
    return sum(
        _rows_or_zero(rows, slabs[k]).astype(jnp.float32)
        * (1.0 if weights is None else weights[k][:, None])
        for k in range(slabs.shape[0]))


#: ``moe_rows_to_tokens``: the tokens of a grid step, the first that divides
#: ``tokens`` (its float32 rows are the step's output block, twice in VMEM),
#: and the fetches in flight. Measured on the v5e at the three cells' shapes
#: (PERF.md, PR 43).
_TOKEN_TILES, _IN_FLIGHT = (512, 256, 128), 64
#: Rows of one tile of a 2-D array in HBM, whatever its dtype: what a DMA
#: may slice, so a row comes with the seven that share its tile.
_HBM_ROWS = 8
_VMEM_LIMIT = 64 * 1024 * 1024


def _token_tile(rows, at, tokens: int) -> Optional[int]:
    """The tokens a grid step of ``moe_rows_to_tokens`` takes, or None where
    the ``jax.numpy`` form runs: rows of bfloat16 or float32 whose width is
    a multiple of 128, a buffer of whole HBM tiles, ``tokens`` a multiple
    of the tile, the tile's float32 rows twice within half the stated VMEM,
    and the held bits of every assignment within SMEM (64 Ki words)."""
    bound, d = rows.shape
    if (rows.dtype not in (jnp.bfloat16, jnp.float32) or d % 128
            or bound % _HBM_ROWS or at.shape[0] > 32 * 65536):
        return None
    return next((tile for tile in _TOKEN_TILES if tokens % tile == 0
                 and 2 * tile * d * 4 <= _VMEM_LIMIT // 2), None)


def _rows_to_tokens_kernel(bits, at_ref, *refs, weighted: bool):
    """One tile of tokens: the output block is the accumulator, zeroed and
    then given, choice by choice and token by token, the rows ``bits`` says
    the buffer holds. ``bits`` [K * T / 32] (SMEM, whole): bit ``t % 32``
    of word ``(k * T + t) // 32`` is set where ``at_ref[k, t]`` (SMEM, this
    tile's [K, tile]) is a row of the buffer. A row is fetched from HBM as
    the tile of ``_HBM_ROWS`` rows it lies in, ``_IN_FLIGHT`` fetches ahead
    of the sum, and added in the order fetched, which is (k, t): a token's
    terms in the order of k."""
    if weighted:
        w_ref, rows_ref, out_ref, stage, q_t, q_r, q_w, sems = refs
    else:
        rows_ref, out_ref, stage, q_t, q_r, sems = refs
    (top_k, tile), j = at_ref.shape, pl.program_id(0)
    words = tile // 32
    out_ref[...] = jnp.zeros_like(out_ref)
    # bfloat16 rows 2p and 2p + 1 are the low and high halves of the 32-bit
    # words of row p: read as such, a row is a slice of whole words.
    packed = rows_ref.dtype == jnp.bfloat16
    table = rows_ref.bitcast(jnp.uint32) if packed else rows_ref
    fetched = stage.shape[1]

    def fetch(slot, r):
        first = pl.multiple_of((r // _HBM_ROWS) * fetched, fetched)
        return pltpu.make_async_copy(
            table.at[pl.ds(first, fetched)], stage.at[slot], sems.at[slot])

    def add(slot):
        fetch(slot, 0).wait()
        t, r = q_t[slot], q_r[slot]
        if packed:
            word = stage[slot, pl.ds((r % _HBM_ROWS) // 2, 1), :]
            term = jax.lax.bitcast_convert_type(
                jnp.where(r % 2 == 1, word & jnp.uint32(0xFFFF0000),
                          word << 16), jnp.float32)
        else:
            term = stage[slot, pl.ds(r % _HBM_ROWS, 1), :].astype(jnp.float32)
        if weighted:
            term = term * q_w[slot]
        out_ref[pl.ds(t, 1), :] += term

    def held_word(k, i, n):
        def one(carry):
            held, n = carry
            low = held & -held
            t = i * 32 + 31 - jax.lax.clz(low)
            slot = n % _IN_FLIGHT

            @pl.when(n >= _IN_FLIGHT)
            def _():
                add(slot)
            r = at_ref[k, t]
            q_t[slot], q_r[slot] = t, r
            if weighted:
                q_w[slot] = w_ref[k, t]
            fetch(slot, r).start()
            return held ^ low, n + 1
        word = bits[(k * pl.num_programs(0) + j) * words + i]
        return jax.lax.while_loop(lambda c: c[0] != 0, one, (word, n))[1]

    n = jnp.int32(0)
    for k in range(top_k):
        n = jax.lax.fori_loop(0, words, partial(held_word, k), n)
    left = jnp.minimum(n, _IN_FLIGHT)
    jax.lax.fori_loop(
        0, left, lambda i, _: add((n - left + i) % _IN_FLIGHT), None)


def _rows_to_tokens(rows, at, tokens: int, weights, tile: int):
    """``_to_tokens_xla`` as the kernel ``moe_rows_to_tokens``, to the bit:
    the same float32 products summed in the same order with the exact zeros
    left out, and only the held rows read."""
    bound, d = rows.shape
    top_k = at.shape[0] // tokens
    weighted = weights is not None
    held = (at < bound).reshape(-1, 32).astype(jnp.uint32)
    bits = jax.lax.bitcast_convert_type(
        (held << jnp.arange(32, dtype=jnp.uint32)).sum(-1, dtype=jnp.uint32),
        jnp.int32)
    a_tile = pl.BlockSpec((top_k, tile), lambda j, *_: (0, j),
                          memory_space=pltpu.SMEM)
    operands = [at.reshape(top_k, tokens)] + ([weights] if weighted else [])
    if rows.dtype == jnp.bfloat16:
        stage = pltpu.VMEM((_IN_FLIGHT, _HBM_ROWS // 2, d), jnp.uint32)
    else:
        stage = pltpu.VMEM((_IN_FLIGHT, _HBM_ROWS, d), rows.dtype)
    return pl.pallas_call(
        partial(_rows_to_tokens_kernel, weighted=weighted),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1, grid=(tokens // tile,),
            in_specs=[a_tile] * len(operands)
            + [pl.BlockSpec(memory_space=pl.ANY)],
            out_specs=pl.BlockSpec((tile, d), lambda j, *_: (j, 0)),
            scratch_shapes=[stage, pltpu.SMEM((_IN_FLIGHT,), jnp.int32),
                            pltpu.SMEM((_IN_FLIGHT,), jnp.int32)]
            + ([pltpu.SMEM((_IN_FLIGHT,), jnp.float32)] if weighted else [])
            + [pltpu.SemaphoreType.DMA((_IN_FLIGHT,))]),
        out_shape=jax.ShapeDtypeStruct((tokens, d), jnp.float32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",),
            vmem_limit_bytes=_VMEM_LIMIT),
        interpret=_flash._interpret(), name="moe_rows_to_tokens",
    )(bits, *operands, rows)


def _to_tokens(rows, at, tokens: int, weights=None):
    """sum_k [weights[k, t] *] rows[at[k * T + t]] in float32 -> [T, d], a
    token's terms in the order of k: a buffer's rows [bound, d] summed into
    their tokens, an assignment with ``at`` = bound adding nothing. The
    kernel where the shapes tile (``_token_tile``), else the gathers."""
    tile = _token_tile(rows, at, tokens)
    if tile is None:
        return _to_tokens_xla(rows, at, tokens, weights)
    return _rows_to_tokens(rows, at, tokens, weights, tile)


def _over_buffers(one, needed):
    """one(0) + one(1) + ... + one(needed - 1), leaf by leaf. The first
    buffer is computed on every routing, and on one within the bound it is
    all there is and nothing is added to it; the others are a loop autodiff
    never sees. ``one`` is a jitted function of the buffer's number, so the
    two places that call it trace and lower it once."""
    return jax.lax.fori_loop(
        1, needed,
        lambda i, total: jax.tree.map(jnp.add, total, one(i)),
        one(jnp.int32(0)))


@partial(jax.jit, static_argnums=(0, 1))
def _buffer_forward(bound, activation, i, x, weights, w_gate, w_up, w_down,
                    order, place, sizes):
    """Buffer ``i``'s part of ``_held_experts``' sum, in float32, the rows
    it gave each held expert, the rows its way back to tokens read (the
    held ones where the kernel ran, one a routed assignment where the
    gathers did) and ``_gauges``."""
    tokens = x.shape[0]
    groups, rows_of = _buffer(i, bound, sizes, order)
    with jax.named_scope("moe_dispatch"):
        rows = _rows(x, rows_of % tokens)
    with jax.named_scope("moe_experts"):
        hidden = _expert_hidden(rows, w_gate, w_up, groups, activation)
        out = grouped_matmul(hidden, w_down, groups)
        gauges = _gauges(hidden, groups, activation)
    with jax.named_scope("moe_combine"):
        at = _at(place, i, bound, groups)
        summed = (jnp.int32(at.shape[0])
                  if _token_tile(out, at, tokens) is None
                  else groups[:-1].sum())
        return (_to_tokens(out, at, tokens, weights), groups[:-1], summed,
                gauges)


def _laid(values, i, bound: int, groups, length: int):
    """``values`` [bound], a number a row of buffer ``i``, at that buffer's
    place among ``length`` rows in expert order: exact zeros at the rows of
    the other buffers and at those of this one that no held expert is
    given."""
    held = jnp.arange(bound) < groups[:-1].sum()
    return jax.lax.dynamic_update_slice(
        jnp.zeros(length, values.dtype), jnp.where(held, values, 0),
        (i * bound,))


@partial(jax.jit, static_argnums=(0, 1))
def _buffer_backward(bound, activation, i, g, x, w_sorted, w_gate, w_up,
                     w_down, order, place, sizes):
    """(d x in float32, d weights in expert order [as long as ``order``],
    [d w_gate, d w_up, d w_down]) of the rows of buffer ``i``, which it
    multiplies again, from g = d y and ``w_sorted``, the weights in expert
    order."""
    tokens = x.shape[0]
    groups, rows_of, w_rows = _buffer(i, bound, sizes, order, w_sorted)
    at = _at(place, i, bound, groups)
    with jax.named_scope("moe_dispatch"):
        token_of = rows_of % tokens
        rows = _rows(x, token_of)
    with jax.named_scope("moe_combine"):
        g_rows = _rows(g, token_of).astype(jnp.float32)
        d_out = (g_rows * w_rows[:, None]).astype(x.dtype)
    with jax.named_scope("moe_experts"):
        out, experts_vjp = jax.vjp(
            partial(_expert_groups, group_sizes=groups,
                    activation=activation),
            rows, w_gate, w_up, w_down)
        d_rows, *d_experts = experts_vjp(d_out)
    with jax.named_scope("moe_combine"):
        d_w_rows = (out.astype(jnp.float32) * g_rows).sum(-1)
        d_w_sorted = _laid(d_w_rows, i, bound, groups, order.shape[0])
    with jax.named_scope("moe_dispatch"):
        return _to_tokens(d_rows, at, tokens), d_w_sorted, d_experts


@partial(jax.custom_vjp, nondiff_argnums=(0, 1))
def _held_experts(bound, activation, x, weights, w_gate, w_up, w_down, order,
                  place, sizes):
    """sum_{k: picked and held} weights[k, t] * Expert(x[t]) -> ([T, d] in
    x's dtype, the rows each held expert was given [count], the rows the
    way back to tokens read, ``_gauges`` summed over the buffers).

    x [T, d]; weights [K, T] float32; the held experts' w_gate (None of
    experts without a gate), w_up [count, d, f], w_down [count, f, d] in
    x's dtype; order [K * T, padded
    to whole buffers], the assignments sorted by held expert, the others
    after them; place [K * T], its inverse; sizes [count], the router's
    histogram over the held experts. One buffer of ``bound`` rows at a time
    (the module text)."""
    y, placed, summed, gauges = _over_buffers(
        lambda i: _buffer_forward(bound, activation, i, x, weights, w_gate,
                                  w_up, w_down, order, place, sizes),
        _buffers_needed(sizes.sum(), bound))
    return y.astype(x.dtype), placed, summed, gauges


def _held_experts_fwd(bound, activation, *args):
    return _held_experts(bound, activation, *args), args


def _held_experts_bwd(bound, activation, residuals, cotangents):
    x, weights, w_gate, w_up, w_down, order, place, sizes = residuals
    # ``weights`` has no entry for the rows that fill ``order`` to whole
    # buffers.
    with jax.named_scope("moe_combine"):
        w_sorted = jnp.pad(_permuted(weights.reshape(-1), place),
                           (0, order.shape[0] - place.shape[0]))
    d_x, d_w_sorted, d_experts = _over_buffers(
        lambda i: _buffer_backward(bound, activation, i, cotangents[0], x,
                                   w_sorted, w_gate, w_up, w_down, order,
                                   place, sizes),
        _buffers_needed(sizes.sum(), bound))
    with jax.named_scope("moe_combine"):
        d_weights = _permuted(d_w_sorted, order)[:place.shape[0]]
    return (d_x.astype(x.dtype), d_weights.reshape(weights.shape),
            *d_experts, None, None, None)


_held_experts.defvjp(_held_experts_fwd, _held_experts_bwd)


def routed_experts(x, router, bias, w_gate, w_up, w_down, *, top_k: int,
                   scaling: float, normalize: bool = True,
                   held: Optional[Tuple[int, int]] = None,
                   score: str = "sigmoid", activation: str = "silu"
                   ) -> Tuple[jax.Array, Dict[str, jax.Array]]:
    """sum_i w_i Expert_i(x) over each token's ``top_k`` experts, dropless.

    x [T, d] (compute dtype); router [d, E]; bias [E], or None under
    ``score`` ``"softmax"`` (``route``); w_gate, w_up [E, d, f]; w_down [E,
    f, d]. Expert_i is ``(act(x w_gate_i) * x w_up_i) w_down_i``, a SwiGLU
    under ``activation`` ``"silu"``, or, with ``w_gate`` None, two matrices
    with the activation between them, ``act(x w_up_i) w_down_i``:
    ``"relu2"`` is a squared ReLU, and ``aux["relu2_zero_share"]`` then says
    what share of the computed rows' hidden activations it zeroed. Returns
    (y [T, d], aux) with
    ``aux["picked"]`` [T, K] (the router's choice, for a reference to compare
    with), ``aux["group_sizes"]`` [E] (assignments each expert computed;
    their sum is T * K, or something was dropped) and, of a softmax router,
    ``aux["picked_mass"]`` (the mean over tokens of the probability the
    picked experts hold before normalising).

    ``held = (first, count)``: the weights are those of experts ``first`` to
    ``first + count`` alone, [count, ...], of the router's E (the module
    text). The sum is then over the picked experts that are held, computed
    over buffers of ``_held_bound(T, K, count, E)`` rows: twice the even
    share, one buffer on a routing within it and as many as the routing
    needs beyond. ``aux["group_sizes"]`` is [count], the rows of each held
    expert that the buffers placed and the grouped matmuls were given;
    ``aux["asked"]`` counts the assignments the router gave the held experts
    (equal to that sum, or something was cut), ``aux["within_bound"]`` is 1
    where one buffer held them all, else 0, and ``aux["rows_summed"]``
    counts the rows the buffers' way back to tokens read: the asked ones
    where the kernel ran, ``tokens x top_k`` a buffer where the gathers
    did. None, or all E held, is the whole layer, at ``tokens x top_k``
    rows."""
    tokens, n_experts = x.shape[0], router.shape[-1]
    dt = x.dtype
    first = None
    if held is not None and tuple(held) != (0, n_experts):
        first, count = held
        if first < 0 or count < 1 or first + count > n_experts:
            raise ValueError(f"held={held} of {n_experts} experts")
    if activation not in ACTIVATIONS:
        raise ValueError(f"activation {activation!r}: one of "
                         f"{sorted(ACTIVATIONS)}")
    if w_up.shape[0] != (n_experts if first is None else count):
        raise ValueError(
            f"weights of {w_up.shape[0]} experts, router of {n_experts}, "
            f"held={held}")
    with jax.named_scope("moe_route"):
        picked, weights, mass = route(x, router, bias, top_k, scaling,
                                      normalize, score)
    gauges = {} if mass is None else {"picked_mass": mass.mean()}
    if first is not None:
        y, aux = _share(x, picked, weights, _cast(w_gate, dt),
                        w_up.astype(dt), w_down.astype(dt), first, n_experts,
                        activation)
        return y, dict(aux, **gauges)
    with jax.named_scope("moe_dispatch"):
        expert_of = picked.T.reshape(-1)  # assignment a = k * T + t
        order = jnp.argsort(expert_of, stable=True).astype(jnp.int32)
        place = jnp.argsort(order).astype(jnp.int32)
        group_sizes = _group_sizes(expert_of, n_experts)
        rows = _dispatch(x, order, place)  # [K*T, d], grouped by expert
    with jax.named_scope("moe_experts"):
        w_gate, w_up, w_down = (_cast(w, dt) for w in (w_gate, w_up, w_down))
        hidden = _expert_hidden(rows, w_gate, w_up, group_sizes, activation)
        out = grouped_matmul(hidden, w_down, group_sizes)
        gauges.update(_shares_of(_gauges(hidden, None, activation)))
    with jax.named_scope("moe_combine"):
        y = _all_to_tokens(out, weights.T, order, place)
    return y, {"picked": picked, "group_sizes": group_sizes, **gauges}


def _cast(weights, dtype):
    """``weights`` in ``dtype``; None (no gate) stays None."""
    return None if weights is None else weights.astype(dtype)


def _shares_of(gauges):
    """``_gauges``' counts (summed over a share's buffers) as
    ``routed_experts``' aux has them: ``relu2_zero_share``, the zeroed of
    the computed; {} of none."""
    if not gauges:
        return {}
    return {"relu2_zero_share": gauges["zeroed"].astype(jnp.float32)
            / jnp.maximum(gauges["computed"], 1)}


def _share(x, picked, weights, w_gate, w_up, w_down, first: int,
           n_experts: int, activation: str):
    """``routed_experts`` on the experts ``first`` to ``first + count``, the
    weights given: the sort that puts their assignments first, then
    ``_held_experts``."""
    (tokens, top_k), count = picked.shape, w_up.shape[0]
    bound = _held_bound(tokens, top_k, count, n_experts)
    with jax.named_scope("moe_dispatch"):
        expert_of = picked.T.reshape(-1)  # assignment a = k * T + t
        is_held = (expert_of >= first) & (expert_of < first + count)
        key = jnp.where(is_held, expert_of - first, count)
        order = jnp.argsort(key, stable=True).astype(jnp.int32)
        place = jnp.argsort(order).astype(jnp.int32)
        sizes = _group_sizes(key, count)
        # Whole buffers: a slice of the order never runs off its end, and
        # the rows that fill it number on, so it stays a permutation.
        order = jnp.concatenate([order, jnp.arange(
            order.shape[0], -(-order.shape[0] // bound) * bound,
            dtype=jnp.int32)])
    y, placed, summed, gauges = _held_experts(
        bound, activation, x, weights.T, w_gate, w_up, w_down, order, place,
        sizes)
    asked = is_held.sum()
    return y, {"picked": picked, "asked": asked, "group_sizes": placed,
               "within_bound": (asked <= bound).astype(jnp.int32),
               "rows_summed": summed,
               **_shares_of(gauges)}
