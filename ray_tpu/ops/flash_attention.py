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
first row) has not: leaving the mask out of full tiles was measured twice
and bought nothing (PR 28; at PR 34 the second body it takes cost the
forward 3-7 %). ``causal_tile_census`` counts the classes.

A ``window`` (sliding-window attention: key j is seen by query i where
``0 <= i - j < window``) is the same table with one more edge: the tiles
wholly behind the window's trailing edge are left out as those above the
diagonal are, and a tile the trailing edge cuts is masked as one the
diagonal cuts (``window_tile_census``). With ``window=None``, or a window
the sequence does not reach, tables, kernels and their names are the causal
ones; a call with a window that cuts something carries names of its own
(``flash_fwd_win``, ``flash_bwd_win``; ``flash_bwd_dq_win`` and
``flash_bwd_dkv_win`` where the backward runs as the pair), so that a trace
tells a window layer's kernels from a full layer's.

The tables can say more than those two edges, and what they say is static
(a function of S and a layer's sizes, nothing of the data). ``Summaries``
is a third mask, EVA's (``ops/eva.py``): the window is a *block* (query t
sees the keys of its own window ``W (t // W) .. t``, not the ``W`` before
it) and the keys carry a **second source** stacked in front of them, one
pooled key and value a chunk of ``chunk`` keys, of which a query sees those
whose chunk lies in a window before its own. One K and one V of ``rows +
S`` rows, one table of (Q tile, KV tile) pairs (a row of tiles walks the
summary tiles it sees, then its own window's tiles up to the diagonal), one
online-softmax state through both sources; a tile is all summaries or all
keys (``rows`` is a multiple of ``blk_k``), and which it is decides the
tile's mask. The three kernel bodies are the ones below with that mask in
the causal one's place (under the names ``eva_fwd``, ``eva_bwd_dq``,
``eva_bwd_dkv``: ``ops/eva.py`` makes the calls); ``eva_tile_census``
counts the table. A call with ``window`` None or an int builds the tables
above and lowers to the text it always did.

The forward kernel walks the table Q-major, KV tiles ascending, with the
online-softmax state in VMEM scratch, keeping the MXU fed with
[blk_q, D] x [D, blk_k] matmuls (pallas_guide.md: grid/BlockSpec + scratch
accumulators), and emits the per-row logsumexp needed by the backward
pass. The running maximum m and sum l are kept lane-dense ([blk_q, 128],
every lane the row's value), so that the maximum goes back over the tile,
and the correction over o, as whole vregs: as [blk_q, 1] they were 64
vregs of one lane, and that, not the wait of ``p v`` for the row maximum,
was what kept the forward at 18-38 % of its roofline
(``_flash_fwd_kernel`` has the chip's table of us a tile). Every sum keeps
its order and every operand its type: ``out`` and ``lse`` are what one-lane
statistics gave, bit for bit, at every head size. Nothing of length S is
resident in VMEM in the forward, and q/k may have another head size (D) than
v and the output (Dv): latent attention trains with D = 192, Dv = 128.

Backward is one kernel, ``_flash_bwd_kernel`` (``flash_bwd``,
``flash_bwd_win``), wherever a head's dq fits VMEM beside the tiles, which
is every shape the benchmark has: it walks the table KV-major (Q tiles
ascending from the first that sees the KV tile), recomputes a tile's
probabilities once from q, k and the saved logsumexp (it knows the shift
beforehand, so it is bound by its products), and from the one ``p`` and the
one ``ds`` adds ``p^T dO`` into dv and ``ds^T q`` into dk, written when the
KV tile's row ends, and ``ds k`` into the Q tile's part of dq: **five
products a tile**. What is resident across a head's pairs is dq, [S, D]
float32 (D up to a whole 128 lanes: 16 MiB at 32768 x 64 and at 16384 x
192) and its result's block in the operands' dtype, zeroed at the head's
first pair and scaled and cast once at its last; the call states
``_BWD_VMEM_LIMIT`` (64 MiB) for it. Where that does not fit
(``one_backward_kernel``, a pure function of the shapes: from S = 65536 at
heads of 128 in bfloat16), backward is the standard two-kernel
FlashAttention scheme under the names ``flash_bwd_dq`` (the forward's
table) and ``flash_bwd_dkv`` (the KV-major table): **seven products a
tile pair**, ``q k^T``, ``exp`` and ``dO v^T`` made twice and the six
operands' tiles fetched twice, but nothing of length S resident, so there
the sequence length is bounded by HBM alone. Both forms keep every sum's
order (for a fixed Q tile the KV tiles arrive ascending in either walk) and
every operand's type: dq, dk and dv are the same to the bit
(tests/test_flash_window.py). Either way: O(S) memory in HBM, no S x S
tensor ever materializes there. ``_flash_bwd_kernel`` has the chip's table
of us a tile for the three kernels. ``ops/eva.py`` builds its own pair from
``_flash_bwd_dq_kernel`` and ``_flash_bwd_dkv_kernel``. The one dispatch is
models/lm.py ``attention``; every model reaches the kernels through it.

On non-TPU backends the kernels run in interpreter mode so the same code
path is testable on the CPU mesh (SURVEY.md §4: fake-TPU strategy), and
sequences the kernels cannot tile (not a multiple of 128) take the jnp
blockwise path there. On a TPU such a sequence is an error: the chip never
runs anything but the kernels under this name.
"""

from __future__ import annotations

import dataclasses
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

# Lanes of a forward kernel's running maximum and sum, here and in
# ops/dsa.py, ops/eva.py and ops/infllm.py: a vreg's, every lane the row's
# value (``_flash_fwd_kernel``).
_STAT_LANES = 128

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


@dataclasses.dataclass(frozen=True)
class Summaries:
    """EVA's mask over ``rows + S`` stacked key rows, given where a kernel
    takes a ``window``: columns ``0 .. rows - 1`` are summaries (summary j
    pools keys ``chunk j .. chunk j + chunk - 1``; rows past ``S / chunk``
    are padding up to a whole tile and seen by nobody), column ``rows + m``
    is key m. Query t, whose window starts at ``s = window * (t //
    window)``, sees key m where ``s <= m <= t`` and summary j where ``chunk
    j < s``: the chunks of the windows before its own, never one of its
    own window (those keys it sees themselves)."""
    window: int
    chunk: int
    rows: int

    def __post_init__(self):
        if self.window < 1 or self.chunk < 1 or self.window % self.chunk:
            raise ValueError(
                f"a window of {self.window} keys is not whole chunks of "
                f"{self.chunk}")

    def aligned(self, blk_q: int, blk_k: int) -> bool:
        """Every row of a Q tile lies in one window and no KV tile crosses
        a window's edge: the window's start is a scalar of the grid step
        and the table alone keeps a row from the windows before its own."""
        return self.window % blk_q == 0 and self.window % blk_k == 0


def _summaries_mask(qi, ki, blk_q: int, blk_k: int, eva: Summaries):
    """``_causal_mask`` under ``Summaries``: [blk_q, blk_k] bool for Q tile
    qi against tile ki of the stacked keys."""
    q_pos = qi * blk_q + jax.lax.broadcasted_iota(
        jnp.int32, (blk_q, blk_k), 0)
    col = ki * blk_k + jax.lax.broadcasted_iota(
        jnp.int32, (blk_q, blk_k), 1)
    is_summary = ki * blk_k < eva.rows  # of the whole tile
    if eva.aligned(blk_q, blk_k):
        # One compare: a summary tile's bound is the scalar count of
        # summaries before the window, a key tile's the diagonal (the
        # table holds no key tile of an earlier window).
        seen = (qi * blk_q) // eva.window * (eva.window // eva.chunk)
        slope = jnp.where(is_summary, 0, 1)
        bound = jnp.where(is_summary, seen - 1, eva.rows)
        return col <= slope * q_pos + bound
    # A tile is all summaries or all keys, so the two masks never meet;
    # Mosaic has no select between vectors of booleans.
    start = q_pos - jax.lax.rem(q_pos, eva.window)
    key = col - eva.rows
    return (col * eva.chunk < start) & (key < 0) \
        | (key <= q_pos) & (key >= start)


def _causal_mask(qi, ki, blk_q: int, blk_k: int, window=None):
    """[blk_q, blk_k] bool: query row >= key column, for tiles qi and ki;
    with a ``window`` also query row - key column < window; with
    ``Summaries`` for a window, their mask."""
    if isinstance(window, Summaries):
        return _summaries_mask(qi, ki, blk_q, blk_k, window)
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


def _summaries_tiles(S: int, blk_q: int, blk_k: int, eva: Summaries):
    """(seen, full): [S / blk_q, (rows + S) / blk_k] bool, whether a tile
    of ``Summaries``' mask allows some pair, and whether every one. Exact:
    each query row against each tile's first and last column, then over a
    Q tile's rows."""
    if eva.rows % blk_k or eva.rows * eva.chunk < S:
        raise ValueError(
            f"{eva.rows} summary rows in front of {S} keys: a multiple of "
            f"the KV tile ({blk_k}) that holds S / chunk = {S // eva.chunk}")
    t = np.arange(S, dtype=np.int64)[:, None]
    start = t - t % eva.window
    first = np.arange(0, eva.rows + S, blk_k, dtype=np.int64)[None, :]
    last = first + blk_k - 1
    summary = first < eva.rows
    lo, hi = first - eva.rows, last - eva.rows  # as keys
    seen = np.where(summary, first * eva.chunk < start,
                    np.maximum(lo, start) <= np.minimum(hi, t))
    full = np.where(summary, last * eva.chunk < start,
                    (start <= lo) & (hi <= t))

    def over_rows(a, fn):
        return fn(a.reshape(S // blk_q, blk_q, -1), axis=1)

    return over_rows(seen, np.any), over_rows(full, np.all)


def _tile_pairs(S: int, blk_q: int, blk_k: int, causal: bool,
                kv_major: bool, window=None):
    """The tiles with work as two int32 tables (qi_tab, ki_tab), one entry
    a grid step: Q-major with KV tiles ascending, or KV-major with Q tiles
    ascending, so every sum a kernel carries keeps its order. Under
    ``Summaries`` the KV tiles are those of the stacked rows, and KV-major
    every one of them has a step (a tile nobody sees, the last window's
    summaries or padding, gets the last Q tile: its cotangents are written,
    as zeros)."""
    if isinstance(window, Summaries):
        keep = _summaries_tiles(S, blk_q, blk_k, window)[0]
        if kv_major:
            keep[-1, ~keep.any(0)] = True
            ki, qi = np.nonzero(keep.T)
        else:
            qi, ki = np.nonzero(keep)
        return qi.astype(np.int32), ki.astype(np.int32)
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


def summary_rows(S: int, chunk: int, blk_k: int) -> int:
    """Rows the summaries take in front of the keys: S / chunk, up to a
    whole KV tile."""
    return -(-(S // chunk) // blk_k) * blk_k


def eva_tile_census(S: int, window: int, chunk: int, blk_q: int,
                    blk_k: int) -> dict:
    """``window_tile_census`` of ``Summaries``' table over one head of S
    queries: ``local`` tiles of keys (``diagonal`` of them cut by the
    diagonal or a window's edge) and ``summary`` tiles, ``executed`` their
    sum, the length of the forward's table; ``pairs`` the (query, key or
    summary) pairs EVA defines (the closed form) and ``summary_pairs``
    those on summaries, ``counted_pairs`` the pairs the table's tiles allow
    under the kernels' own mask (a whole tile counted whole, a cut one by
    its mask: equal to ``pairs``, or table and mask are wrong),
    ``causal_pairs`` what causal attention would touch, and
    ``summary_columns_masked`` the share of the summary tiles' columns that
    are computed and masked. At S = 32768, a window of 2048, chunks of 16
    and tiles of 512 x 512: 160 local (64 diagonal) and 144 summary tiles,
    304 where causal attention has 2,080; 65,028,096 pairs, 12.11 % of the
    536,887,296 causal ones, 48.4 % of them summaries; of the summary
    tiles' columns a sixth is masked."""
    rows = summary_rows(S, chunk, blk_k)
    eva = Summaries(window, chunk, rows)
    seen, full = _summaries_tiles(S, blk_q, blk_k, eva)
    summary = np.arange(seen.shape[1]) * blk_k < rows
    t = np.arange(S, dtype=np.int64)
    start = t - t % window
    local_pairs = int((t - start + 1).sum())
    summary_pairs = int((start // chunk).sum())
    summary_tiles = int(seen[:, summary].sum())
    with jax.ensure_compile_time_eval():
        counted = int(full.sum()) * blk_q * blk_k + sum(
            int(_summaries_mask(int(qi), int(ki), blk_q, blk_k, eva).sum())
            for qi, ki in zip(*np.nonzero(seen & ~full)))
    return {"executed": int(seen.sum()), "counted_pairs": counted,
            "local": int(seen[:, ~summary].sum()),
            "diagonal": int((seen & ~full)[:, ~summary].sum()),
            "summary": summary_tiles,
            "pairs": local_pairs + summary_pairs,
            "summary_pairs": summary_pairs,
            "causal_pairs": S * (S + 1) // 2,
            "summary_columns_masked":
                1.0 - summary_pairs / max(summary_tiles * blk_q * blk_k, 1)}


def _cutting(window, S: int):
    """``window`` where it cuts something of a causal S x S attention, else
    None: a window the sequence does not reach is causal attention, by the
    causal kernels under their own names."""
    if window is None:
        return None
    if window < 1:
        raise ValueError(f"window must be at least 1, got {window}")
    return int(window) if window < S else None


def worth_keeping(S: int, Dv: int, window=None) -> bool:
    """Whether the forward kernel's outputs are worth their bytes to a
    backward pass that could run the kernel again instead: from
    keys / Dv = 32, the keys a query sees: S, or the layer's ``window``
    where that is fewer (512 of them are 63 tiles of 512 x 512 a
    16384-token head where causal attention is 528, for the same bytes
    of output; 4096 over a Dv of 128 still keep). A head's causal forward
    is S^2 / (2 blk^2) tiles for 2 S Dv bytes of output, so a kept byte
    buys kernel time in proportion to S / Dv: 1.0-2.2 ms a GB for each
    unit of it at 512 x 512 tiles of 1.05-2.21 us (``_flash_fwd_kernel``;
    1.9-2.4 us before PR 34, when the figures that follow were taken).
    Measured on a v5e, step time saved over bytes kept (PERF.md §6, PR
    30): 887 ms a GB at S / Dv = 512
    (S 32768, heads of 64), 220 at 64 (S 8192, Dv 128), but 12-16 at 8
    (S 2048, heads of 256), less than the 21 ms a GB that the output of a
    4096-wide matmul buys, and there the 1.35 GB a chip crowded a
    checkpoint's transfers (a save stalled the loop 10.3 s, not 8.9).
    Nothing was measured between 8 and 64; at 32 the estimate was three
    times a matmul's and with the faster forward is still 1.5-3 times."""
    return (S if window is None else min(S, window)) >= 32 * Dv


# What ``_flash_bwd_kernel``'s call states as its ``vmem_limit_bytes``, half
# of a v5e core's 128 MiB (``ops/moe.py`` states as much for its kernel).
_BWD_VMEM_LIMIT = 64 << 20


def one_backward_kernel(S: int, D: int, Dv: int, blk_q: int, blk_k: int,
                        itemsize: int = 2) -> bool:
    """Whether the backward pass runs as one kernel (``flash_bwd``: a head's
    whole dq resident in VMEM beside the tiles) and not as the pair
    (``flash_bwd_dq``, ``flash_bwd_dkv``: nothing of length S resident): a
    pure function of the shapes, true where what the kernel holds fits
    ``_BWD_VMEM_LIMIT``. Counted at a lane's 128 columns and, for the
    operands' and results' tiles, two buffers each of ``itemsize`` bytes:
    dq's float32 accumulator [S, D] and its result's block [S, D], the six
    operands' tiles, dk's and dv's blocks and accumulators, and eight
    [blk_q, blk_k] float32 temporaries (logits, p, dp, ds, the mask, two
    transposes and room). True at every benchmark cell's shape (the
    largest, 32768 x 64 and 16384 x 192, hold 32 MiB of dq and 9 MiB of the
    rest); false from S = 65536 at D = 128 in bfloat16."""
    def lanes(d):
        return -(-d // 128) * 128

    width = lanes(D) + lanes(Dv)
    dq = S * lanes(D) * (4 + 2 * itemsize)
    tiles = 2 * itemsize * ((blk_q + 2 * blk_k) * width + 2 * 8 * blk_q)
    accumulators = 4 * blk_k * width
    temporaries = 8 * 4 * blk_q * blk_k
    return dq + tiles + accumulators + temporaries <= _BWD_VMEM_LIMIT


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


def _lanes(stat, width: int):
    """A row statistic [rows, ``_STAT_LANES``] (every lane the row's value)
    over a tile ``width`` lanes wide: as it is at that width, else repeated
    and cut to it."""
    lanes = stat.shape[1]
    if width == lanes:
        return stat
    if width > lanes:
        stat = pltpu.repeat(stat, -(-width // lanes), 1)
    return stat[:, :width]


def _flash_fwd_kernel(qi_tab, ki_tab, q_ref, k_ref, v_ref, o_ref, lse_ref,
                      m_scr, l_scr, o_scr, *, blk_q: int, blk_k: int,
                      causal: bool, scale: float, window=None, mass=None):
    """Grid: (batch*heads, pairs), Q-major, the pair axis sequential. One
    [blk_q, D] Q tile against one [blk_k, D] / [blk_k, Dv] KV tile per
    step, the online-softmax state (m, l, o) carried in VMEM scratch along
    a row of tiles, m and l [blk_q, ``_STAT_LANES``] lane-dense at every
    head size; o_ref [blk_q, Dv] and lse_ref [1, blk_q] are written at the
    row's last step. ``mass`` (under ``Summaries`` alone) is one more output
    and one more statistic, ``(mass_ref [1, blk_q], s_scr)``: the part of l
    that the summary tiles gave, kept beside l, and at the last step its
    share of l.

    us a 512 x 512 tile on a v5e by the statistics' lanes, the kernel alone
    at the benchmark cells' shapes, median of ten calls under one trace
    (PERF.md §6, PR 34; ``out`` and ``lse`` bit-equal in both rows, on the
    chip as on the CPU interpreter):

    ==========  =====  =====  ===========  =========  =====
    lanes          64    128  128, window  192 | 128    256
    ==========  =====  =====  ===========  =========  =====
    1            1.88   1.91         2.00       2.28   2.36
    128          1.05   1.07         1.14       1.50   2.21
    ==========  =====  =====  ===========  =========  =====

    As [blk_q, 1] the statistics are 64 vregs of one lane, and the
    maximum's way back over the tile, ``corr`` over o and every ``exp`` on
    them run at a lane a vreg; dense, the tile is its products and its
    ``exp`` again. Two to four tiles of a row a grid step, every ``q k^T``
    issued ahead of the row maxima, were tried on top: within 0.4 % at 64
    and 128, -2.4 % at 192 | 128 and -3.5 % at 256 (3 % of that cell's
    step), not worth their tables and bodies."""
    t = pl.program_id(1)
    qi, ki = qi_tab[t], ki_tab[t]
    first, last = _row_ends(qi_tab)
    Dv = o_scr.shape[1]

    @pl.when(first)
    def _():
        m_scr[...] = jnp.full(m_scr.shape, _NEG_INF, jnp.float32)
        l_scr[...] = jnp.zeros(l_scr.shape, jnp.float32)
        o_scr[...] = jnp.zeros(o_scr.shape, jnp.float32)
        if mass is not None:
            mass[1][...] = jnp.zeros(mass[1].shape, jnp.float32)

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
    p = jnp.exp(logits - _lanes(m_new, blk_k))
    if mass is None:
        l_scr[...] = l_scr[...] * corr + p.sum(-1, keepdims=True)
    else:
        p_sum = p.sum(-1, keepdims=True)
        l_scr[...] = l_scr[...] * corr + p_sum
        mass[1][...] = mass[1][...] * corr + jnp.where(
            ki * blk_k < window.rows, p_sum, 0.0)
    o_scr[...] = o_scr[...] * _lanes(corr, Dv) + jax.lax.dot_general(
        p, v_blk, (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)
    m_scr[...] = m_new

    @pl.when(last)
    def _():
        l_safe = jnp.maximum(l_scr[...], 1e-30)
        o_ref[...] = (o_scr[...] / _lanes(l_safe, Dv)).astype(o_ref.dtype)
        lse_ref[...] = (m_scr[...] + jnp.log(l_safe))[:, 0][None, :]
        if mass is not None:
            mass[0][...] = (mass[1][...] / l_safe)[:, 0][None, :]


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
                          scale: float, window=None, dq_of=None):
    """Grid: (batch*heads, pairs), KV-major, the pair axis sequential:
    dk/dv for one KV tile, accumulated over the Q, dO, lse and delta tiles
    of its row (those at or after the diagonal when causal). ``dq_of``
    (``_flash_bwd_kernel``'s) is called with the tile's ``ds`` and k, both
    float32, once they are there and before the row's closing write."""
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
    if dq_of is not None:
        dq_of(ds, k)

    @pl.when(last)
    def _():
        dk_ref[...] = dk_scr[...].astype(dk_ref.dtype)
        dv_ref[...] = dv_scr[...].astype(dv_ref.dtype)


def _flash_bwd_kernel(qi_tab, ki_tab, q_ref, k_ref, v_ref, g_ref, lse_ref,
                      delta_ref, dq_ref, dk_ref, dv_ref, dq_scr, dk_scr,
                      dv_scr, *, scale: float, **static):
    """Grid: (batch*heads, pairs), KV-major as ``_flash_bwd_dkv_kernel``'s,
    the pair axis sequential: dk/dv for one KV tile accumulated over its row
    of Q tiles, and beside them, from the same ``p`` and ``ds``, every Q
    tile's ``ds k`` added into a head's whole dq, ``dq_scr`` [S / blk_q,
    blk_q, D] float32, resident across the pair axis (zeroed at a head's
    first pair; scaled, cast and written to ``dq_ref``, whose block is the
    head's and holds still over the axis, at its last). For a fixed Q tile
    the KV tiles arrive ascending, as in ``_flash_bwd_dq_kernel``'s row, so
    dq, dk and dv are the pair's, bit for bit.

    us a 512 x 512 tile on a v5e, the kernels alone at the benchmark
    cells' shapes, median of ten calls under one trace (PERF.md §6, PR 69;
    dq, dk and dv equal to the bit on the chip as on the CPU interpreter):

    =================  =====  ========  =====  ===========  =========  =====
    D | Dv                64  64 | 128    128  128, window  192 | 128    256
    =================  =====  ========  =====  ===========  =========  =====
    ``flash_bwd_dq``    1.35      1.34   1.34         1.35       2.04   2.41
    ``flash_bwd_dkv``   1.93      1.93   1.92         2.00       2.55   3.25
    the pair            3.28      3.27   3.26         3.35       4.59   5.66
    ``flash_bwd``       2.35      2.34   2.33         2.43       3.31   4.04
    =================  =====  ========  =====  ===========  =========  =====

    28 % off the pair at every head size: two of seven products, one
    ``exp``, one fetch of the six tiles and one grid step. With the fifth
    product behind the row's closing write (a conditional region, which the
    scheduler does not move a product across) a tile cost 0.08-0.10 us
    more at every shape: ``dq_of`` is called ahead of it.
    """
    t, last_t = pl.program_id(1), pl.num_programs(1) - 1
    n_q = dq_scr.shape[0]

    @pl.when(t == 0)
    def _():
        def zero(i, _):
            dq_scr[i] = jnp.zeros(dq_scr.shape[1:], jnp.float32)
        jax.lax.fori_loop(0, n_q, zero, None)

    def add_dq(ds, k):
        dq_scr[qi_tab[t]] += jax.lax.dot_general(
            ds, k, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)

    _flash_bwd_dkv_kernel(qi_tab, ki_tab, q_ref, k_ref, v_ref, g_ref, lse_ref,
                          delta_ref, dk_ref, dv_ref, dk_scr, dv_scr,
                          scale=scale, dq_of=add_dq, **static)

    @pl.when(t == last_t)
    def _():
        def write(i, _):
            dq_ref[i] = (dq_scr[i] * scale).astype(dq_ref.dtype)
        jax.lax.fori_loop(0, n_q, write, None)


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


def _head(b, t, qi_tab, ki_tab):
    return b, 0, 0, 0


def _tiled_call(kernel, name: str, batch_heads: int, pairs, in_specs,
                out_specs, out_shape, scratch_shapes, vmem_limit_bytes=None):
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
            dimension_semantics=("parallel", "arbitrary"),
            vmem_limit_bytes=vmem_limit_bytes),
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
        scratch_shapes=[pltpu.VMEM((blk_q, _STAT_LANES), jnp.float32),
                        pltpu.VMEM((blk_q, _STAT_LANES), jnp.float32),
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
    # The six operands of every backward kernel, tiled alike in all.
    operands = (qf, kf, vf, gf, lse, delta)
    in_specs = [
        pl.BlockSpec((None, blk_q, D), _q_tile),
        pl.BlockSpec((None, blk_k, D), _kv_tile),
        pl.BlockSpec((None, blk_k, Dv), _kv_tile),
        pl.BlockSpec((None, blk_q, Dv), _q_tile),
        pl.BlockSpec((None, 1, blk_q), _q_row),
        pl.BlockSpec((None, 1, blk_q), _q_row),
    ]
    kv_major = _tile_pairs(S, blk_q, blk_k, causal, True, window)
    kv_out = dict(
        out_specs=[pl.BlockSpec((None, blk_k, D), _kv_tile),
                   pl.BlockSpec((None, blk_k, Dv), _kv_tile)],
        out_shape=[jax.ShapeDtypeStruct((B * H, S, D), k.dtype),
                   jax.ShapeDtypeStruct((B * H, S, Dv), v.dtype)],
        scratch_shapes=[pltpu.VMEM((blk_k, D), jnp.float32),
                        pltpu.VMEM((blk_k, Dv), jnp.float32)])
    if one_backward_kernel(S, D, Dv, blk_q, blk_k, q.dtype.itemsize):
        n_q = S // blk_q
        dq, dk, dv = _tiled_call(
            functools.partial(_flash_bwd_kernel, **common),
            _named("flash_bwd", window), B * H, kv_major,
            in_specs=in_specs,
            out_specs=[pl.BlockSpec((None, n_q, blk_q, D), _head)]
            + kv_out["out_specs"],
            out_shape=[jax.ShapeDtypeStruct((B * H, n_q, blk_q, D), q.dtype)]
            + kv_out["out_shape"],
            scratch_shapes=[pltpu.VMEM((n_q, blk_q, D), jnp.float32)]
            + kv_out["scratch_shapes"],
            vmem_limit_bytes=_BWD_VMEM_LIMIT,
        )(*operands)
        dq = dq.reshape(B * H, S, D)
    else:
        # A head's dq does not fit beside the tiles: the pair, with nothing
        # of length S resident.
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
            _named("flash_bwd_dkv", window), B * H, kv_major,
            in_specs=in_specs, **kv_out)(*operands)

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
    if worth_keeping(q.shape[1], v.shape[-1], window):
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
