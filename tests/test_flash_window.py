"""The flash kernels under a window (``ops/flash_attention.py``: the pair
table's trailing edge, the cut tiles' mask, the kernels' own names) against
``lm.dot_attention`` with the same mask; the window's tile census against a
count made pair by pair; the refusals of the paths that have no window; and
the single backward kernel (``flash_bwd``, ``flash_bwd_win``) against the
pair it replaces, to the bit, with the rule that chooses between them.

The kernels run interpreted on the CPU in float32, where both sides compute
the same sums in another order.
"""

import importlib
from functools import partial
from types import SimpleNamespace

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ray_tpu.models import lm

# The module, not the function ``ray_tpu.ops`` re-exports under its name.
flash_mod = importlib.import_module("ray_tpu.ops.flash_attention")
flash_attention = flash_mod.flash_attention


def _inputs(B, S, H, KVH, D, seed=0, sharp=3.0):
    ks = jax.random.split(jax.random.PRNGKey(seed), 4)
    return (sharp * jax.random.normal(ks[0], (B, S, H, D)),
            sharp * jax.random.normal(ks[1], (B, S, KVH, D)),
            jax.random.normal(ks[2], (B, S, KVH, D)),
            jax.random.normal(ks[3], (B, S, H, D)))


def _brute_census(S, window, blk_q, blk_k):
    """The classes of tiles from the S x S mask itself."""
    i, j = np.arange(S)[:, None], np.arange(S)[None, :]
    allowed = j <= i
    if window is not None:
        allowed &= i - j < window
    share = allowed.reshape(S // blk_q, blk_q, S // blk_k, blk_k
                            ).mean(axis=(1, 3))
    counts = {"empty": int((share == 0).sum()),
              "full": int((share == 1).sum())}
    counts["diagonal"] = share.size - counts["empty"] - counts["full"]
    counts["executed"] = counts["diagonal"] + counts["full"]
    return counts


# Smaller than the tile, equal to it, not a multiple of it, a multiple, one
# key (itself alone), and one short of the sequence.
WINDOWS = [1, 48, 128, 200, 256, 511]


@pytest.mark.parametrize("window", WINDOWS)
@pytest.mark.parametrize("blk_q,blk_k", [(128, 128), (256, 128), (128, 256)])
def test_windowed_kernels_against_dot_attention(window, blk_q, blk_k):
    """Forward and the three cotangents, q and k sharpened so that a key
    wrongly let in or left out moves the softmax."""
    q, k, v, g = _inputs(1, 512, 4, 4, 32)
    want, want_vjp = jax.vjp(partial(lm.dot_attention, window=window),
                             q, k, v)
    got, got_vjp = jax.vjp(
        lambda q, k, v: flash_attention(q, k, v, True, blk_q, blk_k, None,
                                        window), q, k, v)
    np.testing.assert_allclose(got, want, atol=1e-4)
    for a, b in zip(got_vjp(g), want_vjp(g)):
        # A window of one key has a softmax of one term: dq and dk are zero.
        np.testing.assert_allclose(
            a, b, atol=1e-5 + 2e-4 * float(jnp.abs(b).max()))
    if window <= 256:  # 511 of 512 leaves one key of one row out
        causal = flash_attention(q, k, v, True, blk_q, blk_k)
        assert float(jnp.abs(causal - want).max()) > 0.1


@pytest.mark.parametrize("window", [96, 256])
def test_windowed_kernels_with_grouped_heads(window):
    """8 KV heads under 48 query heads (head i reads KV head i // 6), as the
    window layers of the benchmark's configuration have them."""
    q, k, v, g = _inputs(1, 384, 48, 8, 16, seed=1)
    want, want_vjp = jax.vjp(partial(lm.dot_attention, window=window),
                             q, k, v)
    got, got_vjp = jax.vjp(
        lambda q, k, v: flash_attention(q, k, v, True, 128, 128, None,
                                        window), q, k, v)
    np.testing.assert_allclose(got, want, atol=1e-4)
    for a, b in zip(got_vjp(g), want_vjp(g)):
        assert a.shape == b.shape
        np.testing.assert_allclose(a, b, atol=2e-4 * float(jnp.abs(b).max()))
    rolled = flash_attention(q, jnp.roll(k, 1, axis=2),
                             jnp.roll(v, 1, axis=2), True, 128, 128, None,
                             window)
    assert float(jnp.abs(rolled - want).max()) > 0.1


@pytest.mark.parametrize("window", [512, 513, 4096])
def test_a_window_the_sequence_does_not_reach_is_causal(window):
    """S <= window: the causal tables, the causal kernels under their own
    names, the causal result bit for bit."""
    S, blk = 512, 128
    for kv_major in (False, True):
        got = flash_mod._tile_pairs(S, blk, blk, True, kv_major, window)
        want = flash_mod._tile_pairs(S, blk, blk, True, kv_major)
        assert all((a == b).all() for a, b in zip(got, want))
    assert flash_mod.window_tile_census(S, window, blk, blk) == \
        flash_mod.causal_tile_census(S, blk, blk)
    q, k, v, g = _inputs(1, S, 2, 2, 32)

    def run(window):
        return jax.vjp(lambda q, k, v: flash_attention(
            q, k, v, True, blk, blk, None, window), q, k, v)

    (got, got_vjp), (want, want_vjp) = run(window), run(None)
    assert (got == want).all()
    assert all((a == b).all() for a, b in zip(got_vjp(g), want_vjp(g)))
    text = str(jax.make_jaxpr(lambda q, k, v: flash_attention(
        q, k, v, True, blk, blk, None, window))(q, k, v))
    assert "flash_fwd" in text and "flash_fwd_win" not in text


def test_a_window_that_cuts_has_kernel_names_of_its_own():
    q, k, v, g = _inputs(1, 512, 2, 2, 32)

    def loss(q, k, v):
        return (flash_attention(q, k, v, True, 128, 128, None, 200)
                * g).sum()

    text = str(jax.make_jaxpr(jax.grad(loss, argnums=(0, 1, 2)))(q, k, v))
    assert "flash_fwd_win" in text and "flash_bwd_win" in text
    assert "flash_bwd_d" not in text


def _backward(q, k, v, g, causal, window, blk, single):
    """(dq, dk, dv) of ``_flash_backward`` on the forward's own ``out`` and
    ``lse``: by the single kernel, or (``single`` false: the rule overruled)
    by ``_flash_bwd_dq_kernel`` and ``_flash_bwd_dkv_kernel``."""
    out, lse = flash_mod._flash_forward(q, k, v, causal, blk, blk, None,
                                        window)
    rule = flash_mod.one_backward_kernel
    flash_mod.one_backward_kernel = lambda *shape: single and rule(*shape)
    try:
        return flash_mod._flash_backward(q, k, v, out, lse, g, causal, blk,
                                         blk, None, window)
    finally:
        flash_mod.one_backward_kernel = rule


HEAD_SIZES = [(64, 64), (64, 128), (192, 128), (256, 256)]
#: (causal, window) at tiles of 128: every pair, the causal table, a window
#: that cuts (inside the one tile already; tiles behind it leave the table
#: from 3 x 3 on) and a window the sequence does not reach.
MASKS = {"all_pairs": (False, None), "causal": (True, None),
         "window_cuts": (True, 100), "window_out_of_reach": (True, 4096)}
#: Query heads over KV heads by the table's tiles a side: 32 over 8 on the
#: one-tile table (an interpreted grid step a head and pair is what a case
#: costs), 8 over 2 at 3 x 3, a KV head a query head at 2 x 2 and 4 x 4.
HEADS = {1: (32, 8), 2: (2, 2), 3: (8, 2), 4: (2, 2)}
#: Head sizes x tables of 1 x 1 to 4 x 4 tiles, the masks going round so
#: that each meets every head size and every table once; bfloat16 on the
#: odd tables, float32 on the even.
BACKWARD_CASES = [
    pytest.param(d, dv, tiles, list(MASKS)[(i + tiles) % 4], HEADS[tiles],
                 jnp.bfloat16 if tiles % 2 else jnp.float32,
                 id=f"{d}_{dv}-{tiles}x{tiles}-{list(MASKS)[(i + tiles) % 4]}")
    for i, (d, dv) in enumerate(HEAD_SIZES) for tiles in (1, 2, 3, 4)]


@pytest.mark.parametrize("d,dv,tiles,mask,heads,dtype", BACKWARD_CASES)
def test_one_backward_kernel_is_the_pair_to_the_bit(d, dv, tiles, mask,
                                                    heads, dtype):
    """``flash_bwd``'s dq, dk and dv against ``flash_bwd_dq``'s and
    ``flash_bwd_dkv``'s on the same operands: for a fixed Q tile the KV
    tiles arrive ascending in both walks, every product and sum is float32
    in both, so nothing may differ. The short tables are where a resident
    accumulator's revisit of a Q tile goes wrong."""
    causal, window = MASKS[mask]
    (h, kvh), S, blk = heads, 128 * tiles, 128
    ks = jax.random.split(jax.random.PRNGKey(tiles), 4)
    q, k, v, g = (jax.random.normal(key, shape, dtype) for key, shape in zip(
        ks, [(1, S, h, d), (1, S, kvh, d), (1, S, kvh, dv), (1, S, h, dv)]))
    one = _backward(q, k, v, g, causal, window, blk, True)
    pair = _backward(q, k, v, g, causal, window, blk, False)
    for name, a, b in zip(("dq", "dk", "dv"), one, pair):
        assert a.dtype == b.dtype == dtype and a.shape == b.shape
        a, b = np.asarray(a, np.float32), np.asarray(b, np.float32)
        assert np.isfinite(a).all() and np.abs(a).max() > 0
        assert (a.view(np.uint32) == b.view(np.uint32)).all(), name


def test_a_backward_whose_dq_does_not_fit_is_the_pair():
    """S = 131072 at heads of 128: dq's float32 accumulator alone is the
    limit's 64 MiB, so the call lowers to the two kernels that keep nothing
    of length S resident, under their names."""
    assert not flash_mod.one_backward_kernel(131072, 128, 128, 512, 512)
    assert not flash_mod.one_backward_kernel(65536, 128, 128, 512, 512)
    assert flash_mod.one_backward_kernel(32768, 128, 128, 512, 512)
    q = jax.ShapeDtypeStruct((1, 131072, 1, 128), jnp.bfloat16)

    def loss(q, k, v):
        return flash_attention(q, k, v, True, 512, 512).astype(
            jnp.float32).sum()

    text = str(jax.make_jaxpr(jax.grad(loss, argnums=(0, 1, 2)))(q, q, q))
    assert "flash_bwd_dq" in text and "flash_bwd_dkv" in text
    assert "name=flash_bwd " not in text and "flash_bwd_win" not in text


@pytest.mark.parametrize("S,window,blk_q,blk_k", [
    (1024, 200, 128, 128), (1024, 256, 128, 256), (1024, 512, 256, 128),
    (1536, 64, 512, 512), (1536, 1000, 512, 256), (2048, 1, 512, 512)])
def test_window_tile_census_matches_the_mask(S, window, blk_q, blk_k):
    census = flash_mod.window_tile_census(S, window, blk_q, blk_k)
    assert census == _brute_census(S, window, blk_q, blk_k)
    # The tables are the executed tiles, each once, rows ascending and
    # ascending within a row, so that every carried sum keeps its order.
    for kv_major in (False, True):
        qi, ki = flash_mod._tile_pairs(S, blk_q, blk_k, True, kv_major,
                                       window)
        assert len(qi) == len(ki) == census["executed"]
        pairs = list(zip(ki, qi) if kv_major else zip(qi, ki))
        assert pairs == sorted(set(pairs))


# The 32768 case walks 64 x 64 tiles row by row in numpy (65 s): slow since
# PR 51 (ROADMAP Queue 3 item 8); the 16384 case holds the same code.
@pytest.mark.parametrize("S,executed,causal", [
    (16384, 252, 528),
    pytest.param(32768, 540, 2080, marks=pytest.mark.slow)])
def test_window_tile_census_of_the_cell(S, executed, causal):
    """A window of 4096 at tiles of 512 x 512: a Q tile from the ninth on
    sees 7 full tiles and 2 cut ones."""
    census = flash_mod.window_tile_census(S, 4096, 512, 512)
    assert census["executed"] == executed
    assert flash_mod.causal_tile_census(S, 512, 512)["executed"] == causal
    tiles = S // 512
    assert census["diagonal"] == tiles + (tiles - 8)
    assert census["full"] == executed - census["diagonal"]
    assert census == _brute_census(S, 4096, 512, 512)


def test_a_window_needs_the_causal_mask():
    q, k, v, _ = _inputs(1, 256, 2, 2, 32)
    with pytest.raises(ValueError, match="window"):
        flash_attention(q, k, v, False, 128, 128, None, 64)
    with pytest.raises(ValueError, match="window"):
        flash_attention(q, k, v, True, 128, 128, None, 0)


def test_the_ragged_path_refuses_a_window():
    """A sequence the kernels cannot tile takes the blockwise path on the
    CPU, and that path has no window."""
    q, k, v, _ = _inputs(1, 100, 2, 2, 32)
    with pytest.raises(NotImplementedError, match="window"):
        flash_attention(q, k, v, True, 128, 128, None, 64)
    # Without a window it runs, and a window it does not reach is none.
    assert flash_attention(q, k, v, True, 128, 128, None, 100).shape \
        == q.shape


@pytest.mark.parametrize("impl", ["ring", "ulysses"])
def test_attention_refuses_a_window_where_it_has_none(impl):
    q, k, v, _ = _inputs(1, 256, 2, 2, 32)
    cfg = SimpleNamespace(attn_impl=impl, attn_blk_q=128, attn_blk_k=128)
    with pytest.raises(NotImplementedError, match="window"):
        lm.attention(q, k, v, cfg, window=64)


@pytest.mark.parametrize("impl", ["dot", "flash"])
def test_attention_hands_the_window_on(impl):
    q, k, v, _ = _inputs(1, 256, 4, 2, 32)
    cfg = SimpleNamespace(attn_impl=impl, attn_blk_q=128, attn_blk_k=128)
    got = lm.attention(q, k, v, cfg, window=100)
    np.testing.assert_allclose(got, lm.dot_attention(q, k, v, window=100),
                               atol=1e-4)
    assert float(jnp.abs(got - lm.attention(q, k, v, cfg)).max()) > 0.1
