"""Numerics tests for ray_tpu.ops against the reference dot attention."""

import functools
import importlib
import math

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ray_tpu.ops import (blockwise_attention, flash_attention,
                         ring_attention)
from ray_tpu.ops.ring_attention import make_ring_attention

# The module, not the function ``ray_tpu.ops`` re-exports under its name.
flash_mod = importlib.import_module("ray_tpu.ops.flash_attention")


def _dot_reference(q, k, v, causal=True):
    B, S, H, D = q.shape
    scale = 1.0 / math.sqrt(D)
    logits = jnp.einsum("bqhd,bkhd->bhqk", q, k) * scale
    if causal:
        qpos = jax.lax.broadcasted_iota(jnp.int32, (S, S), 0)
        kpos = jax.lax.broadcasted_iota(jnp.int32, (S, S), 1)
        logits = jnp.where((qpos >= kpos)[None, None], logits, -1e30)
    probs = jax.nn.softmax(logits.astype(jnp.float32), axis=-1)
    return jnp.einsum("bhqk,bkhd->bqhd", probs, v.astype(jnp.float32)
                      ).astype(q.dtype)


def _qkv(B=2, S=128, H=4, D=32, seed=0, dtype=jnp.float32):
    ks = jax.random.split(jax.random.PRNGKey(seed), 3)
    q = jax.random.normal(ks[0], (B, S, H, D), dtype)
    k = jax.random.normal(ks[1], (B, S, H, D), dtype)
    v = jax.random.normal(ks[2], (B, S, H, D), dtype)
    return q, k, v


def test_blockwise_matches_dot():
    q, k, v = _qkv()
    ref = _dot_reference(q, k, v)
    out = blockwise_attention(q, k, v, chunk_size=32)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               atol=1e-5, rtol=1e-5)


def test_blockwise_ragged_chunk():
    q, k, v = _qkv(S=100)
    ref = _dot_reference(q, k, v)
    out = blockwise_attention(q, k, v, chunk_size=33)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               atol=1e-5, rtol=1e-5)


def test_blockwise_grad_matches_dot():
    q, k, v = _qkv(S=64)

    def loss_ref(q, k, v):
        return (_dot_reference(q, k, v) ** 2).sum()

    def loss_blk(q, k, v):
        return (blockwise_attention(q, k, v, chunk_size=16) ** 2).sum()

    g_ref = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
    g_blk = jax.grad(loss_blk, argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(g_ref, g_blk):
        np.testing.assert_allclose(np.asarray(b), np.asarray(a),
                                   atol=1e-4, rtol=1e-4)


def test_flash_matches_dot():
    q, k, v = _qkv(S=128)
    ref = _dot_reference(q, k, v)
    out = flash_attention(q, k, v, True, 32, 32)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               atol=1e-5, rtol=1e-5)


def test_flash_non_causal():
    q, k, v = _qkv(S=64)
    ref = _dot_reference(q, k, v, causal=False)
    out = flash_attention(q, k, v, False, 32, 32)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               atol=1e-5, rtol=1e-5)


def test_flash_grad():
    q, k, v = _qkv(S=64)

    def loss_ref(q, k, v):
        return (_dot_reference(q, k, v) ** 2).sum()

    def loss_fl(q, k, v):
        return (flash_attention(q, k, v, True, 32, 32) ** 2).sum()

    g_ref = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
    g_fl = jax.grad(loss_fl, argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(g_ref, g_fl):
        np.testing.assert_allclose(np.asarray(b), np.asarray(a),
                                   atol=1e-4, rtol=1e-4)


def test_flash_gqa():
    B, S, H, D = 2, 64, 8, 16
    ks = jax.random.split(jax.random.PRNGKey(1), 3)
    q = jax.random.normal(ks[0], (B, S, H, D))
    k = jax.random.normal(ks[1], (B, S, 2, D))
    v = jax.random.normal(ks[2], (B, S, 2, D))
    k_full = jnp.repeat(k, 4, axis=2)
    v_full = jnp.repeat(v, 4, axis=2)
    ref = _dot_reference(q, k_full, v_full)
    out = flash_attention(q, k, v, True, 32, 32)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               atol=1e-5, rtol=1e-5)


def test_flash_kernel_path_fwd_and_lse():
    """S=256 with 128-lane blocks runs the real Pallas kernels (not the
    blockwise fallback); interpret mode emulates TPU bf16 matmuls, so the
    reference must be compared under 'highest' matmul precision."""
    from ray_tpu.ops.flash_attention import _flash_forward, _pick_block

    assert _pick_block(256, 1024) == 256
    assert _pick_block(1536, 1024) == 768  # multiple of 128, not of 1024
    assert _pick_block(100, 1024) == 0  # ragged → fallback
    with jax.default_matmul_precision("highest"):
        q, k, v = _qkv(S=256)
        out, lse = _flash_forward(q, k, v, True, 128, 128)
        assert lse is not None, "kernel path not taken"
        ref = _dot_reference(q, k, v)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   atol=1e-4, rtol=1e-4)
        # lse matches direct logsumexp of the masked logits
        B, S, H, D = q.shape
        logits = jnp.einsum("bqhd,bkhd->bhqk", q, k) / math.sqrt(D)
        qpos = jax.lax.broadcasted_iota(jnp.int32, (S, S), 0)
        kpos = jax.lax.broadcasted_iota(jnp.int32, (S, S), 1)
        logits = jnp.where((qpos >= kpos)[None, None], logits, -1e30)
        lse_ref = jax.scipy.special.logsumexp(logits, -1)
        np.testing.assert_allclose(
            np.asarray(lse.reshape(B, H, S)), np.asarray(lse_ref),
            atol=1e-4, rtol=1e-4)


@pytest.mark.parametrize("causal", [True, False])
def test_flash_kernel_backward(causal):
    """Pallas dq/dk/dv kernels (blk >= 128) against the dot reference."""
    with jax.default_matmul_precision("highest"):
        q, k, v = _qkv(S=256)

        def loss_ref(q, k, v):
            return (_dot_reference(q, k, v, causal) ** 2).sum()

        def loss_fl(q, k, v):
            return (flash_attention(q, k, v, causal, 128, 256) ** 2).sum()

        g_ref = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
        g_fl = jax.grad(loss_fl, argnums=(0, 1, 2))(q, k, v)
        for a, b in zip(g_ref, g_fl):
            np.testing.assert_allclose(np.asarray(b), np.asarray(a),
                                       atol=2e-3, rtol=1e-3)


def test_flash_kernel_backward_gqa():
    with jax.default_matmul_precision("highest"):
        B, S, H, D = 2, 256, 8, 32
        ks = jax.random.split(jax.random.PRNGKey(5), 3)
        q = jax.random.normal(ks[0], (B, S, H, D))
        k = jax.random.normal(ks[1], (B, S, 2, D))
        v = jax.random.normal(ks[2], (B, S, 2, D))

        def loss_ref(q, k, v):
            ref = _dot_reference(q, jnp.repeat(k, 4, 2), jnp.repeat(v, 4, 2))
            return (ref ** 2).sum()

        def loss_fl(q, k, v):
            return (flash_attention(q, k, v, True, 128, 128) ** 2).sum()

        g_ref = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
        g_fl = jax.grad(loss_fl, argnums=(0, 1, 2))(q, k, v)
        for a, b in zip(g_ref, g_fl):
            np.testing.assert_allclose(np.asarray(b), np.asarray(a),
                                       atol=2e-3, rtol=1e-3)


# --- the kernels' table of (Q tile, KV tile) pairs and the causal mask ---

BLOCK_PAIRS = [(128, 128), (256, 128), (128, 256), (512, 512)]


def _flash_and_grads(q, k, v, g, causal, blk_q, blk_k):
    """out and (dq, dk, dv) for the cotangent g, through the kernels."""
    out, vjp = jax.vjp(
        lambda q_, k_, v_: flash_attention(q_, k_, v_, causal, blk_q, blk_k),
        q, k, v)
    return (out,) + vjp(g)


def _reference_and_grads(q, k, v, g, causal):
    from ray_tpu.models import lm
    ref = lm.dot_attention if causal else (
        lambda q_, k_, v_: _dot_reference(q_, k_, v_, causal=False))
    out, vjp = jax.vjp(ref, q, k, v)
    return (out,) + vjp(g)


@pytest.mark.parametrize("on_boundary", [True, False])
@pytest.mark.parametrize("S", [512, 1024])
@pytest.mark.parametrize("blk_q,blk_k", BLOCK_PAIRS)
def test_flash_future_poison(blk_q, blk_k, S, on_boundary):
    """k and v after position t are replaced by large finite values (not
    NaN: 0 x NaN is NaN) in the second batch entry; the cotangent is zero
    after t. Nothing at or before t may change, bit for bit: a mask built
    for another tile than the table's entry, or a masked probability that
    is not 0.0 exactly, lets a row see its future."""
    t = S // 2 - 1 if on_boundary else S // 2 + 37
    q, k, v = _qkv(B=1, S=S, H=1, D=32, seed=7)
    future = (jnp.arange(S) > t)[None, :, None, None]
    q2 = jnp.concatenate([q, q])
    k2 = jnp.concatenate([k, jnp.where(future, 1e3, k)])
    v2 = jnp.concatenate([v, jnp.where(future, -7e3, v)])
    g = jax.random.normal(jax.random.PRNGKey(8), v.shape, v.dtype)
    g2 = jnp.where(future, 0.0, jnp.concatenate([g, g]))
    for name, x in zip(("out", "dq", "dk", "dv"), _flash_and_grads(
            q2, k2, v2, g2, True, blk_q, blk_k)):
        x = np.asarray(x)
        assert np.isfinite(x).all(), name
        np.testing.assert_array_equal(x[1, :t + 1], x[0, :t + 1], name)


@pytest.mark.parametrize("causal", [True, False])
@pytest.mark.parametrize("blk_q,blk_k", BLOCK_PAIRS)
def test_flash_sharp_matches_dot(blk_q, blk_k, causal):
    """q and k sharpened, so a row's weight sits on a few columns wherever
    they are: a masking fault far from the diagonal shows (at a seeded init
    attention is soft and it hides). Forward and all three gradients
    against the dot reference, in float32; causal=False is the table of
    all pairs, no mask."""
    with jax.default_matmul_precision("highest"):
        q, k, v = _qkv(B=1, S=1024, H=2, D=32, seed=11)
        q, k = 3.0 * q, 3.0 * k
        g = jax.random.normal(jax.random.PRNGKey(12), v.shape, v.dtype)
        got = _flash_and_grads(q, k, v, g, causal, blk_q, blk_k)
        want = _reference_and_grads(q, k, v, g, causal)
        for name, a, b in zip(("out", "dq", "dk", "dv"), got, want):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       atol=2e-4, rtol=2e-4, err_msg=name)


@pytest.mark.parametrize("blk_q,blk_k", [(128, 128), (256, 128), (128, 256)])
def test_flash_two_head_sizes(blk_q, blk_k):
    """q/k of 192 beside v of 128 (latent attention) through the table."""
    with jax.default_matmul_precision("highest"):
        ks = jax.random.split(jax.random.PRNGKey(13), 4)
        q = jax.random.normal(ks[0], (1, 512, 2, 192))
        k = jax.random.normal(ks[1], (1, 512, 2, 192))
        v = jax.random.normal(ks[2], (1, 512, 2, 128))
        g = jax.random.normal(ks[3], v.shape)
        got = _flash_and_grads(q, k, v, g, True, blk_q, blk_k)
        want = _reference_and_grads(q, k, v, g, True)
        for name, a, b in zip(("out", "dq", "dk", "dv"), got, want):
            assert a.shape == b.shape, name
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       atol=2e-4, rtol=2e-4, err_msg=name)


def _brute_census(S, blk_q, blk_k):
    """Classes of the tiles of the S x S causal mask, counted on it."""
    allowed = np.arange(S)[:, None] >= np.arange(S)[None, :]
    tiles = allowed.reshape(S // blk_q, blk_q, S // blk_k, blk_k)
    share = tiles.mean(axis=(1, 3))
    counts = {"empty": int((share == 0).sum()),
              "full": int((share == 1).sum())}
    counts["diagonal"] = share.size - counts["empty"] - counts["full"]
    counts["executed"] = counts["diagonal"] + counts["full"]
    return counts


@pytest.mark.parametrize("S", [512, 1024, 1536])
@pytest.mark.parametrize("blk_q,blk_k", BLOCK_PAIRS)
def test_causal_tile_census_matches_the_mask(blk_q, blk_k, S):
    from ray_tpu.ops.flash_attention import (_tile_pairs,
                                             causal_tile_census)
    census = causal_tile_census(S, blk_q, blk_k)
    assert census == _brute_census(S, blk_q, blk_k)
    # benchmark/flops_deepseek.py causal_tiles, restated: what the
    # kernels' rooflines count as executed.
    assert census["executed"] == sum(
        min(-(-((qi + 1) * blk_q) // blk_k), S // blk_k)
        for qi in range(S // blk_q))
    # The tables are the executed tiles, each once, in the order that
    # keeps every carried sum's order: rows ascending, and ascending
    # within a row.
    for kv_major in (False, True):
        qi, ki = _tile_pairs(S, blk_q, blk_k, True, kv_major)
        assert len(qi) == len(ki) == census["executed"]
        pairs = list(zip(ki, qi) if kv_major else zip(qi, ki))
        assert pairs == sorted(set(pairs))
    n_all = (S // blk_q) * (S // blk_k)
    assert len(_tile_pairs(S, blk_q, blk_k, False, False)[0]) == n_all
    assert census["executed"] + census["empty"] == n_all


@pytest.mark.parametrize("S,census", [
    (8192, {"executed": 136, "diagonal": 16, "full": 120, "empty": 120}),
    (2048, {"executed": 10, "diagonal": 4, "full": 6, "empty": 6}),
    (32768, {"executed": 2080, "diagonal": 64, "full": 2016, "empty": 2016}),
    (16384, {"executed": 528, "diagonal": 32, "full": 496, "empty": 496})])
def test_causal_tile_census_of_the_cells(S, census):
    """What the benchmark's cells run, at tiles of 512 x 512."""
    from ray_tpu.ops.flash_attention import causal_tile_census
    assert causal_tile_census(S, 512, 512) == census


# --- the forward kernel, m and l lane-dense, against the kernel it replaced

def _one_lane_forward(q, k, v, causal, blk_q, blk_k, window=None):
    """The forward kernel as it was before it kept m and l lane-dense
    (PR 34), frozen here: the statistics are [blk_q, 1] at every head
    size. Returns (out, lse)."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu
    fm = flash_mod

    def kernel(qi_tab, ki_tab, q_ref, k_ref, v_ref, o_ref, lse_ref, m_scr,
               l_scr, o_scr):
        t = pl.program_id(1)
        qi, ki = qi_tab[t], ki_tab[t]
        first, last = fm._row_ends(qi_tab)

        @pl.when(first)
        def _():
            m_scr[...] = jnp.full(m_scr.shape, fm._NEG_INF, jnp.float32)
            l_scr[...] = jnp.zeros(l_scr.shape, jnp.float32)
            o_scr[...] = jnp.zeros(o_scr.shape, jnp.float32)

        q_blk = q_ref[...].astype(jnp.float32) * scale
        k_blk = k_ref[...].astype(jnp.float32)
        v_blk = v_ref[...].astype(jnp.float32)
        logits = jax.lax.dot_general(
            q_blk, k_blk, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32)
        if causal:
            logits = jnp.where(fm._causal_mask(qi, ki, blk_q, blk_k, window),
                               logits, fm._NEG_INF)
        m = m_scr[...]
        m_new = jnp.maximum(m, logits.max(-1, keepdims=True))
        corr = jnp.exp(m - m_new)
        p = jnp.exp(logits - m_new)
        l_scr[...] = l_scr[...] * corr + p.sum(-1, keepdims=True)
        o_scr[...] = o_scr[...] * corr + jax.lax.dot_general(
            p, v_blk, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        m_scr[...] = m_new

        @pl.when(last)
        def _():
            l_safe = jnp.maximum(l_scr[...], 1e-30)
            o_ref[...] = (o_scr[...] / l_safe).astype(o_ref.dtype)
            lse_ref[...] = (m_scr[...] + jnp.log(l_safe))[:, 0][None, :]

    B, S, H, D = q.shape
    Dv = v.shape[-1]
    k, v = fm._repeat_heads(k, v, H)
    scale = 1.0 / math.sqrt(D)
    window = fm._cutting(window, S)
    qi_tab, ki_tab = fm._tile_pairs(S, blk_q, blk_k, causal, False, window)

    def q_tile(b, t, qi_tab, ki_tab):
        return b, qi_tab[t], 0

    def kv_tile(b, t, qi_tab, ki_tab):
        return b, ki_tab[t], 0

    out, lse = pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2, grid=(B * H, len(qi_tab)),
            in_specs=[pl.BlockSpec((None, blk_q, D), q_tile),
                      pl.BlockSpec((None, blk_k, D), kv_tile),
                      pl.BlockSpec((None, blk_k, Dv), kv_tile)],
            out_specs=[pl.BlockSpec((None, blk_q, Dv), q_tile),
                       pl.BlockSpec((None, 1, blk_q),
                                    lambda b, t, qi_tab, ki_tab:
                                    (b, 0, qi_tab[t]))],
            scratch_shapes=[pltpu.VMEM((blk_q, 1), jnp.float32),
                            pltpu.VMEM((blk_q, 1), jnp.float32),
                            pltpu.VMEM((blk_q, Dv), jnp.float32)]),
        out_shape=[jax.ShapeDtypeStruct((B * H, S, Dv), q.dtype),
                   jax.ShapeDtypeStruct((B * H, 1, S), jnp.float32)],
        interpret=True,
    )(jnp.asarray(qi_tab), jnp.asarray(ki_tab), fm._to_bh(q), fm._to_bh(k),
      fm._to_bh(v))
    return fm._from_bh(out, B, H), lse


def _heads(S, H, KVH, D, Dv, seed, dtype=jnp.float32):
    ks = jax.random.split(jax.random.PRNGKey(seed), 4)
    return (2.0 * jax.random.normal(ks[0], (1, S, H, D), dtype),
            2.0 * jax.random.normal(ks[1], (1, S, KVH, D), dtype),
            jax.random.normal(ks[2], (1, S, KVH, Dv), dtype),
            jax.random.normal(ks[3], (1, S, H, Dv), dtype))


# (S, H, KVH, D, Dv, causal, window, blk_q, blk_k); a name that starts with
# ``bf16`` runs in bfloat16.
FORWARD_CASES = {
    # The four head sizes; rows of 1, 2, 3 and 4 tiles: odd and even.
    "causal-64": (512, 2, 2, 64, 64, True, None, 128, 128),
    "causal-128": (512, 2, 2, 128, 128, True, None, 128, 128),
    "causal-192-128": (512, 2, 2, 192, 128, True, None, 128, 128),
    "causal-256": (512, 1, 1, 256, 256, True, None, 128, 128),
    # Rows of 1 .. 6 tiles.
    "causal-192-128-long": (768, 1, 1, 192, 128, True, None, 128, 128),
    # All pairs: every row 3 tiles (odd), 4 (even), 1.
    "all-pairs-odd": (384, 2, 2, 64, 64, False, None, 128, 128),
    "all-pairs-even": (512, 2, 2, 128, 128, False, None, 128, 128),
    "all-pairs-192-128": (640, 1, 1, 192, 128, False, None, 128, 128),
    "all-pairs-256": (256, 1, 1, 256, 256, False, None, 128, 128),
    "one-tile": (128, 2, 2, 64, 64, True, None, 128, 128),
    "one-tile-of-256": (256, 2, 1, 128, 128, True, None, 256, 256),
    "one-tile-all-pairs": (128, 1, 1, 192, 128, False, None, 128, 128),
    # Windows of 128 / 384 / 1024 at tiles of 128 and 256.
    "window-128-tiles-128": (1280, 1, 1, 128, 128, True, 128, 128, 128),
    "window-384-tiles-128": (1280, 1, 1, 128, 128, True, 384, 128, 128),
    "window-1024-tiles-128": (1280, 1, 1, 64, 64, True, 1024, 128, 128),
    "window-128-tiles-256": (1280, 1, 1, 64, 64, True, 128, 256, 256),
    "window-384-tiles-256": (1280, 1, 1, 192, 128, True, 384, 256, 256),
    "window-1024-tiles-256": (1280, 1, 1, 128, 128, True, 1024, 256, 256),
    "window-384-256": (768, 1, 1, 256, 256, True, 384, 128, 128),
    # Unequal tiles: rows of 1, 1, 2, 2 ... and of 2, 4, 6 ...
    "q-128-k-256": (1024, 1, 1, 64, 64, True, None, 128, 256),
    "q-256-k-128": (1024, 1, 1, 128, 128, True, None, 256, 128),
    "q-256-k-128-window": (1024, 1, 1, 128, 128, True, 300, 256, 128),
    "q-128-k-256-192-128": (768, 1, 1, 192, 128, True, None, 128, 256),
    # Query head i reads KV head i // (H // KVH).
    "grouped-8-over-2": (384, 8, 2, 64, 64, True, None, 128, 128),
    "grouped-6-over-2-window": (512, 6, 2, 128, 128, True, 200, 128, 128),
    "grouped-4-over-1-192-128": (384, 4, 1, 192, 128, True, None, 128, 128),
    # bfloat16 operands, as the models pass them.
    "bf16-128": (512, 2, 1, 128, 128, True, None, 128, 128),
    "bf16-192-128-window": (640, 2, 2, 192, 128, True, 256, 128, 128),
    # Head sizes that are no multiple of 128, under and over it.
    "causal-80": (384, 2, 2, 80, 80, True, None, 128, 128),
    "causal-160": (384, 2, 1, 160, 160, True, None, 128, 128),
    "causal-192-192": (512, 1, 1, 192, 192, True, None, 128, 256),
    "window-320": (640, 1, 1, 320, 320, True, 200, 128, 128),
    # Latent attention's head sizes once more: rows of 1 .. 5 tiles,
    # unequal tiles over grouped heads, and the benchmark cells' own tiles
    # of 512 x 512 (rows of one and two), causal, cut by a window and over
    # all pairs.
    "dense-192-128": (640, 1, 1, 192, 128, True, None, 128, 128),
    "dense-192-128-all-pairs": (768, 2, 1, 192, 128, False, None, 128, 256),
    "tiles-512-192-128": (1024, 2, 2, 192, 128, True, None, 512, 512),
    "tiles-512-192-128-window": (1024, 1, 1, 192, 128, True, 700, 512, 512),
    "tiles-512-192-128-all-pairs": (1024, 1, 1, 192, 128, False, None, 512,
                                    512),
    "bf16-192-128-grouped": (512, 4, 2, 192, 128, True, None, 128, 256),
}


@pytest.mark.parametrize("case", FORWARD_CASES)
def test_flash_forward_equals_the_one_lane_kernel(case):
    """``out`` and ``lse`` of the forward kernel, bit for bit what the
    kernel with one-lane statistics gave: the same operands and types, the
    same order of every sum along a row of tiles."""
    fm = flash_mod
    S, H, KVH, D, Dv, causal, window, blk_q, blk_k = FORWARD_CASES[case]
    dtype = jnp.bfloat16 if case.startswith("bf16") else jnp.float32
    q, k, v, _ = _heads(S, H, KVH, D, Dv, seed=len(case), dtype=dtype)
    out, lse = fm._flash_forward(q, k, v, causal, blk_q, blk_k, None, window)
    want_out, want_lse = _one_lane_forward(q, k, v, causal, blk_q, blk_k,
                                           window)
    assert out.dtype == want_out.dtype and out.shape == (1, S, H, Dv)
    np.testing.assert_array_equal(np.asarray(out, np.float32),
                                  np.asarray(want_out, np.float32))
    np.testing.assert_array_equal(np.asarray(lse), np.asarray(want_lse))


@pytest.mark.parametrize("case", [
    "causal-64", "causal-128", "causal-192-128-long", "causal-256",
    "all-pairs-odd", "window-384-tiles-128", "window-384-tiles-256",
    "q-256-k-128-window", "grouped-8-over-2", "grouped-6-over-2-window",
    "grouped-4-over-1-192-128", "q-128-k-256-192-128", "tiles-512-192-128"])
def test_flash_gradients_through_the_forward(case):
    """The custom VJP, its backward kernels fed by the forward's ``out``
    and ``lse``, against the dot reference's gradients."""
    from ray_tpu.models import lm
    S, H, KVH, D, Dv, causal, window, blk_q, blk_k = FORWARD_CASES[case]
    with jax.default_matmul_precision("highest"):
        q, k, v, g = _heads(S, H, KVH, D, Dv, seed=len(case))
        got_out, got_vjp = jax.vjp(
            lambda q_, k_, v_: flash_attention(q_, k_, v_, causal, blk_q,
                                               blk_k, None, window), q, k, v)
        reference = functools.partial(lm.dot_attention, window=window) \
            if causal else functools.partial(_dot_reference, causal=False)
        want_out, want_vjp = jax.vjp(reference, q, k, v)
        for name, a, b in zip(("out", "dq", "dk", "dv"),
                              (got_out,) + got_vjp(g),
                              (want_out,) + want_vjp(g)):
            assert a.shape == b.shape, name
            np.testing.assert_allclose(
                np.asarray(a), np.asarray(b), rtol=2e-4,
                atol=2e-4 * max(1.0, float(jnp.abs(b).max())), err_msg=name)


def _sp_mesh(n=4):
    devices = np.array(jax.devices("cpu")[:n])
    return jax.sharding.Mesh(devices, ("sp",))


def test_ring_attention_matches_dot():
    mesh = _sp_mesh(4)
    q, k, v = _qkv(S=128)
    ref = _dot_reference(q, k, v)
    fn = make_ring_attention(mesh, "sp")
    out = jax.jit(fn)(q, k, v)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               atol=1e-5, rtol=1e-5)


def test_ring_attention_grad():
    mesh = _sp_mesh(4)
    q, k, v = _qkv(S=64)
    fn = make_ring_attention(mesh, "sp")

    def loss_ring(q, k, v):
        return (fn(q, k, v) ** 2).sum()

    def loss_ref(q, k, v):
        return (_dot_reference(q, k, v) ** 2).sum()

    g_ref = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
    g_ring = jax.jit(jax.grad(loss_ring, argnums=(0, 1, 2)))(q, k, v)
    for a, b in zip(g_ref, g_ring):
        np.testing.assert_allclose(np.asarray(b), np.asarray(a),
                                   atol=1e-4, rtol=1e-4)


def test_ring_attention_8_devices():
    mesh = _sp_mesh(8)
    q, k, v = _qkv(B=1, S=64, H=2, D=16, seed=3)
    ref = _dot_reference(q, k, v)
    out = jax.jit(make_ring_attention(mesh, "sp"))(q, k, v)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               atol=1e-5, rtol=1e-5)


def test_model_flash_impl():
    """attn_impl='flash' produces the same logits as 'dot'."""
    from ray_tpu.models import gpt
    cfg_dot = gpt.config("gpt-tiny")
    cfg_flash = gpt.config("gpt-tiny", attn_impl="flash")
    params = gpt.init(cfg_dot, jax.random.PRNGKey(0))
    tokens = jax.random.randint(jax.random.PRNGKey(1), (2, 64), 0,
                                cfg_dot.vocab_size)
    ref = gpt.forward(params, cfg_dot, tokens)
    out = gpt.forward(params, cfg_flash, tokens)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               atol=2e-4, rtol=2e-4)


def test_train_step_ring_attention():
    """Full sharded train step with attn_impl='ring' on an sp>1 mesh
    matches the dot-attention loss."""
    import jax
    from ray_tpu.models import gpt
    from ray_tpu.parallel import MeshConfig, ShardingRules, build_mesh
    from ray_tpu.parallel.train_step import (default_optimizer,
                                             init_train_state,
                                             make_train_step)

    devices = jax.devices("cpu")[:4]
    mesh = build_mesh(MeshConfig(dp=2, fsdp=1, tp=1, sp=2), devices=devices)
    rules = ShardingRules(sequence="sp")
    opt = default_optimizer(learning_rate=1e-3)
    tokens = np.random.default_rng(0).integers(0, 256, (4, 64))
    batch = {"tokens": jnp.asarray(tokens, jnp.int32),
             "targets": jnp.asarray(tokens, jnp.int32)}

    losses = {}
    for impl in ("dot", "ring"):
        cfg = gpt.config("gpt-tiny", attn_impl=impl)
        state = init_train_state(cfg, mesh, rules, opt, seed=0)
        step = make_train_step(cfg, mesh, rules, opt)
        _, metrics = step(state, batch)
        losses[impl] = float(metrics["loss"])
    assert losses["ring"] == pytest.approx(losses["dot"], abs=1e-4)
