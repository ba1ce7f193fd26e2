"""ops/dsa.py: the indexer's scores against the sum written out, the
selection against ``jax.lax.top_k`` on whole rows (exactly ``min(t + 1, k)``
keys a row, all causal), the three kernels of the attention over a selection
(interpreted) against ``dot_attention`` with the same mask at heads of 256 |
256, the head-summed probabilities against a softmax written out, and the
indexer's loss and its gradient."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ray_tpu.models import lm
from ray_tpu.ops import dsa

B, S, H, D = 2, 256, 2, 256
HEADS, WIDTH = 32, 16   # the indexer's: with few heads whole scores tie at 0


def _indexer(seed=0, seq=S):
    ks = jax.random.split(jax.random.PRNGKey(seed), 3)
    return (jax.random.normal(ks[0], (B, seq, HEADS, WIDTH)),
            jax.random.normal(ks[1], (B, seq, WIDTH)),
            jax.random.normal(ks[2], (B, seq, HEADS)))


def _qkv(seed=1, dv=D):
    ks = jax.random.split(jax.random.PRNGKey(seed), 4)
    return (jax.random.normal(ks[0], (B, S, H, D)),
            jax.random.normal(ks[1], (B, S, H, D)),
            jax.random.normal(ks[2], (B, S, H, dv)),
            jax.random.normal(ks[3], (B, S, H, dv)))


CAUSAL = np.tril(np.ones((S, S), bool))


def _selection(topk, seed=0):
    return dsa.select(dsa.index_scores(*_indexer(seed)), topk)


@pytest.mark.parametrize("rows", [32, 256, 1000])
def test_index_scores_are_the_weighted_relu_sum_under_the_diagonal(rows):
    q, k, w = _indexer()
    got = dsa.index_scores(q, k, w, rows=rows)
    want = jnp.einsum("bqj,bqjk->bqk", w, jax.nn.relu(
        jnp.einsum("bqje,bke->bqjk", q, k)))
    np.testing.assert_allclose(np.where(CAUSAL, got, 0.0),
                               np.where(CAUSAL, want, 0.0), atol=1e-4)
    assert np.isneginf(np.asarray(got)[:, ~CAUSAL]).all()
    assert got.dtype == jnp.float32


@pytest.mark.parametrize("topk", [1, 48, 128, 255, 256, 4096])
def test_the_selection_is_top_k_of_every_causal_row(topk):
    scores = dsa.index_scores(*_indexer())
    got = np.asarray(dsa.select(scores, topk))
    assert got.dtype == np.int8 and set(np.unique(got)) <= {0, 1}
    assert (got.sum(-1) == np.minimum(np.arange(S) + 1, topk)).all()
    assert not got[:, ~CAUSAL].any()
    _, best = jax.lax.top_k(scores, min(topk, S))
    want = np.zeros((B, S, S), bool)
    np.put_along_axis(want, np.asarray(best), True, axis=-1)
    assert ((want & CAUSAL) == (got != 0)).all()


def test_the_selection_orders_negative_scores_and_zeros():
    """The bit trick's order is float32's: negatives, both zeros, and
    positives, with ``-inf`` (the masked pairs) below them all."""
    x = jnp.asarray([-jnp.inf, -3.5, -1e-30, -0.0, 0.0, 1e-30, 2.0, jnp.inf])
    bits = np.asarray(dsa._ordered_bits(x)).astype(np.int64)
    assert (np.diff(bits) >= 0).all() and bits[3] < bits[4]
    scores = jnp.where(CAUSAL, -jnp.abs(dsa.index_scores(*_indexer())) - 1.0,
                       -jnp.inf)
    got = np.asarray(dsa.select(scores, 48))
    assert (got.sum(-1) == np.minimum(np.arange(S) + 1, 48)).all()


def _masked_dot(q, k, v, selection):
    return dsa.dot_selected_attention(q, k, v, selection)[0]


@pytest.mark.parametrize("topk,dv", [(48, 256), (200, 256), (48, 128),
                                     (4096, 256)])
def test_the_forward_kernel_is_masked_attention(topk, dv):
    q, k, v, _ = _qkv(dv=dv)
    selection = _selection(topk)
    out, lse = dsa.selected_attention(q, k, v, selection, 128, 128, None)
    want, want_lse = dsa.dot_selected_attention(q, k, v, selection)
    np.testing.assert_allclose(out, want, atol=2e-5)
    np.testing.assert_allclose(lse, want_lse, atol=2e-5)
    if topk >= S:  # every causal key: plain causal attention
        np.testing.assert_allclose(out, lm.dot_attention(q, k, v), atol=2e-5)


@pytest.mark.parametrize("wrt", [0, 1, 2], ids=["dq", "dk", "dv"])
@pytest.mark.parametrize("topk", [48, 200])
def test_the_backward_kernels_are_masked_attentions_gradients(topk, wrt):
    q, k, v, g = _qkv()
    selection = _selection(topk)
    got = jax.grad(lambda *a: (dsa.selected_attention(
        *a, selection, 128, 128, None)[0] * g).sum(), wrt)(q, k, v)
    want = jax.grad(lambda *a: (_masked_dot(*a, selection) * g).sum(),
                    wrt)(q, k, v)
    np.testing.assert_allclose(got, want, atol=5e-5 * float(
        jnp.abs(want).max()) + 1e-6)


def test_a_tile_with_nothing_selected_is_passed_over():
    """Every query keeps the first 16 keys alone: the diagonal tile of the
    second row of tiles selects nothing, its flag is 0, and the kernels give
    what the mask says."""
    q, k, v, g = _qkv()
    selection = jnp.broadcast_to(
        (CAUSAL & (np.arange(S) < 16)[None, :]).astype(jnp.int8), (B, S, S))
    pairs = dsa._tile_pairs(S, 128, 128, True, False)
    assert list(np.asarray(dsa._live(selection, pairs, 128, 128))) == [1, 1, 0]
    out, lse = dsa.selected_attention(q, k, v, selection, 128, 128, None)
    want, want_lse = dsa.dot_selected_attention(q, k, v, selection)
    np.testing.assert_allclose(out, want, atol=2e-5)
    np.testing.assert_allclose(lse, want_lse, atol=2e-5)
    for wrt in range(3):
        got = jax.grad(lambda *a: (dsa.selected_attention(
            *a, selection, 128, 128, None)[0] * g).sum(), wrt)(q, k, v)
        want = jax.grad(lambda *a: (_masked_dot(*a, selection) * g).sum(),
                        wrt)(q, k, v)
        np.testing.assert_allclose(got, want, atol=1e-4)


def test_the_selection_and_lse_carry_no_cotangent():
    q, k, v, _ = _qkv()
    selection = _selection(48)
    grads = jax.grad(lambda q: dsa.selected_attention(
        q, k, v, selection, 128, 128, None)[1].sum())(q)
    assert not np.asarray(grads).any()


@pytest.mark.parametrize("topk", [48, 200])
def test_head_probs_are_the_softmax_summed_over_heads(topk):
    q, k, v, _ = _qkv()
    selection = _selection(topk)
    _, lse = dsa.selected_attention(q, k, v, selection, 128, 128, None)
    got = dsa.head_probs(q, k, lse, selection, 128, 128)
    logits = jnp.einsum("bqhd,bkhd->bhqk", q, k) / np.sqrt(D)
    want = jax.nn.softmax(jnp.where(selection[:, None] != 0, logits,
                                    -jnp.inf), axis=-1).sum(1)
    np.testing.assert_allclose(got, want, atol=2e-5)
    np.testing.assert_allclose(got.sum(-1), H, rtol=1e-5)
    np.testing.assert_allclose(dsa.dot_head_probs(q, k, lse, selection),
                               want, atol=2e-5)


def test_the_index_loss_is_the_kl_and_its_gradient_the_difference():
    scores = dsa.index_scores(*_indexer())
    selection = dsa.select(scores, 48)
    probs = jnp.where(selection != 0, jax.random.uniform(
        jax.random.PRNGKey(5), scores.shape), 0.0)
    chosen = np.asarray(selection) != 0
    p = np.asarray(probs, np.float64)
    p = p / p.sum(-1, keepdims=True)
    masked = np.where(chosen, np.asarray(scores, np.float64), -np.inf)
    log_q = masked - np.log(np.exp(masked - masked.max(-1, keepdims=True))
                            .sum(-1, keepdims=True)) \
        - masked.max(-1, keepdims=True)
    with np.errstate(divide="ignore", invalid="ignore"):
        terms = np.where(chosen & (p > 0), p * (np.log(p) - log_q), 0.0)
    got = dsa.index_loss(scores, probs, selection)
    np.testing.assert_allclose(got, terms.sum(-1).mean(-1), rtol=1e-5)
    grad = jax.grad(lambda s: dsa.index_loss(s, probs, selection).sum())(
        jnp.where(CAUSAL, scores, 0.0))
    np.testing.assert_allclose(
        grad, np.where(chosen, np.exp(log_q) - p, 0.0) / S, atol=1e-6)


def test_a_ragged_sequence_is_refused():
    q, k, v, _ = _qkv()
    with pytest.raises(ValueError, match="multiple of 128"):
        dsa.selected_attention(q[:, :200], k[:, :200], v[:, :200],
                               jnp.ones((B, 200, 200), jnp.int8), 128, 128,
                               None)
