"""ops/dsa.py: the indexer's scores in their ``jax.numpy`` form against the
sum written out, its kernels (interpreted) against that form, values and
gradients, the
selection against ``jax.lax.top_k`` on whole rows (exactly ``min(t + 1, k)``
keys a row, all causal), the three kernels of the attention over a selection
(interpreted) against ``dot_attention`` with the same mask at heads of 256 |
256, the head-summed probabilities against a softmax written out, the indexer's
loss and its gradient, and what a rematerialised block that holds all of
them keeps by name (``lm.rematerialised``): the loss's own rule against
autodiff without remat, and the calls its backward pass makes."""

import sys
from types import SimpleNamespace

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.ad_checkpoint import checkpoint_name

from ray_tpu.models import lm
from ray_tpu.ops import dsa
from ray_tpu.parallel.collectives import kernel_census

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
    return dsa.select(dsa.dot_index_scores(*_indexer(seed)), topk)


@pytest.mark.parametrize("rows", [32, 256, 1000])
def test_index_scores_are_the_weighted_relu_sum_under_the_diagonal(rows):
    q, k, w = _indexer()
    got = dsa.dot_index_scores(q, k, w, rows=rows)
    want = jnp.einsum("bqj,bqjk->bqk", w, jax.nn.relu(
        jnp.einsum("bqje,bke->bqjk", q, k)))
    np.testing.assert_allclose(np.where(CAUSAL, got, 0.0),
                               np.where(CAUSAL, want, 0.0), atol=1e-4)
    assert np.isneginf(np.asarray(got)[:, ~CAUSAL]).all()
    assert got.dtype == jnp.float32


#: (heads, width, length, what ``_index_fwd_blocks`` and
#: ``_index_bwd_blocks`` give, None for their own choice): one head on one
#: tile, the diagonal alone; several tiles of the module's own sizes (``blk_q
#: != blk_k`` forward; three heads a head a turn of the loop, four heads all
#: in one); tiles, pieces and chunks of heads that no length under a
#: thousand would be given; heads of the chip's width.
INDEX_CASES = {
    "one_head_one_tile": (1, 16, 128, None, None),
    "two_tiles": (3, 16, 256, None, None),
    "own_tiles_of_512": (4, 8, 512, None, None),
    "narrow_tiles_heads_in_chunks": (4, 16, 512, (128, 256, 128, 2),
                                     (128, 256, 2)),
    "wide_queries_a_head_a_step": (2, 16, 384, (128, 128, 128, 1),
                                   (384, 128, 1)),
    "heads_of_128": (2, 128, 256, None, None),
}


@pytest.fixture(params=list(INDEX_CASES))
def index_case(request, monkeypatch):
    """(q, k, w, the causal mask) of a case, the case's tiles in place of
    the module's."""
    heads, width, seq, forward, backward = INDEX_CASES[request.param]
    if forward:
        monkeypatch.setattr(dsa, "_index_fwd_blocks", lambda *a: forward)
    if backward:
        monkeypatch.setattr(dsa, "_index_bwd_blocks", lambda *a: backward)
    ks = jax.random.split(jax.random.PRNGKey(seq + heads), 3)
    rows = B if seq == 256 else 1
    return (jax.random.normal(ks[0], (rows, seq, heads, width)),
            jax.random.normal(ks[1], (rows, seq, width)),
            jax.random.normal(ks[2], (rows, seq, heads)),
            np.tril(np.ones((seq, seq), bool)))


def test_the_index_kernel_is_the_sum_written_out(index_case):
    q, k, w, causal = index_case
    got = dsa.index_scores(q, k, w)
    want = dsa.dot_index_scores(q, k, w)
    assert got.dtype == jnp.float32
    assert np.isneginf(np.asarray(got)[:, ~causal]).all()
    np.testing.assert_allclose(np.where(causal, got, 0.0),
                               np.where(causal, want, 0.0), atol=1e-4)


_INDEX_GRADS = {}


@pytest.mark.parametrize("wrt", [0, 1, 2], ids=["dq", "dk", "dw"])
def test_the_index_kernels_gradients_are_the_sums(index_case, wrt, request):
    """Of the indexer's own loss, which masks the scores to a selection
    before it reads them: the cotangent is zero off the selection, above
    the diagonal too. A case's three gradients are one backward pass, made
    by the case's first test."""
    q, k, w, causal = index_case
    case = request.node.callspec.params["index_case"]
    if case not in _INDEX_GRADS:
        # Two keys of three under the diagonal, and the diagonal.
        at = np.arange(causal.shape[0])
        selection = jnp.broadcast_to(causal & (
            ((at[:, None] + at[None, :]) % 3 > 0)
            | (at[:, None] == at[None, :])), (q.shape[0],) + causal.shape)
        probs = jnp.where(selection, jax.random.uniform(
            jax.random.PRNGKey(5), selection.shape), 0.0)
        _INDEX_GRADS[case] = [jax.grad(lambda *a: dsa.index_loss(
            scores(*a), probs, selection).sum(), (0, 1, 2))(q, k, w)
            for scores in (dsa.index_scores, dsa.dot_index_scores)]
    got, want = (grads[wrt] for grads in _INDEX_GRADS[case])
    assert got.dtype == want.dtype and np.isfinite(np.asarray(got)).all()
    np.testing.assert_allclose(got, want, atol=1e-4 * float(
        jnp.abs(want).max()) + 1e-7)


def test_the_index_kernels_refuse_what_they_cannot_tile(monkeypatch):
    q, k, w = _indexer(seq=200)
    with pytest.raises(ValueError, match="multiple of 128"):
        dsa.index_scores(q, k, w)
    with pytest.raises(ValueError, match="multiple of 128"):
        jax.grad(lambda q: dsa.index_scores(q, k, w).sum())(q)
    # Heads of 16 are the interpreter's alone: the chip's compiler wants
    # whole lanes.
    monkeypatch.setattr(sys.modules["ray_tpu.ops.flash_attention"],
                        "_interpret", lambda: False)
    q, k, w = _indexer()
    with pytest.raises(ValueError, match="head width"):
        dsa.index_scores(q, k, w)


@pytest.mark.parametrize("topk", [1, 48, 128, 255, 256, 4096])
def test_the_selection_is_top_k_of_every_causal_row(topk):
    scores = dsa.dot_index_scores(*_indexer())
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
    scores = jnp.where(CAUSAL,
                       -jnp.abs(dsa.dot_index_scores(*_indexer())) - 1.0,
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
    scores = dsa.dot_index_scores(*_indexer())
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


def _kl(scores, probs, selection):
    """The indexer's loss as autodiff reads it, with no rule of its own."""
    chosen = selection != 0
    masked = jnp.where(chosen, scores, -jnp.inf)
    log_q = masked - jax.scipy.special.logsumexp(masked, -1, keepdims=True)
    p = jax.lax.stop_gradient(probs)
    p = p / jnp.maximum(p.sum(-1, keepdims=True), 1e-30)
    terms = jnp.where(chosen & (p > 0),
                      p * (jnp.log(jnp.maximum(p, 1e-38))
                           - jnp.where(chosen, log_q, 0.0)), 0.0)
    return terms.sum(-1).mean(-1)


def _indexed_block(kernels: bool, loss):
    """A layer that owns an indexer, as ``models/glm_moe_dsa.py`` and
    ``models/dots3_note.py`` write it: (iq, ik, iw, q, k, v) -> (the main
    attention's output, the indexer's loss [B])."""
    def block(iq, ik, iw, q, k, v):
        scores = (dsa.index_scores if kernels
                  else dsa.dot_index_scores)(iq, ik, iw)
        selection = checkpoint_name(
            dsa.select(jax.lax.stop_gradient(scores), 48),
            dsa.SELECTION_NAME)
        if kernels:
            out, lse = dsa.selected_attention(q, k, v, selection, 128, 128,
                                              None)
        else:
            out, lse = dsa.dot_selected_attention(q, k, v, selection)
        target = [jax.lax.stop_gradient(a) for a in (q, k, lse)]
        probs = dsa.head_probs(*target, selection, 128, 128) if kernels \
            else dsa.dot_head_probs(*target, selection)
        return out, loss(scores, probs, selection)
    return block


def _block_total(block):
    """A scalar of both outputs, the batch rows' losses weighted apart."""
    def total(*operands):
        out, loss = block(*operands)
        return jnp.square(out).mean() + (loss * jnp.array([1.0, 2.5])).sum()
    return total


_FULL = SimpleNamespace(remat=True, remat_policy="full")
_BLOCK_GRADS = {}


def _block_operands():
    return (*_indexer(), *_qkv()[:3])


@pytest.mark.parametrize("wrt", [0, 1, 2], ids=["dq", "dk", "dw"])
@pytest.mark.parametrize("kernels", [True, False], ids=["kernels", "dot"])
def test_a_rematerialised_blocks_index_gradients_are_autodiffs(kernels, wrt):
    """The loss's own rule under ``lm.rematerialised`` against autodiff of
    the loss written out, without remat: the value, and the gradient by the
    indexer's q, k and w. An implementation's three gradients are one
    backward pass each side, made by its first test."""
    if kernels not in _BLOCK_GRADS:
        operands = _block_operands()
        _BLOCK_GRADS[kernels] = [jax.value_and_grad(
            _block_total(block), (0, 1, 2))(*operands) for block in (
                lm.rematerialised(_FULL, _indexed_block(
                    kernels, dsa.index_loss)),
                _indexed_block(kernels, _kl))]
    (got_value, got), (want_value, want) = _BLOCK_GRADS[kernels]
    np.testing.assert_allclose(got_value, want_value, rtol=1e-6)
    assert np.abs(np.asarray(want[wrt])).max() > 1e-3
    np.testing.assert_allclose(got[wrt], want[wrt], atol=1e-6 * float(
        jnp.abs(want[wrt]).max()))


def test_a_rematerialised_block_runs_the_loss_once():
    """What ``"full"`` keeps by name of a layer with an indexer: the
    selection and the loss's gradient by the scores. The backward pass then
    holds neither the head-summed probabilities nor the scores' forward
    kernel a second time (the main attention's forward kernel it does, at a
    length below ``worth_keeping``); without the loss's name it holds
    both."""
    operands = _block_operands()
    block = _indexed_block(True, dsa.index_loss)

    def calls(rematerialised):
        return kernel_census(jax.make_jaxpr(jax.grad(
            _block_total(rematerialised), (0, 1, 2, 3, 4, 5)))(*operands))

    assert calls(lm.rematerialised(_FULL, block)) == {
        "dsa_index_fwd": 1, "dsa_index_bwd": 1, "dsa_probs": 1,
        "dsa_fwd": 2, "dsa_bwd_dq": 1, "dsa_bwd_dkv": 1}
    selection_alone = jax.checkpoint(
        block, policy=jax.checkpoint_policies.save_only_these_names(
            dsa.SELECTION_NAME))
    assert calls(selection_alone) == {
        "dsa_index_fwd": 2, "dsa_index_bwd": 1, "dsa_probs": 2,
        "dsa_fwd": 2, "dsa_bwd_dq": 1, "dsa_bwd_dkv": 1}


def test_the_index_losss_cotangent_scales_a_batch_row():
    scores = dsa.dot_index_scores(*_indexer())
    selection = dsa.select(scores, 48)
    probs = jnp.where(selection != 0, jax.random.uniform(
        jax.random.PRNGKey(5), scores.shape), 0.0)
    loss, pulled = jax.vjp(lambda s: dsa.index_loss(s, probs, selection),
                           scores)
    assert loss.shape == (B,)
    ones, = pulled(jnp.ones(B))
    got, = pulled(jnp.array([3.0, -0.5]))
    assert np.asarray(ones)[:, 1:].any(-1).all()   # row 0 has one key
    np.testing.assert_array_equal(got[0], 3.0 * ones[0])
    np.testing.assert_array_equal(got[1], -0.5 * ones[1])
    want, = jax.vjp(lambda s: _kl(s, probs, selection),
                    scores)[1](jnp.array([3.0, -0.5]))
    np.testing.assert_allclose(got, want, atol=1e-6 * float(
        jnp.abs(want).max()))


def test_a_family_without_an_indexer_lowers_to_what_it_did(monkeypatch):
    """One more name in the policy changes no program that does not emit
    it: a ``deepseek_v3`` model's gradient lowers to the text it lowers to
    under the policy without ``LOSS_GRADIENT_NAME``."""
    from ray_tpu.models import deepseek
    from ray_tpu.ops.flash_attention import RESIDUAL_NAMES
    cfg = deepseek.config("deepseek-tiny", remat=True)
    params = jax.eval_shape(lambda: deepseek.init(cfg,
                                                  jax.random.PRNGKey(0)))
    tokens = jax.ShapeDtypeStruct((2, 64), jnp.int32)

    def lowered():
        return jax.jit(jax.grad(lambda p, t: deepseek.loss_fn(
            p, cfg, t, t)[0])).lower(params, tokens).as_text()

    got = lowered()
    monkeypatch.setattr(lm, "rematerialised", lambda cfg, block: (
        jax.checkpoint(
            block, policy=jax.checkpoint_policies.save_only_these_names(
                *RESIDUAL_NAMES, dsa.SELECTION_NAME))))
    assert dsa.LOSS_GRADIENT_NAME not in got and got == lowered()


def test_a_ragged_sequence_is_refused():
    q, k, v, _ = _qkv()
    with pytest.raises(ValueError, match="multiple of 128"):
        dsa.selected_attention(q[:, :200], k[:, :200], v[:, :200],
                               jnp.ones((B, 200, 200), jnp.int8), 128, 128,
                               None)
