"""The plain reference against ``ray_tpu/models/gpt.py`` at a tiny width on
the CPU, in float32 with plain attention: the same equations, so they agree
to rounding, with every bias and LayerNorm vector drawn away from its
init."""

import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import harness
from ray_tpu.models import gpt

reference = harness.load_module("reference", "gptj")

CFG = gpt.config("gptj-6b", vocab_size=384, n_layers=3, d_model=128,
                 n_heads=4, d_ff=512, rotary_dim=16, max_seq_len=64,
                 dtype=jnp.float32, param_dtype=jnp.float32, remat=False,
                 attn_impl="dot")


@pytest.fixture(scope="module")
def case():
    params = gpt.init(CFG, jax.random.PRNGKey(0))
    leaves, treedef = jax.tree.flatten(params)
    keys = jax.random.split(jax.random.PRNGKey(1), len(leaves))
    params = jax.tree.unflatten(treedef, [
        leaf + 0.05 * jax.random.normal(k, leaf.shape) if leaf.ndim <= 2
        and leaf.size < 4096 else leaf for leaf, k in zip(leaves, keys)])
    # At width 128 an init of 0.02 leaves attention nearly uniform; at 4096
    # it does not. Sharpen q and k so that the pattern matters here too.
    params["layers"] = dict(params["layers"],
                            wq=params["layers"]["wq"] * 6.0,
                            wk=params["layers"]["wk"] * 6.0)
    rows = np.random.default_rng(0).integers(0, CFG.vocab_size, (2, 65))
    tokens, targets = jnp.asarray(rows[:, :-1]), jnp.asarray(rows[:, 1:])
    return params, tokens, targets


def test_logits_and_loss_agree(case):
    params, tokens, targets = case
    where = jnp.asarray(np.tile(np.arange(0, 64, 4), (2, 1)), jnp.int32)
    want, want_loss, rms = reference.forward(
        params, tokens, targets, where, rotary_dim=CFG.rotary_dim)
    with jax.default_matmul_precision("highest"):
        got = jnp.take_along_axis(gpt.forward(params, CFG, tokens),
                                  where[..., None], axis=1)
        got_loss = [gpt.loss_fn(params, CFG, tokens, targets,
                                mask=jnp.zeros((2, 64)).at[i].set(1.0))[0]
                    for i in range(2)]
    # float32 on both sides, summed in different orders.
    assert float(jnp.abs(got - want).max()) / float(rms) < 1e-4
    np.testing.assert_allclose(np.asarray(got_loss), np.asarray(want_loss),
                               atol=1e-5)


def test_a_dropped_term_fails(case):
    """The tolerance means something: without the FFN's output bias, or
    with the program's rotary pairing taken for the published one, the
    same comparison is off by orders of magnitude more."""
    params, tokens, targets = case
    where = jnp.zeros((2, 1), jnp.int32) + 63
    want, _, rms = reference.forward(params, tokens, targets, where,
                                     rotary_dim=CFG.rotary_dim)
    broken = dict(params, layers=dict(
        params["layers"], b_out=jnp.zeros_like(params["layers"]["b_out"])))
    got, _, _ = reference.forward(broken, tokens, targets, where,
                                  rotary_dim=CFG.rotary_dim)
    assert float(jnp.abs(got - want).max()) / float(rms) > 1e-2
    unpermuted = reference._published_heads
    reference._published_heads = lambda w, rotary_dim: w
    try:
        reference._block_at.clear_cache()
        got, _, _ = reference.forward(params, tokens, targets, where,
                                      rotary_dim=CFG.rotary_dim)
    finally:
        reference._published_heads = unpermuted
        reference._block_at.clear_cache()
    assert float(jnp.abs(got - want).max()) / float(rms) > 1e-2


def test_gradients_agree(case):
    params, tokens, targets = case
    want = jax.grad(reference.loss)(params, tokens, targets,
                                    rotary_dim=CFG.rotary_dim)
    with jax.default_matmul_precision("highest"):
        got = jax.grad(lambda p: gpt.loss_fn(p, CFG, tokens, targets)[0])(
            params)
    flat_want = jax.tree_util.tree_leaves_with_path(want)
    flat_got = jax.tree.leaves(got)
    for (path, w), g in zip(flat_want, flat_got):
        scale = float(jnp.abs(w).max()) + 1e-12
        assert float(jnp.abs(g - w).max()) / scale < 1e-3, \
            jax.tree_util.keystr(path)


def test_reference_does_not_import_the_program():
    path = os.path.join(harness.HERE, "reference", "gptj.py")
    with open(path) as f:
        source = f.read()
    assert "import ray_tpu" not in source and "from ray_tpu" not in source
