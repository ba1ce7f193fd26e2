"""models/evabyte.py (EVA attention: an exact softmax inside a block window
and, under the same softmax, a learned summary of every chunk before it;
several prediction heads on one hidden state) against a copy of the
benchmark's plain reference, which attends over the explicit ``[S, S /
chunk + S]`` mask; the heads' targets and mask against a loop written out;
one head against ``next_token_loss``; the step's kernels and gauges.

Everything runs on the CPU at the tiny preset in float32 under the highest
matmul precision, the kernels interpreted, where both sides compute the same
sums in another order: tolerances of 1e-3 of the logits' RMS and 1e-4 of a
gradient leaf's norm leave room for float32 reassociation and nothing else.
"""

import os
from dataclasses import replace
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import reference_evabyte as reference
from family_cases import Family, batch, compared, drawn
from ray_tpu.models import evabyte, lm
from ray_tpu.parallel import MeshConfig, build_mesh
from ray_tpu.parallel.collectives import kernel_census
from ray_tpu.parallel.train_step import init_train_state, make_train_step
from ray_tpu.util import metrics as metrics_mod

CFG = evabyte.config("evabyte-tiny")
SEQ = 256   # four windows of 64; the last sees 24 summaries of 8
FLASH = replace(CFG, attn_impl="flash", attn_blk_q=128, attn_blk_k=128)
HERE = os.path.dirname(os.path.abspath(__file__))


def published(cfg):
    return {"window_size": cfg.window_size, "chunk_size": cfg.chunk_size,
            "rope_theta": cfg.rope_theta, "rms_norm_eps": cfg.rms_norm_eps,
            "num_pred_heads": cfg.num_pred_heads}


def moved(name, leaf, key):
    """Every norm's offset off zero, ``phi`` and ``mu`` large enough that
    pooling is not the mean and ``mu`` not nothing, and W_q, W_k larger: at
    ``init_std`` every softmax is flat, and a wrong mask would move
    nothing."""
    if name.endswith("_scale']"):
        return leaf + 0.1 * jax.random.normal(key, leaf.shape)
    if name.endswith(("['wq']", "['wk']")):
        return leaf * 12.0
    if name.endswith(("['eva_phi']", "['eva_mu']")):
        return leaf * 4.0
    return leaf


EVA = Family(module=evabyte, reference=reference, cfg=CFG, seq=SEQ,
             published=published, moved=moved)


@pytest.fixture(scope="module")
def params():
    return drawn(EVA, CFG)


@pytest.fixture(scope="module")
def want(params):
    """The reference over the whole mask: all heads' logits, every head's
    loss and their mean, a sequence at a time."""
    tokens, targets = batch(CFG, SEQ)
    kw = reference.arguments(published(CFG))
    logits, head_losses, losses = jax.jit(lambda p: (
        reference.logits(p, tokens, **kw),
        reference.head_losses(p, tokens, targets, **kw),
        [reference.loss(p, tokens[row:row + 1], targets[row:row + 1], **kw)
         for row in range(2)]))(params)
    return {"logits": logits, "head_losses": head_losses, "losses": losses}


@pytest.mark.parametrize("cfg", [CFG, FLASH], ids=["dot", "flash"])
def test_model_matches_reference(cfg, want):
    """All heads' logits within 1e-3 of their RMS, every head's loss and
    their mean, every gradient leaf; ``phi`` and ``mu`` with gradient that
    is not zero."""
    found = compared(EVA, cfg, SEQ, reference_of=CFG)
    logits, want_logits = found["logits"]
    assert logits.shape == (2, SEQ, 4, CFG.vocab_size)
    assert logits.dtype == jnp.float32
    assert float(jnp.abs(logits.reshape(want_logits.shape) - want_logits
                         ).max()) < 1e-3 * found["rms"]
    (loss, want_loss), metrics = found["loss"], found["metrics"]
    assert abs(float(loss) - float(want_loss)) < 1e-5
    assert abs(float(metrics["total_loss"]) - float(loss)) == 0.0
    for i in range(4):
        assert abs(float(metrics[f"mbp_loss_{i}"])
                   - float(want["head_losses"][i])) < 1e-5
    assert float(metrics["loss"]) == float(metrics["mbp_loss_0"])
    grads, want_grads = found["grads"]
    ref = dict(jax.tree_util.tree_leaves_with_path(want_grads))
    for path, leaf in jax.tree_util.tree_leaves_with_path(grads):
        norm = float(jnp.linalg.norm(ref[path]))
        assert norm > 0, path
        assert float(jnp.linalg.norm(leaf - ref[path])) < 1e-4 * norm, path
    for name in ("eva_phi", "eva_mu"):
        assert float(jnp.abs(grads["run00_eva"][name]).max()) > 0


def test_reference_by_stretches_is_the_whole_mask(params, want):
    """``reference.forward`` (a window at a time, as it runs at the timed
    size) against ``reference.logits`` and ``reference.loss`` over the
    whole mask."""
    tokens, targets = batch(CFG, SEQ)
    where = jnp.asarray([[0, 63, 64, 200, 255], [5, 100, 128, 254, 255]])
    kw = reference.arguments(published(CFG))
    sampled, loss, rms = reference.forward(params, tokens, targets, where,
                                           **kw)
    whole = want["logits"].reshape(2, SEQ, -1)
    picked = jnp.take_along_axis(whole, where[..., None], axis=1)
    np.testing.assert_allclose(sampled, picked, atol=2e-5)
    np.testing.assert_allclose(rms, jnp.sqrt((whole ** 2).mean()),
                               rtol=1e-5)
    np.testing.assert_allclose(loss, jnp.stack(want["losses"]), atol=1e-5)


@pytest.mark.parametrize("masked", [False, True])
def test_shifted_targets_against_a_loop(masked):
    targets = jnp.asarray(np.random.default_rng(1).integers(
        0, 320, (2, 12), dtype=np.int32))
    mask = jnp.asarray(np.random.default_rng(2).integers(0, 2, (2, 12))) \
        if masked else None
    got_t, got_m = lm.shifted_targets(targets, mask, 4)
    assert got_t.shape == got_m.shape == (2, 12, 4)
    for b in range(2):
        for t in range(12):
            for i in range(4):
                inside = t + i < 12
                keep = inside and (mask is None or int(mask[b, t + i]))
                assert float(got_m[b, t, i]) == float(keep)
                if inside:
                    assert int(got_t[b, t, i]) == int(targets[b, t + i])


@pytest.mark.parametrize("chunk", [0, 64])
def test_one_head_is_next_token_loss_bit_for_bit(chunk):
    key = jax.random.PRNGKey(0)
    x = jax.random.normal(key, (2, 128, 32))
    w = jax.random.normal(jax.random.fold_in(key, 1), (32, 50)) * 0.3
    targets = jax.random.randint(jax.random.fold_in(key, 2), (2, 128), 0, 50)
    mask = (jax.random.uniform(jax.random.fold_in(key, 3), (2, 128)) > 0.2)

    def one(w):
        return lm.next_token_loss(lambda x: x @ w, x, targets, mask, chunk,
                                  0.0)

    def several(w):
        return lm.multi_token_loss(lambda x: (x @ w)[..., None, :], x,
                                   targets, mask, chunk, 1)

    (a, m_a), g_a = jax.value_and_grad(one, has_aux=True)(w)
    (b, m_b), g_b = jax.value_and_grad(several, has_aux=True)(w)
    assert float(a) == float(b)
    for name in ("loss", "accuracy", "perplexity"):
        assert float(m_a[name]) == float(m_b[name])
    assert float(m_b["mbp_loss_0"]) == float(a)
    assert (np.asarray(g_a) == np.asarray(g_b)).all()


def test_chunked_heads_are_the_unchunked(params):
    """The chunked walk over (position, head) rows against the whole
    logits: the loss, every head's, and the gradients."""
    tokens, targets = batch(CFG, SEQ)

    def run(chunk):
        cfg = replace(CFG, loss_chunk=chunk)
        return jax.jit(jax.value_and_grad(
            lambda p: evabyte.loss_fn(p, cfg, tokens, targets),
            has_aux=True))(params)

    (a, m_a), g_a = run(0)
    (b, m_b), g_b = run(128)
    assert abs(float(a) - float(b)) < 1e-6
    for name in m_a:
        np.testing.assert_allclose(m_a[name], m_b[name], atol=1e-6,
                                   rtol=1e-5, err_msg=name)
    for x, y in zip(jax.tree.leaves(g_a), jax.tree.leaves(g_b)):
        np.testing.assert_allclose(x, y, atol=1e-6)


def test_step_kernels_gauges_and_falling_loss():
    """A train step through ``make_train_step``: the step's census counts
    the ``eva_*`` kernels (the forward twice under remat: 24 + 64 keys a
    query are under ``worth_keeping``'s 32 x 32) and no ``flash_*``; the
    loss falls; the gauges read what the tables say."""
    cfg = replace(FLASH, remat=True, loss_chunk=128)
    mesh = build_mesh(MeshConfig(dp=1, fsdp=1, tp=1),
                      devices=jax.devices()[:1])
    state = init_train_state(cfg, mesh, seed=0)
    step = make_train_step(cfg, mesh)
    tokens, targets = batch(CFG, SEQ, rows=1)
    census = kernel_census(jax.make_jaxpr(
        lambda p: jax.grad(lambda p: evabyte.loss_fn(
            p, cfg, tokens, targets)[0])(p))(state["params"]), a_step=True)
    assert census == {"eva_fwd": 4, "eva_bwd_dq": 2, "eva_bwd_dkv": 2}
    losses = []
    for _ in range(3):
        state, metrics = step(state, {"tokens": tokens, "targets": targets})
        losses.append(float(metrics["total_loss"]))
    state, metrics = step(state, {"tokens": tokens, "targets": targets})
    assert losses[-1] < losses[0]
    assert abs(losses[0] - np.log(CFG.vocab_size)) < 0.5
    series = {e["name"]: e["series"] for e in metrics_mod.snapshot()}
    share = list(series["ray_tpu_train_eva_pairs_share"].values())[0]
    assert abs(share - 11392 / 32896) < 1e-6
    mass = list(series["ray_tpu_train_eva_summary_mass"].values())[0]
    assert 0.0 < mass < 1.0
    heads = series["ray_tpu_train_mbp_loss"]
    assert len(heads) == 4  # the tiny preset's four heads, none of the rest
    assert all(abs(v - np.log(CFG.vocab_size)) < 0.5 for v in heads.values())


def test_kernels_per_shard_of_a_mesh(params):
    """Under a mesh the kernels run per shard of the batch and the heads:
    the same loss as on one device."""
    from ray_tpu.parallel import mesh as mesh_mod
    tokens, targets = batch(CFG, SEQ)
    want = compared(EVA, FLASH, SEQ, reference_of=CFG)["loss"][0]
    mesh = build_mesh(MeshConfig(dp=2, fsdp=1, tp=2),
                      devices=jax.devices()[:4])
    previous = mesh_mod.current_mesh()
    mesh_mod.set_current_mesh(mesh)
    try:
        got = jax.jit(lambda p: evabyte.loss_fn(p, FLASH, tokens,
                                                targets)[0])(params)
    finally:
        mesh_mod.set_current_mesh(previous)
    assert abs(float(got) - float(want)) < 1e-5


def test_config_refuses_what_the_layer_cannot_compute():
    with pytest.raises(ValueError, match="KV heads"):
        replace(CFG, num_key_value_heads=2)
    with pytest.raises(ValueError, match="remainder"):
        replace(CFG, window_size=60)
    with pytest.raises(ValueError, match="prediction heads"):
        replace(CFG, num_pred_heads=9)


def test_reference_copy_is_the_benchmarks():
    with open(os.path.join(HERE, "reference_evabyte.py")) as mine, \
            open(os.path.join(HERE, os.pardir, "benchmark", "reference",
                              "evabyte.py")) as theirs:
        assert mine.read() == theirs.read()


def test_published_count_of_parameters():
    """202.39 M a layer and 6.49 B for the 32 layers, by the shapes the
    program makes."""
    cfg = evabyte.config("evabyte-6.5b")
    shapes = jax.eval_shape(partial(evabyte.init, cfg),
                            jax.random.PRNGKey(0))
    layer = sum(int(np.prod(a.shape[1:]))
                for a in jax.tree.leaves(shapes["run00_eva"]))
    assert layer == 202_391_552
    total = sum(int(np.prod(a.shape)) for a in jax.tree.leaves(shapes))
    assert total == 32 * layer + 320 * 4096 + 4096 + 4096 * 8 * 320
    assert round(total / 1e9, 2) == 6.49
