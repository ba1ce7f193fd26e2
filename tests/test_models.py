"""Model-zoo tests: GPT's grouped-query variants, ViT, diffusion UNet."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ray_tpu.models import diffusion, gpt, vit
from ray_tpu.parallel import MeshConfig, build_mesh
from ray_tpu.parallel.sharding import (ShardingRules, shard_tree,
                                       tp_fsdp_rules)


# -- GPT with grouped-query attention ------------------------------------
# (the plain decoder is covered in test_model_parallel.py)

GQA = gpt.config("gpt-tiny", n_kv_heads=2)


def test_gqa_forward_shape():
    params = gpt.init(GQA, jax.random.PRNGKey(0))
    tokens = jnp.zeros((2, 16), jnp.int32)
    logits = gpt.forward(params, GQA, tokens)
    assert logits.shape == (2, 16, GQA.vocab_size)
    assert np.isfinite(np.asarray(logits, np.float32)).all()


def test_gqa_causality():
    params = gpt.init(GQA, jax.random.PRNGKey(1))
    rng = np.random.default_rng(0)
    toks = rng.integers(0, GQA.vocab_size, (1, 12))
    a = gpt.forward(params, GQA, jnp.asarray(toks, jnp.int32))
    toks2 = toks.copy()
    toks2[0, -1] = (toks2[0, -1] + 1) % GQA.vocab_size
    b = gpt.forward(params, GQA, jnp.asarray(toks2, jnp.int32))
    # Changing the last token must not affect logits at earlier positions.
    np.testing.assert_allclose(np.asarray(a[0, :-1]), np.asarray(b[0, :-1]),
                               atol=1e-5)


def test_gqa_param_count_matches_init():
    params = gpt.init(GQA, jax.random.PRNGKey(0))
    actual = sum(x.size for x in jax.tree.leaves(params))
    assert actual == GQA.num_params() < gpt.config("gpt-tiny").num_params()


def test_gqa_fewer_kv_heads():
    assert GQA.kv_heads == 2 and GQA.n_heads == 4
    layers = gpt.init(GQA, jax.random.PRNGKey(0))["layers"]
    assert layers["wq"].shape == (GQA.n_layers, GQA.d_model, 4, GQA.head_dim)
    for name in ("wk", "wv"):
        assert layers[name].shape == (
            GQA.n_layers, GQA.d_model, 2, GQA.head_dim), name


def test_gqa_loss_decreases():
    params = gpt.init(GQA, jax.random.PRNGKey(0))
    rng = np.random.default_rng(0)
    toks = jnp.asarray(rng.integers(0, GQA.vocab_size, (4, 17)), jnp.int32)
    tokens, targets = toks[:, :-1], toks[:, 1:]

    @jax.jit
    def step(params):
        (loss, m), grads = jax.value_and_grad(
            lambda p: gpt.loss_fn(p, GQA, tokens, targets),
            has_aux=True)(params)
        params = jax.tree.map(lambda p, g: p - 0.1 * g, params, grads)
        return params, loss

    params, first = step(params)
    for _ in range(10):
        params, loss = step(params)
    assert float(loss) < float(first)


@pytest.mark.parametrize("attn_impl", ["dot", "flash"])
@pytest.mark.parametrize("tp,n_kv_heads", [(2, 1), (4, 2)])
def test_kv_heads_tp_does_not_divide_sharded_forward(tp, n_kv_heads,
                                                     attn_impl):
    """KV heads that tp does not divide stay whole on every chip (the rules
    say so for the weights, ``lm.attention_specs`` for the kernel's
    shard_map) under four query heads that tp splits; with two of them a
    shard that kept only its own query heads would pair them wrongly. 128
    positions are one whole tile, so ``flash`` runs its kernel and not the
    fallback."""
    from ray_tpu.parallel import mesh as mesh_mod
    mesh = build_mesh(MeshConfig(dp=1, fsdp=2, tp=tp),
                      devices=jax.devices()[:2 * tp])
    plain = gpt.config("gpt-tiny", n_kv_heads=n_kv_heads)
    cfg = gpt.config("gpt-tiny", n_kv_heads=n_kv_heads, attn_impl=attn_impl)
    rules = ShardingRules(kv_heads=None)
    params = gpt.init(cfg, jax.random.PRNGKey(0))
    tokens = jnp.asarray(np.random.default_rng(0).integers(
        0, cfg.vocab_size, (4, 128)), jnp.int32)
    expect = gpt.forward(params, plain, tokens)
    sharded = shard_tree(params, mesh, gpt.param_specs(cfg, rules))
    previous = mesh_mod.current_mesh(), mesh_mod.current_rules()
    mesh_mod.set_current_mesh(mesh, rules)
    try:
        got = jax.jit(lambda p, t: gpt.forward(p, cfg, t))(sharded, tokens)
    finally:
        mesh_mod.set_current_mesh(*previous)
    np.testing.assert_allclose(np.asarray(expect), np.asarray(got),
                               atol=2e-3)


# -- ViT ----------------------------------------------------------------

def test_vit_forward_shape():
    cfg = vit.config("vit-tiny")
    params = vit.init(cfg, jax.random.PRNGKey(0))
    images = jnp.zeros((2, 32, 32, 3), jnp.float32)
    logits = vit.forward(params, cfg, images)
    assert logits.shape == (2, 10)


def test_vit_param_count_matches_init():
    cfg = vit.config("vit-tiny")
    params = vit.init(cfg, jax.random.PRNGKey(0))
    actual = sum(x.size for x in jax.tree.leaves(params))
    assert actual == cfg.num_params()


def test_vit_patchify_roundtrip():
    cfg = vit.config("vit-tiny")
    rng = np.random.default_rng(0)
    imgs = jnp.asarray(rng.normal(size=(2, 32, 32, 3)), jnp.float32)
    patches = vit.patchify(cfg, imgs)
    assert patches.shape == (2, cfg.n_patches, cfg.patch_dim)
    # First patch = top-left 8x8 tile, flattened row-major.
    np.testing.assert_allclose(
        np.asarray(patches[0, 0]),
        np.asarray(imgs[0, :8, :8, :]).reshape(-1))


def test_vit_training_learns():
    cfg = vit.config("vit-tiny")
    params = vit.init(cfg, jax.random.PRNGKey(0))
    rng = np.random.default_rng(0)
    # Two trivially separable classes (bright vs dark images).
    images = np.concatenate([
        rng.normal(2.0, 0.1, (8, 32, 32, 3)),
        rng.normal(-2.0, 0.1, (8, 32, 32, 3))]).astype(np.float32)
    labels = np.array([0] * 8 + [1] * 8, np.int32)
    images, labels = jnp.asarray(images), jnp.asarray(labels)

    @jax.jit
    def step(params):
        (loss, m), grads = jax.value_and_grad(
            lambda p: vit.loss_fn(p, cfg, images, labels),
            has_aux=True)(params)
        params = jax.tree.map(lambda p, g: p - 0.05 * g, params, grads)
        return params, m

    for _ in range(20):
        params, m = step(params)
    assert float(m["accuracy"]) >= 0.9


def test_vit_sharded_forward():
    mesh = build_mesh(MeshConfig(dp=4, tp=2))
    cfg = vit.config("vit-tiny")
    rules = ShardingRules(batch="dp", embed=None, heads="tp",
                          kv_heads="tp", mlp="tp", vocab=None)
    params = vit.init(cfg, jax.random.PRNGKey(0))
    sharded = shard_tree(params, mesh, vit.param_specs(cfg, rules))
    images = jnp.zeros((4, 32, 32, 3), jnp.float32)
    expect = vit.forward(params, cfg, images)
    with mesh:
        got = jax.jit(lambda p, x: vit.forward(p, cfg, x))(sharded, images)
    np.testing.assert_allclose(np.asarray(expect), np.asarray(got),
                               atol=2e-3)


# -- Diffusion ----------------------------------------------------------

def test_unet_forward_shape():
    cfg = diffusion.config("unet-tiny")
    params = diffusion.init(cfg, jax.random.PRNGKey(0))
    x = jnp.zeros((2, 16, 16, 3), jnp.float32)
    t = jnp.zeros((2,), jnp.int32)
    out = diffusion.forward(params, cfg, x, t)
    assert out.shape == (2, 16, 16, 3)
    assert np.isfinite(np.asarray(out)).all()


def test_unet_timestep_conditioning():
    cfg = diffusion.config("unet-tiny")
    params = diffusion.init(cfg, jax.random.PRNGKey(0))
    rng = np.random.default_rng(0)
    x = jnp.asarray(rng.normal(size=(1, 16, 16, 3)), jnp.float32)
    a = diffusion.forward(params, cfg, x, jnp.array([0], jnp.int32))
    b = diffusion.forward(params, cfg, x, jnp.array([40], jnp.int32))
    assert np.abs(np.asarray(a) - np.asarray(b)).max() > 1e-6


def test_unet_loss_decreases():
    cfg = diffusion.config("unet-tiny")
    params = diffusion.init(cfg, jax.random.PRNGKey(0))
    rng = np.random.default_rng(0)
    images = jnp.asarray(rng.normal(size=(4, 16, 16, 3)) * 0.1, jnp.float32)

    @jax.jit
    def step(params, key):
        (loss, _), grads = jax.value_and_grad(
            lambda p: diffusion.loss_fn(p, cfg, images, key),
            has_aux=True)(params)
        params = jax.tree.map(lambda p, g: p - 0.01 * g, params, grads)
        return params, loss

    key = jax.random.PRNGKey(0)
    losses = []
    for i in range(15):
        key, sub = jax.random.split(key)
        params, loss = step(params, sub)
        losses.append(float(loss))
    assert np.mean(losses[-3:]) < np.mean(losses[:3])


def test_ddim_sample_shapes_and_finite():
    cfg = diffusion.config("unet-tiny")
    params = diffusion.init(cfg, jax.random.PRNGKey(0))
    out = diffusion.ddim_sample(params, cfg, jax.random.PRNGKey(1),
                                batch=2, n_steps=4)
    assert out.shape == (2, 16, 16, 3)
    assert np.isfinite(np.asarray(out)).all()


def test_gpt_loss_chunk_matches_unchunked():
    """Chunked CE (incl. non-divisor chunk sizes) must match the unchunked
    path in loss, metrics, and gradients (models/gpt.py loss_chunk)."""
    from ray_tpu.models import gpt

    cfg = gpt.config("gpt-tiny")
    params = gpt.init(cfg, jax.random.PRNGKey(0))
    rng = np.random.default_rng(0)
    toks = jnp.asarray(rng.integers(0, cfg.vocab_size, (2, 128)), jnp.int32)
    tgts = jnp.asarray(rng.integers(0, cfg.vocab_size, (2, 128)), jnp.int32)
    mask = jnp.asarray(rng.integers(0, 2, (2, 128)), jnp.float32)

    base_loss, base_m = gpt.loss_fn(params, cfg, toks, tgts, mask,
                                    z_loss=1e-4)
    base_g = jax.grad(
        lambda p: gpt.loss_fn(p, cfg, toks, tgts, mask, z_loss=1e-4)[0]
    )(params)
    for chunk in (64, 100):  # 100 does not divide 256 → divisor fallback
        ccfg = gpt.config("gpt-tiny", loss_chunk=chunk)
        loss, m = gpt.loss_fn(params, ccfg, toks, tgts, mask, z_loss=1e-4)
        np.testing.assert_allclose(float(loss), float(base_loss), rtol=1e-6)
        np.testing.assert_allclose(float(m["accuracy"]),
                                   float(base_m["accuracy"]), rtol=1e-6)
        g = jax.grad(
            lambda p: gpt.loss_fn(p, ccfg, toks, tgts, mask, z_loss=1e-4)[0]
        )(params)
        err = jax.tree.reduce(max, jax.tree.map(
            lambda a, b: float(jnp.abs(a - b).max()), g, base_g))
        assert err < 1e-6, f"chunk={chunk} grad err {err}"


def test_gpt_selective_remat_matches_full():
    """remat_policy='selective' must be a pure memory/compute trade: same
    loss and gradients as 'full' (models/gpt.py remat_policy)."""
    from ray_tpu.models import gpt

    rng = np.random.default_rng(1)
    toks = jnp.asarray(rng.integers(0, 256, (2, 128)), jnp.int32)
    tgts = jnp.asarray(rng.integers(0, 256, (2, 128)), jnp.int32)
    cfg_full = gpt.config("gpt-tiny", remat=True, remat_policy="full")
    cfg_sel = gpt.config("gpt-tiny", remat=True, remat_policy="selective")
    params = gpt.init(cfg_full, jax.random.PRNGKey(0))
    l_full = gpt.loss_fn(params, cfg_full, toks, tgts)[0]
    l_sel = gpt.loss_fn(params, cfg_sel, toks, tgts)[0]
    np.testing.assert_allclose(float(l_sel), float(l_full), rtol=1e-6)
    g_full = jax.grad(lambda p: gpt.loss_fn(p, cfg_full, toks, tgts)[0])(params)
    g_sel = jax.grad(lambda p: gpt.loss_fn(p, cfg_sel, toks, tgts)[0])(params)
    err = jax.tree.reduce(max, jax.tree.map(
        lambda a, b: float(jnp.abs(a - b).max()), g_full, g_sel))
    assert err < 1e-5, f"selective remat grad err {err}"
    with pytest.raises(ValueError):
        gpt.loss_fn(params, gpt.config("gpt-tiny", remat=True,
                                       remat_policy="Selective"),
                    toks, tgts)


# -- T5 (encoder-decoder) ----------------------------------------------


def test_t5_forward_shape():
    from ray_tpu.models import t5
    cfg = t5.config("t5-tiny")
    params = t5.init(cfg, jax.random.PRNGKey(0))
    enc = jnp.zeros((2, 24), jnp.int32)
    dec = jnp.zeros((2, 12), jnp.int32)
    logits = t5.forward(params, cfg, enc, dec)
    assert logits.shape == (2, 12, cfg.vocab_size)
    assert np.isfinite(np.asarray(logits)).all()


def test_t5_param_count_matches_init():
    from ray_tpu.models import t5
    cfg = t5.config("t5-tiny")
    params = t5.init(cfg, jax.random.PRNGKey(0))
    actual = sum(int(np.prod(p.shape)) for p in jax.tree.leaves(params))
    assert actual == cfg.num_params(), (actual, cfg.num_params())


def test_t5_decoder_causality():
    """Changing a future decoder token must not affect earlier logits;
    changing any encoder token may affect all decoder positions."""
    from ray_tpu.models import t5
    rng = np.random.default_rng(0)
    cfg = t5.config("t5-tiny")
    params = t5.init(cfg, jax.random.PRNGKey(0))
    enc = jnp.asarray(rng.integers(0, 256, (1, 16)), jnp.int32)
    dec = jnp.asarray(rng.integers(0, 256, (1, 10)), jnp.int32)
    base = np.asarray(t5.forward(params, cfg, enc, dec))
    dec2 = dec.at[0, 7].set((dec[0, 7] + 1) % 256)
    out2 = np.asarray(t5.forward(params, cfg, enc, dec2))
    np.testing.assert_allclose(out2[0, :7], base[0, :7], atol=1e-5)
    assert not np.allclose(out2[0, 7:], base[0, 7:])
    enc2 = enc.at[0, 0].set((enc[0, 0] + 1) % 256)
    out3 = np.asarray(t5.forward(params, cfg, enc2, dec))
    assert not np.allclose(out3[0, 0], base[0, 0])


def test_t5_overfits_seq2seq_batch():
    """End-to-end learning check: a tiny T5 drives one fixed teacher-forced
    copy batch to ~zero loss (generalized copying needs more capacity than
    a CI-sized model; single-batch overfit proves every path — encoder,
    cross-attention, decoder, tied head — carries gradient)."""
    import optax
    from ray_tpu.models import t5
    cfg = t5.config("t5-tiny")
    params = t5.init(cfg, jax.random.PRNGKey(0))
    opt = optax.adam(3e-3)
    opt_state = opt.init(params)
    rng = np.random.default_rng(0)
    seq = rng.integers(2, 40, (4, 8))
    enc = jnp.asarray(seq, jnp.int32)
    dec_in = jnp.asarray(np.concatenate(
        [np.zeros((4, 1)), seq[:, :-1]], 1), jnp.int32)
    tgt = jnp.asarray(seq, jnp.int32)

    @jax.jit
    def step(params, opt_state):
        (loss, metrics), grads = jax.value_and_grad(
            lambda p: t5.loss_fn(p, cfg, enc, dec_in, tgt),
            has_aux=True)(params)
        updates, opt_state = opt.update(grads, opt_state)
        return optax.apply_updates(params, updates), opt_state, metrics

    for _ in range(250):
        params, opt_state, metrics = step(params, opt_state)
    assert float(metrics["accuracy"]) == 1.0, float(metrics["accuracy"])
    assert float(metrics["loss"]) < 0.2, float(metrics["loss"])


def test_t5_sharded_forward():
    from ray_tpu.models import t5
    devices = np.array(jax.devices("cpu")[:4]).reshape(2, 2)
    mesh = jax.sharding.Mesh(devices, ("fsdp", "tp"))
    rules = tp_fsdp_rules()
    cfg = t5.config("t5-tiny")
    params = t5.init(cfg, jax.random.PRNGKey(0))
    sharded = shard_tree(params, mesh, t5.param_specs(cfg, rules))
    enc = jnp.zeros((2, 16), jnp.int32)
    dec = jnp.zeros((2, 8), jnp.int32)
    out = jax.jit(lambda p: t5.forward(p, cfg, enc, dec))(sharded)
    assert out.shape == (2, 8, cfg.vocab_size)


def test_t5_decoder_rel_bias_covers_past():
    """Regression: the unidirectional bucket computation once flipped the
    sign, putting every causally-visible (past) pair in bucket 0 — the
    decoder had no positional signal. Past distances must bucket
    monotonically."""
    from ray_tpu.models.t5 import _relative_buckets
    q = jnp.arange(6)[:, None]
    k = jnp.arange(6)[None, :]
    b = np.asarray(_relative_buckets(q - k, False, 8, 32))
    # strictly below the diagonal (visible past), buckets are nonzero and
    # grow with distance
    for i in range(1, 6):
        for j in range(i):
            assert b[i, j] > 0, (i, j, b)
    assert b[5, 0] >= b[5, 3] > b[5, 4]


# -- BERT (bidirectional encoder + MLM) ---------------------------------


def test_bert_forward_shapes():
    from ray_tpu.models import bert
    cfg = bert.config("bert-tiny")
    params = bert.init(cfg, jax.random.PRNGKey(0))
    toks = jnp.zeros((2, 16), jnp.int32)
    logits = bert.mlm_logits(params, cfg, toks)
    assert logits.shape == (2, 16, cfg.vocab_size)
    assert np.isfinite(np.asarray(logits)).all()
    cls = bert.pooled(params, cfg, toks)
    assert cls.shape == (2, cfg.d_model)
    assert (np.abs(np.asarray(cls)) <= 1.0).all()  # tanh pooler


def test_bert_param_count_matches_init():
    from ray_tpu.models import bert
    cfg = bert.config("bert-tiny")
    params = bert.init(cfg, jax.random.PRNGKey(0))
    actual = sum(int(np.prod(p.shape)) for p in jax.tree.leaves(params))
    assert actual == cfg.num_params(), (actual, cfg.num_params())


def test_bert_bidirectional_and_padding_mask():
    """Every position sees every non-padded position (bidirectional),
    and padded positions influence nothing."""
    from ray_tpu.models import bert
    rng = np.random.default_rng(1)
    cfg = bert.config("bert-tiny")
    params = bert.init(cfg, jax.random.PRNGKey(1))
    toks = jnp.asarray(rng.integers(0, 256, (1, 12)), jnp.int32)
    base = np.asarray(bert.mlm_logits(params, cfg, toks))
    # bidirectional: changing the LAST token changes the FIRST logit
    toks2 = np.asarray(toks).copy()
    toks2[0, -1] = (toks2[0, -1] + 1) % 256
    out2 = np.asarray(bert.mlm_logits(params, cfg, jnp.asarray(toks2)))
    assert np.abs(out2[0, 0] - base[0, 0]).max() > 0
    # padding: tokens behind the mask don't affect unmasked positions
    mask = np.ones((1, 12), np.int64)
    mask[0, 8:] = 0
    masked1 = np.asarray(bert.mlm_logits(
        params, cfg, toks, attention_mask=jnp.asarray(mask)))
    toks3 = np.asarray(toks).copy()
    toks3[0, 9] = (toks3[0, 9] + 7) % 256
    masked2 = np.asarray(bert.mlm_logits(
        params, cfg, jnp.asarray(toks3), attention_mask=jnp.asarray(mask)))
    np.testing.assert_allclose(masked1[0, :8], masked2[0, :8],
                               rtol=1e-5, atol=1e-5)


def test_bert_mlm_loss_trains():
    """A few optimizer steps on a fixed masked batch reduce the loss."""
    import optax
    from ray_tpu.models import bert
    rng = np.random.default_rng(2)
    cfg = bert.config("bert-tiny")
    params = bert.init(cfg, jax.random.PRNGKey(2))
    targets = jnp.asarray(rng.integers(0, 256, (2, 16)), jnp.int32)
    mask_pos = jnp.asarray(rng.random((2, 16)) < 0.25, jnp.float32)
    toks = jnp.where(mask_pos > 0, 103, targets)  # [MASK]=103

    opt = optax.adam(1e-3)
    state = opt.init(params)

    @jax.jit
    def step(params, state):
        loss, grads = jax.value_and_grad(bert.mlm_loss)(
            params, cfg, toks, targets, mask_pos)
        updates, state = opt.update(grads, state, params)
        return optax.apply_updates(params, updates), state, loss

    params, state, first = step(params, state)
    for _ in range(12):
        params, state, last = step(params, state)
    assert float(last) < float(first), (first, last)


def test_bert_sharded_specs_cover_params():
    """param_specs mirrors the param tree exactly (GSPMD-shardable)."""
    from ray_tpu.models import bert
    from ray_tpu.parallel.sharding import ShardingRules
    cfg = bert.config("bert-tiny")
    params = bert.init(cfg, jax.random.PRNGKey(3))
    specs = bert.param_specs(cfg, ShardingRules())
    flat_p = jax.tree_util.tree_structure(params)
    flat_s = jax.tree_util.tree_structure(
        specs, is_leaf=lambda x: isinstance(x, type(specs["wte"])))
    assert flat_p == flat_s
