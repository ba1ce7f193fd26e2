"""models/granite.py (Mamba-2 layers by ops/ssd.py, grouped-query attention
without positions, the layer scan over two kinds of layer, the tied scaled
head) against a copy of the benchmark's plain reference, through
``family_cases.py``; that reference
against ``transformers``' ``GraniteMoeHybridForCausalLM``; the flash kernels
with grouped KV heads and a model's own score scale; ``lm.scan_blocks`` over
a mixed ``layer_types``.

Everything runs on the CPU at tiny widths in float32 under the highest
matmul precision, the kernels interpreted, where both sides compute the same
sums in another order: tolerances of 1e-4 (relative, on gradients: of a
leaf's norm) leave room for float32 reassociation across a few hundred terms
and nothing else.
"""

import itertools
from dataclasses import replace
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import family_cases
import reference_granitemoehybrid as reference
from family_cases import batch, drawn, forward_alone
from ray_tpu.models import granite, lm
from ray_tpu.ops.flash_attention import flash_attention
from ray_tpu.parallel import MeshConfig, build_mesh
from ray_tpu.parallel.train_step import init_train_state, make_train_step

CFG = granite.config("granite-tiny")
SEQ = 256


def published(cfg):
    return {"layer_types": list(cfg.layer_types),
            "num_hidden_layers": cfg.num_hidden_layers,
            "mamba_n_heads": cfg.mamba_n_heads,
            "mamba_d_state": cfg.mamba_d_state,
            "attention_multiplier": cfg.attention_multiplier,
            "embedding_multiplier": cfg.embedding_multiplier,
            "residual_multiplier": cfg.residual_multiplier,
            "logits_scaling": cfg.logits_scaling,
            "rms_norm_eps": cfg.rms_norm_eps}


def moved(name, leaf, key):
    """Every vector off its one or zero, step sizes small enough that
    states outlive a chunk, and Wq and Wk eight times larger: at the init's
    scale every softmax is flat and attention is the running mean of v
    whichever head it reads."""
    if "dt_bias" in name:
        return leaf - 4.0 + jax.random.normal(key, leaf.shape)
    if "wq" in name or "wk" in name:
        return 8.0 * leaf
    if leaf.ndim == (2 if "run" in name else 1):
        return leaf + 0.2 * jax.random.normal(key, leaf.shape)
    return leaf


GRANITE = family_cases.Family(
    module=granite, reference=reference, cfg=CFG, seq=SEQ,
    published=published, moved=moved)
globals().update(family_cases.cases(GRANITE))


@pytest.mark.parametrize("dropped", ["D", "conv_b", "gate", "residual",
                                     "kv_pairing"])
def test_a_dropped_term_shows(both, dropped):
    """Each of the terms a fast path could lose moves the logits by far
    more than the agreement above allows."""
    params = drawn(GRANITE, CFG)
    tokens, _ = batch(CFG, SEQ)
    cfg = CFG

    def changed(kind, change):
        return dict(params, **{
            run: change(dict(params[run]))
            for run, run_kind, _ in lm.runs(CFG.layers)
            if run_kind == kind})

    if dropped in ("D", "conv_b"):
        params = changed("mamba", lambda w: dict(
            w, **{dropped: jnp.zeros_like(w[dropped])}))
    elif dropped == "gate":
        # silu(z) of a constant: the gate no longer reads the token.
        params = changed("mamba", lambda w: dict(
            w, w_in=w["w_in"].at[:, :, :cfg.mamba_d_inner].set(0.0)))
    elif dropped == "residual":
        cfg = replace(CFG, residual_multiplier=1.0)
    else:
        params = changed("attention", lambda w: dict(
            w, wk=w["wk"][:, :, ::-1], wv=w["wv"][:, :, ::-1]))
    got = forward_alone(GRANITE, params, cfg, tokens)
    err = float(jnp.sqrt(((got - both["logits"][1]) ** 2).mean()))
    assert err > 0.01 * both["rms"], (dropped, err, both["rms"])


def _published_logits(cfg, params, tokens):
    """``transformers``' ``GraniteMoeHybridForCausalLM`` (``torch_forward``,
    the chunked form, chunks of 64) on the program's parameters, with the
    experts where ``cfg`` has them (``tests/test_granite_moe.py``): logits
    [B, S, vocab]."""
    torch = pytest.importorskip("torch")
    pytest.importorskip("transformers")
    try:
        from transformers import (GraniteMoeHybridConfig,
                                  GraniteMoeHybridForCausalLM)
    except ImportError:
        pytest.skip("this transformers has no granitemoehybrid")
    hf_config = GraniteMoeHybridConfig(
        vocab_size=cfg.vocab_size, hidden_size=cfg.hidden_size,
        intermediate_size=cfg.intermediate_size,
        shared_intermediate_size=cfg.shared_intermediate_size,
        num_hidden_layers=cfg.num_hidden_layers,
        layer_types=list(cfg.layers),
        num_attention_heads=cfg.num_attention_heads,
        num_key_value_heads=cfg.num_key_value_heads,
        attention_multiplier=cfg.attention_multiplier,
        embedding_multiplier=cfg.embedding_multiplier,
        residual_multiplier=cfg.residual_multiplier,
        logits_scaling=cfg.logits_scaling,
        num_local_experts=cfg.num_local_experts,
        num_experts_per_tok=cfg.num_experts_per_tok,
        mamba_n_heads=cfg.mamba_n_heads,
        mamba_d_head=cfg.mamba_d_head, mamba_d_state=cfg.mamba_d_state,
        mamba_n_groups=cfg.mamba_n_groups, mamba_d_conv=cfg.mamba_d_conv,
        mamba_expand=cfg.mamba_expand, mamba_chunk_size=64,
        mamba_conv_bias=True, mamba_proj_bias=False,
        rms_norm_eps=cfg.rms_norm_eps, position_embedding_type="nope",
        tie_word_embeddings=True, attention_bias=False,
        max_position_embeddings=cfg.max_position_embeddings,
        attn_implementation="eager")
    model = GraniteMoeHybridForCausalLM(hf_config).eval()

    def t(a):
        return torch.tensor(np.asarray(a, np.float32))

    state = {"model.embed_tokens.weight": t(params["wte"]),
             "lm_head.weight": t(params["wte"]),
             "model.norm.weight": t(params["lnf_scale"])}
    d = cfg.hidden_size
    stacks = [(kind, jax.tree.map(lambda a: a[j], params[run]))
              for run, kind, n in lm.runs(cfg.layers) for j in range(n)]
    for i, (kind, w) in enumerate(stacks):
        pre = f"model.layers.{i}."
        state[pre + "input_layernorm.weight"] = t(w["ln1_scale"])
        state[pre + "post_attention_layernorm.weight"] = t(w["ln2_scale"])
        state[pre + "shared_mlp.input_linear.weight"] = t(w["mlp_in"].T)
        state[pre + "shared_mlp.output_linear.weight"] = t(w["mlp_out"].T)
        if cfg.num_local_experts:
            # input_linear [experts, 2 f, d]: the gated half first.
            state[pre + "block_sparse_moe.input_linear.weight"] = t(
                jnp.concatenate([w["w_gate"], w["w_up"]], -1).swapaxes(1, 2))
            state[pre + "block_sparse_moe.output_linear.weight"] = t(
                w["w_down"].swapaxes(1, 2))
            state[pre + "block_sparse_moe.router.layer.weight"] = t(
                w["router"].T)
        if kind == "mamba":
            state[pre + "mamba.in_proj.weight"] = t(w["w_in"].T)
            state[pre + "mamba.conv1d.weight"] = t(w["conv_w"].T[:, None, :])
            state[pre + "mamba.conv1d.bias"] = t(w["conv_b"])
            state[pre + "mamba.dt_bias"] = t(w["dt_bias"])
            state[pre + "mamba.A_log"] = t(w["A_log"])
            state[pre + "mamba.D"] = t(w["D"])
            state[pre + "mamba.norm.weight"] = t(w["norm_scale"])
            state[pre + "mamba.out_proj.weight"] = t(w["w_out"].T)
        else:
            state[pre + "self_attn.q_proj.weight"] = t(
                w["wq"].reshape(d, -1).T)
            state[pre + "self_attn.k_proj.weight"] = t(
                w["wk"].reshape(d, -1).T)
            state[pre + "self_attn.v_proj.weight"] = t(
                w["wv"].reshape(d, -1).T)
            state[pre + "self_attn.o_proj.weight"] = t(
                w["wo"].reshape(-1, d).T)
    missing, unexpected = model.load_state_dict(state, strict=False)
    assert not unexpected and not missing, (missing, unexpected)
    with torch.no_grad():
        return model(torch.tensor(np.asarray(tokens, np.int64))
                     ).logits.numpy()


def test_the_reference_is_the_published_implementation(monkeypatch):
    """``reference/granitemoehybrid.py`` (the literal recurrence, here in
    five stretches of 32 positions) against ``transformers``'
    ``GraniteMoeHybridForCausalLM`` (``torch_forward``, the chunked form) on
    the same seeded weights: a sequence longer than a chunk and not a
    multiple of it."""
    monkeypatch.setattr(reference, "SEGMENT", 32)
    cfg = replace(CFG, mamba_chunk_size=64)
    seq = 160
    params = drawn(GRANITE, cfg, seed=3)
    tokens, targets = batch(cfg, seq, seed=5)
    want = _published_logits(cfg, params, tokens)
    where = jnp.broadcast_to(jnp.arange(seq, dtype=jnp.int32), tokens.shape)
    got, _, rms = reference.forward(
        params, tokens, targets, where,
        **reference.arguments(published(cfg)))
    assert float(rms) > 0.01
    np.testing.assert_allclose(got, want, atol=1e-3 * float(rms))


def test_two_groups_of_b_and_c_are_the_published_implementations():
    """``mamba_n_groups`` 2 (two heads of 64 a group, so that the kernels
    take a head block a group): the program with its kernels, interpreted,
    against ``transformers``, whose norm stays over the whole row; and the
    groups are read: with group 1's B and C in group 0's place the logits
    move."""
    cfg = replace(CFG, mamba_n_groups=2)
    params = drawn(GRANITE, cfg, seed=3)
    tokens, _ = batch(cfg, SEQ, seed=5)
    want = _published_logits(cfg, params, tokens)
    got = forward_alone(GRANITE, params, cfg, tokens)
    rms = float(np.sqrt((want ** 2).mean()))
    assert rms > 0.01
    np.testing.assert_allclose(got, want, atol=1e-3 * rms)
    di, n = cfg.mamba_d_inner, cfg.mamba_d_state

    def one_group(w):
        """Group 0's B and C columns of the projection and the conv copied
        over group 1's: every head then reads group 0."""
        def copied(a, at):
            for first in (at + di, at + di + 2 * n):
                a = a.at[..., first + n:first + 2 * n].set(
                    a[..., first:first + n])
            return a
        return dict(w, w_in=copied(w["w_in"], di),
                    conv_w=copied(w["conv_w"], 0),
                    conv_b=copied(w["conv_b"], 0))

    mamba = {run: one_group(params[run])
             for run, kind, _ in lm.runs(cfg.layers) if kind == "mamba"}
    moved = forward_alone(GRANITE, dict(params, **mamba), cfg, tokens)
    assert float(np.abs(moved - want).max()) > 0.05 * rms


@pytest.mark.parametrize("blk_q,blk_k", [(128, 128), (256, 128)])
def test_flash_grouped_heads_and_a_models_own_scale(blk_q, blk_k):
    """8 KV heads under 32 query heads (head i reads KV head i // 4), a
    score scale that is not 1/sqrt(D), q and k sharpened so that a wrong
    pairing or scale moves the softmax: the kernels (interpreted) against
    ``dot_attention``, forward and the three cotangents folded back onto
    the KV heads."""
    B, S, H, KVH, D, scale = 1, 256, 32, 8, 32, 0.4
    ks = jax.random.split(jax.random.PRNGKey(0), 4)
    q = 3.0 * jax.random.normal(ks[0], (B, S, H, D))
    k = 3.0 * jax.random.normal(ks[1], (B, S, KVH, D))
    v = jax.random.normal(ks[2], (B, S, KVH, D))
    g = jax.random.normal(ks[3], (B, S, H, D))

    def flash(q, k, v):
        return flash_attention(q, k, v, True, blk_q, blk_k, scale)

    want, want_vjp = jax.vjp(partial(lm.dot_attention, scale=scale), q, k, v)
    got, got_vjp = jax.vjp(flash, q, k, v)
    np.testing.assert_allclose(got, want, atol=1e-4)
    for a, b in zip(got_vjp(g), want_vjp(g)):
        assert a.shape == b.shape
        np.testing.assert_allclose(a, b, atol=2e-4 * float(jnp.abs(b).max()))
    unscaled = flash_attention(q, k, v, True, blk_q, blk_k)
    assert float(jnp.abs(unscaled - want).max()) > 0.1
    rolled = flash(q, jnp.roll(k, 1, axis=2), jnp.roll(v, 1, axis=2))
    assert float(jnp.abs(rolled - want).max()) > 0.1


@pytest.mark.parametrize("layer_types", [
    ("a", "b", "a", "a"), ("b", "a", "a", "b", "b", "a"), ("a",) * 3])
@pytest.mark.parametrize("remat", [False, True])
def test_scan_blocks_over_mixed_layer_types(layer_types, remat):
    """Runs of one kind scanned, one stack a run, kinds alternating: equal
    to the layers applied one by one in order, values and gradients."""
    cfg = replace(CFG, remat=remat)
    runs = [(kind, len(list(run)))
            for kind, run in itertools.groupby(layer_types)]
    ks = jax.random.split(jax.random.PRNGKey(0), len(runs) + 1)
    layers = [{"w": jax.random.normal(k, (n, 8, 8)) / 3} if kind == "a"
              else {"v": jax.random.normal(k, (n, 8))}
              for k, (kind, n) in zip(ks, runs)]
    blocks = {"a": lambda x, layer, pos: (jnp.tanh(x @ layer["w"]), None),
              "b": lambda x, layer, pos: (x * layer["v"] + pos, x.sum())}
    x0 = jax.random.normal(ks[-1], (4, 8))

    def scanned(layers):
        x, auxes = lm.scan_blocks(cfg, blocks, x0, layers, 0.5, runs=runs)
        return x.sum(), auxes

    def one_by_one(layers):
        x = x0
        for (kind, n), stack in zip(runs, layers):
            for j in range(n):
                x, _ = blocks[kind](
                    x, jax.tree.map(lambda a: a[j], stack), 0.5)
        return x.sum()

    (got, auxes), got_grads = jax.value_and_grad(scanned, has_aux=True)(
        layers)
    want, want_grads = jax.value_and_grad(one_by_one)(layers)
    np.testing.assert_allclose(got, want, rtol=1e-6)
    for a, b in zip(jax.tree.leaves(got_grads), jax.tree.leaves(want_grads)):
        np.testing.assert_allclose(a, b, rtol=1e-5, atol=1e-6)
    assert [aux is None for aux in auxes] == [k == "a" for k, _ in runs]
    with pytest.raises(ValueError):
        lm.scan_blocks(cfg, blocks, x0, layers[:-1], 0.5, runs=runs)


def test_trains_through_the_train_step_typed_to_no_model():
    """``make_train_step`` finds the model from ``type(cfg)``: the loss
    falls on a repeated batch, remat and the chunked loss on, flash on."""
    import optax
    cfg = replace(CFG, remat=True, attn_impl="flash", loss_chunk=128,
                  attn_blk_q=128, attn_blk_k=128)
    mesh = build_mesh(MeshConfig(dp=1, fsdp=1, tp=1),
                      devices=jax.devices()[:1])
    from ray_tpu.parallel.sharding import ShardingRules
    rules, optimizer = ShardingRules(), optax.adam(3e-3)
    state = init_train_state(cfg, mesh, rules, optimizer, seed=0)
    step = make_train_step(cfg, mesh, rules, optimizer)
    tokens, targets = batch(cfg, SEQ, rows=1)
    losses = []
    for _ in range(3):
        state, metrics = step(state, {"tokens": tokens, "targets": targets})
        losses.append(float(metrics["loss"]))
    assert all(np.isfinite(losses)) and losses[-1] < losses[0]


def test_state_space_runs_per_shard_under_a_mesh():
    """Under a data-parallel mesh the scan's kernels run on each shard's
    own rows (GSPMD cannot partition a Mosaic kernel): the same numbers as
    without a mesh, forward and the gradient of u."""
    from ray_tpu.ops.ssd import ssd
    from ray_tpu.parallel import mesh as mesh_mod
    ks = jax.random.split(jax.random.PRNGKey(0), 6)
    u = jax.random.normal(ks[0], (2, 256, 4, 64))
    dt = jax.nn.softplus(jax.random.normal(ks[1], (2, 256, 4)) - 2.0)
    A = -jnp.exp(jax.random.normal(ks[2], (4,)))
    B = 0.3 * jax.random.normal(ks[3], (2, 256, 128))
    C = 0.3 * jax.random.normal(ks[4], (2, 256, 128))
    D = jax.random.normal(ks[5], (4,))

    def loss(fn, u):
        return (fn(u, dt, A, B, C, D, 128) ** 2).sum()

    want = jax.value_and_grad(partial(loss, lambda *a: ssd(*a[:-1],
                                                           chunk=a[-1])))(u)
    mesh = build_mesh(MeshConfig(dp=2, fsdp=1, tp=1),
                      devices=jax.devices()[:2])
    previous = mesh_mod.current_mesh()
    mesh_mod.set_current_mesh(mesh)
    try:
        got = jax.jit(jax.value_and_grad(partial(loss, lm.state_space)))(u)
    finally:
        mesh_mod.set_current_mesh(previous)
    np.testing.assert_allclose(got[0], want[0], rtol=1e-5)
    np.testing.assert_allclose(got[1], want[1], atol=1e-4)


def test_config_refuses_what_the_program_does_not_compute():
    with pytest.raises(NotImplementedError):
        replace(CFG, tie_word_embeddings=False)
    # Experts are computed since PR 68 (tests/test_granite_moe.py): the
    # configuration is accepted, and holds every expert unless told its
    # share.
    accepted = replace(CFG, num_local_experts=8, num_experts_per_tok=2)
    assert accepted.n_moe_layers == 4 and accepted.experts_held is None
    assert replace(accepted, experts_held=[2, 3]).experts_held == (2, 3)
    with pytest.raises(ValueError):
        replace(CFG, num_local_experts=8)       # 0 experts a token
    with pytest.raises(ValueError):
        replace(CFG, mamba_n_groups=3)
    with pytest.raises(ValueError):
        replace(CFG, num_hidden_layers=9)
    assert granite.config("granite-4.0-h-micro").layers.count(
        "attention") == 4
    assert granite.config("granite-4.0-h-micro", num_hidden_layers=20
                          ).layers.count("mamba") == 18
