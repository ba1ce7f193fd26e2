"""models/lfm2.py (the double-gated short convolution as the mixer, grouped-
query attention with a norm on q and k before rope, a chip's share of the
experts with no shared expert beside it, one table as embedding and head)
against the installed ``transformers``' ``Lfm2ForCausalLM`` where that has
the layers (every layer dense), and against a copy of the benchmark's plain
reference for the whole model, through ``family_cases.py``; the share tied to
the model; the cut's seven runs.
"""

from dataclasses import replace
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import family_cases
import reference_lfm2_moe as reference
from family_cases import (batch, drawn, expert_layer, forward_alone,
                          share_of)
from ray_tpu.models import lfm2, lm
from ray_tpu.ops import short_conv as short_conv_op
from ray_tpu.ops.moe import routed_experts
from ray_tpu.parallel import MeshConfig, build_mesh
from ray_tpu.parallel.train_step import init_train_state, make_train_step

CFG = lfm2.config("lfm2-tiny")
SEQ = 64
# The kernels (interpreted: the flash three and the convolution's pair, at a
# width and a length that tile), remat, the chunked loss, and a share of the
# experts: 3 of 8, from the third.
FLASH = replace(CFG, hidden_size=128, attn_impl="flash", attn_blk_q=128,
                attn_blk_k=128, remat=True, loss_chunk=128,
                experts_held=(2, 3))
FLASH_SEQ = short_conv_op.ROWS


def published(cfg):
    out = {"layer_types": list(cfg.layer_types),
           "num_hidden_layers": cfg.num_hidden_layers,
           "num_dense_layers": cfg.num_dense_layers,
           "rope_parameters": {"rope_theta": cfg.rope_parameters.rope_theta,
                               "rope_type": "default"},
           "num_experts_per_tok": cfg.num_experts_per_tok,
           "routed_scaling_factor": cfg.routed_scaling_factor,
           "norm_topk_prob": cfg.norm_topk_prob, "norm_eps": cfg.norm_eps,
           "deployment": {"layers_run": {"first": cfg.first_layer,
                                         "count": cfg.num_hidden_layers}}}
    if cfg.experts_held is not None:
        first, count = cfg.experts_held
        out["deployment"]["experts_held"] = {
            "first": first, "count": count, "of": cfg.num_experts}
    return out


def moved(name, leaf, key):
    """Every vector off its one or zero (the expert bias too: routing
    uneven), and the q and k norms' scales doubled: the scores of a random
    model then spread by four units, so that a key wrongly seen, a missing
    rotation or a wrong KV head moves the softmax."""
    if "router_bias" in name:
        return 0.05 * jax.random.normal(key, leaf.shape)
    if name.endswith("_scale']"):
        gain = 2.0 if "q_norm" in name or "k_norm" in name else 1.0
        return gain * (leaf + 0.2 * jax.random.normal(key, leaf.shape))
    return leaf


def drop(dropped, params, cfg, monkeypatch):
    """Each term the equations have and a sibling family lacks."""
    if dropped in ("gate_before", "gate_after"):
        d = cfg.hidden_size
        chunk = 0 if dropped == "gate_before" else 1
        plain = lm.short_conv
        monkeypatch.setattr(
            lm, "short_conv", lambda bcx, w: plain(
                bcx.at[..., chunk * d:(chunk + 1) * d].set(1.0), w))
    elif dropped == "a_tap":
        params = {name: dict(leaf, conv_w=leaf["conv_w"].at[:, 0].set(0.0))
                  if "conv_w" in leaf else leaf
                  for name, leaf in params.items()}
    elif dropped == "rope":
        monkeypatch.setattr(lm, "rope", lambda x, positions, theta: x)
    elif dropped == "qk_norm":
        plain = lm.rmsnorm
        monkeypatch.setattr(
            lm, "rmsnorm", lambda x, scale, eps:
            x if x.ndim == 4 else plain(x, scale, eps))
    elif dropped == "bias":
        cfg = replace(cfg, use_expert_bias=False)
    return params, cfg


LFM2 = family_cases.Family(
    module=lfm2, reference=reference, cfg=CFG, seq=SEQ, flash=FLASH,
    flash_seq=FLASH_SEQ, published=published, moved=moved,
    extras=("picked",), drop=drop, dropped=(
        "gate_before", "gate_after", "a_tap", "rope", "qk_norm", "bias"),
    top_k=CFG.num_experts_per_tok, accum_steps=(1, 2), scan_atol=1e-5,
    bfloat16=replace(FLASH, dtype=jnp.bfloat16, experts_held=None),
    flash_kernels=("short_conv_fwd", "short_conv_bwd", "flash_fwd"),
    wrong=(dict(experts_held=(6, 4)), dict(num_hidden_layers=6),
           dict(first_layer=1), dict(layer_types=("conv", "mamba") * 3),
           dict(conv_bias=True), dict(tie_word_embeddings=False),
           dict(rope_parameters={"rope_theta": 1e6, "rope_type": "yarn"})),
    refuses=(ValueError, NotImplementedError))
globals().update(family_cases.cases(LFM2))


def test_the_tiny_stack_has_all_four_kinds_of_layer():
    assert [kind for _, kind, _ in lm.runs(CFG.layers)] == [
        "dense_conv", "dense_full_attention", "moe_conv",
        "moe_full_attention", "moe_conv"]


# -- against the installed transformers -----------------------------------

def test_every_layer_dense_is_transformers_lfm2():
    """``Lfm2ForCausalLM`` has the mixer, the attention, the layer and the
    head, with a dense FFN in every layer: the model with
    ``num_dense_layers`` = ``num_hidden_layers`` is that model, weights
    copied over. The chunk order of the convolution's projection, the taps'
    order, the norms on q and k before the rotation, the rotation's
    pairing, ``embedding_norm`` last and the table as the head all show in
    the logits."""
    torch = pytest.importorskip("torch")
    try:
        from transformers.models.lfm2.configuration_lfm2 import \
            Lfm2Config as TorchConfig
        from transformers.models.lfm2.modeling_lfm2 import Lfm2ForCausalLM
    except ImportError as exc:
        pytest.skip(f"no lfm2 in transformers: {exc}")
    kinds = ("conv", "full_attention", "conv")
    cfg = replace(CFG, num_hidden_layers=3, num_dense_layers=3,
                  layer_types=kinds)
    theirs = TorchConfig(
        vocab_size=cfg.vocab_size, hidden_size=cfg.hidden_size,
        intermediate_size=cfg.intermediate_size, num_hidden_layers=3,
        num_attention_heads=cfg.num_attention_heads,
        num_key_value_heads=cfg.num_key_value_heads,
        max_position_embeddings=cfg.max_position_embeddings,
        norm_eps=cfg.norm_eps, rope_theta=cfg.rope_parameters.rope_theta,
        conv_bias=False, conv_L_cache=cfg.conv_L_cache,
        block_auto_adjust_ff_dim=False, layer_types=list(kinds),
        tie_word_embeddings=True, attn_implementation="eager")
    model = Lfm2ForCausalLM(theirs).float().eval()
    params = drawn(LFM2, cfg)
    as_torch = lambda a: torch.from_numpy(np.array(a, np.float32))  # noqa: E731
    d = cfg.hidden_size
    with torch.no_grad():
        model.model.embed_tokens.weight.copy_(as_torch(params["wte"]))
        model.model.embedding_norm.weight.copy_(
            as_torch(params["embedding_norm_scale"]))
        for layer, (run, kind, _) in zip(model.model.layers,
                                         lm.runs(cfg.layers)):
            w = jax.tree.map(lambda a: a[0], params[run])
            layer.operator_norm.weight.copy_(
                as_torch(w["operator_norm_scale"]))
            layer.ffn_norm.weight.copy_(as_torch(w["ffn_norm_scale"]))
            layer.feed_forward.w1.weight.copy_(as_torch(w["w_gate"].T))
            layer.feed_forward.w3.weight.copy_(as_torch(w["w_up"].T))
            layer.feed_forward.w2.weight.copy_(as_torch(w["w_down"].T))
            if kind.endswith("conv"):
                layer.conv.in_proj.weight.copy_(as_torch(w["w_in"].T))
                layer.conv.out_proj.weight.copy_(as_torch(w["w_out"].T))
                layer.conv.conv.weight.copy_(
                    as_torch(w["conv_w"].T[:, None, :]))
            else:
                attn = layer.self_attn
                attn.q_proj.weight.copy_(as_torch(w["wq"].reshape(d, -1).T))
                attn.k_proj.weight.copy_(as_torch(w["wk"].reshape(d, -1).T))
                attn.v_proj.weight.copy_(as_torch(w["wv"].reshape(d, -1).T))
                attn.out_proj.weight.copy_(
                    as_torch(w["wo"].reshape(-1, d).T))
                attn.q_layernorm.weight.copy_(as_torch(w["q_norm_scale"]))
                attn.k_layernorm.weight.copy_(as_torch(w["k_norm_scale"]))
        tokens, _ = batch(cfg, SEQ)
        want = model(torch.from_numpy(np.array(tokens, np.int64))
                     ).logits.numpy()
    got = forward_alone(LFM2, params, cfg, tokens)
    rms = float(np.sqrt((want ** 2).mean()))
    assert rms > 0.01
    np.testing.assert_allclose(got, want, atol=1e-4 * rms)


# -- the share ------------------------------------------------------------

def test_the_eight_shares_add_up_to_the_uncut_layer():
    """Nothing is computed alike on every chip here (no shared expert), so
    nothing is counted once: the eight shares' parts, each an eighth of the
    experts, add up to the uncut layer of the reference, and every share
    computes exactly the assignments the router gave its experts."""
    w, x = expert_layer(shared=False)
    top_k, count = 4, 2
    with jax.default_matmul_precision("highest"):
        want, picked = reference._ffn(x, w, top_k, 1.0, True, 0)
        total, computed = jnp.zeros_like(x), 0
        for first in range(0, 16, count):
            share = share_of(w, first, count)
            part, aux = routed_experts(
                x, w["router"], w["router_bias"], share["w_gate"],
                share["w_up"], share["w_down"], top_k=top_k, scaling=1.0,
                held=(first, count))
            mine = ((picked >= first) & (picked < first + count)).sum()
            assert int(aux["group_sizes"].sum()) == int(mine) \
                == int(aux["asked"])
            total, computed = total + part, computed + int(mine)
            # The reference given the same share gives the same part.
            np.testing.assert_allclose(part, reference._ffn(
                x, share, top_k, 1.0, True, first)[0], atol=2e-5)
    assert computed == x.shape[0] * top_k
    np.testing.assert_allclose(total, want, atol=5e-5)


def test_a_token_with_no_held_expert_gets_exactly_zero():
    """No shared expert stands beside the share: where none of a token's
    experts is held the layer returns zeros, and the block the residual
    alone."""
    cfg = replace(CFG, num_hidden_layers=1, num_dense_layers=0,
                  layer_types=("conv",), experts_held=(6, 2))
    params = drawn(LFM2, cfg)
    tokens, _ = batch(cfg, SEQ, rows=4)
    layer = jax.tree.map(lambda a: a[0], params["run00_moe_conv"])
    h = lm.embed(params["wte"], tokens, cfg.dtype)
    mixed = h + lfm2._short_conv(cfg, lm.rmsnorm(
        h, layer["operator_norm_scale"], cfg.norm_eps), layer)
    out, aux = lfm2._block(cfg, "moe_conv", h, layer, lm.positions_of(tokens))
    unheld = ~((aux["picked"] >= 6) & (aux["picked"] < 8)).any(-1)
    assert 0 < int(unheld.sum()) < unheld.size
    assert np.array_equal(np.asarray(out)[np.asarray(unheld)],
                          np.asarray(mixed)[np.asarray(unheld)])
    assert not np.array_equal(np.asarray(out)[~np.asarray(unheld)],
                              np.asarray(mixed)[~np.asarray(unheld)])


def test_a_data_parallel_mesh_runs_the_kernels_per_shard():
    """Two shards of the batch: the convolution's kernels and the flash
    kernels run on each shard's own sequences, and the first step's loss is
    the one chip's (``trained``'s, from the same seed on the same batch)."""
    tokens, targets = batch(FLASH, FLASH_SEQ)
    mesh = build_mesh(MeshConfig(dp=2, fsdp=1, tp=1),
                      devices=jax.devices()[:2])
    state = init_train_state(FLASH, mesh, seed=0)
    _, metrics = make_train_step(FLASH, mesh)(
        state, {"tokens": tokens, "targets": targets})
    np.testing.assert_allclose(
        float(metrics["loss"]),
        family_cases.trained(LFM2, 1)["metrics"][0]["loss"], rtol=1e-5)


# -- the layer scan over the cut's seven runs -----------------------------

CUT = replace(lfm2.config("lfm2-24b-a2b"), num_hidden_layers=13,
              num_dense_layers=1, first_layer=1, experts_held=(0, 8),
              vocab_size=8192)


def test_the_cut_configuration_is_seven_runs():
    """ISSUE 37's cut, published layers 1-13 (``layer_types[1:14]``): the
    dense layer a convolution layer as published layer 1 is, then three
    whole periods of expert layers, 1 attention : 3 convolution. The
    benchmark's cell runs a fourth period (layers 1-17: nine runs)."""
    assert lm.runs(CUT.layers) == (
        ("run00_dense_conv", "dense_conv", 1),
        ("run01_moe_full_attention", "moe_full_attention", 1),
        ("run02_moe_conv", "moe_conv", 3),
        ("run03_moe_full_attention", "moe_full_attention", 1),
        ("run04_moe_conv", "moe_conv", 3),
        ("run05_moe_full_attention", "moe_full_attention", 1),
        ("run06_moe_conv", "moe_conv", 3))
    assert CUT.n_moe_layers == 12
    deeper = lm.runs(replace(CUT, num_hidden_layers=17).layers)
    assert deeper[:7] == lm.runs(CUT.layers) and deeper[7:] == (
        ("run07_moe_full_attention", "moe_full_attention", 1),
        ("run08_moe_conv", "moe_conv", 3))
    shapes = jax.eval_shape(partial(lfm2.init, CUT), jax.random.PRNGKey(0))
    assert [jax.tree.leaves(shapes[run])[0].shape[0]
            for run, _, _ in lm.runs(CUT.layers)] == [1, 1, 3, 1, 3, 1, 3]
    assert shapes["run02_moe_conv"]["w_gate"].shape == (3, 8, 2048, 1536)
    assert shapes["run02_moe_conv"]["router"].shape == (3, 2048, 64)
    assert shapes["run02_moe_conv"]["w_in"].shape == (3, 2048, 6144)
    assert shapes["run02_moe_conv"]["conv_w"].shape == (3, 3, 2048)
    assert shapes["run01_moe_full_attention"]["wk"].shape == (1, 2048, 8, 64)
    assert shapes["run00_dense_conv"]["w_gate"].shape == (1, 2048, 11776)
    assert shapes["wte"].shape == (8192, 2048) and "lm_head" not in shapes
    # Without the offset the first thirteen published layers are eight
    # runs: two convolution layers lead, and the pattern falls a layer late.
    assert len(lm.runs(replace(CUT, first_layer=0).layers)) == 8
