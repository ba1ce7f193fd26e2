"""models/granite.py with experts (granite-4.0-h-small's block: a routed
expert layer beside the shared SwiGLU in every layer, a biasless softmax
router whose weights are renormalised over the picked) against a copy of the
benchmark's plain reference with experts, through ``family_cases.py``; that
reference against ``transformers``' ``GraniteMoeHybridForCausalLM`` with
``num_local_experts`` > 0; the published gate's order (top-k of the logits,
softmax over the picked) against ``moe.route``'s (softmax over all,
renormalised over the picked); the shares of the experts adding up to the
uncut layer; the published member's count and the cut's shapes; what the
step's gauge reads. ``tests/test_granite.py`` is the family without experts.

Everything runs on the CPU at tiny widths in float32 under the highest
matmul precision, the kernels interpreted.
"""

from dataclasses import replace
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import family_cases
import reference_granitemoehybrid_moe as reference
from family_cases import batch, drawn, in_every_run, trained
from ray_tpu.models import granite, lm
from ray_tpu.ops import moe
from test_granite import _published_logits

CFG = granite.config("granite-moe-tiny")
SEQ = 256
# The attention kernels too (interpreted), remat, the chunked loss, experts
# of a width the grouped product's kernels take and a share of them: 6 of
# 16, from the fifth.
FLASH = replace(CFG, attn_impl="flash", attn_blk_q=128, attn_blk_k=128,
                remat=True, loss_chunk=128, intermediate_size=128,
                experts_held=(4, 6))
FLASH_SEQ = 256
PUBLISHED = granite.config("granite-4.0-h-small")
# The benchmark's cut: one period, 9 of 72 experts, an eighth of the
# vocabulary.
CUT = replace(PUBLISHED, num_hidden_layers=10, experts_held=(0, 9),
              vocab_size=12544)


def published(cfg):
    out = {"layer_types": list(cfg.layer_types),
           "num_hidden_layers": cfg.num_hidden_layers,
           "mamba_n_heads": cfg.mamba_n_heads,
           "mamba_d_state": cfg.mamba_d_state,
           "attention_multiplier": cfg.attention_multiplier,
           "embedding_multiplier": cfg.embedding_multiplier,
           "residual_multiplier": cfg.residual_multiplier,
           "logits_scaling": cfg.logits_scaling,
           "rms_norm_eps": cfg.rms_norm_eps,
           "num_experts_per_tok": cfg.num_experts_per_tok}
    if cfg.experts_held is not None:
        first, count = cfg.experts_held
        out["deployment"] = {"experts_held": {
            "first": first, "count": count, "of": cfg.num_local_experts}}
    return out


def moved(name, leaf, key):
    """``tests/test_granite.py``'s rule (every vector off its one or zero,
    small step sizes, ``Wq`` and ``Wk`` eight times larger), the router's
    columns ten times larger, so that a token's picked logits differ, and the
    experts' matrices four times, so that the routed sum is no smaller than
    the shared SwiGLU's."""
    if "dt_bias" in name:
        return leaf - 4.0 + jax.random.normal(key, leaf.shape)
    if "wq" in name or "wk" in name:
        return 8.0 * leaf
    if name.endswith("router']"):
        return 10.0 * leaf
    if any(name.endswith(f"{leaf_name}']")
           for leaf_name in ("w_gate", "w_up", "w_down")):
        return 4.0 * leaf
    if leaf.ndim == (2 if "run" in name else 1):
        return leaf + 0.2 * jax.random.normal(key, leaf.shape)
    return leaf


def drop(dropped, params, cfg, monkeypatch):
    """One term of the expert layer (or of what stands beside it) out of the
    program."""
    plain = lm.expert_ffn
    if dropped == "renormalise":  # softmax over all, the picked as they are
        monkeypatch.setattr(lm, "expert_ffn", lambda *a, **kw: plain(
            *a, **dict(kw, normalize=False)))
    elif dropped == "sigmoid":
        monkeypatch.setattr(lm, "expert_ffn", lambda x, layer, **kw: plain(
            x, dict(layer, router_bias=jnp.zeros(layer["router"].shape[-1])),
            **dict(kw, score="sigmoid")))
    elif dropped == "top_k":
        cfg = replace(cfg, num_experts_per_tok=cfg.num_experts_per_tok - 1)
    elif dropped == "gate_up_swapped":
        params = in_every_run(params, lambda w: dict(
            w, w_gate=w["w_up"], w_up=w["w_gate"]))
    elif dropped in ("mlp_out", "w_down", "D"):  # shared, routed, D u
        params = in_every_run(params, lambda w: dict(w, **{
            dropped: jnp.zeros_like(w[dropped])} if dropped in w else {}))
    elif dropped == "residual_on_shared_only":
        # residual_multiplier applied to s alone: r enters the stream whole.
        monkeypatch.setattr(lm, "expert_ffn", lambda *a, **kw: (
            lambda routed, shared, aux: (
                routed / cfg.residual_multiplier, shared, aux))(
                    *plain(*a, **kw)))
    return params, cfg


GRANITE_MOE = family_cases.Family(
    module=granite, reference=reference, cfg=CFG, seq=SEQ, flash=FLASH,
    flash_seq=FLASH_SEQ, published=published, moved=moved,
    extras=("picked",), drop=drop, dropped=(
        "renormalise", "sigmoid", "top_k", "gate_up_swapped", "mlp_out",
        "w_down", "D", "residual_on_shared_only"),
    top_k=CFG.num_experts_per_tok, accum_steps=(1,),
    wrong=({"experts_held": (12, 6)}, {"num_experts_per_tok": 0},
           {"num_experts_per_tok": 17}, {"mamba_n_groups": 3},
           {"tie_word_embeddings": False},
           {"num_local_experts": 0, "num_experts_per_tok": 0,
            "experts_held": (0, 4)}),
    refuses=(ValueError, NotImplementedError), scan_atol=1e-4,
    flash_kernels=("ssd_fwd", "ssd_bwd", "conv_silu_fwd", "conv_silu_bwd",
                   "gated_norm_fwd", "gated_norm_bwd", "flash_fwd",
                   "flash_bwd/", "gmm", "tgmm",
                   "moe_rows_to_tokens"))
globals().update(family_cases.cases(GRANITE_MOE))


# -- the published member and the cut ------------------------------------

def _count(cfg):
    shapes = jax.eval_shape(partial(granite.init, cfg),
                            jax.random.PRNGKey(0))
    return shapes, sum(a.size for a in jax.tree.leaves(shapes))


def test_the_published_keys_read_straight_in():
    """granite-4.0-h-small's ``config.json`` keys, as the catalog has them,
    are the preset's values (the keys that say nothing to the program left
    out), and the preset is the published 32 B."""
    row = {"attention_multiplier": 0.0078125, "embedding_multiplier": 12,
           "hidden_size": 4096, "intermediate_size": 768,
           "logits_scaling": 16, "mamba_chunk_size": 256, "mamba_d_conv": 4,
           "mamba_d_head": 64, "mamba_d_state": 128, "mamba_expand": 2,
           "mamba_n_groups": 1, "mamba_n_heads": 128,
           "max_position_embeddings": 131072, "num_attention_heads": 32,
           "num_experts_per_tok": 10, "num_hidden_layers": 40,
           "num_key_value_heads": 8, "num_local_experts": 72,
           "residual_multiplier": 0.22, "rms_norm_eps": 1e-05,
           "shared_intermediate_size": 1536, "tie_word_embeddings": True,
           "vocab_size": 100352,
           "layer_types": (("mamba",) * 5 + ("attention",)
                           + ("mamba",) * 4) * 4}
    assert granite.GraniteConfig(**row) == PUBLISHED
    assert PUBLISHED.head_dim == 128 and PUBLISHED.mamba_d_inner == 8192
    assert PUBLISHED.conv_dim == 8448 and PUBLISHED.n_moe_layers == 40
    assert _count(PUBLISHED)[1] == 32_207_337_984     # the row's 32B


def test_the_cuts_shapes_and_count():
    """One period, 9 of 72 experts held, an eighth of the vocabulary: 2.055
    B parameters, the router at its whole width."""
    assert CUT.layers == ("mamba",) * 5 + ("attention",) + ("mamba",) * 4
    shapes, count = _count(CUT)
    assert count == 2_055_031_424
    assert sorted(shapes) == ["lnf_scale", "run00_mamba", "run01_attention",
                              "run02_mamba", "wte"]
    first, attention = shapes["run00_mamba"], shapes["run01_attention"]
    assert first["w_in"].shape == (5, 4096, 8192 + 8448 + 128)
    assert first["router"].shape == (5, 4096, 72)
    assert first["w_gate"].shape == first["w_up"].shape == (5, 9, 4096, 768)
    assert first["w_down"].shape == (5, 9, 768, 4096)
    assert first["mlp_in"].shape == (5, 4096, 2 * 1536)
    assert attention["wq"].shape == (1, 4096, 32, 128)
    assert attention["wk"].shape == (1, 4096, 8, 128)
    assert attention["w_up"].shape == (1, 9, 4096, 768)
    assert shapes["wte"].shape == (12544, 4096)
    assert "router_bias" not in first and "shared_w_up" not in first
    # The share's one buffer, and the grouped product's tiles at 768.
    assert moe._held_bound(16384, 10, 9, 72) == 40960
    assert moe._tile_n(768) == 768 and moe._tile_n(4096) == 1024


def test_a_model_without_experts_holds_no_expert_leaf():
    """``num_local_experts`` picks the block's FFN: granite-tiny's tree and
    its loss's metrics are what they were before the family had experts."""
    cfg = granite.config("granite-tiny")
    shapes, _ = _count(cfg)
    assert not {"router", "w_gate", "w_up", "w_down"} & set(
        shapes["run00_mamba"])
    tokens, targets = batch(cfg, 128)
    metrics = jax.eval_shape(partial(granite.loss_fn, cfg=cfg),
                             shapes, tokens=tokens, targets=targets)[1]
    assert sorted(metrics) == ["accuracy", "loss", "perplexity"]
    assert cfg.n_moe_layers == 0 and CFG.n_moe_layers == 3


# -- the gate --------------------------------------------------------------

def test_the_published_gates_order_is_routes():
    """The published gate is a top-k of the logits and a softmax over the
    picked; ``moe.route`` takes a softmax over all the experts, its top-k,
    and renormalises the picked: the same experts and the same weights, at
    72 experts and 10 a token (neither a power of two nor a multiple of the
    lanes)."""
    ks = jax.random.split(jax.random.PRNGKey(3), 2)
    x = jax.random.normal(ks[0], (512, 64))
    router = jax.random.normal(ks[1], (64, 72)) / 4
    with jax.default_matmul_precision("highest"):
        picked, weights, mass = moe.route(x, router, None, 10, 1.0, True,
                                          "softmax")
        top, want_picked = jax.lax.top_k(x @ router, 10)
    np.testing.assert_array_equal(np.sort(picked, -1),
                                  np.sort(want_picked, -1))
    want = jax.nn.softmax(top, -1)
    order, want_order = np.argsort(picked, -1), np.argsort(want_picked, -1)
    np.testing.assert_allclose(
        np.take_along_axis(np.asarray(weights), order, -1),
        np.take_along_axis(np.asarray(want), want_order, -1), rtol=2e-6)
    np.testing.assert_allclose(weights.sum(-1), 1.0, rtol=1e-6)
    assert 0.0 < float(mass.min()) and float(mass.max()) < 1.0
    # The reference's own gate, laid at the experts.
    _, gates = reference._gates(x, router, 10)
    np.testing.assert_allclose(
        np.take_along_axis(np.asarray(gates), np.asarray(picked), -1),
        weights, rtol=2e-6)


# -- the share tied to the model --------------------------------------------

def _expert_layer(experts=16, tokens=256, d=128, f=128, seed=1):
    ks = jax.random.split(jax.random.PRNGKey(seed), 8)
    normal = jax.random.normal
    return {"ln2_scale": 1.0 + 0.1 * normal(ks[7], (d,)),
            "router": 0.3 * normal(ks[0], (d, experts)),
            "w_gate": 0.1 * normal(ks[1], (experts, d, f)),
            "w_up": 0.1 * normal(ks[2], (experts, d, f)),
            "w_down": 0.1 * normal(ks[3], (experts, f, d)),
            "mlp_in": 0.1 * normal(ks[4], (d, 3 * f)),
            "mlp_out": 0.1 * normal(ks[5], (3 * f // 2, d))}, \
        normal(ks[6], (1, tokens, d))


def test_the_four_shares_add_up_to_the_uncut_layer():
    """The guide's share test: the routed parts that ``experts_held`` = (0,
    4), (4, 4), (8, 4), (12, 4) give of a layer of 16 experts at 4 a token
    through the model's own block, with the shared SwiGLU counted once, add
    up to the uncut reference's layer; every share computes exactly the
    assignments the router gave its experts, and is the reference's part for
    the same share."""
    w, h = _expert_layer()
    cfg = replace(CFG, num_hidden_layers=1, layer_types=("mamba",))
    scale, eps = cfg.residual_multiplier, cfg.rms_norm_eps
    kw = dict(residual_multiplier=scale, eps=eps, top_k=4)
    captured = {}
    plain = granite._MIXERS["mamba"]
    with jax.default_matmul_precision("highest"):
        want, picked = reference._ffn(h, w, first_expert=0, **kw)
        x = lm.rmsnorm(h, w["ln2_scale"], eps)
        shared = granite._mlp(cfg, x, w)
        total, computed = h + scale * shared, 0
        for first in range(0, 16, 4):
            share = dict(w, **{name: w[name][first:first + 4]
                               for name in ("w_gate", "w_up", "w_down")})
            held = replace(cfg, experts_held=(first, 4))
            # The block's FFN half alone: a mixer that adds nothing.
            granite._MIXERS["mamba"] = lambda cfg, x, layer: jnp.zeros_like(x)
            try:
                got, aux = granite._block(held, "mamba", h, dict(
                    share, ln1_scale=jnp.ones(h.shape[-1])), None)
            finally:
                granite._MIXERS["mamba"] = plain
            mine = ((picked >= first) & (picked < first + 4)).sum()
            assert int(aux["group_sizes"].sum()) == int(mine) \
                == int(aux["asked"])
            computed += int(mine)
            captured[first] = got
            # The reference given the same share gives the same layer.
            np.testing.assert_allclose(got, reference._ffn(
                h, share, first_expert=first, **kw)[0], atol=5e-5)
            # This share's routed part: the block's result without h and s.
            total = total + (got - h - scale * shared)
    assert computed == h.shape[1] * 4
    np.testing.assert_allclose(total, want, atol=1e-4)
    assert float(jnp.abs(captured[0] - captured[4]).max()) > 1e-3


# -- the reference is the published implementation ---------------------------

def test_reference_and_program_are_the_published_implementation(monkeypatch):
    """``reference/granitemoehybrid_moe.py`` (the literal recurrence in five
    stretches of 32 positions, the experts a loop) and the program (the
    chunked scan, the sorted grouped products) against ``transformers``'
    ``GraniteMoeHybridForCausalLM`` with 16 experts at 4 a token on the same
    seeded weights: a sequence longer than a chunk and not a multiple of
    it."""
    monkeypatch.setattr(reference, "SEGMENT", 32)
    cfg = replace(CFG, mamba_chunk_size=64)
    seq = 160
    params = drawn(GRANITE_MOE, cfg, seed=3)
    tokens, targets = batch(cfg, seq, seed=5)
    want = _published_logits(cfg, params, tokens)
    where = jnp.broadcast_to(jnp.arange(seq, dtype=jnp.int32), tokens.shape)
    got, _, rms = reference.forward(
        params, tokens, targets, where,
        **reference.arguments(published(cfg)))
    assert float(rms) > 0.01
    np.testing.assert_allclose(got, want, atol=1e-3 * float(rms))
    with jax.default_matmul_precision("highest"):
        program = jax.jit(partial(granite.forward, cfg=cfg))(
            params, tokens=tokens)
    np.testing.assert_allclose(program, want, atol=1e-3 * float(rms))


# -- what a step's gauge reads ----------------------------------------------

def test_a_step_sets_the_picked_mass():
    """``moe_picked_mass``: what of a token's probability over all 16
    experts its 4 picked hold before renormalising: above 4 / 16, below
    one, and what the registry's gauge reads."""
    found = trained(GRANITE_MOE, 1)
    for metrics in found["metrics"]:
        assert 0.25 < metrics["moe_picked_mass"] < 1.0
    # Fed one call late at most: the last step's value, or the one before.
    gauge = found["gauges"]["ray_tpu_train_moe_picked_mass"]
    assert any(gauge == pytest.approx(metrics["moe_picked_mass"])
               for metrics in found["metrics"][-2:])


def test_a_step_without_experts_records_nothing_of_them():
    """The family's module binds the expert layers' ``RECORDED_METRICS`` for
    all its members; a step of one without experts returns none of them, and
    the recorder passes them over."""
    import optax
    from ray_tpu.parallel.sharding import ShardingRules
    from ray_tpu.parallel.train_step import (init_train_state,
                                             make_train_step)
    cfg = replace(granite.config("granite-tiny"), num_hidden_layers=1)
    mesh = family_cases.one_chip()
    rules, optimizer = ShardingRules(), optax.adam(3e-3)
    state = init_train_state(cfg, mesh, rules, optimizer, seed=0)
    step = make_train_step(cfg, mesh, rules, optimizer)
    tokens, targets = batch(cfg, 128, rows=1)
    before = family_cases.series()
    for _ in range(2):
        state, metrics = step(state, {"tokens": tokens, "targets": targets})
    assert not [name for name in metrics if name.startswith("moe_")]
    after = family_cases.series()
    assert {name: value for name, value in after.items()
            if "train_moe" in name} == {
        name: value for name, value in before.items() if "train_moe" in name}
