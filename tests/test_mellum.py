"""models/mellum.py (window and full attention layers with a rope table a
kind of layer: plain rope beside YaRN, a norm on q and k, a softmax router
over every expert with no shared expert, no bias and no dense layer) against
a copy of the benchmark's plain reference, through ``family_cases.py``; the
YaRN table and the softmax router against ``transformers``';
``lm.expert_ffn`` without shared leaves and the shares of its experts; the
cut configuration's runs and count; what the step's two gauges read.
"""

import math
from dataclasses import replace
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import family_cases
import reference_mellum as reference
from family_cases import expert_layer, in_every_run, share_of, trained
from ray_tpu.models import lm, mellum
from ray_tpu.ops.moe import route

CFG = mellum.config("mellum-tiny")
SEQ = 64
# The kernels (interpreted), remat, the chunked loss, a window that is not a
# multiple of the tile, and a share of the experts: 3 of 8, from the third.
FLASH = replace(CFG, attn_impl="flash", attn_blk_q=128, attn_blk_k=128,
                sliding_window=100, remat=True, loss_chunk=128,
                experts_held=(2, 3))
FLASH_SEQ = 256


def published(cfg):
    out = {"layer_types": list(cfg.layer_types),
           "num_hidden_layers": cfg.num_hidden_layers,
           "sliding_window": cfg.sliding_window, "head_dim": cfg.head_dim,
           "rope_parameters": {kind: dict(parameters) for kind, parameters
                               in cfg.rope_parameters.items()},
           "num_experts_per_tok": cfg.num_experts_per_tok,
           "norm_topk_prob": cfg.norm_topk_prob,
           "rms_norm_eps": cfg.rms_norm_eps}
    if cfg.experts_held is not None:
        first, count = cfg.experts_held
        out["deployment"] = {"experts_held": {
            "first": first, "count": count, "of": cfg.num_experts}}
    return out


def moved(name, leaf, key):
    """Every vector off its one, the q and k norms' scales doubled (the
    scores of a random model then spread by four units, so that a key
    wrongly seen, a wrong table or a wrong KV head moves the softmax), and
    the router's columns twenty times the init's, so that a token's picked
    experts hold well over their even share and their weights differ."""
    if name.endswith("_scale']"):
        gain = 2.0 if "q_norm" in name or "k_norm" in name else 1.0
        return gain * (leaf + 0.2 * jax.random.normal(key, leaf.shape))
    if name.endswith("['router']"):
        return 20.0 * leaf
    return leaf


def _with_rope(cfg, kind, **changed):
    return replace(cfg, rope_parameters=dict(
        cfg.rope_parameters, **{kind: dict(cfg.rope_parameters[kind],
                                           **changed)}))


def drop(dropped, params, cfg, monkeypatch):
    if dropped == "ramp":  # plain rope on the full layer, its factor kept
        cfg = _with_rope(cfg, "full_attention", factor=1.0)
    elif dropped == "attention_factor":
        cfg = _with_rope(cfg, "full_attention", attention_factor=1.0)
    elif dropped == "yarn_on_window":
        cfg = replace(cfg, rope_parameters={
            kind: cfg.rope_parameters["full_attention"]
            for kind in cfg.rope_parameters})
    elif dropped == "window":
        cfg = replace(cfg, sliding_window=10 ** 6)
    elif dropped == "window_off_by_one":
        cfg = replace(cfg, sliding_window=cfg.sliding_window + 1)
    elif dropped == "qk_norm":
        plain = lm.rmsnorm
        monkeypatch.setattr(
            lm, "rmsnorm", lambda x, scale, eps:
            x if x.ndim == 4 else plain(x, scale, eps))
    elif dropped == "norm_topk_prob":
        cfg = replace(cfg, norm_topk_prob=False)
    elif dropped == "sigmoid_for_softmax":
        plain = lm.expert_ffn
        monkeypatch.setattr(lm, "expert_ffn", lambda x, layer, **kw: plain(
            x, dict(layer, router_bias=jnp.zeros(layer["router"].shape[-1])),
            **dict(kw, score="sigmoid")))
    elif dropped == "kv_pairing":
        params = in_every_run(params, lambda w: dict(
            w, wk=jnp.roll(w["wk"], 1, axis=2),
            wv=jnp.roll(w["wv"], 1, axis=2)))
    return params, cfg


MELLUM = family_cases.Family(
    module=mellum, reference=reference, cfg=CFG, seq=SEQ, flash=FLASH,
    flash_seq=FLASH_SEQ, published=published, moved=moved,
    extras=("picked",), drop=drop, dropped=(
        "ramp", "attention_factor", "yarn_on_window", "window", "qk_norm",
        "norm_topk_prob", "sigmoid_for_softmax", "kv_pairing"),
    top_k=CFG.num_experts_per_tok, accum_steps=(1, 2), scan_atol=1e-4,
    wrong=({"experts_held": (6, 4)}, {"experts_held": (0, 0)},
           {"layer_types": ("sliding_attention",) * 2},
           {"layer_types": ("mamba",) * 4},
           {"rope_parameters": {"sliding_attention": {"rope_theta": 1e4}}},
           {"rope_parameters": {
               "sliding_attention": {"rope_theta": 1e4},
               "full_attention": {"rope_type": "llama3",
                                  "rope_theta": 1e4}}}),
    refuses=(ValueError, NotImplementedError),
    flash_kernels=("flash_fwd_win", "flash_bwd_win", "flash_fwd"))
globals().update(family_cases.cases(MELLUM))


def test_the_tiny_stack_has_both_kinds_of_layer(both):
    assert [kind for _, kind, _ in lm.runs(CFG.layers)] == [
        "sliding_attention", "full_attention", "sliding_attention"]
    assert CFG.sliding_window < SEQ and FLASH.sliding_window < FLASH_SEQ
    assert FLASH.sliding_window % FLASH.attn_blk_k
    assert both["aux"]["group_sizes"].shape == (4, CFG.num_experts)
    shapes = jax.eval_shape(partial(mellum.init, FLASH),
                            jax.random.PRNGKey(0))
    assert shapes["run00_sliding_attention"]["w_up"].shape[1] == 3
    assert not any("router_bias" in name or "shared" in name
                   for name in family_cases.leaves(MELLUM))


def test_a_window_one_key_longer_moves_the_logits(both, monkeypatch):
    """``i - j <= window`` for ``i - j < window``: one key of 20 a query, so
    less than a dropped term moves, and still far outside the agreement."""
    params, cfg = drop("window_off_by_one", family_cases.drawn(MELLUM, CFG),
                       CFG, monkeypatch)
    tokens, _ = family_cases.batch(CFG, SEQ)
    got = family_cases.forward_alone(MELLUM, params, cfg, tokens)
    _, want = both["logits"]
    assert float(jnp.abs(got - want).max()) > 10 * MELLUM.logits_tol \
        * both["rms"]


# -- the rope tables ------------------------------------------------------

def _transformers_yarn(parameters, head_dim):
    transformers = pytest.importorskip("transformers")
    from transformers.modeling_rope_utils import _compute_yarn_parameters
    scaling = {key: value for key, value in parameters.items()
               if key != "rope_theta"}
    config = transformers.PretrainedConfig(
        rope_theta=parameters["rope_theta"], head_dim=head_dim,
        hidden_size=head_dim, num_attention_heads=1,
        max_position_embeddings=131072, rope_scaling=scaling)
    inv_freq, factor = _compute_yarn_parameters(config, "cpu")
    return inv_freq.numpy(), factor


@pytest.mark.parametrize("preset", ["mellum2-12b-a2.5b", "mellum-tiny"])
def test_the_yarn_table_is_transformers(preset):
    cfg = mellum.config(preset)
    parameters = cfg.rope_parameters["full_attention"]
    want, factor = _transformers_yarn(parameters, cfg.head_dim)
    got, scaled = lm.rope_table(parameters, cfg.head_dim)
    np.testing.assert_allclose(got, want, rtol=2e-6)
    assert scaled == factor == parameters["attention_factor"]
    mine, mine_scaled = reference.rope_table(parameters, cfg.head_dim)
    np.testing.assert_allclose(mine, want, rtol=2e-6)
    assert mine_scaled == factor
    # The ramp is neither all 0 nor all 1: some pairs keep their frequency,
    # some have it divided by the whole factor, some lie between.
    plain, one = lm.rope_table(cfg.rope_parameters["sliding_attention"],
                               cfg.head_dim)
    ratio = np.asarray(got / plain)
    assert one == 1.0 and ratio[0] == 1.0
    assert ratio[-1] == pytest.approx(1.0 / parameters["factor"])
    assert ((ratio < 0.999) & (ratio > 1.001 / parameters["factor"])).any()
    without = dict(parameters)
    del without["attention_factor"]
    assert lm.rope_table(without, cfg.head_dim)[1] == pytest.approx(
        0.1 * math.log(parameters["factor"]) + 1.0)


def test_the_published_ramp_runs_over_pairs_18_to_35():
    cfg = mellum.config("mellum2-12b-a2.5b")
    table, factor = lm.rope_table(cfg.rope_parameters["full_attention"], 128)
    plain, _ = lm.rope_table(500000, 128)
    ratio = np.asarray(table / plain)
    assert factor == 1.2772588722239782
    assert (ratio[:19] == 1.0).all() and ratio[19] < 1.0
    assert ratio[34] > 1 / 16 and np.allclose(ratio[35:], 1 / 16)
    np.testing.assert_allclose(plain, 500000.0 ** (-np.arange(64) / 64),
                               rtol=1e-6)


def test_the_window_layers_table_is_the_plain_one():
    x = jax.random.normal(jax.random.PRNGKey(0), (2, SEQ, 4, 32))
    positions = lm.positions_of(jnp.zeros((2, SEQ), jnp.int32))
    a = lm.rope(x, positions, CFG.rope_parameters["sliding_attention"])
    assert (a == lm.rope(x, positions, 10000.0)).all()
    b = lm.rope(x, positions, CFG.rope_parameters["full_attention"])
    # Position 0 is rotated by nothing and scaled by the factor.
    np.testing.assert_allclose(b[:, 0], 1.2079441541679836 * x[:, 0],
                               rtol=1e-6)
    assert float(jnp.abs(a - b).max()) > 0.1


@pytest.mark.parametrize("kind", ["linear", "dynamic", "llama3", "longrope"])
def test_the_other_scalings_are_refused(kind):
    with pytest.raises(NotImplementedError, match=kind):
        lm.rope_table({"rope_type": kind, "rope_theta": 1e4, "factor": 2.0},
                      32)


# -- the softmax router ---------------------------------------------------

@pytest.mark.parametrize("normalize", [True, False])
def test_the_softmax_router_is_qwen3_moes(normalize):
    """Weights and picks of ``Qwen3MoeSparseMoeBlock``'s forward on seeded
    logits: a softmax over all the experts in float32, the top of it,
    renormalised where ``norm_topk_prob``."""
    torch = pytest.importorskip("torch")
    tokens, d, experts, top_k = 96, 32, 64, 8
    ks = jax.random.split(jax.random.PRNGKey(3), 2)
    x = jax.random.normal(ks[0], (tokens, d))
    router = jax.random.normal(ks[1], (d, experts)) / math.sqrt(d) * 3.0
    with jax.default_matmul_precision("highest"):
        picked, weights, mass = route(x, router, None, top_k, 1.0, normalize,
                                      "softmax")
    logits = torch.from_numpy(np.array(x)) @ torch.from_numpy(
        np.array(router))
    want = torch.nn.functional.softmax(logits, dim=1, dtype=torch.float)
    want, selected = torch.topk(want, top_k, dim=-1)
    np.testing.assert_allclose(mass, want.sum(-1).numpy(), rtol=1e-5)
    if normalize:
        want = want / want.sum(dim=-1, keepdim=True)
    assert (np.asarray(picked) == selected.numpy()).all()
    np.testing.assert_allclose(weights, want.numpy(), rtol=1e-5)
    assert 8 / 64 < float(mass.mean()) < 1.0


@pytest.mark.parametrize("normalize", [True, False])
def test_an_unpicked_logit_moves_the_weights_only_unnormalised(normalize):
    """Normalised, the weights are a softmax over the picked logits: the
    others cancel, and a router column no token picked gets no gradient."""
    tokens, d, experts, top_k = 32, 16, 8, 2
    ks = jax.random.split(jax.random.PRNGKey(5), 3)
    x = jax.random.normal(ks[0], (tokens, d))
    router = jax.random.normal(ks[1], (d, experts))
    values = jax.random.normal(ks[2], (tokens, top_k))

    def weighted(logits):
        probs = jax.nn.softmax(logits, -1)
        _, picked = jax.lax.top_k(probs, top_k)
        w = jnp.take_along_axis(probs, picked, -1)
        if normalize:
            w = w / w.sum(-1, keepdims=True)
        return (w * values).sum(), picked

    def through_route(logits_of):
        # route's own product, reached through a router that is the logits.
        picked, w, _ = route(logits_of, jnp.eye(experts), None, top_k, 1.0,
                             normalize, "softmax")
        return (w * values).sum(), picked

    with jax.default_matmul_precision("highest"):
        logits = x @ router
        grad, picked = jax.grad(through_route, has_aux=True)(logits)
        want, _ = jax.grad(weighted, has_aux=True)(logits)
    np.testing.assert_allclose(grad, want, atol=1e-6)
    unpicked = ~np.asarray(jax.nn.one_hot(picked, experts).sum(1), bool)
    moved = np.abs(np.asarray(grad))[unpicked]
    assert (moved < 1e-7).all() if normalize else (moved > 1e-7).any()


def test_a_softmax_router_takes_no_bias():
    w, x = expert_layer(experts=8, shared=False)
    with pytest.raises(ValueError, match="no bias"):
        route(x, w["router"], w["router_bias"], 2, 1.0, True, "softmax")
    with pytest.raises(ValueError, match="score"):
        route(x, w["router"], w["router_bias"], 2, 1.0, True, "tanh")


# -- the expert layer without shared experts, and its shares --------------

def test_expert_ffn_without_shared_leaves_returns_no_shared_term():
    w, x = expert_layer(experts=8, shared=False, rank=3)
    del w["router_bias"]
    routed, shared, aux = lm.expert_ffn(x, w, top_k=2, scaling=1.0,
                                        normalize=True, held=None,
                                        score="softmax")
    assert shared is None and routed.shape == x.shape
    assert sorted(aux) == ["asked", "group_sizes", "picked", "picked_mass",
                           "rows_summed", "within_bound"]
    text = jax.jit(lambda x: lm.expert_ffn(
        x, w, top_k=2, scaling=1.0, normalize=True, held=None,
        score="softmax")[0]).lower(x).as_text(debug_info=True)
    assert "shared_expert" not in text
    # The five families with a bias and shared leaves read both, as before.
    w, x = expert_layer(experts=8, rank=3)
    _, shared, aux = lm.expert_ffn(x, w, top_k=2, scaling=1.0,
                                   normalize=True, held=None)
    assert shared.shape == x.shape and "picked_mass" not in aux


def test_the_four_shares_of_16_add_up_to_the_uncut_layer():
    """The guide's share test: the parts that ``experts_held`` = (0, 16),
    (16, 16), (32, 16), (48, 16) give of a layer of 64 experts at 8 a token
    add up to the uncut reference's layer, and every share computes exactly
    the assignments the router gave its experts."""
    w, h = expert_layer(experts=64, tokens=96, shared=False, rank=3)
    del w["router_bias"]
    w["router"] = 4.0 * w["router"]
    top_k = 8
    with jax.default_matmul_precision("highest"):
        want, picked = reference._ffn(h, w, top_k, True, 0)
        total, computed = jnp.zeros_like(h), 0
        for first in range(0, 64, 16):
            share = share_of(w, first, 16)
            routed, shared, aux = lm.expert_ffn(
                h, share, top_k=top_k, scaling=1.0, normalize=True,
                held=(first, 16), score="softmax")
            mine = ((picked >= first) & (picked < first + 16)).sum()
            assert shared is None
            assert int(aux["group_sizes"].sum()) == int(mine) \
                == int(aux["asked"])
            total, computed = total + routed, computed + int(mine)
            # The reference given the same share gives the same part.
            np.testing.assert_allclose(
                routed, reference._ffn(h, share, top_k, True, first)[0],
                atol=5e-5)
    assert computed == h.shape[1] * top_k
    np.testing.assert_allclose(total, want, atol=1e-4)


# -- the cut configuration ------------------------------------------------

CUT = replace(mellum.config("mellum2-12b-a2.5b"), num_hidden_layers=4)


def test_the_cut_configuration_is_one_period_in_two_runs():
    """The benchmark's cut: one whole period, window, window, window,
    full, every expert held and the whole vocabulary."""
    assert lm.runs(CUT.layers) == (
        ("run00_sliding_attention", "sliding_attention", 3),
        ("run01_full_attention", "full_attention", 1))
    shapes = jax.eval_shape(partial(mellum.init, CUT), jax.random.PRNGKey(0))
    window = shapes["run00_sliding_attention"]
    assert window["w_gate"].shape == (3, 64, 2304, 896)
    assert window["router"].shape == (3, 2304, 64)
    assert window["wk"].shape == (3, 2304, 4, 128)
    assert shapes["lm_head"].shape == (2304, 98304)
    a_layer = sum(math.prod(a.shape[1:]) for a in jax.tree.leaves(window))
    assert a_layer == 417_747_712
    assert sum(math.prod(a.shape) for a in jax.tree.leaves(shapes)) \
        == 2_123_977_984


@pytest.mark.parametrize("tile,executed,fill", [(512, 93, 2 / 3),
                                                (256, 310, 0.8)])
def test_the_cells_window_tile_fill(tile, executed, fill):
    """At 16384 tokens under a window of 1024: a row of tiles of 512 is
    three, two of them cut; of 256 five, two cut."""
    from ray_tpu.ops.flash_attention import window_tile_census
    cfg = replace(CUT, attn_impl="flash", attn_blk_q=tile, attn_blk_k=tile)
    assert window_tile_census(16384, 1024, tile, tile)["executed"] == executed
    assert mellum.window_tile_fill(cfg, 16384) == pytest.approx(fill,
                                                                abs=2e-3)
    assert mellum.window_tile_fill(CUT, 16384) is None  # dot: no tiles
    assert mellum.window_tile_fill(cfg, 1024) is None   # nothing cut


# -- what a step's gauges read ---------------------------------------------

def test_a_step_sets_the_picked_mass_and_the_tile_fill():
    found = trained(MELLUM, 1)
    for metrics in found["metrics"]:
        # 2 of 8 picked: a flat router reads 0.25, one that picks all 1.
        assert 0.25 < metrics["moe_picked_mass"] < 1.0
        assert metrics["attn_window_tile_fill"] == pytest.approx(
            mellum.window_tile_fill(FLASH, FLASH_SEQ))
    gauges = found["gauges"]
    # Fed one call late at most: the last step's value, or the one before.
    assert any(gauges["ray_tpu_train_moe_picked_mass"] == pytest.approx(
        metrics["moe_picked_mass"]) for metrics in found["metrics"][-2:])
    # 256 tokens under a window of 100 at tiles of 128: three tiles, the
    # pairs the mask keeps over their 3 x 128 x 128.
    assert gauges["ray_tpu_train_attn_window_tile_fill"] == pytest.approx(
        (100 * 101 // 2 + 156 * 100) / (3 * 128 * 128))


def test_not_a_number_sets_no_tile_fill():
    from ray_tpu._private import builtin_metrics
    builtin_metrics.train_attn_window_tile_fill().set(0.5)
    mellum.RECORDED_METRICS["attn_window_tile_fill"](float("nan"))
    assert family_cases.series()[
        "ray_tpu_train_attn_window_tile_fill"] == 0.5
