"""``families/nemotron_h.py`` and ``reference/nemotron_h.py`` on the
configurations that name them: the widths, the pattern, the layers and the
share the file publishes, at full and at tiny size; the weights the family
draws; the reference against the program through the family at the tiny size
in float32 (logits, loss per sequence, routing, gradients per leaf), the
kernels interpreted; and the tier-1 copy of the reference, letter for letter.

Float32 under the highest matmul precision on both sides: the same sums in
another order, so 1e-4 of a leaf's norm (1e-3 of the logits' RMS) is
reassociation over a few hundred terms and nothing else. The chip's
tolerances, for bfloat16 and the real share, are the configuration's and are
measured there.
"""

import json
import os

import jax
import jax.numpy as jnp
import numpy as np

import harness

family = harness.load_module("families", "nemotron_h")
reference = harness.load_module("reference", "nemotron_h")
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"


def configs():
    for entry in harness.load_spec()["configs"]:
        config = harness.load_json(os.path.join(harness.ROOT, entry["file"]))
        if config["program"]["family"] == "nemotron_h":
            yield config


def tiny_float32():
    config = family.tiny(next(configs()))
    return config, family.config(config["program"])


def test_the_program_runs_the_published_widths_and_the_stated_share():
    seen = 0
    for config in configs():
        seen += 1
        cfg = family.config(config["program"])
        assert family.problems(config, cfg) == []
        assert family.vocab_size(cfg) == config["vocab_size"] == 32768
        assert config["reference"]["family"] == "nemotron_h"
        assert len(config["hybrid_override_pattern"]) == 52  # kept whole
        # Every published width.
        assert (cfg.hidden_size, cfg.mamba_num_heads, cfg.mamba_head_dim,
                cfg.ssm_state_size, cfg.n_groups, cfg.conv_kernel) == (
            2688, 64, 64, 128, 8, 4)
        assert (cfg.num_attention_heads, cfg.num_key_value_heads,
                cfg.head_dim) == (32, 2, 128)
        assert (cfg.moe_intermediate_size,
                cfg.moe_shared_expert_intermediate_size,
                cfg.n_routed_experts, cfg.num_experts_per_tok,
                cfg.routed_scaling_factor) == (1856, 3712, 128, 6, 2.5)
        # The cut: depth, the experts held, the vocabulary; 4 chips a layer.
        assert sorted(config["reduced"]) == [
            "n_routed_experts", "num_hidden_layers", "vocab_size"]
        deployment = config["deployment"]
        assert deployment["chips_sharing_a_layer"] == 4
        assert deployment["experts_held"] == {"first": 0, "count": 32,
                                              "of": 128}
        assert deployment["vocab_slice"] == {"first": 0, "count": 32768,
                                             "of": 131072}
        assert deployment["layers_run"] == {"first": 34, "count": 9}
        assert cfg.experts_held == (0, 32) and cfg.first_layer == 34
        # The guide's floors: a whole period, every kind at 4 : 4 : 1.
        assert cfg.layers == ("experts", "mamba") * 4 + ("attention",)
        assert len(config["assumed"]["readings"]) == 5
        tiny = family.tiny(config)
        assert family.problems(tiny, family.config(tiny["program"])) == []
        assert tiny["layout"]["mesh"] == config["layout"]["mesh"]
    assert seen


def test_every_published_key_is_in_the_file_at_its_published_value():
    if not os.path.isfile(CATALOG):
        return
    with open(CATALOG) as f:
        rows = {row["source_url"]: row for row in map(json.loads, f)}
    for config in configs():
        published = rows[config["source"]]["config"]
        differing = sorted(key for key, value in published.items()
                           if config.get(key, "absent") != value)
        assert differing == sorted(config["reduced"])
        for key, cut in config["reduced"].items():
            assert cut["published"] == published[key]
            assert cut["here"] == config[key]
        assert len(config["source"]) <= 200


def test_a_width_a_mechanism_or_a_share_that_differs_is_reported():
    for config in configs():
        cfg = family.config(config["program"])
        wrong = dict(config, n_groups=1, moe_intermediate_size=2048,
                     mlp_hidden_act="silu", norm_topk_prob=False,
                     attention_bias=True, n_routed_experts=16,
                     hybrid_override_pattern="M" * 52)
        assert len(family.problems(wrong, cfg)) == 7
        moved = dict(config, deployment=dict(
            config["deployment"], layers_run={"first": 0, "count": 9}))
        assert len(family.problems(moved, cfg)) == 1
        long = dict(config, layout=dict(config["layout"], seq_len=524288))
        assert len(family.problems(long, cfg)) == 1


def test_the_reference_takes_its_arguments_from_the_published_keys():
    for config in configs():
        kw = reference.arguments(config)
        assert kw == {
            "layer_types": ("experts", "mamba") * 4 + ("attention",),
            "heads": 64, "groups": 8, "state": 128, "top_k": 6,
            "norm_topk_prob": True, "scaling": 2.5, "eps": 1e-05,
            "first_expert": 0}


def test_the_drawn_weights_are_what_the_configuration_asks_for():
    config, cfg = tiny_float32()
    program = dict(config["program"], attention_qk_gain=3.0,
                   router_bias_max=0.5, norm_scale_sigma=0.0,
                   conv_bias_sigma=0.0, router_balance_steps=0)
    plain = jax.jit(lambda key: family._model().init(cfg, key))(
        jax.random.PRNGKey(7))
    params = family.init(cfg, 7, program)
    assert jax.tree.structure(params) == jax.tree.structure(plain)
    unit, attention = params["run00_experts_mamba"], params["run01_attention"]
    np.testing.assert_allclose(attention["wq"],
                               3.0 * plain["run01_attention"]["wq"])
    np.testing.assert_allclose(attention["wv"],
                               plain["run01_attention"]["wv"])
    np.testing.assert_allclose(unit["a_ln_scale"], 1.0)
    np.testing.assert_allclose(unit["b_conv_b"], 0.0)
    np.testing.assert_allclose(unit["b_dt_bias"],
                               plain["run00_experts_mamba"]["b_dt_bias"])
    # A bias a layer: the largest entry 0.5, the others on both sides of 0.
    bias = np.asarray(unit["a_router_bias"])
    np.testing.assert_allclose(bias.max(-1), 0.5, rtol=1e-5)
    assert (bias < 0).any() and bias.shape == (4, 16)
    # The steps start between the published time_step_min and _max.
    steps = np.log1p(np.exp(np.asarray(unit["b_dt_bias"], np.float64)))
    assert 0.001 * 0.99 < steps.min() and steps.max() < 0.1 * 1.01
    moved = family.init(cfg, 7, config["program"])
    for leaf in ("b_norm_scale", "b_D", "b_A_log", "b_conv_b", "a_ln_scale"):
        assert np.abs(np.asarray(moved["run00_experts_mamba"][leaf])
                      - np.asarray(plain["run00_experts_mamba"][leaf])
                      ).max() > 0.0, leaf
    assert np.abs(np.asarray(moved["lnf_scale"]) - 1.0).max() > 0.0


def test_the_balanced_bias_evens_the_load_out():
    """``balanced`` moves every expert layer's correction bias against its
    experts' load: on other tokens than it saw, the busiest of the 16
    experts stands closer to the mean than with the drawn bias alone, in
    the worst layer and on average, and nothing but the bias moved."""
    config, cfg = tiny_float32()
    assert config["program"]["router_balance_steps"] > 0
    assert config["program"]["router_balance_tokens"] == 256
    tokens = jnp.asarray(np.random.default_rng(99).integers(
        0, cfg.vocab_size, (4, 256), dtype=np.int32))

    def busiest(params):
        _, picked = family.picked_experts(params, cfg, tokens)
        picked = np.asarray(picked).reshape(picked.shape[0], -1)
        loads = np.stack([np.bincount(row, minlength=16) for row in picked])
        return loads.max(-1) / loads.mean(-1)

    drawn = family.init(cfg, 5, dict(config["program"],
                                     router_balance_steps=0))
    even = family.init(cfg, 5, config["program"])
    before, after = busiest(drawn), busiest(even)
    assert after.max() < before.max() and after.mean() < before.mean()
    for (path, a), b in zip(jax.tree_util.tree_leaves_with_path(drawn),
                            jax.tree.leaves(even)):
        same = bool((np.asarray(a) == np.asarray(b)).all())
        assert same != ("router_bias" in jax.tree_util.keystr(path)), path


def test_reference_against_program_at_tiny_size():
    config, cfg = tiny_float32()
    params = family.init(cfg, 0, config["program"])
    seq = config["layout"]["seq_len"]
    assert cfg.attn_impl == "flash" and cfg.experts_held == (0, 4)
    rows = np.random.default_rng(0).integers(0, cfg.vocab_size, (2, seq + 1),
                                             dtype=np.int32)
    tokens, targets = jnp.asarray(rows[:, :-1]), jnp.asarray(rows[:, 1:])
    where = jnp.broadcast_to(jnp.arange(seq, dtype=jnp.int32), tokens.shape)
    kw = reference.arguments(config)
    want, want_loss, rms, want_picked = reference.forward(
        params, tokens, targets, where, with_picked=True, **kw)
    with jax.default_matmul_precision("highest"):
        got, got_loss = jax.jit(lambda p: family.logits_and_losses(
            p, cfg, tokens, targets))(params)
        _, picked = family.picked_experts(params, cfg, tokens)
        grads = jax.grad(lambda p: family.loss(p, cfg, tokens, targets))(
            params)
    assert float(rms) > 0.01
    np.testing.assert_allclose(got, want, atol=1e-3 * float(rms))
    np.testing.assert_allclose(got_loss, want_loss, rtol=1e-5)
    assert (np.sort(picked, -1) == np.sort(want_picked, -1)).all()
    want_grads = jax.grad(lambda p: reference.loss(
        p, tokens, targets, **kw))(params)
    for (path, w), g in zip(jax.tree_util.tree_leaves_with_path(want_grads),
                            jax.tree.leaves(grads)):
        norm = float(jnp.linalg.norm(w.ravel()))
        if "router_bias" in jax.tree_util.keystr(path):
            assert norm == 0.0 and not np.any(g)  # selection only
            continue
        assert norm > 0.0, path
        assert float(jnp.linalg.norm((g - w).ravel())) < 1e-4 * norm, path


def test_the_tier_1_copy_of_the_reference_is_the_reference():
    with open(os.path.join(harness.HERE, "reference", "nemotron_h.py")) as f:
        yardstick = f.read()
    with open(os.path.join(harness.ROOT, "tests",
                           "reference_nemotron_h.py")) as f:
        assert f.read() == yardstick
