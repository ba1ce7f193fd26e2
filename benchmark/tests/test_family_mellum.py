"""``families/mellum.py`` and ``reference/mellum.py`` on the configurations
that name them: the widths, the layer pattern and the rope parameters the
file publishes, at full and at tiny size; the weights the family draws; the
reference against the program through the family at the tiny size in float32
(logits, loss per sequence, routing, gradients per leaf), the kernels
interpreted; and the tier-1 copy of the reference, letter for letter.

Float32 under the highest matmul precision on both sides: the same sums in
another order, so 1e-4 of a leaf's norm (1e-3 of the logits' RMS) is
reassociation over a few hundred terms and nothing else. The chip's
tolerances, for bfloat16, are the configuration's and are measured there.
"""

import json
import os

import jax
import jax.numpy as jnp
import numpy as np

import harness

family = harness.load_module("families", "mellum")
reference = harness.load_module("reference", "mellum")
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"


def configs():
    for entry in harness.load_spec()["configs"]:
        config = harness.load_json(os.path.join(harness.ROOT, entry["file"]))
        if config["program"]["family"] == "mellum":
            yield config


def tiny_float32():
    config = family.tiny(next(configs()))
    return config, family.config(config["program"])


def test_the_program_runs_the_published_widths_and_every_expert():
    seen = 0
    for config in configs():
        seen += 1
        cfg = family.config(config["program"])
        assert family.problems(config, cfg) == []
        assert family.vocab_size(cfg) == config["vocab_size"] == 98304
        assert config["reference"]["family"] == "mellum"
        assert len(config["layer_types"]) == 28  # the published list, whole
        # Nothing cut but the depth: no deployment group, every expert held.
        assert "deployment" not in config and cfg.experts_held is None
        assert list(config["reduced"]) == ["num_hidden_layers"]
        assert cfg.num_experts == config["num_experts"] == 64
        # The guide's floors: a whole period of four layers, both kinds.
        assert cfg.layers == ("sliding_attention",) * 3 + ("full_attention",)
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
                           if config.get(key) != value)
        assert differing == sorted(config["reduced"])
        for key, cut in config["reduced"].items():
            assert cut["published"] == published[key]
            assert cut["here"] == config[key]


def test_a_width_a_mechanism_or_a_table_that_differs_is_reported():
    for config in configs():
        cfg = family.config(config["program"])
        ropes = dict(config["rope_parameters"], full_attention=dict(
            config["rope_parameters"]["full_attention"], factor=8))
        wrong = dict(config, sliding_window=2048, norm_topk_prob=False,
                     attention_bias=True, rope_parameters=ropes,
                     layer_types=["full_attention"] * 28, num_experts=16,
                     mlp_layer_types=["dense"] + ["sparse"] * 27)
        assert len(family.problems(wrong, cfg)) == 7
        long = dict(config, layout=dict(config["layout"], seq_len=262144))
        assert len(family.problems(long, cfg)) == 1


def test_the_reference_takes_its_arguments_from_the_published_keys():
    for config in configs():
        kw = reference.arguments(config)
        assert kw["layer_types"] == ("sliding_attention",) * 3 + (
            "full_attention",)
        assert {k: kw[k] for k in ("window", "top_k", "norm_topk_prob",
                                   "eps", "first_expert")} == {
            "window": 1024, "top_k": 8, "norm_topk_prob": True,
            "eps": 1e-06, "first_expert": 0}
        table, factor = kw["ropes"]["full_attention"]
        plain, one = kw["ropes"]["sliding_attention"]
        assert factor == 1.2772588722239782 and one == 1.0
        ratio = np.asarray(table) / np.asarray(plain)
        assert (ratio[:19] == 1.0).all() and ratio[19] < 1.0
        assert ratio[34] > 1 / 16 and np.allclose(ratio[35:], 1 / 16)
        np.testing.assert_allclose(plain, 500000.0 ** (-np.arange(64) / 64),
                                   rtol=1e-6)


def test_the_drawn_weights_are_what_the_configuration_asks_for():
    config, cfg = tiny_float32()
    program = dict(config["program"], router_spread=3.0, router_gain=1.0,
                   attention_qk_gain=3.0, norm_scale_sigma=0.0)
    plain = jax.jit(lambda key: family._model().init(cfg, key))(
        jax.random.PRNGKey(7))
    params = family.init(cfg, 7, program)
    runs = sorted(k for k in params if k.startswith("run"))
    assert len(runs) == 2 and jax.tree.structure(params) == \
        jax.tree.structure(plain)
    for run in runs:
        np.testing.assert_allclose(params[run]["q_norm_scale"], 3.0)
        np.testing.assert_allclose(params[run]["ln_in_scale"], 1.0)
        np.testing.assert_allclose(params[run]["wq"], plain[run]["wq"])
        # A gain a column and layer: the largest 3, none under a third.
        router, drawn = np.asarray(plain[run]["router"]), \
            np.asarray(params[run]["router"])
        gains = np.abs(drawn).sum(1) / np.abs(router).sum(1)  # [layers, E]
        np.testing.assert_allclose(drawn, router * gains[:, None], rtol=1e-4,
                                   atol=1e-7)
        np.testing.assert_allclose(gains.max(-1), 3.0, rtol=1e-5)
        assert gains.min() >= 1 / 3 - 1e-5 and (gains < 1.0).any()
    doubled = family.init(cfg, 7, dict(program, router_gain=2.0))
    np.testing.assert_allclose(doubled[runs[0]]["router"],
                               2.0 * params[runs[0]]["router"], rtol=1e-6)
    moved = family.init(cfg, 7, config["program"])
    assert np.abs(np.asarray(moved["lnf_scale"]) - 1.0).max() > 0.0
    assert np.abs(np.asarray(moved[runs[0]]["ln_post_scale"])
                  - 1.0).max() > 0.0


def test_reference_against_program_at_tiny_size():
    config, cfg = tiny_float32()
    params = family.init(cfg, 0, config["program"])
    seq = config["layout"]["seq_len"]
    assert cfg.sliding_window < seq and cfg.attn_impl == "flash"
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
        assert norm > 0.0, path
        assert float(jnp.linalg.norm((g - w).ravel())) < 1e-4 * norm, path


def test_the_tier_1_copy_of_the_reference_is_the_reference():
    with open(os.path.join(harness.HERE, "reference", "mellum.py")) as f:
        yardstick = f.read()
    with open(os.path.join(harness.ROOT, "tests",
                           "reference_mellum.py")) as f:
        assert f.read() == yardstick
