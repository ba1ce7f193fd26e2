"""``families/lfm2_moe.py`` and ``reference/lfm2_moe.py`` on the
configurations that name them: the widths, the layer pattern, the layers
that run and the share the file publishes, at full and at tiny size; the
weights the family draws; the reference against the program (the
convolution's and the flash kernels, interpreted) through the family at the
tiny size in float32 (logits, loss per sequence, gradients per leaf); and
the tier-1 copy of the reference, letter for letter.

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
from ray_tpu.ops import short_conv

family = harness.load_module("families", "lfm2_moe")
reference = harness.load_module("reference", "lfm2_moe")
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"


def configs():
    for entry in harness.load_spec()["configs"]:
        config = harness.load_json(os.path.join(harness.ROOT, entry["file"]))
        if config["program"]["family"] == "lfm2_moe":
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
        assert family.vocab_size(cfg) == config["vocab_size"]
        assert config["reference"]["family"] == "lfm2_moe"
        assert len(config["layer_types"]) == \
            config["reduced"]["num_hidden_layers"]["published"] == 40
        deployment = config["deployment"]
        run = deployment["layers_run"]
        assert run["count"] == config["num_hidden_layers"] \
            and cfg.first_layer == run["first"]
        # Leading dense layers count once: the cut starts at the last of
        # the published ones, so the pattern behind it falls as published.
        assert run["first"] == config["reduced"]["num_dense_layers"][
            "published"] - config["num_dense_layers"]
        held = deployment["experts_held"]
        assert cfg.num_experts == held["of"] == \
            config["reduced"]["num_experts"]["published"]
        assert cfg.experts_held == (held["first"], held["count"])
        assert config["num_experts"] == held["count"]
        assert held["of"] == held["count"] * \
            deployment["chips_sharing_a_layer"]
        piece = deployment["vocab_slice"]
        assert piece["count"] == config["vocab_size"] and \
            piece["of"] == config["reduced"]["vocab_size"]["published"]
        # The guide's floors: whole periods of four expert layers (1
        # attention : 3 convolution), 8 experts, an eighth of the vocabulary.
        kinds = cfg.layers[config["num_dense_layers"]:]
        assert len(kinds) % 4 == 0 and kinds[:4] == (
            "moe_full_attention", "moe_conv", "moe_conv", "moe_conv")
        assert kinds.count("moe_conv") == 3 * kinds.count(
            "moe_full_attention") >= 3
        assert held["count"] >= 8 and 8 * piece["count"] >= piece["of"]
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


def test_a_width_a_mechanism_or_a_share_that_differs_is_reported():
    for config in configs():
        cfg = family.config(config["program"])
        wrong = dict(
            config, conv_L_cache=4, num_key_value_heads=4,
            moe_intermediate_size=1024, norm_topk_prob=False,
            layer_types=["conv"] * 40, model_type="lfm2",
            rope_parameters={"rope_theta": 10000.0, "rope_type": "default"},
            num_experts=16)
        assert len(family.problems(wrong, cfg)) == 8
        moved = dict(config, deployment=dict(
            config["deployment"],
            experts_held={"first": 8, "count": 8, "of": 64},
            layers_run={"first": 0, "count": 17}))
        assert len(family.problems(moved, cfg)) == 2


def test_the_reference_takes_its_arguments_from_the_published_keys():
    for config in configs():
        run = config["deployment"]["layers_run"]
        assert reference.arguments(config) == {
            "layer_types": tuple(config["layer_types"][
                run["first"]:run["first"] + run["count"]]),
            "num_dense_layers": config["num_dense_layers"],
            "theta": config["rope_parameters"]["rope_theta"],
            "top_k": config["num_experts_per_tok"],
            "scaling": config["routed_scaling_factor"],
            "normalize": config["norm_topk_prob"],
            "eps": config["norm_eps"],
            "first_expert": config["deployment"]["experts_held"]["first"]}
        assert reference.arguments(config)["layer_types"][:5] == (
            "conv", "full_attention", "conv", "conv", "conv")


def test_the_drawn_weights_are_what_the_configuration_asks_for():
    config, cfg = tiny_float32()
    program = dict(config["program"], router_bias_max=0.07,
                   norm_scale_sigma=0.0, qk_norm_gain=3.0)
    plain = jax.jit(lambda key: family._model().init(cfg, key))(
        jax.random.PRNGKey(7))
    params = family.init(cfg, 7, program)
    runs = sorted(k for k in params if k.startswith("run"))
    assert len(runs) == 9 and jax.tree.structure(params) == \
        jax.tree.structure(plain)
    for run in runs:
        np.testing.assert_allclose(params[run]["operator_norm_scale"], 1.0)
        for name in ("w_in", "conv_w", "wq"):
            if name in params[run]:   # matrices and taps as the init drew
                np.testing.assert_allclose(params[run][name],
                                           plain[run][name])
        if "q_norm_scale" in params[run]:
            np.testing.assert_allclose(params[run]["q_norm_scale"], 3.0)
            np.testing.assert_allclose(params[run]["k_norm_scale"], 3.0)
        if "router_bias" in params[run]:
            bias = np.asarray(params[run]["router_bias"])
            np.testing.assert_allclose(bias.max(-1), 0.07, rtol=1e-6)
    taps = np.asarray(plain[runs[0]]["conv_w"])
    assert abs(taps.std() - (3 * cfg.conv_L_cache) ** -0.5) < 0.1
    moved = family.init(cfg, 7, config["program"])
    assert np.abs(np.asarray(moved["embedding_norm_scale"]) - 1.0).max() > 0
    for run, name in ((runs[0], "ffn_norm_scale"), (runs[1], "k_norm_scale")):
        scale = np.asarray(moved[run][name])
        assert np.abs(scale - scale.mean()).max() > 0.0


def test_reference_against_program_at_tiny_size():
    config, cfg = tiny_float32()
    params = family.init(cfg, 0, config["program"])
    seq = config["layout"]["seq_len"]
    # Both kernel sets run: the flash three and the convolution's pair.
    assert cfg.attn_impl == "flash" and seq % short_conv.ROWS == 0 \
        and cfg.hidden_size % 128 == 0
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
        grads = jax.jit(jax.grad(
            lambda p: family.loss(p, cfg, tokens, targets)))(params)
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
            assert norm == 0.0 and not np.any(g)
            continue
        assert norm > 0.0, path
        assert float(jnp.linalg.norm((g - w).ravel())) < 1e-4 * norm, path


def test_the_tier_1_copy_of_the_reference_is_the_reference():
    with open(os.path.join(harness.HERE, "reference", "lfm2_moe.py")) as f:
        yardstick = f.read()
    with open(os.path.join(harness.ROOT, "tests",
                           "reference_lfm2_moe.py")) as f:
        assert f.read() == yardstick
