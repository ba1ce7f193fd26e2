"""``families/granitemoehybrid.py`` and ``reference/granitemoehybrid.py`` on
the configurations that name them: the widths and the layer pattern the file
publishes, at full and at tiny size; the weights the family draws; the
reference against the program through the family at the tiny size in float32
(logits, loss per sequence, gradients per leaf), the kernels interpreted; and
the tier-1 copy of the reference, letter for letter.

Float32 under the highest matmul precision on both sides: the same sums in
another order, so 1e-4 of a leaf's norm (1e-3 of the logits' RMS) is
reassociation over a few hundred terms and nothing else. The chip's
tolerances, for bfloat16, are the configuration's and are measured there.
"""

import os

import jax
import jax.numpy as jnp
import numpy as np

import harness

family = harness.load_module("families", "granitemoehybrid")
reference = harness.load_module("reference", "granitemoehybrid")


def configs():
    for entry in harness.load_spec()["configs"]:
        config = harness.load_json(os.path.join(harness.ROOT, entry["file"]))
        if config["program"]["family"] == "granitemoehybrid":
            yield config


def tiny_float32():
    config = family.tiny(next(configs()))
    program = dict(config["program"])
    program["overrides"] = dict(
        program["overrides"], dtype="float32", param_dtype="float32")
    return dict(config, program=program), family.config(program)


def test_the_program_runs_the_published_widths():
    seen = 0
    for config in configs():
        seen += 1
        cfg = family.config(config["program"])
        assert family.problems(config, cfg) == []
        assert family.vocab_size(cfg) == config["vocab_size"]
        assert config["reference"]["family"] == "granitemoehybrid"
        assert len(config["layer_types"]) == 40  # the published list, whole
        assert cfg.layers == tuple(
            config["layer_types"][:config["num_hidden_layers"]])
        assert cfg.head_dim * cfg.num_attention_heads == cfg.hidden_size
        tiny = family.tiny(config)
        assert family.problems(tiny, family.config(tiny["program"])) == []
        assert tiny["layout"]["mesh"] == config["layout"]["mesh"]
    assert seen


def test_a_width_or_a_mechanism_that_differs_is_reported():
    for config in configs():
        cfg = family.config(config["program"])
        wrong = dict(config, mamba_d_state=64,
                     position_embedding_type="rope", attention_bias=True,
                     layer_types=["attention"] * 40)
        assert len(family.problems(wrong, cfg)) == 4


def test_the_reference_takes_its_arguments_from_the_published_keys():
    for config in configs():
        n = config["num_hidden_layers"]
        assert reference.arguments(config) == {
            "layer_types": tuple(config["layer_types"][:n]),
            "heads": config["mamba_n_heads"],
            "d_state": config["mamba_d_state"],
            "attention_multiplier": config["attention_multiplier"],
            "embedding_multiplier": config["embedding_multiplier"],
            "residual_multiplier": config["residual_multiplier"],
            "logits_scaling": config["logits_scaling"],
            "eps": config["rms_norm_eps"]}


def test_the_drawn_weights_are_what_the_configuration_asks_for():
    config, cfg = tiny_float32()
    program = dict(config["program"], dt_range=[0.01, 0.05],
                   attention_qk_gain=3.0)
    plain = jax.jit(lambda key: family._model().init(cfg, key))(
        jax.random.PRNGKey(7))
    params = family.init(cfg, 7, program)
    assert sorted(k for k in params if k.startswith("run")) == [
        "run00_mamba", "run01_attention", "run02_mamba"]
    for run in ("run00_mamba", "run02_mamba"):
        dt = np.asarray(jax.nn.softplus(params[run]["dt_bias"]))
        assert 0.01 <= dt.min() and dt.max() <= 0.05 * (1 + 1e-5)
    np.testing.assert_allclose(params["run01_attention"]["wq"],
                               3.0 * plain["run01_attention"]["wq"],
                               rtol=1e-6)
    np.testing.assert_allclose(params["run01_attention"]["wv"],
                               plain["run01_attention"]["wv"])
    for stack, leaf in (("run00_mamba", "conv_b"), ("run02_mamba", "D"),
                        ("run02_mamba", "norm_scale"),
                        ("run01_attention", "ln2_scale")):
        assert np.abs(np.asarray(params[stack][leaf])
                      - np.asarray(plain[stack][leaf])).max() > 0.0
    assert np.abs(np.asarray(params["lnf_scale"]) - 1.0).max() > 0.0


def test_reference_against_program_at_tiny_size():
    config, cfg = tiny_float32()
    params = family.init(cfg, 0, config["program"])
    seq = config["layout"]["seq_len"]
    rows = np.random.default_rng(0).integers(0, cfg.vocab_size, (2, seq + 1),
                                             dtype=np.int32)
    tokens, targets = jnp.asarray(rows[:, :-1]), jnp.asarray(rows[:, 1:])
    where = jnp.broadcast_to(jnp.arange(seq, dtype=jnp.int32), tokens.shape)
    kw = reference.arguments(config)
    want, want_loss, rms = reference.forward(params, tokens, targets, where,
                                             **kw)
    with jax.default_matmul_precision("highest"):
        got, got_loss = jax.jit(lambda p: family.logits_and_losses(
            p, cfg, tokens, targets))(params)
        grads = jax.grad(lambda p: family.loss(p, cfg, tokens, targets))(
            params)
    assert float(rms) > 0.01
    np.testing.assert_allclose(got, want, atol=1e-3 * float(rms))
    np.testing.assert_allclose(got_loss, want_loss, rtol=1e-5)
    want_grads = jax.grad(lambda p: reference.loss(
        p, tokens, targets, **kw))(params)
    for (path, w), g in zip(jax.tree_util.tree_leaves_with_path(want_grads),
                            jax.tree.leaves(grads)):
        norm = float(jnp.linalg.norm(w.ravel()))
        assert norm > 0.0, path
        assert float(jnp.linalg.norm((g - w).ravel())) < 1e-4 * norm, path


def test_the_tier_1_copy_of_the_reference_is_the_reference():
    with open(os.path.join(harness.HERE, "reference",
                           "granitemoehybrid.py")) as f:
        yardstick = f.read()
    with open(os.path.join(harness.ROOT, "tests",
                           "reference_granitemoehybrid.py")) as f:
        assert f.read() == yardstick
