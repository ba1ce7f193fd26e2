"""``families/evabyte.py`` and ``reference/evabyte.py`` on the configurations
that name them: the widths, the window, the chunks and the heads the file
publishes, at full and at tiny size; the weights the family draws; the
reference (the explicit mask over explicitly pooled rows) against the
program (the stacked rows on the flash kernels' tables, interpreted) through
the family at the tiny size in float32 (all heads' logits, the loss per
sequence, gradients per leaf), through the runner's own comparison too; and
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

family = harness.load_module("families", "evabyte")
reference = harness.load_module("reference", "evabyte")
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"


def configs():
    for entry in harness.load_spec()["configs"]:
        config = harness.load_json(os.path.join(harness.ROOT, entry["file"]))
        if config["program"]["family"] == "evabyte":
            yield config


def tiny_float32():
    config = family.tiny(next(configs()))
    return config, family.config(config["program"])


def test_the_program_runs_the_published_widths():
    seen = 0
    for config in configs():
        seen += 1
        cfg = family.config(config["program"])
        assert family.problems(config, cfg) == []
        assert family.vocab_size(cfg) == config["vocab_size"] == 320
        assert config["reference"]["family"] == "evabyte"
        assert (cfg.hidden_size, cfg.num_attention_heads, cfg.head_dim,
                cfg.intermediate_size, cfg.window_size, cfg.chunk_size,
                cfg.num_pred_heads) == (4096, 32, 128, 11008, 2048, 16, 8)
        assert list(config["reduced"]) == ["num_hidden_layers"]
        assert config["num_hidden_layers"] >= 4
        assert config["layout"]["seq_len"] == 32768
        tiny = family.tiny(config)
        assert family.problems(tiny, family.config(tiny["program"])) == []
    assert seen == 1


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


def test_a_width_or_a_mechanism_that_differs_is_reported():
    for config in configs():
        cfg = family.config(config["program"])
        wrong = dict(config, window_size=4096, chunk_size=32,
                     num_pred_heads=1, attention_class="softmax",
                     norm_add_unit_offset=False, rope_theta=10000,
                     num_key_value_heads=8, fp32_logits=False)
        assert len(family.problems(wrong, cfg)) == 8
        longer = dict(config, layout=dict(config["layout"], seq_len=65536))
        assert len(family.problems(longer, cfg)) == 1


def test_the_reference_takes_its_arguments_from_the_published_keys():
    for config in configs():
        assert reference.arguments(config) == {
            "window": 2048, "chunk": 16, "theta": 100000.0,
            "eps": 1e-5, "pred_heads": 8}


def test_the_drawn_weights_are_what_the_configuration_asks_for():
    config, cfg = tiny_float32()
    program = dict(config["program"], norm_offset_sigma=0.0,
                   gains={"wq": 3.0, "eva_mu": 5.0, "wo": 0.5})
    plain = jax.jit(lambda key: family._model().init(cfg, key))(
        jax.random.PRNGKey(7))
    params = family.init(cfg, 7, program)
    assert jax.tree.structure(params) == jax.tree.structure(plain)
    stack, was = params["run00_eva"], plain["run00_eva"]
    for name, gain in (("wq", 3.0), ("wk", 1.0), ("wv", 1.0), ("wo", 0.5),
                       ("eva_mu", 5.0), ("eva_phi", 1.0), ("w_down", 1.0)):
        np.testing.assert_allclose(stack[name], gain * was[name], rtol=1e-6)
    for name in ("ln1_scale", "ln2_scale"):
        np.testing.assert_allclose(stack[name], 0.0)
    np.testing.assert_allclose(params["lnf_scale"], 0.0)
    np.testing.assert_allclose(params["lm_head"], plain["lm_head"])
    assert abs(float(params["wte"].std()) - cfg.init_std) < 2e-3
    moved = family.init(cfg, 7, config["program"])
    for leaf in (moved["lnf_scale"], moved["run00_eva"]["ln1_scale"],
                 moved["run00_eva"]["ln2_scale"]):
        assert 0.05 < float(jnp.std(leaf)) < 0.2
    gains = config["program"]["gains"]
    assert set(gains) <= set(stack)
    for name, gain in gains.items():
        np.testing.assert_allclose(moved["run00_eva"][name],
                                   gain * was[name], rtol=1e-6)


def test_reference_against_program_at_tiny_size():
    config, cfg = tiny_float32()
    assert cfg.attn_impl == "flash" and cfg.remat
    kw = reference.arguments(config)
    params = family.init(cfg, 3, config["program"])
    rows = np.random.default_rng(3).integers(0, 320, (2, 257),
                                             dtype=np.int32)
    tokens, targets = jnp.asarray(rows[:, :-1]), jnp.asarray(rows[:, 1:])
    where = jnp.asarray([[0, 63, 64, 200, 255], [7, 99, 128, 254, 255]])
    with jax.default_matmul_precision("highest"):
        logits, losses = jax.jit(lambda p: family.logits_and_losses(
            p, cfg, tokens, targets))(params)
        grads = jax.jit(jax.grad(lambda p: family.loss(
            p, cfg, tokens, targets)))(params)
    assert logits.shape == (2, 256, 4 * 320)
    want, want_loss, rms = reference.forward(params, tokens, targets, where,
                                             **kw)
    got = jnp.take_along_axis(logits, where[..., None], axis=1)
    assert float(rms) > 0.01
    np.testing.assert_allclose(got, want, atol=1e-3 * float(rms))
    np.testing.assert_allclose(losses, want_loss, rtol=1e-5)
    want_grads = jax.jit(jax.grad(lambda p: reference.loss(
        p, tokens, targets, **kw)))(params)
    for (path, w), g in zip(jax.tree_util.tree_leaves_with_path(want_grads),
                            jax.tree.leaves(grads)):
        norm = float(jnp.linalg.norm(w.ravel()))
        assert norm > 0.0, path
        assert float(jnp.linalg.norm((g - w).ravel())) < 1e-4 * norm, path


def test_the_tier_1_copy_of_the_reference_is_the_reference():
    with open(os.path.join(harness.HERE, "reference", "evabyte.py")) as f:
        yardstick = f.read()
    with open(os.path.join(harness.ROOT, "tests",
                           "reference_evabyte.py")) as f:
        assert f.read() == yardstick
