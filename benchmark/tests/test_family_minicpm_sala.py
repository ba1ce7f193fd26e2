"""``families/minicpm_sala.py`` and ``reference/minicpm_sala.py`` on the
configurations that name them: the widths, the heads, the mixers and the
selection's sizes the file publishes or assumes, at full and at tiny size;
the weights the family draws (a gain a leaf, or a leaf of one kind of
layer); the reference against the program (the interpreted kernels) through
the family at the tiny size in float32 (logits, the loss, gradients per
leaf); and the tier-1 copy of the reference, letter for letter.

Float32 under the highest matmul precision on both sides: the same sums in
another order, so 1e-4 of a leaf's norm (1e-3 of the logits' RMS) is
reassociation over a few hundred terms and nothing else. The chip's
tolerances, for bfloat16, are the configuration's and are measured there.
"""

import json
import math
import os

import jax
import jax.numpy as jnp
import numpy as np

import harness

family = harness.load_module("families", "minicpm_sala")
reference = harness.load_module("reference", "minicpm_sala")
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"


def configs():
    for entry in harness.load_spec()["configs"]:
        config = harness.load_json(os.path.join(harness.ROOT, entry["file"]))
        if config["program"]["family"] == "minicpm_sala":
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
        assert family.vocab_size(cfg) == config["vocab_size"] == 73448
        assert config["reference"]["family"] == "minicpm_sala"
        assert (cfg.hidden_size, cfg.num_attention_heads,
                cfg.num_key_value_heads, cfg.head_dim, cfg.lightning_nh,
                cfg.lightning_head_dim, cfg.intermediate_size) \
            == (4096, 32, 2, 128, 32, 128, 16384)
        assert list(config["reduced"]) == ["num_hidden_layers"]
        assert config["num_hidden_layers"] >= 4
        assert cfg.layers[:4] == ("sparse",) + ("lightning",) * 3
        assert config["layout"]["seq_len"] \
            > config["assumed"]["sparse_config"]["dense_len"]
        assert len(config["assumed"]["readings"]) == 8
        tiny = family.tiny(config)
        assert family.problems(tiny, family.config(tiny["program"])) == []
    assert seen == 1


def test_the_entries_one_line_texts_fit_the_benchmark_files_limit():
    """``test_spec.py`` holds a cell's ``why`` to 200 characters; the driver
    holds a configuration's ``why`` and ``source`` and a metric's ``layer``
    to the same, and refused this configuration's first ``why`` at 204."""
    spec = harness.load_spec()
    texts = [entry[key] for entry in spec["configs"]
             for key in ("why", "source")]
    texts += [cell["why"] for cell in spec["workloads"]]
    texts += [metric["layer"] for metric in spec["per_layer"]]
    for text in texts:
        assert 1 <= len(text) <= 200 and text.isprintable(), text


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
        wrong = dict(config, num_key_value_heads=8, lightning_nh=16,
                     scale_emb=1, qk_norm=False, attn_use_rope=True,
                     use_output_norm=False, rope_theta=1000000,
                     mixer_types=config["mixer_types"][::-1])
        assert len(family.problems(wrong, cfg)) == 8
        sparse = dict(config["assumed"]["sparse_config"], topk=32,
                      block_size=128)
        other = dict(config, assumed=dict(config["assumed"],
                                          sparse_config=sparse))
        assert len(family.problems(other, cfg)) == 2
        longer = dict(config, layout=dict(config["layout"], seq_len=1 << 20))
        assert len(family.problems(longer, cfg)) == 1


def test_the_reference_takes_its_arguments_from_the_published_keys():
    for config in configs():
        assert reference.arguments(config) == {
            "eps": 1e-6, "theta": 10000.0, "scale_emb": 12.0,
            "r": 1.4 / math.sqrt(32), "divisor": 16.0,
            "published_layers": 32,
            "sparse": (32, 16, 64, 64, 1, 2048, 8192)}


def test_the_drawn_weights_are_what_the_configuration_asks_for():
    config, cfg = tiny_float32()
    program = dict(config["program"], norm_scale_sigma=0.0,
                   gains={"wq": 3.0, "sparse.wo": 5.0, "lightning.wo": 0.5,
                          "wte": 2.0, "q_norm_scale": 1.5})
    plain = jax.jit(lambda key: family._model().init(cfg, key))(
        jax.random.PRNGKey(7))
    params = family.init(cfg, 7, program)
    assert jax.tree.structure(params) == jax.tree.structure(plain)
    for run, wo in (("run00_sparse", 5.0), ("run01_lightning", 0.5)):
        stack, was = params[run], plain[run]
        for name, gain in (("wq", 3.0), ("wk", 1.0), ("wo", wo),
                           ("w_down", 1.0), ("w_g", 1.0)):
            np.testing.assert_allclose(stack[name], gain * was[name],
                                       rtol=1e-6)
        np.testing.assert_allclose(stack["q_norm_scale"], 1.5)
        for name in ("ln1_scale", "ln2_scale", "k_norm_scale"):
            np.testing.assert_allclose(stack[name], 1.0)
    np.testing.assert_allclose(params["lnf_scale"], 1.0)
    np.testing.assert_allclose(params["wte"], 2.0 * plain["wte"], rtol=1e-6)
    np.testing.assert_allclose(params["lm_head"], plain["lm_head"])
    moved = family.init(cfg, 7, config["program"])
    gains = config["program"]["gains"]
    for leaf, gain in (("ln1_scale", 1.0),
                       ("q_norm_scale", gains["q_norm_scale"])):
        for run in ("run00_sparse", "run01_lightning"):
            assert 0.05 * gain < float(jnp.std(moved[run][leaf])) < 0.2 * gain
            assert abs(float(jnp.mean(moved[run][leaf])) - gain) < 0.1 * gain
    for key, gain in gains.items():
        kind, _, leaf = key.rpartition(".")
        for run in moved:
            if run.startswith("run") and leaf in moved[run] \
                    and run.endswith(kind) and not leaf.endswith("_scale"):
                np.testing.assert_allclose(moved[run][leaf],
                                           gain * plain[run][leaf], rtol=1e-6)


def test_reference_against_program_at_tiny_size():
    config, cfg = tiny_float32()
    assert cfg.attn_impl == "flash" and cfg.remat
    assert config["layout"]["seq_len"] > cfg.sparse_dense_len
    kw = reference.arguments(config)
    params = family.init(cfg, 3, config["program"])
    rows = np.random.default_rng(3).integers(0, 512, (1, 513), dtype=np.int32)
    tokens, targets = jnp.asarray(rows[:, :-1]), jnp.asarray(rows[:, 1:])
    where = jnp.asarray([[0, 30, 31, 64, 300, 511]])
    with jax.default_matmul_precision("highest"):
        logits, losses = jax.jit(lambda p: family.logits_and_losses(
            p, cfg, tokens, targets))(params)
        grads = jax.jit(jax.grad(lambda p: family.loss(
            p, cfg, tokens, targets)))(params)
    assert logits.shape == (1, 512, 512) and logits.dtype == jnp.float32
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


def test_with_layers_cuts_file_and_program_alike():
    config = next(configs())
    cut = family.with_layers(config, 2, dense_len=4096)
    cfg = family.config(cut["program"])
    assert cfg.layers == ("sparse", "lightning")
    assert cfg.sparse_dense_len == 4096
    assert family.problems(cut, cfg) == []
    assert config["assumed"]["sparse_config"]["dense_len"] == 8192


def test_the_tier_1_copy_of_the_reference_is_the_reference():
    with open(os.path.join(harness.HERE, "reference",
                           "minicpm_sala.py")) as f:
        yardstick = f.read()
    with open(os.path.join(harness.ROOT, "tests",
                           "reference_minicpm_sala.py")) as f:
        assert f.read() == yardstick
