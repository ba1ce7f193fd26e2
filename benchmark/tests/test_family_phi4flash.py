"""``families/phi4flash.py`` and ``reference/phi4flash.py`` on the
configurations that name them: the widths, the layers that run and the sizes
the file assumes, at full and at tiny size; the weights the family draws; the
reference against the program (the scan's, the convolution's and the flash
kernels, interpreted) through the family at the tiny size in float32 (logits,
loss per sequence, gradients per leaf, by ``check_grads_phi4flash``'s own
comparison); and the tier-1 copy of the reference, letter for letter.

Float32 under the highest matmul precision on both sides: the same sums in
another order, so 1e-4 of a leaf's norm (1e-3 of the logits' RMS) is
reassociation over a few hundred terms and nothing else; a lambda's gradient
is one number summed over every output of a layer, of terms that nearly
cancel (1e-4 of a bias's), and gets 5e-2. The chip's tolerances, for bfloat16, are
the configuration's and are measured there.
"""

import json
import os

import jax
import jax.numpy as jnp
import numpy as np

import check_grads_phi4flash
import harness
from ray_tpu.ops import selective_scan

family = harness.load_module("families", "phi4flash")
reference = harness.load_module("reference", "phi4flash")
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"


def configs():
    for entry in harness.load_spec()["configs"]:
        config = harness.load_json(os.path.join(harness.ROOT, entry["file"]))
        if config["program"]["family"] == "phi4flash":
            yield config


def tiny_float32():
    config = family.tiny(next(configs()))
    return config, family.config(config["program"])


def test_the_program_runs_the_published_widths_and_the_stated_layers():
    seen = 0
    for config in configs():
        seen += 1
        cfg = family.config(config["program"])
        assert family.problems(config, cfg) == []
        assert family.vocab_size(cfg) == config["vocab_size"] == 200064
        assert config["reference"]["family"] == "phi4flash"
        cut = config["reduced"]["num_hidden_layers"]
        run = config["layers_run"]
        assert cut["published"] == cfg.num_hidden_layers == 32
        assert cut["layers_run"] == run == list(cfg.layers)
        assert cut["here"] == config["num_hidden_layers"] == len(run)
        # Whole pairs of each of the three parts, the middle pair among
        # them, and the guide's floor of four layers beside a whole period.
        firsts = run[0::2]
        assert [first + 1 for first in firsts] == run[1::2]
        kinds = [family._model().pair_kind(cfg, first) for first in firsts]
        assert kinds == sorted(kinds, key=["self", "middle", "cross"].index)
        assert kinds.count("middle") == 1 and kinds.count("self") >= 1 \
            and kinds.count("cross") >= 1 and len(run) >= 6
        assert config["layout"]["batch"] == 1 \
            and config["layout"]["seq_len"] == 16384
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
        assert differing == sorted(config["reduced"]) == ["num_hidden_layers"]
        for key, cut in config["reduced"].items():
            assert cut["published"] == published[key]
            assert cut["here"] == config[key]


def test_a_width_a_mechanism_or_a_layer_that_differs_is_reported():
    for config in configs():
        cfg = family.config(config["program"])
        wrong = dict(
            config, sliding_window=1024, num_key_value_heads=10,
            intermediate_size=8192, layer_norm_eps=1e-6,
            model_type="phi3", resid_pdrop=0.1,
            assumed=dict(config["assumed"], mamba_sizes=dict(
                config["assumed"]["mamba_sizes"], mamba_d_state=64)))
        assert len(family.problems(wrong, cfg)) == 7
        moved = dict(config, layers_run=[2, 3] + config["layers_run"][2:])
        assert len(family.problems(moved, cfg)) == 1
        shallower = dict(config, reduced={"num_hidden_layers": dict(
            config["reduced"]["num_hidden_layers"], published=24)})
        assert len(family.problems(shallower, cfg)) == 1


def test_the_reference_takes_its_arguments_from_the_published_keys():
    for config in configs():
        assert reference.arguments(config) == {
            "layers_run": tuple(config["layers_run"]), "depth": 32,
            "heads": 40, "kv_heads": 20, "window": 512, "eps": 1e-5,
            "d_state": 16, "dt_rank": 160}
        walked = list(reference._walk(tuple(config["layers_run"]), 32))
        assert [w[0] for w in walked] == config["layers_run"]
        assert walked[0][1:] == ("mamba", "run00_self", 0, "a_")
        assert walked[-1][1:3] == ("cross", "run02_cross")
        assert [w[1] for w in walked if w[0] in (16, 17)] == ["mamba", "full"]


def test_the_drawn_weights_are_what_the_configuration_asks_for():
    config, cfg = tiny_float32()
    program = dict(config["program"], vector_sigma=0.0, qk_gain=3.0)
    plain = jax.jit(lambda key: family._model().init(cfg, key))(
        jax.random.PRNGKey(7))
    params = family.init(cfg, 7, program)
    runs = sorted(k for k in params if k.startswith("run"))
    assert runs == ["run00_self", "run01_middle", "run02_cross"] \
        and jax.tree.structure(params) == jax.tree.structure(plain)
    for run in runs:
        # Without a sigma the vectors stay, and only Wq, Wk take the gain.
        np.testing.assert_allclose(params[run]["a_ln1_scale"], 1.0)
        np.testing.assert_allclose(params[run]["b_wq"],
                                   3.0 * plain[run]["b_wq"], rtol=1e-6)
        np.testing.assert_allclose(params[run]["b_wo"], plain[run]["b_wo"])
        if "b_wk" in params[run]:
            np.testing.assert_allclose(params[run]["b_wk"],
                                       3.0 * plain[run]["b_wk"], rtol=1e-6)
            np.testing.assert_allclose(params[run]["b_wv"],
                                       plain[run]["b_wv"])
    # Mamba's published initialisation: log(1..16) a channel, steps
    # log-uniform in (0.001, 0.1) under the softplus.
    mamba = plain["run00_self"]
    np.testing.assert_allclose(np.exp(mamba["a_A_log"][0, 5]),
                               np.arange(1, 17), rtol=1e-6)
    steps = np.log1p(np.exp(np.asarray(mamba["a_b_dt"], np.float64)))
    assert 0.001 <= steps.min() and steps.max() <= 0.1 \
        and steps.max() > 30 * steps.min()
    moved = family.init(cfg, 7, config["program"])
    sigma = config["program"]["vector_sigma"]
    for run, name, centre in (
            ("run00_self", "a_D", 1.0), ("run00_self", "a_conv_b", 0.0),
            ("run00_self", "b_subln_scale", 1.0),
            ("run01_middle", "b_bv", 0.0), ("run02_cross", "b_bo", 0.0),
            ("run02_cross", "a_ln2_bias", 0.0)):
        leaf = np.asarray(moved[run][name]) - centre
        assert 0.5 * sigma < leaf.std() < 1.5 * sigma, (run, name)
    spread = np.asarray(moved["run00_self"]["a_A_log"]
                        - plain["run00_self"]["a_A_log"])
    assert 0.5 * sigma < spread.std() < 1.5 * sigma
    np.testing.assert_allclose(moved["run00_self"]["a_b_dt"],
                               plain["run00_self"]["a_b_dt"])
    assert np.abs(np.asarray(moved["final_norm_bias"])).max() > 0


def test_reference_against_program_at_tiny_size():
    config, cfg = tiny_float32()
    params = family.init(cfg, 0, config["program"])
    seq = config["layout"]["seq_len"]
    # Every kernel runs: the flash three with and without a window, the
    # scan's pair and the convolution's.
    assert cfg.attn_impl == "flash" and seq % selective_scan.CHUNK == 0 \
        and cfg.d_inner % 128 == 0 and cfg.sliding_window < seq
    rows = np.random.default_rng(0).integers(0, cfg.vocab_size, (2, seq + 1),
                                             dtype=np.int32)
    tokens, targets = jnp.asarray(rows[:, :-1]), jnp.asarray(rows[:, 1:])
    where = jnp.broadcast_to(jnp.arange(seq, dtype=jnp.int32), tokens.shape)
    want, want_loss, rms = reference.forward(
        params, tokens, targets, where, **reference.arguments(config))
    with jax.default_matmul_precision("highest"):
        got, got_loss = jax.jit(lambda p: family.logits_and_losses(
            p, cfg, tokens, targets))(params)
        leaves, lambdas, got_mean, want_mean, zero = \
            check_grads_phi4flash.compared(
                config, family, reference, cfg, params, tokens, targets)
    assert float(rms) > 0.01
    np.testing.assert_allclose(got, want, atol=1e-3 * float(rms))
    np.testing.assert_allclose(got_loss, want_loss, rtol=1e-5)
    assert abs(got_mean - want_mean) < 1e-5
    assert len(leaves) > 80 and len(lambdas) == 4 and not any(
        "_bk" in name or "lambda_" in name for name in leaves)
    assert max(leaves.values()) < 1e-4, leaves
    assert zero < 1e-4
    for name, one in lambdas.items():
        # The terms the reference gives in forward mode sum to the gradient
        # it gives in reverse; the program's number is inside what it is
        # held to, along its direction from all four vectors.
        assert abs(one["reference_by_terms"] - one["reference"]) \
            < 1e-3 * abs(one["reference"]), (name, one)
        assert one["ok"] and one["off_direction"] < 1e-4 \
            and one["spread"] < 1e-4, (name, one)
        assert one["err"] < 1e-3 * one["mass"], (name, one)


def test_the_gradient_checks_cut_has_a_pair_of_every_kind():
    config = next(configs())
    cut = family.with_layers(config, [0, 1, 16, 17, 18, 19])
    cfg = family.config(cut["program"])
    assert family.problems(cut, cfg) == []
    assert [kind for _, kind, _ in family._model()._runs(cfg)] == [
        "self", "middle", "cross"]


def test_the_tier_1_copy_of_the_reference_is_the_reference():
    with open(os.path.join(harness.HERE, "reference", "phi4flash.py")) as f:
        yardstick = f.read()
    with open(os.path.join(harness.ROOT, "tests",
                           "reference_phi4flash.py")) as f:
        assert f.read() == yardstick
