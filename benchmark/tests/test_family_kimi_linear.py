"""``families/kimi_linear.py`` and ``reference/kimi_linear.py`` on the
configurations that name them: the widths, the layer pattern and the share
the file publishes, at full and at tiny size; the weights the family draws;
the reference (the literal recurrence) against the program (the chunked
kernels, interpreted) through the family at the tiny size in float32
(logits, loss per sequence, gradients per leaf); and the tier-1 copy of the
reference, letter for letter.

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
from ray_tpu.ops import kda

family = harness.load_module("families", "kimi_linear")
reference = harness.load_module("reference", "kimi_linear")
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"


def configs():
    for entry in harness.load_spec()["configs"]:
        config = harness.load_json(os.path.join(harness.ROOT, entry["file"]))
        if config["program"]["family"] == "kimi_linear":
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
        assert config["reference"]["family"] == "kimi_linear"
        linear = config["linear_attn_config"]  # the published lists, whole
        assert sorted(linear["kda_layers"] + linear["full_attn_layers"]) == \
            list(range(1, 28))
        held = config["deployment"]["experts_held"]
        assert cfg.num_experts == held["of"] == \
            config["reduced"]["num_experts"]["published"]
        assert cfg.experts_held == (held["first"], held["count"])
        assert config["num_experts"] == held["count"]
        assert held["of"] == held["count"] * \
            config["deployment"]["chips_sharing_a_layer"]
        piece = config["deployment"]["vocab_slice"]
        assert piece["count"] == config["vocab_size"] and \
            piece["of"] == config["reduced"]["vocab_size"]["published"]
        # The guide's floors: a whole period of four expert layers (3 KDA :
        # 1 latent), 8 experts, an eighth of the vocabulary.
        kinds = cfg.layers[config["first_k_dense_replace"]:]
        assert kinds.count("moe_kda") == 3 * kinds.count("moe_mla") >= 3
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
        linear = dict(config["linear_attn_config"], head_dim=64,
                      kda_layers=[1, 2, 3])
        wrong = dict(config, kv_lora_rank=256, mla_use_nope=False,
                     moe_router_activation_func="softmax", num_expert_group=2,
                     routed_scaling_factor=1.0, linear_attn_config=linear,
                     num_experts=16)
        assert len(family.problems(wrong, cfg)) == 8
        moved = dict(config, deployment=dict(
            config["deployment"], experts_held={"first": 32, "count": 32,
                                                "of": 256}))
        assert len(family.problems(moved, cfg)) == 1


def test_the_reference_takes_its_arguments_from_the_published_keys():
    for config in configs():
        n = config["num_hidden_layers"]
        kda = config["linear_attn_config"]["kda_layers"]
        assert reference.arguments(config) == {
            "kda_layers": tuple(l in kda for l in range(1, n + 1)),
            "first_k_dense_replace": config["first_k_dense_replace"],
            "nope": config["qk_nope_head_dim"],
            "rank": config["kv_lora_rank"],
            "top_k": config["num_experts_per_token"],
            "scaling": config["routed_scaling_factor"],
            "renormalize": config["moe_renormalize"],
            "eps": config["rms_norm_eps"],
            "first_expert": config["deployment"]["experts_held"]["first"]}


def test_the_drawn_weights_are_what_the_configuration_asks_for():
    config, cfg = tiny_float32()
    program = dict(config["program"], router_bias_max=0.07,
                   norm_scale_sigma=0.0, attention_q_gain=3.0)
    plain = jax.jit(lambda key: family._model().init(cfg, key))(
        jax.random.PRNGKey(7))
    params = family.init(cfg, 7, program)
    runs = sorted(k for k in params if k.startswith("run"))
    assert len(runs) == 4 and jax.tree.structure(params) == \
        jax.tree.structure(plain)
    for run in runs:
        np.testing.assert_allclose(params[run]["ln_in_scale"], 1.0)
        # A latent layer's queries carry the gain, a KDA layer's none.
        np.testing.assert_allclose(
            params[run]["wq"],
            (3.0 if run.endswith("mla") else 1.0) * plain[run]["wq"])
        if "A_log" in params[run]:
            # The decay's vectors stay the program's published draw.
            for name in ("A_log", "dt_bias"):
                np.testing.assert_allclose(params[run][name],
                                           plain[run][name])
            rate = -np.exp(params[run]["A_log"])[..., None] * np.asarray(
                jax.nn.softplus(params[run]["dt_bias"]))
            assert -1.6 <= rate.min() and rate.max() <= -0.001 * 0.999
        if "router_bias" in params[run]:
            bias = np.asarray(params[run]["router_bias"])
            np.testing.assert_allclose(bias.max(-1), 0.07, rtol=1e-6)
    moved = family.init(cfg, 7, config["program"])
    assert np.abs(np.asarray(moved["lnf_scale"]) - 1.0).max() > 0.0
    for run, name in ((runs[0], "o_norm_scale"), (runs[2], "kv_norm_scale")):
        assert np.abs(np.asarray(moved[run][name]) - 1.0).max() > 0.0


def test_reference_against_program_at_tiny_size():
    config, cfg = tiny_float32()
    params = family.init(cfg, 0, config["program"])
    seq = config["layout"]["seq_len"]
    assert cfg.attn_impl == "flash" and seq % kda.CHUNK == 0 \
        and cfg.linear_attn_config.head_dim == 128    # both kernel pairs
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
            assert norm == 0.0 and not np.any(g)
            continue
        assert norm > 0.0, path
        assert float(jnp.linalg.norm((g - w).ravel())) < 1e-4 * norm, path


def test_the_tier_1_copy_of_the_reference_is_the_reference():
    with open(os.path.join(harness.HERE, "reference",
                           "kimi_linear.py")) as f:
        yardstick = f.read()
    with open(os.path.join(harness.ROOT, "tests",
                           "reference_kimi_linear.py")) as f:
        assert f.read() == yardstick
