"""``families/granitemoehybrid_moe.py`` and ``reference/
granitemoehybrid_moe.py`` on the configurations that name them: the widths,
the pattern and the share the file publishes, at full and at tiny size; the
weights the family draws (the experts' gain, the centred routers); the
reference against the program through the family at the tiny size in float32
(logits, loss per sequence, routing, gradients per leaf), the kernels
interpreted; and the tier-1 copy of the reference, letter for letter.

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

FAMILY = "granitemoehybrid_moe"
family = harness.load_module("families", FAMILY)
reference = harness.load_module("reference", FAMILY)
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"
PERIOD = ("mamba",) * 5 + ("attention",) + ("mamba",) * 4


def configs():
    for entry in harness.load_spec()["configs"]:
        config = harness.load_json(os.path.join(harness.ROOT, entry["file"]))
        if config["program"]["family"] == FAMILY:
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
        assert family.vocab_size(cfg) == config["vocab_size"] == 12544
        assert config["reference"]["family"] == FAMILY
        assert len(config["layer_types"]) == 40  # kept whole
        # Every published width, and the four multipliers.
        assert (cfg.hidden_size, cfg.mamba_n_heads, cfg.mamba_d_head,
                cfg.mamba_d_state, cfg.mamba_n_groups, cfg.mamba_d_conv) == (
            4096, 128, 64, 128, 1, 4)
        assert (cfg.num_attention_heads, cfg.num_key_value_heads,
                cfg.head_dim) == (32, 8, 128)
        assert (cfg.intermediate_size, cfg.shared_intermediate_size,
                cfg.num_local_experts, cfg.num_experts_per_tok) == (
            768, 1536, 72, 10)
        assert (cfg.embedding_multiplier, cfg.residual_multiplier,
                cfg.attention_multiplier, cfg.logits_scaling) == (
            12, 0.22, 0.0078125, 16)
        # The cut: depth, the experts held, the vocabulary; 8 chips a layer.
        assert sorted(config["reduced"]) == [
            "num_hidden_layers", "num_local_experts", "vocab_size"]
        deployment = config["deployment"]
        assert deployment["chips_sharing_a_layer"] == 8
        assert deployment["experts_held"] == {"first": 0, "count": 9,
                                              "of": 72}
        assert deployment["vocab_slice"] == {"first": 0, "count": 12544,
                                             "of": 100352}
        assert cfg.experts_held == (0, 9)
        # The guide's floors: a whole period, 8 experts, an eighth.
        assert cfg.layers == PERIOD
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
        wrong = dict(config, intermediate_size=1024, num_experts_per_tok=8,
                     mamba_n_heads=64, hidden_act="gelu",
                     attention_bias=True, num_local_experts=18,
                     vocab_size=100352, layer_types=["mamba"] * 40)
        assert len(family.problems(wrong, cfg)) == 8
        moved = dict(config, deployment=dict(
            config["deployment"],
            experts_held={"first": 9, "count": 9, "of": 72}))
        assert len(family.problems(moved, cfg)) == 1
        long = dict(config, layout=dict(config["layout"], seq_len=262144))
        assert len(family.problems(long, cfg)) == 1


def test_the_reference_takes_its_arguments_from_the_published_keys():
    for config in configs():
        assert reference.arguments(config) == {
            "top_k": 10, "first_expert": 0, "layer_types": PERIOD,
            "heads": 128, "d_state": 128,
            "attention_multiplier": 0.0078125, "embedding_multiplier": 12,
            "residual_multiplier": 0.22, "logits_scaling": 16,
            "eps": 1e-05}


def test_the_drawn_weights_are_what_the_configuration_asks_for():
    config, cfg = tiny_float32()
    program = dict(config["program"], attention_qk_gain=3.0,
                   expert_gain=2.0, norm_scale_sigma=0.0,
                   conv_bias_sigma=0.0, router_centre_tokens=0)
    plain = jax.jit(lambda key: family._model().init(cfg, key))(
        jax.random.PRNGKey(7))
    params = family.init(cfg, 7, program)
    assert jax.tree.structure(params) == jax.tree.structure(plain)
    mamba, attention = params["run00_mamba"], params["run01_attention"]
    np.testing.assert_allclose(attention["wq"],
                               3.0 * plain["run01_attention"]["wq"])
    np.testing.assert_allclose(attention["wv"],
                               plain["run01_attention"]["wv"])
    for run in ("run00_mamba", "run01_attention", "run02_mamba"):
        np.testing.assert_allclose(params[run]["w_down"],
                                   2.0 * plain[run]["w_down"])
        for leaf in ("w_gate", "w_up", "router", "mlp_in", "mlp_out"):
            np.testing.assert_array_equal(params[run][leaf],
                                          plain[run][leaf])
    np.testing.assert_allclose(mamba["ln2_scale"], 1.0)
    np.testing.assert_allclose(mamba["conv_b"], 0.0)
    moved = family.init(cfg, 7, config["program"])
    for leaf in ("norm_scale", "D", "A_log", "conv_b", "ln1_scale",
                 "ln2_scale", "router"):
        assert np.abs(np.asarray(moved["run00_mamba"][leaf])
                      - np.asarray(plain["run00_mamba"][leaf])
                      ).max() > 0.0, leaf


def test_the_centred_routers_even_the_load_out():
    """``experts_drawn`` takes every router's component along its layer's
    mean normed input out. On a stream that leans one way (every row of the
    table moved by one vector, as a deep random model's stream leans), the
    drawn routers send nearly every token to the same experts (the busiest
    of 16 at 4 a token reads up to 4 times the mean); centred, on other
    tokens than the rule saw, it stands near the mean in every layer.
    Nothing but the routers moved, each by a rank-one term."""
    config, cfg = tiny_float32()
    assert config["program"]["router_centre_tokens"] == 256
    tokens = jnp.asarray(np.random.default_rng(99).integers(
        0, cfg.vocab_size, (4, 256), dtype=np.int32))

    def busiest(params):
        _, picked = family.picked_experts(params, cfg, tokens)
        picked = np.asarray(picked).reshape(picked.shape[0], -1)
        loads = np.stack([np.bincount(row, minlength=16) for row in picked])
        return loads.max(-1) / loads.mean(-1)

    drawn = family.init(cfg, 5, dict(config["program"],
                                     router_centre_tokens=0))
    drawn["wte"] = drawn["wte"] + 0.05 * jax.random.normal(
        jax.random.PRNGKey(1), (1, cfg.hidden_size))
    even = family.experts_drawn(
        jax.tree.map(jnp.copy, drawn), cfg, 8,
        dict(config["program"], expert_gain=1.0))
    before, after = busiest(drawn), busiest(even)
    assert before.min() > 2.5 and after.max() < 1.5
    for (path, a), b in zip(jax.tree_util.tree_leaves_with_path(drawn),
                            jax.tree.leaves(even)):
        same = bool((np.asarray(a) == np.asarray(b)).all())
        assert same != jax.tree_util.keystr(path).endswith("['router']"), \
            path
    moved = np.asarray(even["run00_mamba"]["router"][0], np.float64) \
        - np.asarray(drawn["run00_mamba"]["router"][0], np.float64)
    singular = np.linalg.svd(moved, compute_uv=False)
    assert singular[0] > 0.0 and singular[1] < 1e-5 * singular[0]


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
        assert norm > 0.0, path
        assert float(jnp.linalg.norm((g - w).ravel())) < 1e-4 * norm, path


def test_the_tier_1_copy_of_the_reference_is_the_reference():
    with open(os.path.join(harness.HERE, "reference",
                           "granitemoehybrid_moe.py")) as f:
        yardstick = f.read()
    with open(os.path.join(harness.ROOT, "tests",
                           "reference_granitemoehybrid_moe.py")) as f:
        assert f.read() == yardstick


def test_the_last_case_frees_the_compiled_programs():
    """The whole benchmark suite is one process near the kernel's limit of
    memory maps (the verify skill's note): this file's programs go."""
    jax.clear_caches()
