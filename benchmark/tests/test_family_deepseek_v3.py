"""``families/deepseek_v3.py`` and ``reference/deepseek_v3.py`` on the
configurations that name them: the widths the file publishes, at full and
at tiny size; the reference against the program through the family at a
tiny width in float32 (logits, loss per sequence, the experts picked,
gradients per leaf); and the tier-1 copy of the reference, letter for
letter.

Float32 under the highest matmul precision on both sides: the same sums in
another order, so 1e-4 of a leaf's norm (1e-5 absolute on logits of RMS 0.3)
is reassociation over a few hundred terms and nothing else. The chip's
tolerances, for bfloat16 and for tokens that route differently, are the
configuration's and are measured there.
"""

import os

import jax
import jax.numpy as jnp
import numpy as np

import harness

family = harness.load_module("families", "deepseek_v3")
reference = harness.load_module("reference", "deepseek_v3")


def configs():
    for entry in harness.load_spec()["configs"]:
        config = harness.load_json(os.path.join(harness.ROOT, entry["file"]))
        if config["program"]["family"] == "deepseek_v3":
            yield config


def tiny_float32():
    config = family.tiny(next(configs()))
    program = dict(config["program"])
    program["overrides"] = dict(
        program["overrides"], dtype="float32", param_dtype="float32",
        attn_impl="dot", loss_chunk=64)
    return dict(config, program=program), family.config(program)


def test_the_program_runs_the_published_widths():
    seen = 0
    for config in configs():
        seen += 1
        cfg = family.config(config["program"])
        assert family.problems(config, cfg) == []
        assert family.vocab_size(cfg) == config["vocab_size"]
        assert config["reference"]["family"] == "deepseek_v3"
        tiny = family.tiny(config)
        assert family.problems(tiny, family.config(tiny["program"])) == []
        assert tiny["layout"]["mesh"] == config["layout"]["mesh"]
    assert seen


def test_a_width_or_a_mechanism_that_differs_is_reported():
    for config in configs():
        cfg = family.config(config["program"])
        wrong = dict(config, kv_lora_rank=256, scoring_func="softmax",
                     q_lora_rank=1536)
        assert len(family.problems(wrong, cfg)) == 3


def test_the_reference_takes_its_arguments_from_the_published_keys():
    for config in configs():
        assert reference.arguments(config) == {
            "nope": config["qk_nope_head_dim"],
            "rank": config["kv_lora_rank"], "theta": config["rope_theta"],
            "top_k": config["num_experts_per_tok"],
            "scaling": config["routed_scaling_factor"],
            "norm_topk": config["norm_topk_prob"],
            "eps": config["rms_norm_eps"]}


def test_the_drawn_bias_has_the_largest_entry_asked_for():
    config, cfg = tiny_float32()
    params = family.init(cfg, 7, dict(config["program"],
                                      router_bias_max=0.25))
    bias = np.asarray(params["moe_layers"]["router_bias"])
    np.testing.assert_allclose(bias.max(-1), 0.25, rtol=1e-6)
    assert np.abs(np.asarray(params["lnf_scale"]) - 1.0).max() > 0.0
    assert np.abs(np.asarray(
        params["dense_layers"]["kv_norm_scale"]) - 1.0).max() > 0.0


def test_reference_against_program_at_tiny_size():
    config, cfg = tiny_float32()
    params = family.init(cfg, 0, config["program"])
    seq = config["layout"]["seq_len"]
    rows = np.random.default_rng(0).integers(0, cfg.vocab_size, (2, seq + 1),
                                             dtype=np.int32)
    tokens, targets = jnp.asarray(rows[:, :-1]), jnp.asarray(rows[:, 1:])
    where = jnp.broadcast_to(jnp.arange(seq, dtype=jnp.int32), tokens.shape)
    want, want_loss, rms, want_picked = reference.forward(
        params, tokens, targets, where, with_picked=True,
        **reference.arguments(config))
    with jax.default_matmul_precision("highest"):
        got, got_loss = jax.jit(lambda p: family.logits_and_losses(
            p, cfg, tokens, targets))(params)
        _, picked = family.picked_experts(params, cfg, tokens)
        grads = jax.grad(lambda p: family.loss(p, cfg, tokens, targets))(
            params)
    assert float(rms) > 0.1
    np.testing.assert_allclose(got, want, atol=1e-5)
    np.testing.assert_allclose(got_loss, want_loss, rtol=1e-5)
    np.testing.assert_array_equal(np.sort(picked, -1),
                                  np.sort(want_picked, -1))
    want_grads = jax.grad(lambda p: reference.loss(
        p, tokens, targets, **reference.arguments(config)))(params)
    for (path, w), g in zip(jax.tree_util.tree_leaves_with_path(want_grads),
                            jax.tree.leaves(grads)):
        norm = float(jnp.linalg.norm(w.ravel()))
        if "router_bias" in jax.tree_util.keystr(path):
            assert norm == 0.0 and not np.any(g)
            continue
        assert float(jnp.linalg.norm((g - w).ravel())) < 1e-4 * norm, path


def test_the_tier_1_copy_of_the_reference_is_the_reference():
    with open(os.path.join(harness.HERE, "reference", "deepseek_v3.py")) as f:
        yardstick = f.read()
    with open(os.path.join(harness.ROOT, "tests",
                           "reference_deepseek_v3.py")) as f:
        assert f.read() == yardstick
