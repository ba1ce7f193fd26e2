"""``families/dots3_note.py`` and ``reference/dots3_note.py`` on the
configurations that name them: the widths, the layers, the two geometries
and the share the file publishes, at full and at tiny size; the weights the
family draws; the reference (``jax.lax.top_k`` on whole rows, explicit masks
for the selection and the window) against the program (the threshold
search, the selection's and the window's kernels, interpreted) through the
family at the tiny size in float32 (logits, both terms of the loss per
sequence, gradients per leaf); and the tier-1 copy of the reference, letter
for letter.

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
import pytest

import harness

family = harness.load_module("families", "dots3_note")
reference = harness.load_module("reference", "dots3_note")
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"


@pytest.fixture(scope="module", autouse=True)
def leave_no_programs_behind():
    """The suite in one process sits at the kernel's limit on memory maps
    (``vm.max_map_count`` 65,530: every compiled CPU program keeps some),
    and a later file's compile segfaults past it: what this file compiled
    goes when it is done."""
    yield
    jax.clear_caches()


def configs():
    for entry in harness.load_spec()["configs"]:
        config = harness.load_json(os.path.join(harness.ROOT, entry["file"]))
        if config["program"]["family"] == "dots3_note":
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
        assert config["reference"]["family"] == "dots3_note"
        # The published list, whole: 46 layers, 13 of them full.
        depth = config["reduced"]["num_hidden_layers"]["published"]
        assert len(config["layer_types"]) == depth == len(cfg.layer_types)
        assert config["layer_types"].count("full_attention") == 13
        run = config["layers_run"]
        assert len(run) == config["num_hidden_layers"] \
            and run == list(range(run[0], run[0] + len(run)))
        held = config["deployment"]["experts_held"]
        assert cfg.n_routed_experts == held["of"] == \
            config["reduced"]["n_routed_experts"]["published"]
        assert cfg.experts_held == (held["first"], held["count"])
        assert config["n_routed_experts"] == held["count"]
        assert held["of"] == held["count"] * \
            config["deployment"]["chips_sharing_a_layer"]
        piece = config["deployment"]["vocab_slice"]
        assert piece["count"] == config["vocab_size"] and \
            piece["of"] == config["reduced"]["vocab_size"]["published"]
        # The guide's floors: the leading dense layer, a whole period of
        # four layers behind it (1 full : 3 window, as published), 8
        # experts, an eighth of the vocabulary.
        assert cfg.layers[0] == "dense_full"
        kinds = cfg.layers[1:]
        assert kinds.count("moe_window") == 3 * kinds.count("moe_full") >= 3
        assert held["count"] >= 8 and 8 * piece["count"] >= piece["of"]
        # The selection and the window decide something at the cell's
        # length, and the window is one key longer than a tile.
        assert config["layout"]["seq_len"] > config["index_topk"] \
            > config["sliding_window_size"] == cfg.attn_blk_k + 1
        full, window = cfg.latent("full"), cfg.latent("window")
        assert (full.q_lora_scale, full.kv_lora_scale,
                window.kv_lora_scale) == (math.sqrt(5), math.sqrt(10),
                                          math.sqrt(5))
        tiny = family.tiny(config)
        small = family.config(tiny["program"])
        assert family.problems(tiny, small) == []
        assert tiny["layout"]["mesh"] == config["layout"]["mesh"]
        assert tiny["layout"]["seq_len"] > tiny["index_topk"]
        assert tiny["sliding_window_size"] == small.attn_blk_k + 1
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
        wrong = dict(config, swa_kv_lora_rank=512, index_topk=1024,
                     sliding_window_size=512, scoring_func="softmax",
                     attention_gate_type="elementwise",
                     apply_mla_qkv_lora_rescale=False,
                     routed_scaling_factor=2.5, n_routed_experts=16,
                     layers_run=[1, 2, 3, 4, 5], swa_rope_theta=10000,
                     layer_types=["full_attention"] * 46,
                     swa_num_key_value_heads=8,
                     rope_scaling={"type": "yarn"})
        assert len(family.problems(wrong, cfg)) == 13
        moved = dict(config, deployment=dict(
            config["deployment"], experts_held={"first": 8, "count": 8,
                                                "of": 256}))
        assert len(family.problems(moved, cfg)) == 1
        other = dict(config, assumed=dict(config["assumed"], sizes=dict(
            config["assumed"]["sizes"], indexer_loss_coef=0.5)))
        assert len(family.problems(other, cfg)) == 1


def test_the_reference_takes_its_arguments_from_the_published_keys():
    for config in configs():
        assert reference.arguments(config) == {
            "layers": ((True, True), (False, True), (False, False),
                       (False, False), (False, False)),
            "geometries": (
                (192, 64, 1024, 50000.0, math.sqrt(5), math.sqrt(5), 513),
                (128, 64, 512, 80000000.0, math.sqrt(5), math.sqrt(10),
                 None)),
            "topk": config["index_topk"],
            "top_k": config["num_experts_per_tok"],
            "scaling": config["routed_scaling_factor"],
            "renormalize": config["norm_topk_prob"],
            "eps": config["rms_norm_eps"],
            "index_eps": 1e-6, "coef": 1.0,
            "first_expert": config["deployment"]["experts_held"]["first"]}


def test_the_drawn_weights_are_what_the_configuration_asks_for():
    config, cfg = tiny_float32()
    program = dict(config["program"], router_bias_max=0.07,
                   norm_scale_sigma=0.0, attention_q_gain=3.0,
                   embedding_gain=7.0, ffn_out_gain=0.5, head_gain=0.25)
    plain = jax.jit(lambda key: family._model().init(cfg, key))(
        jax.random.PRNGKey(7))
    params = family.init(cfg, 7, program)
    runs = sorted(k for k in params if k.startswith("run"))
    assert len(runs) == 3 and jax.tree.structure(params) == \
        jax.tree.structure(plain)
    np.testing.assert_allclose(params["wte"], 7.0 * plain["wte"], rtol=1e-6)
    np.testing.assert_allclose(params["lm_head"], 0.25 * plain["lm_head"])
    for run in runs:
        np.testing.assert_allclose(params[run]["ln1_scale"], 1.0)
        for name, gain in (("w_q_b", 3.0), ("wo", 1.0), ("w_q_a", 1.0),
                           ("w_kv_b", 1.0), ("w_attn_gate", 1.0)):
            np.testing.assert_allclose(
                params[run][name], gain * plain[run][name], rtol=1e-6)
        assert ("w_iq" in params[run]) == run.endswith("_full")
        if "w_iq" in params[run]:
            np.testing.assert_allclose(params[run]["w_iq"],
                                       plain[run]["w_iq"])
            np.testing.assert_allclose(params[run]["ik_norm_bias"], 0.0)
        # The dense SwiGLU's and the shared expert's way out carry the
        # gain, the routed experts' none.
        if "router_bias" in params[run]:
            bias = np.asarray(params[run]["router_bias"])
            np.testing.assert_allclose(bias.max(-1), 0.07, rtol=1e-6)
            np.testing.assert_allclose(params[run]["w_down"],
                                       plain[run]["w_down"])
            np.testing.assert_allclose(params[run]["shared_w_down"],
                                       0.5 * plain[run]["shared_w_down"])
        else:
            np.testing.assert_allclose(params[run]["w_down"],
                                       0.5 * plain[run]["w_down"])
    moved = family.init(cfg, 7, config["program"])
    assert np.abs(np.asarray(moved["lnf_scale"]) - 1.0).max() > 0.0
    for name in ("q_norm_scale", "kv_norm_scale", "ik_norm_scale"):
        assert np.abs(np.asarray(moved[runs[0]][name]) - 1.0).max() > 0.0
    assert np.abs(np.asarray(moved[runs[2]]["kv_norm_scale"]) - 1.0
                  ).max() > 0.0
    assert np.abs(np.asarray(moved[runs[0]]["ik_norm_bias"])).max() > 0.0


def test_reference_against_program_at_tiny_size():
    config, cfg = tiny_float32()
    params = family.init(cfg, 0, config["program"])
    seq = config["layout"]["seq_len"]
    assert cfg.attn_impl == "flash" and seq % 128 == 0   # the kernels
    rows = np.random.default_rng(0).integers(0, cfg.vocab_size, (2, seq + 1),
                                             dtype=np.int32)
    tokens, targets = jnp.asarray(rows[:, :-1]), jnp.asarray(rows[:, 1:])
    where = jnp.broadcast_to(jnp.arange(seq, dtype=jnp.int32), tokens.shape)
    kw = reference.arguments(config)
    want, want_loss, rms, want_picked, _, want_index, gates = \
        reference.forward(params, tokens, targets, where, with_picked=True,
                          with_selections=True, **kw)
    with jax.default_matmul_precision("highest"):
        got, got_loss = jax.jit(lambda p: family.logits_and_losses(
            p, cfg, tokens, targets))(params)
        _, picked = jax.jit(lambda p: family.picked_experts(
            p, cfg, tokens))(params)
        grads = jax.jit(jax.grad(lambda p: family.loss(
            p, cfg, tokens, targets)))(params)
    assert float(rms) > 0.01
    np.testing.assert_allclose(got, want, atol=1e-3 * float(rms))
    # Per sequence, both terms; the second is not nothing.
    np.testing.assert_allclose(got_loss, want_loss, rtol=1e-5)
    assert float(want_index.min()) > 1e-4
    assert (np.sort(picked, -1) == np.sort(want_picked, -1)).all()
    assert gates.shape == (5,) and 0.3 < float(gates.min()) \
        and float(gates.max()) < 0.7
    want_grads = jax.jit(jax.grad(lambda p: reference.loss(
        p, tokens, targets, **kw)))(params)
    for (path, w), g in zip(jax.tree_util.tree_leaves_with_path(want_grads),
                            jax.tree.leaves(grads)):
        norm = float(jnp.linalg.norm(w.ravel()))
        if "router_bias" in jax.tree_util.keystr(path):
            assert norm == 0.0 and not np.any(g)
            continue
        assert norm > 0.0, path
        assert float(jnp.linalg.norm((g - w).ravel())) < 1e-4 * norm, path


def test_the_tier_1_copy_of_the_reference_is_the_reference():
    with open(os.path.join(harness.HERE, "reference", "dots3_note.py")) as f:
        yardstick = f.read()
    with open(os.path.join(harness.ROOT, "tests",
                           "reference_dots3_note.py")) as f:
        assert f.read() == yardstick
