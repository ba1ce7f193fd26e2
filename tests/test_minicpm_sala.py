"""models/minicpm_sala.py (block-sparse attention by the model's own scores in
one layer of four, Lightning linear attention in the other three, muP's
three scalars) against a copy of the benchmark's plain reference, on the
``dot`` path and the interpreted kernels, over ``dense_len`` and under it;
every term the benchmark's fault script plants shown to move the logits; the
shell's scalars; the step's kernels and gauges.

Everything runs on the CPU at the tiny preset in float32 under the highest
matmul precision, where both sides compute the same sums in another order:
tolerances of 1e-3 of the logits' RMS and 1e-4 of a gradient leaf's norm
leave room for float32 reassociation and nothing else.
"""

import math
import os
import sys
from dataclasses import replace
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import reference_minicpm_sala as reference
from family_cases import Family, batch, compared, drawn, forward_alone
from ray_tpu.models import lm, minicpm_sala
from ray_tpu.parallel import MeshConfig, build_mesh
from ray_tpu.parallel.collectives import kernel_census
from ray_tpu.parallel.train_step import init_train_state, make_train_step
from ray_tpu.util import metrics as metrics_mod

# A sparse layer and two linear-attention layers (published indices 1, 2):
# both mixers, a stack of two with a slope a layer.
CFG = minicpm_sala.config("minicpm-sala-tiny", num_hidden_layers=3)
FLASH = replace(CFG, attn_impl="flash", attn_blk_q=128, attn_blk_k=128)
OVER, UNDER = 512, 128   # dense_len is 256: the sparse path, the dense one
HERE = os.path.dirname(os.path.abspath(__file__))
BENCHMARK = os.path.join(os.path.dirname(HERE), "benchmark")


def published(cfg):
    """The keys ``reference.arguments`` reads, as a configuration file has
    them."""
    return {
        "rms_norm_eps": cfg.rms_norm_eps, "rope_theta": cfg.rope_theta,
        "scale_emb": cfg.scale_emb, "scale_depth": cfg.scale_depth,
        "mixer_types": list(cfg.mixer_types), "hidden_size": cfg.hidden_size,
        "dim_model_base": cfg.dim_model_base,
        "assumed": {"sparse_config": {
            "kernel_size": cfg.sparse_kernel_size,
            "kernel_stride": cfg.sparse_kernel_stride,
            "block_size": cfg.sparse_block_size, "topk": cfg.sparse_topk,
            "init_blocks": cfg.sparse_init_blocks,
            "window_size": cfg.sparse_window_size,
            "dense_len": cfg.sparse_dense_len}}}


def moved(name, leaf, key):
    """Every norm's scale off one, the q/k norms' scales larger (at one both
    softmaxes are nearly flat and a wrong selection would move nothing) and
    W_o larger (the mixers' branches beside the SwiGLU's)."""
    if name.endswith("_scale']"):
        leaf = leaf + 0.1 * jax.random.normal(key, leaf.shape)
    if name.endswith(("['q_norm_scale']", "['k_norm_scale']")):
        return leaf * 1.7
    return leaf * 4.0 if name.endswith("['wo']") else leaf


SALA = Family(module=minicpm_sala, reference=reference, cfg=CFG, seq=OVER,
              published=published, moved=moved, rows=1)


@pytest.fixture(scope="module")
def params():
    return drawn(SALA, CFG)


@pytest.fixture(scope="module", params=[OVER, UNDER], ids=["over", "under"])
def want(request, params):
    """The reference's logits over the whole mask and the loss of them, at
    a length over ``dense_len`` and one under it."""
    seq = request.param
    tokens, targets = batch(CFG, seq, rows=1)
    kw = reference.arguments(published(CFG))

    def loss_and_logits(p):
        logits = reference.logits(p, tokens, **kw)
        nll = -jnp.take_along_axis(jax.nn.log_softmax(logits, axis=-1),
                                   targets[..., None], axis=-1)[..., 0]
        return nll.mean(), logits

    # One program: op by op the same sums take five times as long here.
    loss, logits = jax.jit(loss_and_logits)(params)
    return {"seq": seq, "logits": logits, "loss": loss}


@pytest.mark.parametrize("cfg", [CFG, FLASH], ids=["dot", "flash"])
def test_model_matches_reference(cfg, want):
    """Logits within 1e-3 of their RMS, the loss, every gradient leaf (the
    q/k norms' scales and the output norm's among them, none zero)."""
    found = compared(SALA, cfg, want["seq"], reference_of=CFG)
    logits, want_logits = found["logits"]
    assert logits.shape == (1, want["seq"], CFG.vocab_size)
    assert logits.dtype == jnp.float32
    assert float(jnp.abs(logits - want_logits).max()) < 1e-3 * found["rms"]
    loss, want_loss = found["loss"]
    assert abs(float(loss) - float(want_loss)) < 1e-5
    grads, want_grads = found["grads"]
    ref = dict(jax.tree_util.tree_leaves_with_path(want_grads))
    for path, leaf in jax.tree_util.tree_leaves_with_path(grads):
        norm = float(jnp.linalg.norm(ref[path]))
        assert norm > 0, path
        assert float(jnp.linalg.norm(leaf - ref[path])) < 1e-4 * norm, path
    sparse = want["seq"] > CFG.sparse_dense_len
    assert math.isnan(float(found["metrics"]["sala_free_mass"])) != sparse


def test_reference_forward_is_its_logits_and_loss(params, want):
    """``reference.forward`` (what the runner calls: sampled positions, the
    head by blocks) against ``reference.logits`` and ``reference.loss``."""
    seq = want["seq"]
    tokens, targets = batch(CFG, seq, rows=1)
    where = jnp.asarray([[0, 30, 31, 64, seq // 2, seq - 1]])
    kw = reference.arguments(published(CFG))
    sampled, loss, rms = reference.forward(params, tokens, targets, where,
                                           **kw)
    picked = jnp.take_along_axis(want["logits"], where[..., None], axis=1)
    np.testing.assert_allclose(sampled, picked, atol=2e-6)
    np.testing.assert_allclose(rms, jnp.sqrt((want["logits"] ** 2).mean()),
                               rtol=1e-5)
    np.testing.assert_allclose(loss[0], want["loss"], atol=1e-5)
    np.testing.assert_allclose(
        reference.loss(params, tokens, targets, **kw), want["loss"],
        atol=1e-6)


# -- every term the fault script plants moves the logits --------------------

@pytest.fixture(scope="module")
def script():
    sys.path[:0] = [BENCHMARK]
    try:
        import check_faults_minicpm_sala
        return check_faults_minicpm_sala
    finally:
        sys.path.remove(BENCHMARK)


@pytest.fixture(scope="module")
def two_layers(params):
    """A sparse layer and a linear-attention layer at published index 1,
    ``dot`` attention, with the untouched program's logits."""
    cfg = replace(CFG, num_hidden_layers=2)
    cut = dict(params, run01_lightning=jax.tree.map(
        lambda a: a[:1], params["run01_lightning"]))
    # Six blocks, of which four are kept.
    tokens, _ = batch(CFG, 384, seed=3, rows=1)
    plain = forward_alone(SALA, cut, cfg, tokens)
    return cfg, cut, tokens, plain


FAULTS = ["selection", "nearest_blocks", "init_block", "max_pool",
          "group_sum", "qk_norm", "sparse_gate", "sparse_scale", "decay",
          "decay_layer_factor", "linear_rope", "output_norm", "linear_scale",
          "scale_emb", "residual_scale", "head_divisor",
          "eight_bit_residual"]


def test_every_term_of_the_issue_is_planted(script):
    assert list(script.faults()) == ["untouched"] + FAULTS
    assert script.UNSEEN == {"linear_scale"}


@pytest.mark.parametrize("name", FAULTS)
def test_a_dropped_term_moves_the_logits(script, two_layers, name):
    """Each fault planted as the script plants it on the chip, the program's
    side alone: the logits move by more than 1e-3 of their RMS, but for the
    one term the output norm divides out again (``UNSEEN``), which moves
    them by float32's rounding alone."""
    cfg, cut, tokens, plain = two_layers
    swaps, fields, change = script.faults()[name]
    with script._swapped(swaps):
        got = forward_alone(SALA, change(cut) if change else cut,
                            replace(cfg, **fields), tokens,
                            patched=bool(swaps))
    moved = float(jnp.sqrt(((got - plain) ** 2).mean())
                  / jnp.sqrt((plain ** 2).mean()))
    if name in script.UNSEEN:
        assert moved < 1e-5
    else:
        assert moved > 1e-3, moved


# -- the shell, the config, the step ----------------------------------------

def test_scalars_of_one_leave_the_shell_as_it_was(params):
    """``scale_emb`` 1, r = 1 and a head divisor of 1 against the same shell
    with no scalar at all (``embed_scale`` and ``logits_divisor`` None), bit
    for bit: what every other family's lowered step rests on."""
    tokens, _ = batch(CFG, UNDER, rows=1)
    cfg = replace(CFG, scale_emb=1.0, dim_model_base=CFG.hidden_size,
                  scale_depth=math.sqrt(len(CFG.mixer_types)))
    assert cfg.residual_scale == 1.0
    bare = replace(minicpm_sala._SHELL, embed_scale=None, logits_divisor=None)
    got = minicpm_sala.forward(params, cfg, tokens)
    assert (np.asarray(got)
            == np.asarray(bare.forward(params, cfg, tokens))).all()
    assert float(jnp.abs(got - minicpm_sala.forward(params, CFG, tokens)
                         ).max()) > 0


def test_layers_residual_scale_and_runs():
    cfg = minicpm_sala.config("minicpm-sala-9b")
    kinds = cfg.layers
    assert len(kinds) == 32 and kinds.count("sparse") == 8
    assert [i for i, k in enumerate(kinds) if k == "sparse"] \
        == [0, 9, 16, 17, 22, 29, 30, 31]
    assert abs(cfg.residual_scale - 1.4 / math.sqrt(32)) < 1e-12
    cut = replace(cfg, num_hidden_layers=4)
    assert cut.layers == ("sparse",) + ("lightning",) * 3
    assert cut.residual_scale == cfg.residual_scale  # the published depth's
    assert [run[1:] for run in lm.runs(cut.layers)] \
        == [("sparse", 1), ("lightning", 3)]


@pytest.mark.parametrize("layer", [0, 1, 3, 31])
def test_decay_slopes_are_the_published_formula(layer):
    cfg = minicpm_sala.config("minicpm-sala-9b")
    got = minicpm_sala.decay_slopes(cfg, layer)
    for h in (0, 15, 31):
        want = 2.0 ** (-8.0 * (h + 1) / 32) * (1 - layer / 31 + 1e-5)
        assert abs(got[h] - want) < 1e-7 * max(want, 1e-3)
    assert got.dtype == np.float32 and (got > 0).all()


def test_a_runs_constants_are_its_layers_slopes():
    cfg = replace(minicpm_sala.config("minicpm-sala-9b"),
                  num_hidden_layers=12)
    names = [run[0] for run in lm.runs(cfg.layers)]
    assert names == ["run00_sparse", "run01_lightning", "run02_sparse",
                     "run03_lightning"]
    assert minicpm_sala._constants(cfg, "run00_sparse") == {}
    first = minicpm_sala._constants(cfg, "run01_lightning")["decay_slope"]
    assert first.shape == (8, 32)
    np.testing.assert_array_equal(first[2], minicpm_sala.decay_slopes(cfg, 3))
    later = minicpm_sala._constants(cfg, "run03_lightning")["decay_slope"]
    np.testing.assert_array_equal(later[0], minicpm_sala.decay_slopes(cfg, 10))
    floor = minicpm_sala.decay_floor(replace(cfg, num_hidden_layers=4))
    # float32 slopes: the 256th power of a seventh digit's rounding
    assert abs(floor / math.exp(-2.0 ** -0.25 * (1 - 1 / 31 + 1e-5) * 256)
               - 1) < 1e-4


@pytest.mark.parametrize("fields,match", [
    ({"mixer_types": ("minicpm4", "mamba")}, "mixer_types"),
    ({"num_hidden_layers": 33}, "published mixer_types"),
    ({"num_key_value_heads": 3}, "must divide"),
    ({"lightning_nkv": 1}, "key head a query head")])
def test_config_refuses_what_the_layers_cannot_compute(fields, match):
    with pytest.raises(ValueError, match=match):
        replace(CFG, **fields)


def test_step_kernels_gauges_and_falling_loss():
    """A train step through ``make_train_step``: the step's census counts
    the selection's three kernels once (512 keys a query are under
    ``worth_keeping``'s 32 x 128, so ``sala_fwd`` twice under remat), the
    recurrence's and the gated norm's forward twice a linear layer (two of
    them); the loss falls; the gauges read what the selection says."""
    cfg = replace(FLASH, remat=True, loss_chunk=128)
    mesh = build_mesh(MeshConfig(dp=1, fsdp=1, tp=1),
                      devices=jax.devices()[:1])
    state = init_train_state(cfg, mesh, seed=0)
    step = make_train_step(cfg, mesh)
    tokens, targets = batch(CFG, OVER, rows=1)
    census = kernel_census(jax.make_jaxpr(
        lambda p: jax.grad(lambda p: minicpm_sala.loss_fn(
            p, cfg, tokens, targets)[0])(p))(state["params"]), a_step=True)
    assert census == {"sala_fwd": 2, "sala_bwd_dq": 1, "sala_bwd_dkv": 1,
                      "lightning_fwd": 4, "lightning_bwd": 2,
                      "gated_norm_fwd": 4, "gated_norm_bwd": 2}
    losses = []
    for _ in range(3):
        state, metrics = step(state, {"tokens": tokens, "targets": targets})
        losses.append(float(metrics["loss"]))
    state, metrics = step(state, {"tokens": tokens, "targets": targets})
    assert losses[-1] < losses[0]
    assert abs(losses[0] - np.log(CFG.vocab_size)) < 0.5
    series = {e["name"]: e["series"] for e in metrics_mod.snapshot()}
    share = list(series["ray_tpu_train_sala_selected_share"].values())[0]
    pairs = sum((min(t // 64 + 1, 4) - 1) * 64 + t % 64 + 1
                for t in range(OVER))
    assert abs(share - pairs / (OVER * (OVER + 1) / 2)) < 1e-6
    live = list(series["ray_tpu_train_sala_live_tile_share"].values())[0]
    assert 0.5 < live <= 1.0
    mass = list(series["ray_tpu_train_sala_free_mass"].values())[0]
    assert 0.0 < mass < 1.0
    floor = list(series["ray_tpu_train_lightning_decay_floor"].values())[0]
    assert abs(floor - minicpm_sala.decay_floor(cfg)) < 1e-9


def test_a_mesh_of_several_devices_is_refused_over_dense_len(params):
    from ray_tpu.parallel import mesh as mesh_mod
    tokens, _ = batch(CFG, OVER, rows=1)
    mesh = build_mesh(MeshConfig(dp=2, fsdp=1, tp=1),
                      devices=jax.devices()[:2])
    previous = mesh_mod.current_mesh()
    mesh_mod.set_current_mesh(mesh)
    try:
        with pytest.raises(NotImplementedError, match="one device"):
            jax.eval_shape(lambda p: minicpm_sala.forward(p, CFG, tokens),
                           params)
    finally:
        mesh_mod.set_current_mesh(previous)


def test_reference_copy_is_the_benchmarks():
    with open(os.path.join(HERE, "reference_minicpm_sala.py")) as mine, \
            open(os.path.join(BENCHMARK, "reference",
                              "minicpm_sala.py")) as theirs:
        assert mine.read() == theirs.read()


def test_published_count_of_parameters():
    """253.7 M a sparse layer, 285.2 M a linear one, 9.48 B for the 32
    layers with the untied 73,448-wide table and head, by the shapes the
    program makes."""
    cfg = minicpm_sala.config("minicpm-sala-9b")
    shapes = jax.eval_shape(partial(minicpm_sala.init, cfg),
                            jax.random.PRNGKey(0))

    def a_layer(run):
        return sum(int(np.prod(a.shape[1:]))
                   for a in jax.tree.leaves(shapes[run]))

    assert a_layer("run00_sparse") == 253_763_840
    assert a_layer("run01_lightning") == 285_221_248
    total = sum(int(np.prod(a.shape)) for a in jax.tree.leaves(shapes))
    assert total == 8 * 253_763_840 + 24 * 285_221_248 \
        + 2 * 73448 * 4096 + 4096
    assert round(total / 1e9, 2) == 9.48
