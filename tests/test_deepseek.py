"""models/deepseek.py (latent attention, the dropless expert layer of
ops/moe.py) against a copy of the benchmark's plain reference, the flash
kernels with two head sizes, and the train step typed to no model.

Everything runs on the CPU at tiny widths in float32 under the highest
matmul precision, where both sides compute the same sums in another order:
tolerances of 1e-4 (relative, on gradients: of a leaf's norm) leave room for
float32 reassociation across a few hundred terms and nothing else. On the
chip the program runs in bfloat16 and routes some tokens differently: that is
measured there (benchmark/check_routing.py), not here.
"""

import math
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import family_cases
import reference_deepseek_v3 as reference
from family_cases import batch, drawn
from ray_tpu.models import deepseek, gpt, lm
from ray_tpu.ops import moe
from ray_tpu.ops.flash_attention import flash_attention
from ray_tpu.parallel import MeshConfig, build_mesh
from ray_tpu.parallel import mesh as mesh_mod
from ray_tpu.parallel.train_step import (abstract_train_state,
                                         init_train_state, make_eval_step,
                                         make_train_step)

CFG = deepseek.config("deepseek-tiny")
SEQ = 128
# The chip's recipe: flash kernels (interpreted), full remat, the chunked
# loss.
FLASH = deepseek.config("deepseek-tiny", attn_impl="flash", remat=True,
                        loss_chunk=64)


def published(cfg):
    return {"qk_nope_head_dim": cfg.qk_nope_head_dim,
            "kv_lora_rank": cfg.kv_lora_rank, "rope_theta": cfg.rope_theta,
            "num_experts_per_tok": cfg.num_experts_per_tok,
            "routed_scaling_factor": cfg.routed_scaling_factor,
            "norm_topk_prob": cfg.norm_topk_prob,
            "rms_norm_eps": cfg.rms_norm_eps}


def moved(name, leaf, key):
    """Every RMSNorm scale drawn around one and the correction bias drawn: a
    dropped vector or a bias that reached the weights would show."""
    if "router_bias" in name:
        return 0.3 * jax.random.normal(key, leaf.shape)
    if name.endswith("_scale']"):
        return leaf + 0.1 * jax.random.normal(key, leaf.shape)
    return leaf


DEEPSEEK = family_cases.Family(
    module=deepseek, reference=reference, cfg=CFG, seq=SEQ, flash=FLASH,
    flash_seq=SEQ, published=published, moved=moved, extras=("picked",))
globals().update(family_cases.cases(DEEPSEEK))


def _batch(n_seq=2):
    return batch(CFG, SEQ, rows=n_seq)


@pytest.mark.parametrize("what", ["logits", "loss_per_sequence", "picked"])
def test_program_matches_reference_forward(both, what):
    """To 1e-5 absolute, the loss a sequence at a time (under a mask of one
    row), and the routing."""
    if what == "logits":
        np.testing.assert_allclose(*both["logits"], atol=1e-5)
    elif what == "picked":
        np.testing.assert_array_equal(np.sort(both["aux"]["picked"], -1),
                                      np.sort(both["extras"][0], -1))
    else:
        tokens, targets = _batch()
        with jax.default_matmul_precision("highest"):
            masked = jax.jit(lambda p, mask: deepseek.loss_fn(
                p, CFG, tokens, targets, mask)[0])
            for row in range(tokens.shape[0]):
                got = masked(drawn(DEEPSEEK, CFG),
                             jnp.zeros(tokens.shape).at[row].set(1.0))
                np.testing.assert_allclose(got, both["losses"][row],
                                           rtol=1e-5)


def test_skewed_bias_drops_nothing_and_builds_no_capacity_tensor():
    """One expert's bias far above the others: it takes every token, the
    busiest expert has E / K (>= 3) times the mean load, every assignment is
    still computed, and the result is still the reference's."""
    params, (tokens, targets) = drawn(DEEPSEEK, CFG), _batch()
    stack = params["moe_layers"]
    bias = jnp.zeros((CFG.n_routed_experts,)).at[5].set(4.0)
    params = dict(params, moe_layers=dict(
        stack, router_bias=jnp.broadcast_to(bias, stack["router_bias"].shape)))
    asked = tokens.size * CFG.num_experts_per_tok * CFG.n_moe_layers
    with jax.default_matmul_precision("highest"):
        (logits, aux), (_, metrics) = jax.jit(lambda p: (
            deepseek.forward_with_aux(p, CFG, tokens),
            deepseek.loss_fn(p, CFG, tokens, targets)))(params)
    assert int(aux["group_sizes"].sum()) == asked
    assert float(metrics["moe_assignments"]) == asked == \
        float(metrics["moe_tokens"])
    assert float(metrics["moe_load_max_over_mean"]) >= 2.6  # E / K = 8 / 3
    assert (aux["group_sizes"][:, 5] == tokens.size).all()
    where = jnp.broadcast_to(jnp.arange(SEQ, dtype=jnp.int32), tokens.shape)
    want_logits = reference.forward(
        params, tokens, targets, where,
        **reference.arguments(published(CFG)))[0]
    np.testing.assert_allclose(logits, want_logits, atol=1e-5)

    # No intermediate of tokens x experts x anything: the largest arrays of
    # the layer are the tokens x K rows, and nothing has rank above 2 but
    # the router's pick of its scores, each choice compared with the E
    # experts and summed where it is made ([T, K, E] one-hot, never stored).
    layer = {k: v[0] for k, v in params["moe_layers"].items()}
    x = jnp.zeros((tokens.size, CFG.hidden_size))
    jaxpr = jax.make_jaxpr(lambda x: moe.routed_experts(
        x, layer["router"], layer["router_bias"], layer["w_gate"],
        layer["w_up"], layer["w_down"], top_k=CFG.num_experts_per_tok,
        scaling=1.0)[0])(x)
    t, e, k = tokens.size, CFG.n_routed_experts, CFG.num_experts_per_tok
    widest = max(CFG.hidden_size, CFG.moe_intermediate_size)

    def shapes(jaxpr):
        for eqn in jaxpr.eqns:
            for var in eqn.outvars:
                yield tuple(var.aval.shape)
            for sub in jax.core.jaxprs_in_params(eqn.params):
                yield from shapes(sub)

    for shape in shapes(jaxpr.jaxpr):
        assert math.prod(shape) <= t * k * widest, shape
        assert shape in ((t, 1, e), (t, k, e)) or not (
            len(shape) >= 3 and t in shape and e in shape), shape


@pytest.mark.parametrize("sizes", [[100, 0, 300, 112], [512, 0, 0, 0]])
def test_grouped_matmul_kernels_match_ragged_dot(sizes):
    """Where the shapes tile, the grouped matmul is the megablox kernels
    (interpreted here): the same products as ``ragged_dot``, forward and
    both cotangents, with an empty group and with one group holding all."""
    keys = jax.random.split(jax.random.PRNGKey(0), 2)
    rows = jax.random.normal(keys[0], (512, 128))
    weights = jax.random.normal(keys[1], (4, 128, 256))
    sizes = jnp.asarray(sizes, jnp.int32)

    def loss(fn, rows, weights):
        return (fn(rows, weights, sizes) ** 2).sum()

    with jax.default_matmul_precision("highest"):
        got = jax.value_and_grad(partial(loss, moe.grouped_matmul),
                                 argnums=(0, 1))(rows, weights)
        want = jax.value_and_grad(partial(loss, jax.lax.ragged_dot),
                                  argnums=(0, 1))(rows, weights)
    for a, b in zip(jax.tree.leaves(got), jax.tree.leaves(want)):
        np.testing.assert_allclose(a, b, rtol=1e-5, atol=1e-4)


def _attention_inputs(seq=512, heads=2, d_qk=192, d_v=128, batch=2):
    keys = jax.random.split(jax.random.PRNGKey(0), 4)
    q = jax.random.normal(keys[0], (batch, seq, heads, d_qk))
    k = jax.random.normal(keys[1], (batch, seq, heads, d_qk))
    v = jax.random.normal(keys[2], (batch, seq, heads, d_v))
    g = jax.random.normal(keys[3], (batch, seq, heads, d_v))
    return q, k, v, g


@pytest.mark.parametrize("under", ["plain", "shard_map"])
@pytest.mark.parametrize("what", ["forward", "backward"])
def test_flash_kernels_two_head_sizes_several_blocks(under, what):
    """q/k of 192 and v of 128, four 128-row tiles a side (so K/V, and in
    the dk/dv kernel Q and dO, stream over the grid and causal tiles are
    skipped), against the dot product; under a CPU mesh the kernels run per
    shard through the models' one attention dispatch."""
    q, k, v, g = _attention_inputs()
    cfg = deepseek.config("deepseek-tiny", attn_impl="flash",
                          attn_blk_q=128, attn_blk_k=128)
    mesh = build_mesh(MeshConfig(dp=1, fsdp=2, tp=2),
                      devices=jax.devices()[:4]) if under == "shard_map" \
        else None

    def flash(q, k, v):
        return lm.attention(q, k, v, cfg)

    def dot(q, k, v):
        return lm.dot_attention(q, k, v)

    previous = mesh_mod.current_mesh()
    mesh_mod.set_current_mesh(mesh)
    try:
        with jax.default_matmul_precision("highest"):
            if what == "forward":
                got, want = jax.jit(flash)(q, k, v), dot(q, k, v)
                assert got.shape == (2, 512, 2, 128)
                np.testing.assert_allclose(got, want, atol=2e-5, rtol=1e-4)
            else:
                got = jax.jit(lambda *a: jax.vjp(flash, *a)[1](g))(q, k, v)
                want = jax.vjp(dot, q, k, v)[1](g)
                for a, b in zip(got, want):
                    assert a.shape == b.shape
                    np.testing.assert_allclose(a, b, atol=2e-4, rtol=1e-3)
    finally:
        mesh_mod.set_current_mesh(previous)


def test_flash_blocks_of_unequal_size():
    q, k, v, _ = _attention_inputs(seq=512, heads=1, batch=1)
    with jax.default_matmul_precision("highest"):
        got = flash_attention(q, k, v, True, 256, 128)
        grads = jax.grad(lambda *a: (flash_attention(
            *a, True, 128, 256) ** 2).sum(), argnums=(0, 1, 2))(q, k, v)
        want = jax.grad(lambda *a: (lm.dot_attention(*a) ** 2).sum(),
                        argnums=(0, 1, 2))(q, k, v)
    np.testing.assert_allclose(got, lm.dot_attention(q, k, v), atol=2e-5,
                               rtol=1e-4)
    for a, b in zip(grads, want):
        np.testing.assert_allclose(a, b, atol=5e-4, rtol=1e-3)


def _one_chip():
    return build_mesh(MeshConfig(dp=1, fsdp=1, tp=1),
                      devices=jax.devices()[:1])


def _counters():
    from ray_tpu._private import builtin_metrics as bm
    return (sum(bm.train_moe_assignments().series().values()),
            sum(bm.train_moe_tokens().series().values()),
            sum(bm.train_moe_expert_load().series().values()))


@pytest.mark.parametrize("accum_steps", [1, 2])
def test_train_step_trains_the_new_model_and_feeds_the_counters(accum_steps):
    mesh = _one_chip()
    state = init_train_state(CFG, mesh, seed=0, model=deepseek)
    step = make_train_step(CFG, mesh, accum_steps=accum_steps, model=deepseek)
    tokens, targets = _batch(4)
    asked = tokens.size * CFG.num_experts_per_tok * CFG.n_moe_layers
    before = _counters()
    losses = []
    for _ in range(4):
        state, metrics = step(state, {"tokens": tokens, "targets": targets})
        losses.append(float(metrics["loss"]))
        assert float(metrics["moe_assignments"]) == asked == \
            float(metrics["moe_tokens"])
    assert losses[-1] < losses[0] and all(map(math.isfinite, losses))
    assigned, tokens_total, load = _counters()
    # Fed one call late at most: after four blocking steps, three or four.
    assert assigned - before[0] == tokens_total - before[1]
    assert assigned - before[0] in (3 * asked, 4 * asked)
    assert 1.0 <= load <= CFG.n_routed_experts / CFG.num_experts_per_tok


@pytest.mark.parametrize("accum_steps,want", [
    (1, [5.555258750915527, 5.555258750915527, 5.554657936096191]),
    (2, [5.555259704589844, 5.555259704589844, 5.554657936096191])])
def test_train_step_on_gpt_tiny_is_unchanged(accum_steps, want):
    """The losses the step gave before it took a model as an argument
    (commit 88e3af0, this seed and batch), and the same three metrics."""
    mesh = _one_chip()
    cfg = gpt.config("gpt-tiny")
    state = init_train_state(cfg, mesh, seed=0)
    step = make_train_step(cfg, mesh, accum_steps=accum_steps)
    rows = np.random.default_rng(0).integers(0, 256, (4, 65), dtype=np.int32)
    batch = {"tokens": jnp.asarray(rows[:, :-1]),
             "targets": jnp.asarray(rows[:, 1:])}
    got = []
    for _ in range(3):
        state, metrics = step(state, batch)
        got.append(float(metrics["loss"]))
    assert sorted(metrics) == ["accuracy", "loss", "perplexity"]
    np.testing.assert_allclose(got, want, rtol=1e-5)


@pytest.mark.parametrize("model,cfg", [
    (gpt, gpt.config("gpt-tiny")), (deepseek, CFG)],
    ids=["gpt-tiny", "deepseek-tiny"])
def test_the_builders_find_the_model_that_defines_the_config(model, cfg):
    """Without ``model=`` every builder takes the module ``type(cfg)`` lives
    in: the same state, the same step and the same metrics as with it."""
    mesh = _one_chip()
    tokens, targets = _batch()
    batch = {"tokens": tokens, "targets": targets}

    def run(**named):
        state = init_train_state(cfg, mesh, seed=0, **named)
        abstract = abstract_train_state(cfg, mesh, **named)
        assert jax.tree.map(lambda a: (a.shape, a.dtype), state) == \
            jax.tree.map(lambda a: (a.shape, a.dtype), abstract)
        evaluated = make_eval_step(cfg, mesh, **named)(state["params"], batch)
        _, stepped = make_train_step(cfg, mesh, **named)(state, batch)
        return jax.device_get((evaluated, stepped))

    found, named = run(), run(model=model)
    assert "loss" in found[1] and found == named


def test_moonlight_is_sixteen_billion():
    full = deepseek.config("moonlight-16b-a3b")
    shapes = jax.eval_shape(lambda k: deepseek.init(full, k),
                            jax.random.PRNGKey(0))
    total = sum(math.prod(a.shape) for a in jax.tree.leaves(shapes))
    assert 15.9e9 < total < 16.1e9, total  # "16B"
