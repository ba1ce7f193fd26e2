"""models/afmoe.py (window and full attention layers through one flash pair
table, gated attention with a norm on q and k, a norm before and after every
branch, a chip's share of the experts) against a copy of the benchmark's
plain reference; ``ops/moe.py``'s held experts: the shares add up to the
uncut layer, and every expert held is the layer as it was; the sliced head;
the counters of the share; ``lm.scan_blocks`` over the four kinds of layer.

Everything runs on the CPU at tiny widths in float32 under the highest
matmul precision, the kernels interpreted, where both sides compute the same
sums in another order: tolerances of 1e-4 (relative, on gradients: of a
leaf's norm) leave room for float32 reassociation across a few hundred terms
and nothing else.
"""

import math
from dataclasses import replace
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import reference_afmoe as reference
from ray_tpu.models import afmoe, lm
from ray_tpu.ops.moe import routed_experts
from ray_tpu.parallel import MeshConfig, build_mesh
from ray_tpu.parallel.train_step import init_train_state, make_train_step
from ray_tpu.util import metrics as metrics_mod

CFG = afmoe.config("afmoe-tiny")
SEQ = 64
# The kernels (interpreted), remat, the chunked loss, a window that is not a
# multiple of the tile, and a share of the experts: 3 of 8, from the third.
FLASH = replace(CFG, attn_impl="flash", attn_blk_q=128, attn_blk_k=128,
                sliding_window=100, remat=True, loss_chunk=128,
                experts_held=(2, 3))
FLASH_SEQ = 256


def published(cfg):
    out = {"layer_types": list(cfg.layer_types),
           "num_hidden_layers": cfg.num_hidden_layers,
           "num_dense_layers": cfg.num_dense_layers,
           "hidden_size": cfg.hidden_size, "mup_enabled": cfg.mup_enabled,
           "sliding_window": cfg.sliding_window,
           "rope_theta": cfg.rope_theta,
           "num_experts_per_tok": cfg.num_experts_per_tok,
           "route_scale": cfg.route_scale, "route_norm": cfg.route_norm,
           "rms_norm_eps": cfg.rms_norm_eps}
    if cfg.experts_held is not None:
        first, count = cfg.experts_held
        out["deployment"] = {"experts_held": {
            "first": first, "count": count, "of": cfg.num_experts}}
    return out


def drawn(cfg, seed=0):
    """The init with every vector moved off its one or zero (the expert
    bias too: routing uneven), and the q and k norms' scales doubled: the
    scores of a random model then spread by four units, so that a key
    wrongly seen or a wrong KV head moves the softmax."""
    params = afmoe.init(cfg, jax.random.PRNGKey(seed))
    keys = iter(jax.random.split(jax.random.PRNGKey(seed + 1), 128))

    def moved(path, leaf):
        name = jax.tree_util.keystr(path)
        if "router_bias" in name:
            return 0.05 * jax.random.normal(next(keys), leaf.shape)
        if leaf.ndim == (2 if "run" in name else 1):
            gain = 2.0 if "q_norm" in name or "k_norm" in name else 1.0
            return gain * (leaf + 0.2 * jax.random.normal(next(keys),
                                                          leaf.shape))
        return leaf

    return jax.tree_util.tree_map_with_path(moved, params)


def batch(cfg, seed=0, rows=2, seq=SEQ):
    toks = np.random.default_rng(seed).integers(
        0, cfg.vocab_size, (rows, seq + 1), dtype=np.int32)
    return jnp.asarray(toks[:, :-1]), jnp.asarray(toks[:, 1:])


def compared(cfg, seq):
    """Program and reference on one batch: logits, loss and gradients."""
    params = drawn(cfg)
    tokens, targets = batch(cfg, seq=seq)
    kw = reference.arguments(published(cfg))
    where = jnp.broadcast_to(jnp.arange(seq, dtype=jnp.int32), tokens.shape)
    want_logits, want_loss, rms, want_picked = reference.forward(
        params, tokens, targets, where, with_picked=True, **kw)
    with jax.default_matmul_precision("highest"):
        got_logits, aux = jax.jit(partial(afmoe.forward_with_aux, cfg=cfg))(
            params, tokens=tokens)
        got_loss, got_grads = jax.jit(jax.value_and_grad(
            lambda p: afmoe.loss_fn(p, cfg, tokens, targets)[0]))(params)
    want_grads = jax.grad(
        lambda p: reference.loss(p, tokens, targets, **kw))(params)
    return {"logits": (got_logits, want_logits), "rms": float(rms),
            "loss": (got_loss, want_loss.mean()),
            "picked": (aux["picked"], want_picked),
            "grads": (got_grads, want_grads)}


@pytest.fixture(scope="module")
def both():
    return compared(CFG, SEQ)


@pytest.fixture(scope="module")
def both_flash():
    return compared(FLASH, FLASH_SEQ)


def test_the_tiny_stack_has_all_four_kinds_of_layer():
    assert [kind for _, kind, _ in lm.runs(CFG.layers)] == [
        "dense_sliding_attention", "dense_full_attention",
        "moe_sliding_attention", "moe_full_attention"]
    assert CFG.sliding_window < SEQ and FLASH.sliding_window < FLASH_SEQ


@pytest.mark.parametrize("which", ["both", "both_flash"])
def test_logits_loss_and_routing_match_the_reference(which, request):
    found = request.getfixturevalue(which)
    got, want = found["logits"]
    assert found["rms"] > 0.01
    np.testing.assert_allclose(got, want, atol=1e-3 * found["rms"])
    np.testing.assert_allclose(*found["loss"], rtol=1e-5)
    got, want = found["picked"]
    assert (np.sort(got, -1) == np.sort(want, -1)).all()


LEAVES = sorted(jax.tree_util.keystr(path) for path, _ in
                jax.tree_util.tree_leaves_with_path(
                    jax.eval_shape(partial(afmoe.init, CFG),
                                   jax.random.PRNGKey(0))))


@pytest.mark.parametrize("leaf", LEAVES)
@pytest.mark.parametrize("which", ["both", "both_flash"])
def test_gradients_match_the_reference(which, leaf, request):
    found = request.getfixturevalue(which)
    got, want = (dict((jax.tree_util.keystr(p), a) for p, a in
                      jax.tree_util.tree_leaves_with_path(tree))[leaf]
                 for tree in found["grads"])
    norm = float(jnp.linalg.norm(want.ravel()))
    if "router_bias" in leaf:  # selection only: no gradient on either side
        assert norm == 0.0 and not np.any(got)
        return
    assert norm > 0.0
    assert float(jnp.linalg.norm((got - want).ravel())) < 1e-4 * norm


def _in_every_run(params, cfg, change):
    return dict(params, **{run: change(dict(params[run]))
                           for run, _, _ in lm.runs(cfg.layers)})


@pytest.mark.parametrize("dropped", [
    "window", "rope", "rope_on_full", "attn_gate", "qk_norm", "route_scale",
    "shared_expert", "sqrt_hidden", "kv_pairing"])
def test_a_dropped_term_shows(both, dropped, monkeypatch):
    """Each of the terms a fast path could lose moves the logits by far
    more than the agreement above allows."""
    params, cfg = drawn(CFG), CFG
    tokens, _ = batch(CFG)
    if dropped == "window":
        cfg = replace(CFG, sliding_window=10 ** 6)
    elif dropped == "rope":
        monkeypatch.setattr(lm, "rope", lambda x, positions, theta: x)
    elif dropped == "rope_on_full":
        plain = afmoe._attention
        # A full layer as a window layer whose window holds everything.
        monkeypatch.setattr(
            afmoe, "_attention",
            lambda cfg, sliding, *rest: plain(
                cfg if sliding else replace(cfg, sliding_window=10 ** 6),
                True, *rest))
    elif dropped == "attn_gate":
        # sigmoid(0): a constant, which the norm on the branch takes out.
        params = _in_every_run(params, CFG, lambda w: dict(
            w, w_attn_gate=jnp.zeros_like(w["w_attn_gate"])))
    elif dropped == "qk_norm":
        plain = lm.rmsnorm
        monkeypatch.setattr(
            lm, "rmsnorm", lambda x, scale, eps:
            x if x.ndim == 4 else plain(x, scale, eps))
    elif dropped == "route_scale":
        cfg = replace(CFG, route_scale=1.0)
    elif dropped == "shared_expert":
        params = _in_every_run(params, CFG, lambda w: dict(
            w, shared_w_down=jnp.zeros_like(w["shared_w_down"]))
            if "router" in w else w)
    elif dropped == "sqrt_hidden":
        cfg = replace(CFG, mup_enabled=False)
    elif dropped == "kv_pairing":
        params = _in_every_run(params, CFG, lambda w: dict(
            w, wk=jnp.roll(w["wk"], 1, axis=2),
            wv=jnp.roll(w["wv"], 1, axis=2)))
    with jax.default_matmul_precision("highest"):
        got = afmoe.forward(params, cfg, tokens)
    _, want = both["logits"]
    assert float(jnp.abs(got - want).max()) > 0.05 * both["rms"]


# -- the share ------------------------------------------------------------

def _expert_layer(experts=16, tokens=96, d=32, f=16, seed=0):
    ks = jax.random.split(jax.random.PRNGKey(seed), 9)
    normal = jax.random.normal
    w = {"router": normal(ks[0], (d, experts)) / math.sqrt(d),
         "router_bias": 0.2 * normal(ks[1], (experts,)),
         "w_gate": normal(ks[2], (experts, d, f)) / math.sqrt(d),
         "w_up": normal(ks[3], (experts, d, f)) / math.sqrt(d),
         "w_down": normal(ks[4], (experts, f, d)) / math.sqrt(f),
         "shared_w_gate": normal(ks[5], (d, f)) / math.sqrt(d),
         "shared_w_up": normal(ks[6], (d, f)) / math.sqrt(d),
         "shared_w_down": normal(ks[7], (f, d)) / math.sqrt(f)}
    return w, normal(ks[8], (tokens, d))


@pytest.mark.parametrize("count", [1, 2, 4, 8, 16])
def test_the_shares_add_up_to_the_uncut_layer(count):
    """The routed parts that the 16 / count shares give, plus the shared
    expert once, are the uncut layer of the reference; and every share
    computes exactly the assignments the router gave its experts."""
    w, x = _expert_layer()
    top_k, scale = 4, 2.448
    with jax.default_matmul_precision("highest"):
        want, picked = reference._ffn(x, w, top_k, scale, True, 0)
        shared = lm.swiglu(x, w["shared_w_gate"], w["shared_w_up"],
                           w["shared_w_down"])
        total, computed = shared, 0
        for first in range(0, 16, count):
            part, aux = routed_experts(
                x, w["router"], w["router_bias"],
                *(w[name][first:first + count]
                  for name in ("w_gate", "w_up", "w_down")),
                top_k=top_k, scaling=scale, held=(first, count))
            assert aux["group_sizes"].shape == (count,)
            mine = ((picked >= first) & (picked < first + count)).sum()
            assert int(aux["group_sizes"].sum()) == int(mine) \
                == int(aux.get("asked", mine))
            total, computed = total + part, computed + int(mine)
            # The reference given the same share gives the same part.
            share = dict(w, **{name: w[name][first:first + count]
                               for name in ("w_gate", "w_up", "w_down")})
            ref_part = reference._ffn(x, share, top_k, scale, True,
                                      first)[0] - shared
            np.testing.assert_allclose(part, ref_part, atol=2e-5)
    assert computed == x.shape[0] * top_k
    np.testing.assert_allclose(total, want, atol=5e-5)


@pytest.mark.parametrize("widths", [(32, 16), (128, 128)],
                         ids=["ragged_dot", "megablox"])
def test_every_expert_held_is_the_layer_as_it_was(widths):
    """``held=(0, E)`` and ``held=None`` are one path: output and gradients
    bit for bit (the tiling widths take the grouped-matmul kernels,
    interpreted)."""
    d, f = widths
    w, x = _expert_layer(experts=8, tokens=128, d=d, f=f)
    args = (w["router"], w["router_bias"], w["w_gate"], w["w_up"],
            w["w_down"])

    def run(held):
        def loss(x, *args):
            y, aux = routed_experts(x, *args, top_k=2, scaling=2.0,
                                    held=held)
            return (y ** 2).sum(), (y, aux)
        (_, (y, aux)), grads = jax.value_and_grad(
            loss, argnums=(0, 1, 3, 4, 5), has_aux=True)(x, *args)
        return y, aux, grads

    (y0, aux0, g0), (y1, aux1, g1) = run(None), run((0, 8))
    assert (y0 == y1).all() and sorted(aux0) == sorted(aux1) == [
        "group_sizes", "picked"]
    assert all((a == b).all() for a, b in zip(g0, g1))
    assert int(aux0["group_sizes"].sum()) == x.shape[0] * 2


@pytest.mark.parametrize("held", [(0, 2), (3, 4), (6, 2)])
def test_held_experts_through_the_grouped_matmul_kernels(held):
    """At widths that tile, the share goes through ``megablox`` from the
    first held group on: equal to the ragged path's, values and gradients,
    and zero for a token none of whose experts is held."""
    first, count = held
    w, x = _expert_layer(experts=8, tokens=128, d=128, f=128, seed=1)
    cut = [w[name][first:first + count]
           for name in ("w_gate", "w_up", "w_down")]

    def part(x, w_gate, w_up, w_down):
        return routed_experts(x, w["router"], w["router_bias"], w_gate, w_up,
                              w_down, top_k=2, scaling=2.0, held=held)

    with jax.default_matmul_precision("highest"):
        got, aux = part(x, *cut)
        want = reference._ffn(
            x, dict(w, w_gate=cut[0], w_up=cut[1], w_down=cut[2],
                    shared_w_down=jnp.zeros_like(w["shared_w_down"])),
            2, 2.0, True, first)[0]
        grads = jax.grad(lambda *a: (part(*a)[0] ** 2).sum(),
                         argnums=(0, 1, 2, 3))(x, *cut)
        want_grads = jax.grad(lambda x, g, u, dn: (reference._ffn(
            x, dict(w, w_gate=g, w_up=u, w_down=dn,
                    shared_w_down=jnp.zeros_like(w["shared_w_down"])),
            2, 2.0, True, first)[0] ** 2).sum(), argnums=(0, 1, 2, 3))(
                x, *cut)
    np.testing.assert_allclose(got, want, atol=1e-4)
    for a, b in zip(grads, want_grads):
        np.testing.assert_allclose(a, b, atol=1e-4 * float(jnp.abs(b).max()))
    nobody = ~((aux["picked"] >= first)
               & (aux["picked"] < first + count)).any(-1)
    assert nobody.any() and not np.any(np.asarray(got)[np.asarray(nobody)])


def test_held_experts_must_fit_the_router():
    w, x = _expert_layer(experts=8)
    with pytest.raises(ValueError, match="held"):
        routed_experts(x, w["router"], w["router_bias"], w["w_gate"][:4],
                       w["w_up"][:4], w["w_down"][:4], top_k=2, scaling=1.0,
                       held=(6, 4))
    with pytest.raises(ValueError, match="experts"):
        routed_experts(x, w["router"], w["router_bias"], w["w_gate"][:3],
                       w["w_up"][:3], w["w_down"][:3], top_k=2, scaling=1.0,
                       held=(0, 4))


def test_the_sliced_heads_loss_is_the_whole_heads_on_the_slice():
    """A slice of the vocabulary is a smaller vocabulary: on ids of the
    slice, the loss of the model that holds the slice's rows of ``wte`` and
    columns of the head is the whole model's with its logits restricted to
    those columns."""
    held = 64
    params = drawn(CFG)
    tokens, targets = batch(replace(CFG, vocab_size=held))
    sliced = dict(params, wte=params["wte"][:held],
                  lm_head=params["lm_head"][:, :held])
    with jax.default_matmul_precision("highest"):
        got, metrics = afmoe.loss_fn(sliced, replace(CFG, vocab_size=held),
                                     tokens, targets)
        logits = afmoe.forward(params, CFG, tokens)[..., :held]
    logp = jax.nn.log_softmax(logits.astype(jnp.float32), axis=-1)
    want = -jnp.take_along_axis(logp, targets[..., None], -1).mean()
    np.testing.assert_allclose(got, want, rtol=1e-6)
    assert abs(float(got) - math.log(held)) < 1.0
    assert float(metrics["moe_routed"]) == tokens.size * 2 * 3


# -- the train step and its counters --------------------------------------

def _one_chip():
    return build_mesh(MeshConfig(dp=1, fsdp=1, tp=1),
                      devices=jax.devices()[:1])


def _counter(name):
    for entry in metrics_mod.snapshot():
        if entry["name"] == name:
            return sum(entry["series"].values())
    return 0.0


COUNTERS = ("ray_tpu_train_moe_assignments_total",
            "ray_tpu_train_moe_tokens_total",
            "ray_tpu_train_moe_routed_total")


@pytest.mark.parametrize("accum_steps", [1, 2])
def test_trains_and_feeds_the_shares_counters(accum_steps):
    """``make_train_step`` finds the model from ``type(cfg)``: the loss
    falls on a repeated batch (flash, remat, the chunked loss, a share of
    the experts), and the counters say what the share did: every assignment
    to a held expert computed, and those a part of all the router made."""
    import optax
    from ray_tpu.parallel.sharding import ShardingRules
    mesh = _one_chip()
    rules, optimizer = ShardingRules(), optax.adam(3e-3)
    state = init_train_state(FLASH, mesh, rules, optimizer, seed=0)
    step = make_train_step(FLASH, mesh, rules, optimizer,
                           accum_steps=accum_steps)
    tokens, targets = batch(FLASH, rows=2, seq=FLASH_SEQ)
    routed = tokens.size * FLASH.num_experts_per_tok * FLASH.n_moe_layers
    before = [_counter(name) for name in COUNTERS]
    losses = []
    for _ in range(3):
        state, metrics = step(state, {"tokens": tokens, "targets": targets})
        losses.append(float(metrics["loss"]))
        assert float(metrics["moe_routed"]) == routed
        assert float(metrics["moe_assignments"]) == \
            float(metrics["moe_tokens"])
        assert 0 < float(metrics["moe_tokens"]) < routed
        assert 1.0 <= float(metrics["moe_load_max_over_mean"]) <= 3.0
    assert all(np.isfinite(losses)) and losses[-1] < losses[0]
    assigned, asked, all_routed = (
        _counter(name) - was for name, was in zip(COUNTERS, before))
    # Fed one call late at most: after three blocking steps, two or three.
    assert assigned == asked and all_routed in (2 * routed, 3 * routed)
    # 3 of 8 experts held: about three eighths of the routing's work.
    assert 0.2 < asked / all_routed < 0.6


@pytest.mark.parametrize("accum_steps", [1, 2])
def test_a_step_feeds_the_calls_and_those_within_the_bound(accum_steps):
    """An expert-layer call a layer and microbatch, and each within the
    bound: 3 of 8 experts held get about three eighths of the assignments,
    and the buffer is all of them (twice the even share is three quarters,
    a whole row tile is more than all)."""
    import optax
    from ray_tpu.parallel.sharding import ShardingRules
    names = ("ray_tpu_train_moe_calls_total",
             "ray_tpu_train_moe_calls_within_bound_total")
    mesh = _one_chip()
    rules, optimizer = ShardingRules(), optax.adam(3e-3)
    state = init_train_state(FLASH, mesh, rules, optimizer, seed=0)
    step = make_train_step(FLASH, mesh, rules, optimizer,
                           accum_steps=accum_steps)
    tokens, targets = batch(FLASH, rows=2, seq=FLASH_SEQ)
    calls = FLASH.n_moe_layers * accum_steps
    before = [_counter(name) for name in names]
    for _ in range(3):
        state, metrics = step(state, {"tokens": tokens, "targets": targets})
        assert float(metrics["moe_calls"]) == calls \
            == float(metrics["moe_calls_within_bound"])
    in_all, within = (
        _counter(name) - was for name, was in zip(names, before))
    # Fed one call late at most: after three blocking steps, two or three.
    assert in_all == within and in_all in (2 * calls, 3 * calls)


def test_with_every_expert_held_all_that_is_routed_is_asked():
    tokens, targets = batch(CFG)
    _, metrics = afmoe.loss_fn(drawn(CFG), CFG, tokens, targets)
    routed = tokens.size * CFG.num_experts_per_tok * CFG.n_moe_layers
    assert float(metrics["moe_routed"]) == float(metrics["moe_tokens"]) \
        == float(metrics["moe_assignments"]) == routed


def test_expert_parallel_mesh_is_refused():
    mesh = build_mesh(MeshConfig(dp=1, fsdp=1, tp=1, ep=2),
                      devices=jax.devices()[:2])
    step = make_train_step(CFG, mesh)
    state = init_train_state(CFG, mesh, seed=0)
    tokens, targets = batch(CFG)
    with pytest.raises(NotImplementedError, match="expert parallelism"):
        step(state, {"tokens": tokens, "targets": targets})


# -- the layer scan over the four runs ------------------------------------

CUT = replace(afmoe.config("trinity-large-preview"), num_hidden_layers=5,
              num_dense_layers=1, experts_held=(0, 8), vocab_size=25024)


def test_the_cut_configuration_is_four_runs():
    """The benchmark's cut: the dense layer a window layer as published
    layer 0 is, then one period of expert layers, 3 window : 1 full."""
    assert lm.runs(CUT.layers) == (
        ("run00_dense_sliding_attention", "dense_sliding_attention", 1),
        ("run01_moe_sliding_attention", "moe_sliding_attention", 2),
        ("run02_moe_full_attention", "moe_full_attention", 1),
        ("run03_moe_sliding_attention", "moe_sliding_attention", 1))
    shapes = jax.eval_shape(partial(afmoe.init, CUT), jax.random.PRNGKey(0))
    assert shapes["run01_moe_sliding_attention"]["w_gate"].shape == (
        2, 8, 3072, 3072)
    assert shapes["run01_moe_sliding_attention"]["router"].shape == (
        2, 3072, 256)
    assert shapes["lm_head"].shape == (3072, 25024)
    held = sum(math.prod(a.shape) for a in jax.tree.leaves(shapes))
    assert 1.603e9 < held < 1.606e9


@pytest.mark.parametrize("remat", [False, True])
def test_scan_blocks_over_the_four_runs(remat):
    """The runs scanned, one stack a run, are the layers applied one by one
    in order: hidden states and the expert layers' auxiliary outputs."""
    cfg = replace(CFG, remat=remat)
    params = drawn(cfg)
    tokens, _ = batch(cfg)
    with jax.default_matmul_precision("highest"):
        got, aux = afmoe.hidden_states(params, cfg, tokens)
        x = lm.embed(params["wte"], tokens, cfg.dtype) * math.sqrt(
            cfg.hidden_size)
        picked = []
        for run, kind, depth in lm.runs(cfg.layers):
            for j in range(depth):
                x, one = afmoe._block(cfg, kind, x, jax.tree.map(
                    lambda a: a[j], params[run]), lm.positions_of(tokens))
                if one is not None:
                    picked.append(one["picked"])
    want = lm.rmsnorm(x, params["lnf_scale"], cfg.rms_norm_eps)
    np.testing.assert_allclose(got, want, atol=1e-4)
    assert (aux["picked"] == jnp.stack(picked)).all()
    assert aux["group_sizes"].shape == (cfg.n_moe_layers, cfg.num_experts)


def test_param_specs_match_init():
    from ray_tpu.parallel.sharding import ShardingRules
    for cfg in (CFG, FLASH):
        params = jax.eval_shape(partial(afmoe.init, cfg),
                                jax.random.PRNGKey(0))
        specs = afmoe.param_specs(cfg, ShardingRules())
        assert jax.tree.structure(params) == jax.tree.structure(
            specs, is_leaf=lambda s: isinstance(
                s, jax.sharding.PartitionSpec))
    assert params["run02_moe_sliding_attention"]["w_up"].shape[1] == 3


@pytest.mark.parametrize("wrong", [
    {"experts_held": (6, 4)}, {"experts_held": (0, 0)},
    {"layer_types": ("sliding_attention",) * 2},
    {"layer_types": ("mamba",) * 5}])
def test_config_refuses_what_it_cannot_hold(wrong):
    with pytest.raises(ValueError):
        replace(CFG, **wrong)
