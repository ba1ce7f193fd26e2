"""models/afmoe.py (window and full attention layers through one flash pair
table, gated attention with a norm on q and k, a norm before and after every
branch, a chip's share of the experts) against a copy of the benchmark's
plain reference, through ``family_cases.py``; ``ops/moe.py``'s held experts:
the shares add up to the uncut layer, and every expert held is the layer as
it was; the cut configuration's four runs.
"""

import math
from dataclasses import replace
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import family_cases
import reference_afmoe as reference
from family_cases import batch, drawn, expert_layer, in_every_run
from ray_tpu.models import afmoe, lm
from ray_tpu.ops.moe import routed_experts

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


def moved(name, leaf, key):
    """Every vector off its one or zero (the expert bias too: routing
    uneven), and the q and k norms' scales doubled: the scores of a random
    model then spread by four units, so that a key wrongly seen or a wrong
    KV head moves the softmax."""
    if "router_bias" in name:
        return 0.05 * jax.random.normal(key, leaf.shape)
    if leaf.ndim == (2 if "run" in name else 1):
        gain = 2.0 if "q_norm" in name or "k_norm" in name else 1.0
        return gain * (leaf + 0.2 * jax.random.normal(key, leaf.shape))
    return leaf


def drop(dropped, params, cfg, monkeypatch):
    if dropped == "window":
        cfg = replace(cfg, sliding_window=10 ** 6)
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
        params = in_every_run(params, lambda w: dict(
            w, w_attn_gate=jnp.zeros_like(w["w_attn_gate"])))
    elif dropped == "qk_norm":
        plain = lm.rmsnorm
        monkeypatch.setattr(
            lm, "rmsnorm", lambda x, scale, eps:
            x if x.ndim == 4 else plain(x, scale, eps))
    elif dropped == "route_scale":
        cfg = replace(cfg, route_scale=1.0)
    elif dropped == "shared_expert":
        params = in_every_run(params, lambda w: dict(
            w, shared_w_down=jnp.zeros_like(w["shared_w_down"]))
            if "router" in w else w)
    elif dropped == "sqrt_hidden":
        cfg = replace(cfg, mup_enabled=False)
    elif dropped == "kv_pairing":
        params = in_every_run(params, lambda w: dict(
            w, wk=jnp.roll(w["wk"], 1, axis=2),
            wv=jnp.roll(w["wv"], 1, axis=2)))
    return params, cfg


AFMOE = family_cases.Family(
    module=afmoe, reference=reference, cfg=CFG, seq=SEQ, flash=FLASH,
    flash_seq=FLASH_SEQ, published=published, moved=moved,
    extras=("picked",), drop=drop, dropped=(
        "window", "rope", "rope_on_full", "attn_gate", "qk_norm",
        "route_scale", "shared_expert", "sqrt_hidden", "kv_pairing"),
    top_k=CFG.num_experts_per_tok, accum_steps=(1, 2), scan_atol=1e-4,
    wrong=({"experts_held": (6, 4)}, {"experts_held": (0, 0)},
           {"layer_types": ("sliding_attention",) * 2},
           {"layer_types": ("mamba",) * 5}))
globals().update(family_cases.cases(AFMOE))


def test_the_tiny_stack_has_all_four_kinds_of_layer(both):
    assert [kind for _, kind, _ in lm.runs(CFG.layers)] == [
        "dense_sliding_attention", "dense_full_attention",
        "moe_sliding_attention", "moe_full_attention"]
    assert CFG.sliding_window < SEQ and FLASH.sliding_window < FLASH_SEQ
    assert both["aux"]["group_sizes"].shape == (CFG.n_moe_layers,
                                                CFG.num_experts)
    shapes = jax.eval_shape(partial(afmoe.init, FLASH), jax.random.PRNGKey(0))
    assert shapes["run02_moe_sliding_attention"]["w_up"].shape[1] == 3


# -- the share ------------------------------------------------------------

@pytest.mark.parametrize("count", [1, 2, 4, 8, 16])
def test_the_shares_add_up_to_the_uncut_layer(count):
    """The routed parts that the 16 / count shares give, plus the shared
    expert once, are the uncut layer of the reference; and every share
    computes exactly the assignments the router gave its experts."""
    w, x = expert_layer()
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
    w, x = expert_layer(experts=8, tokens=128, d=d, f=f)
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
    w, x = expert_layer(experts=8, tokens=128, d=128, f=128, seed=1)
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
    w, x = expert_layer(experts=8)
    with pytest.raises(ValueError, match="held"):
        routed_experts(x, w["router"], w["router_bias"], w["w_gate"][:4],
                       w["w_up"][:4], w["w_down"][:4], top_k=2, scaling=1.0,
                       held=(6, 4))
    with pytest.raises(ValueError, match="experts"):
        routed_experts(x, w["router"], w["router_bias"], w["w_gate"][:3],
                       w["w_up"][:3], w["w_down"][:3], top_k=2, scaling=1.0,
                       held=(0, 4))


def test_with_every_expert_held_all_that_is_routed_is_asked():
    tokens, targets = batch(CFG, SEQ)
    _, metrics = jax.jit(lambda p: afmoe.loss_fn(p, CFG, tokens, targets))(
        drawn(AFMOE, CFG))
    routed = tokens.size * CFG.num_experts_per_tok * CFG.n_moe_layers
    assert float(metrics["moe_routed"]) == float(metrics["moe_tokens"]) \
        == float(metrics["moe_assignments"]) == routed


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
