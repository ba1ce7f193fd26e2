"""models/nemotron_h.py (layers of one mixer each: Mamba-2 with several B/C
groups under a grouped norm, squared-ReLU experts without a gate beside a
shared one, grouped-query attention without positions; the scan over units
of two layers) against a copy of the benchmark's plain reference, through
``family_cases.py``; the recurrence at two groups against ``transformers``'
Mamba-2 (``GraniteMoeHybridMambaLayer.torch_forward``) up to the norm, and
the grouped norm against numpy; the squared-ReLU expert in the whole layer
and in a share against a loop over experts at a width that is no multiple of
128; the shares of the experts adding up to the uncut layer; the published
pattern, the cut's layers, the runs of units and the parameter counts; what
the step's gauge reads.
"""

import functools
from dataclasses import replace
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import family_cases
import reference_nemotron_h as reference
from family_cases import batch, drawn, in_every_run, trained
from ray_tpu.models import lm, nemotron_h
from ray_tpu.ops import moe

CFG = nemotron_h.config("nemotron-h-tiny")
SEQ = 128
# The attention kernels too (interpreted), remat of every layer of a unit,
# the chunked loss and a share of the experts: 3 of 8, from the third.
FLASH = replace(CFG, attn_impl="flash", attn_blk_q=128, attn_blk_k=128,
                remat=True, loss_chunk=128, experts_held=(2, 3))
FLASH_SEQ = 256
PUBLISHED = nemotron_h.config("nemotron-3-nano-30b-a3b")
# The benchmark's cut: published layers 34-42, 32 of 128 experts, a quarter
# of the vocabulary.
CUT = replace(PUBLISHED, num_hidden_layers=9, first_layer=34,
              experts_held=(0, 32), vocab_size=32768)


def published(cfg):
    out = {"hybrid_override_pattern": cfg.hybrid_override_pattern,
           "num_hidden_layers": cfg.num_hidden_layers,
           "mamba_num_heads": cfg.mamba_num_heads, "n_groups": cfg.n_groups,
           "ssm_state_size": cfg.ssm_state_size,
           "num_experts_per_tok": cfg.num_experts_per_tok,
           "norm_topk_prob": cfg.norm_topk_prob,
           "routed_scaling_factor": cfg.routed_scaling_factor,
           "layer_norm_epsilon": cfg.layer_norm_epsilon,
           "deployment": {"layers_run": {"first": cfg.first_layer}}}
    if cfg.experts_held is not None:
        first, count = cfg.experts_held
        out["deployment"]["experts_held"] = {
            "first": first, "count": count, "of": cfg.n_routed_experts}
    return out


def moved(name, leaf, key):
    """Every vector off its one or zero (the correction bias too, so that
    the selection leans on it), ``Wq`` and ``Wk`` eight times larger (at the
    init's scale every softmax is flat and attention is the running mean of
    v whichever KV head it reads) and the router's columns ten times, so
    that a token's picked scores differ."""
    if "wq" in name or "wk" in name:
        return 8.0 * leaf
    if name.endswith("router']"):
        return 10.0 * leaf
    if leaf.ndim == (2 if "run" in name else 1):
        return leaf + 0.2 * jax.random.normal(key, leaf.shape)
    return leaf


def _zeroed(params, leaf):
    """``params`` with every ``leaf`` (of a unit's either layer) zero."""
    return in_every_run(params, lambda w: dict(w, **{
        name: jnp.zeros_like(a) for name, a in w.items()
        if name in (leaf, "a_" + leaf, "b_" + leaf)}))


def drop(dropped, params, cfg, monkeypatch):
    if dropped == "relu_not_squared":
        monkeypatch.setitem(moe.ACTIVATIONS, "relu2", jax.nn.relu)
    elif dropped == "group_zero":  # every head reads group 0's B and C
        plain = lm.state_space
        monkeypatch.setattr(
            lm, "state_space", lambda u, dt, A, B, C, D, chunk: plain(
                u, dt, A, *(jnp.broadcast_to(a[:, :, :1], a.shape)
                            for a in (B, C)), D, chunk))
    elif dropped == "whole_row_norm":
        plain = lm.gated_norm
        monkeypatch.setattr(lm, "gated_norm", lambda *args, group, **kw:
                            plain(*args, **kw))
    elif dropped in ("D", "router_bias", "shared_w_down"):
        params = _zeroed(params, dropped)
    elif dropped == "routed_scaling_factor":
        cfg = replace(cfg, routed_scaling_factor=1.0)
    elif dropped == "rope":
        plain = lm.attention
        monkeypatch.setattr(lm, "attention", lambda q, k, v, cfg, **kw: plain(
            *(lm.rope(a, lm.positions_of(a[..., 0, 0]), 10000.0)
              for a in (q, k)), v, cfg, **kw))
    elif dropped == "kv_pairing":  # KV head i % 2 for i // (heads / 2)
        plain = lm.attention
        monkeypatch.setattr(lm, "attention", lambda q, k, v, cfg, **kw: plain(
            q, *(jnp.tile(a, (1, 1, q.shape[2] // a.shape[2], 1))
                 for a in (k, v)), cfg, **kw))
    return params, cfg


NEMOTRON = family_cases.Family(
    module=nemotron_h, reference=reference, cfg=CFG, seq=SEQ, flash=FLASH,
    flash_seq=FLASH_SEQ, published=published, moved=moved,
    extras=("picked",), drop=drop, dropped=(
        "relu_not_squared", "group_zero", "whole_row_norm", "D",
        "router_bias", "shared_w_down", "routed_scaling_factor", "rope",
        "kv_pairing"),
    top_k=CFG.num_experts_per_tok, accum_steps=(1,),
    wrong=({"experts_held": (6, 4)}, {"hybrid_override_pattern": "ME-M*"},
           {"first_layer": 3}, {"n_groups": 3}, {"mlp_hidden_act": "silu"},
           {"tie_word_embeddings": True}),
    refuses=(ValueError, NotImplementedError),
    flash_kernels=("ssd_fwd", "ssd_bwd", "conv_silu_fwd", "conv_silu_bwd",
                   "gated_norm_fwd", "gated_norm_bwd", "flash_fwd",
                   "flash_bwd/", "gmm", "tgmm"))
globals().update(family_cases.cases(NEMOTRON))


# -- the pattern, the cut and the runs of units ---------------------------

def test_the_published_pattern_and_the_cuts_layers():
    kinds = PUBLISHED.layers
    assert len(kinds) == 52 and [kinds.count(k) for k in (
        "mamba", "experts", "attention")] == [23, 23, 6]
    assert [i for i, k in enumerate(kinds) if k == "attention"] == [
        5, 12, 19, 26, 33, 42]
    assert "".join({"mamba": "M", "experts": "E", "attention": "*"}[k]
                   for k in CUT.layers) == "EMEMEMEM*"
    assert CUT.n_moe_layers == 4 and PUBLISHED.n_moe_layers == 23


def test_the_runs_of_units():
    """15 runs for the published depth (a lone state-space layer, seven
    stretches of units of two layers with an attention layer behind each
    but the last, a lone expert layer), 2 for the cut."""
    runs = nemotron_h._runs(PUBLISHED)
    assert len(runs) == 15
    assert [(kind, n) for _, kind, n in runs] == [
        ("mamba", 1), ("experts_mamba", 2), ("attention", 1),
        ("experts_mamba", 3), ("attention", 1), ("experts_mamba", 3),
        ("attention", 1), ("experts_mamba", 3), ("attention", 1),
        ("experts_mamba", 3), ("attention", 1), ("experts_mamba", 4),
        ("attention", 1), ("experts_mamba", 4), ("experts", 1)]
    assert nemotron_h._runs(CUT) == (
        ("run00_experts_mamba", "experts_mamba", 4),
        ("run01_attention", "attention", 1))
    assert [kind for _, kind, _ in nemotron_h._runs(CFG)] == [
        "mamba", "experts_mamba", "attention", "experts"]
    # A stretch that starts with a state-space layer and is even: M, E.
    assert nemotron_h.units(("mamba", "experts") * 2 + ("attention",)) == (
        "mamba_experts", "mamba_experts", "attention")
    # The reference finds the same stacks by its own walk.
    for cfg in (PUBLISHED, CUT, CFG):
        walked = list(reference._walk(cfg.layers))
        assert [kind for kind, *_ in walked] == list(cfg.layers)
        assert sorted({stack for _, stack, _, _ in walked}) == [
            run for run, _, _ in nemotron_h._runs(cfg)]


def test_the_parameter_counts():
    def count(cfg):
        shapes = jax.eval_shape(partial(nemotron_h.init, cfg),
                                jax.random.PRNGKey(0))
        return sum(a.size for a in jax.tree.leaves(shapes))
    assert count(PUBLISHED) == 31_577_940_288      # the row's 31.6B
    assert count(CUT) == 1_712_918_016             # 1.713 B held
    shapes = jax.eval_shape(partial(nemotron_h.init, CUT),
                            jax.random.PRNGKey(0))
    unit = shapes["run00_experts_mamba"]
    assert unit["a_w_up"].shape == (4, 32, 2688, 1856)
    assert unit["a_router"].shape == (4, 2688, 128)
    assert unit["a_shared_w_down"].shape == (4, 3712, 2688)
    assert unit["b_w_in"].shape == (4, 2688, 4096 + 6144 + 64)
    assert unit["b_norm_scale"].shape == (4, 4096)
    assert shapes["run01_attention"]["wk"].shape == (1, 2688, 2, 128)
    assert "a_w_gate" not in unit and "a_shared_w_gate" not in unit


@pytest.mark.parametrize("remat", [False, True])
def test_the_unit_scan_is_the_layers_one_by_one(remat):
    cfg = replace(CFG, remat=remat)
    tokens, _ = batch(cfg, SEQ)
    with jax.default_matmul_precision("highest"):
        got, aux = jax.jit(partial(nemotron_h.hidden_states, cfg=cfg))(
            drawn(NEMOTRON, cfg), tokens=tokens)
    np.testing.assert_allclose(got, _one_by_one(), atol=1e-4)
    assert aux["picked"].shape == (3, 2, SEQ, 2)
    assert aux["relu2_zero_share"].shape == (3,)


@functools.lru_cache(maxsize=None)
def _one_by_one():
    """One program for both scans: a layer does not read ``cfg.remat``."""
    tokens, _ = batch(CFG, SEQ)

    def one_by_one(params):
        x = lm.embed(params["wte"], tokens, CFG.dtype)
        for kind, stack, index, prefix in reference._walk(CFG.layers):
            layer = {name[len(prefix):]: a[index]
                     for name, a in params[stack].items()
                     if name.startswith(prefix)}
            x, _ = nemotron_h._layer(CFG, kind, x, layer)
        return lm.rmsnorm(x, params["lnf_scale"], CFG.layer_norm_epsilon)

    with jax.default_matmul_precision("highest"):
        return jax.jit(one_by_one)(drawn(NEMOTRON, CFG))


# -- the grouped recurrence and the grouped norm ---------------------------

def test_the_recurrence_at_two_groups_is_transformers_up_to_the_norm():
    """``_scanned`` (the projection, the conv with its bias, the grouped
    scan by the kernels, interpreted) against ``transformers``' Mamba-2
    layer at ``n_groups`` 2, its norm and out-projection taken out."""
    torch = pytest.importorskip("torch")
    pytest.importorskip("transformers")
    try:
        from transformers import GraniteMoeHybridConfig
        from transformers.models.granitemoehybrid.modeling_granitemoehybrid \
            import GraniteMoeHybridMambaLayer
    except ImportError:
        pytest.skip("this transformers has no granitemoehybrid")
    cfg, d = CFG, CFG.hidden_size
    layer = jax.tree.map(lambda a: a[0], {
        name[2:]: a for name, a in
        drawn(NEMOTRON, cfg)["run01_experts_mamba"].items()
        if name.startswith("b_")})
    hf = GraniteMoeHybridMambaLayer(GraniteMoeHybridConfig(
        hidden_size=d, mamba_n_heads=cfg.mamba_num_heads,
        mamba_d_head=cfg.mamba_head_dim, mamba_d_state=cfg.ssm_state_size,
        mamba_n_groups=cfg.n_groups, mamba_d_conv=cfg.conv_kernel,
        mamba_expand=cfg.mamba_d_inner // d, mamba_chunk_size=64,
        mamba_conv_bias=True, mamba_proj_bias=False,
        rms_norm_eps=cfg.layer_norm_epsilon), layer_idx=0).eval()

    def t(a):
        return torch.tensor(np.asarray(a, np.float32))

    hf.load_state_dict({
        "in_proj.weight": t(layer["w_in"].T),
        "conv1d.weight": t(layer["conv_w"].T[:, None, :]),
        "conv1d.bias": t(layer["conv_b"]), "dt_bias": t(layer["dt_bias"]),
        "A_log": t(layer["A_log"]), "D": t(layer["D"]),
        "norm.weight": t(layer["norm_scale"]),
        "out_proj.weight": t(layer["w_out"].T)})
    hf.norm.forward = lambda y, gate=None: y
    hf.out_proj = torch.nn.Identity()
    x = jax.random.normal(jax.random.PRNGKey(4), (2, 160 + 96, d))
    with torch.no_grad():
        want = hf.torch_forward(t(x)).numpy()
    with jax.default_matmul_precision("highest"):
        got, _ = jax.jit(partial(nemotron_h._scanned, cfg))(x, layer)
    np.testing.assert_allclose(got, want,
                               atol=1e-4 * float(np.abs(want).max()))


def test_the_grouped_norm_is_ten_lines_of_numpy():
    """Gate first, mean of squares over each group of 128 of 256 channels,
    a scale a channel: ``lm.gated_norm`` with ``group`` (the kernels,
    interpreted: 128 rows) against numpy in float64."""
    ks = jax.random.split(jax.random.PRNGKey(2), 3)
    y = jax.random.normal(ks[0], (2, 128, 256))
    z = jax.random.normal(ks[1], (2, 128, 256 + 64))
    scale = 1.0 + 0.2 * jax.random.normal(ks[2], (256,))
    got = lm.gated_norm(y, z, scale, 1e-5, gate_first=True,
                        activation="silu", group=128)
    y64, z64 = np.asarray(y, np.float64), np.asarray(z[..., :256], np.float64)
    gated = (y64 * z64 / (1.0 + np.exp(-z64))).reshape(2, 128, 2, 128)
    want = (gated / np.sqrt((gated ** 2).mean(-1, keepdims=True) + 1e-5)
            ).reshape(2, 128, 256) * np.asarray(scale, np.float64)
    np.testing.assert_allclose(got, want, atol=1e-5)
    whole = lm.gated_norm(y, z, scale, 1e-5, gate_first=True,
                          activation="silu")
    assert float(jnp.abs(whole - got).max()) > 0.05


# -- the squared-ReLU expert: the whole layer, a share, the shares --------

def _expert_layer(experts=8, tokens=256, d=128, f=192, seed=1):
    ks = jax.random.split(jax.random.PRNGKey(seed), 8)
    normal = jax.random.normal
    return {"ln_scale": jnp.ones((d,)),
            "router": 0.3 * normal(ks[0], (d, experts)),
            "router_bias": 0.1 * normal(ks[1], (experts,)),
            "w_up": 0.1 * normal(ks[2], (experts, d, f)),
            "w_down": 0.1 * normal(ks[3], (experts, f, d)),
            "shared_w_up": 0.1 * normal(ks[4], (d, 2 * f)),
            "shared_w_down": 0.1 * normal(ks[5], (2 * f, d))}, \
        normal(ks[6], (1, tokens, d))


def _loop(x, w, first, count, top_k=2, scaling=2.5):
    """sum_i w_i W_down_i relu(x W_up_i)^2 over the picked experts among
    ``first`` to ``first + count``, an expert after the other."""
    scores = jax.nn.sigmoid(x @ w["router"])
    _, picked = jax.lax.top_k(scores + w["router_bias"], top_k)
    weights = jnp.take_along_axis(scores, picked, -1)
    weights = weights / weights.sum(-1, keepdims=True) * scaling
    y = jnp.zeros_like(x)
    for e in range(first, first + count):
        mine = (weights * (picked == e)).sum(-1)[..., None]
        y = y + mine * (jnp.square(jax.nn.relu(x @ w["w_up"][e]))
                        @ w["w_down"][e])
    return y


@pytest.mark.parametrize("held", [None, (0, 4), (2, 3)],
                         ids=["whole", "half", "three"])
def test_the_squared_relu_expert_is_a_loop_over_experts(held):
    """Forward and every gradient, in the whole-layer path and in the share,
    at experts of 192 (the grouped product's kernels, interpreted, with an
    irregular last tile: 256 tokens x 2 rows of whole tiles)."""
    w, x = _expert_layer()
    first, count = held or (0, 8)
    g = jax.random.normal(jax.random.PRNGKey(9), x.shape)

    def program(x, w_up, w_down, router):
        y, aux = moe.routed_experts(
            x[0], router, w["router_bias"], None, w_up[first:first + count],
            w_down[first:first + count], top_k=2, scaling=2.5, held=held,
            activation="relu2")
        return (y * g[0]).sum(), aux

    def loop(x, w_up, w_down, router):
        return (_loop(x, dict(w, w_up=w_up, w_down=w_down, router=router),
                      first, count) * g).sum()

    args = (x, w["w_up"], w["w_down"], w["router"])
    with jax.default_matmul_precision("highest"):
        (got, aux), got_grads = jax.jit(jax.value_and_grad(
            program, argnums=(0, 1, 2, 3), has_aux=True))(*args)
        want, want_grads = jax.jit(jax.value_and_grad(
            loop, argnums=(0, 1, 2, 3)))(*args)
    np.testing.assert_allclose(got, want, rtol=1e-5)
    for a, b in zip(got_grads, want_grads):
        assert float(jnp.linalg.norm((a - b).ravel())) \
            < 1e-5 * float(jnp.linalg.norm(b.ravel()))
    # About half of a random pre-activation is negative.
    assert 0.4 < float(aux["relu2_zero_share"]) < 0.6
    assert moe._tile_n(192) == 128 and moe._tile_n(1856) == 1024
    assert moe._tile_n(2688) == 896 and moe._tile_n(1408) == 1408


def test_the_four_shares_add_up_to_the_uncut_layer():
    """The guide's share test at the tiny count: the parts that
    ``experts_held`` = (0, 2), (2, 2), (4, 2), (6, 2) give of a layer of 8
    experts, with the shared expert counted once, add up to the uncut
    reference's layer, and every share computes exactly the assignments the
    router gave its experts."""
    w, h = _expert_layer()
    kw = dict(top_k=2, norm_topk_prob=True, scaling=2.5, eps=1e-5)
    x = lm.rmsnorm(h, w["ln_scale"], 1e-5)
    with jax.default_matmul_precision("highest"):
        want, picked = reference._experts_layer(h, w, first_expert=0, **kw)
        total, computed = h, 0
        for first in range(0, 8, 2):
            share = dict(w, w_up=w["w_up"][first:first + 2],
                         w_down=w["w_down"][first:first + 2])
            routed, shared, aux = lm.expert_ffn(
                x, share, top_k=2, scaling=2.5, normalize=True,
                held=(first, 2), activation="relu2")
            mine = ((picked >= first) & (picked < first + 2)).sum()
            assert int(aux["group_sizes"].sum()) == int(mine) \
                == int(aux["asked"])
            # The shared expert is every chip's alike: counted once.
            total = total + routed + (shared if first == 0 else 0.0)
            computed += int(mine)
            # The reference given the same share gives the same part.
            np.testing.assert_allclose(
                h + routed + shared, reference._experts_layer(
                    h, share, first_expert=first, **kw)[0], atol=5e-5)
    assert computed == h.shape[1] * 2
    np.testing.assert_allclose(total, want, atol=1e-4)


# -- what a step's gauge reads ----------------------------------------------

def test_a_step_sets_the_relu2_zero_share():
    found = trained(NEMOTRON, 1)
    for metrics in found["metrics"]:
        assert 0.3 < metrics["moe_relu2_zero_share"] < 0.7
    # Fed one call late at most: the last step's value, or the one before.
    gauge = found["gauges"]["ray_tpu_train_moe_relu2_zero_share"]
    assert any(gauge == pytest.approx(metrics["moe_relu2_zero_share"])
               for metrics in found["metrics"][-2:])
