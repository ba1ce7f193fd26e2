"""models/kimi_linear.py (Kimi Delta Attention layers through ``ops/kda.py``,
a latent-attention layer without positions from ``models/deepseek.py``, a
chip's share of the experts) against a copy of the benchmark's plain
reference, whose delta rule is the literal recurrence, through
``family_cases.py``; the eight shares of an expert layer adding up to the
uncut layer; ``mla_use_nope`` off being ``models/deepseek.py`` as it was; the
decay's gauge; the cut configuration's four runs.
"""

import math
from dataclasses import replace
from functools import partial

import jax
import jax.numpy as jnp
import pytest

import family_cases
import reference_kimi_linear as reference
from family_cases import batch, drawn, in_every_run
from ray_tpu.models import deepseek, kimi_linear, lm
from ray_tpu.ops import kda

CFG = kimi_linear.config("kimi-linear-tiny")
SEQ = 128   # one chunk of the delta rule's kernels; FLASH_SEQ is two
# The flash kernels (interpreted), remat, the chunked loss, and a share of
# the experts: 3 of 8, from the third.
FLASH = replace(CFG, attn_impl="flash", attn_blk_q=128, attn_blk_k=128,
                remat=True, loss_chunk=128, experts_held=(2, 3))
FLASH_SEQ = 256


def published(cfg):
    linear = cfg.linear_attn_config
    out = {"num_hidden_layers": cfg.num_hidden_layers,
           "first_k_dense_replace": cfg.first_k_dense_replace,
           "linear_attn_config": {
               "kda_layers": list(linear.kda_layers),
               "full_attn_layers": list(linear.full_attn_layers)},
           "qk_nope_head_dim": cfg.qk_nope_head_dim,
           "kv_lora_rank": cfg.kv_lora_rank,
           "num_experts_per_token": cfg.num_experts_per_token,
           "routed_scaling_factor": cfg.routed_scaling_factor,
           "moe_renormalize": cfg.moe_renormalize,
           "rms_norm_eps": cfg.rms_norm_eps}
    if cfg.experts_held is not None:
        first, count = cfg.experts_held
        out["deployment"] = {"experts_held": {
            "first": first, "count": count, "of": cfg.num_experts}}
    return out


def moved(name, leaf, key):
    """Every vector off its one or zero (the correction bias too: routing
    uneven). The decay's vectors are drawn by the init."""
    if name.endswith("_scale']"):
        return leaf + 0.2 * jax.random.normal(key, leaf.shape)
    if "router_bias" in name:
        return 0.1 * jax.random.normal(key, leaf.shape)
    if "_mla']['wq']" in name or "w_kv_a" in name:
        # Scores that spread: at 0.02 a latent layer's softmax is flat
        # and a rotation of q and k would move nothing.
        return 8.0 * leaf
    return leaf


def _kda_with(monkeypatch, **changed):
    """``lm.delta_rule`` with some of (q, k, v, a, beta) changed on the way
    in."""
    plain = lm.delta_rule

    def patched(q, k, v, a, beta):
        args = dict(q=q, k=k, v=v, a=a, beta=beta)
        args.update({name: fn(args[name]) for name, fn in changed.items()})
        return plain(**args)

    monkeypatch.setattr(lm, "delta_rule", patched)


def drop(dropped, params, cfg, monkeypatch):
    if dropped == "delta_term":
        # S += beta k v^T alone: an additive state, as ops/ssd.py's.
        def additive(q, k, v, a, beta):
            cum = jnp.cumsum(a, axis=1)
            decay = jnp.exp(cum[:, :, None] - cum[:, None, :])  # t, s
            scores = jnp.einsum("bthk,bshk,btshk->bhts", q, k, decay)
            causal = jnp.tril(jnp.ones(scores.shape[-2:], bool))
            return jnp.einsum("bhts,bsh,bshv->bthv",
                              jnp.where(causal, scores, 0.0), beta, v)
        monkeypatch.setattr(lm, "delta_rule", additive)
    elif dropped == "decay":
        _kda_with(monkeypatch, a=jnp.zeros_like)
    elif dropped == "beta":
        _kda_with(monkeypatch, beta=jnp.ones_like)
    elif dropped == "qk_norm":
        # The rule normalises inside (ops/kda.py ``_unit_rows``, which the
        # chunk's function calls): q and k three times their length there.
        plain = kda._unit_rows
        monkeypatch.setattr(kda, "_unit_rows", lambda y, scale=1.0:
                            plain(y, 3.0 * scale))
    elif dropped == "conv":
        monkeypatch.setattr(lm, "conv_silu", lambda x, w, b=None:
                            jax.nn.silu(x.astype(jnp.float32)).astype(x.dtype))
    elif dropped == "output_gate":
        # sigmoid(0): a constant, where the gate differs a channel.
        params = in_every_run(params, lambda w: dict(
            w, w_gb=jnp.zeros_like(w["w_gb"])) if "w_gb" in w else w)
    elif dropped == "rope_on_mla":
        cfg = replace(cfg, mla_use_nope=False)
    elif dropped == "routed_scaling_factor":
        cfg = replace(cfg, routed_scaling_factor=1.0)
    elif dropped == "shared_expert":
        params = in_every_run(params, lambda w: dict(
            w, shared_w_down=jnp.zeros_like(w["shared_w_down"]))
            if "router" in w else w)
    return params, cfg


KIMI = family_cases.Family(
    module=kimi_linear, reference=reference, cfg=CFG, seq=SEQ, flash=FLASH,
    flash_seq=FLASH_SEQ, published=published, moved=moved,
    extras=("picked",), drop=drop, dropped=(
        "delta_term", "decay", "beta", "qk_norm", "conv", "output_gate",
        "rope_on_mla", "routed_scaling_factor", "shared_expert"),
    top_k=CFG.num_experts_per_token, accum_steps=(1,), scan_atol=1e-4,
    wrong=({"experts_held": (6, 4)}, {"experts_held": (0, 0)},
           {"num_hidden_layers": 6},
           {"linear_attn_config": {"kda_layers": [1, 2],
                                   "full_attn_layers": [2]}}))
globals().update(family_cases.cases(KIMI))


def test_the_tiny_stack_has_all_four_kinds_of_layer(both):
    assert [kind for _, kind, _ in lm.runs(CFG.layers)] == [
        "dense_kda", "dense_mla", "moe_kda", "moe_mla"]
    # The delta rule's kernels run (interpreted): heads of 128, whole chunks.
    from ray_tpu.parallel.collectives import kernel_census
    tokens, _ = batch(CFG, SEQ)
    census = kernel_census(jax.make_jaxpr(partial(
        kimi_linear.forward, cfg=CFG))(drawn(KIMI, CFG), tokens=tokens))
    assert census["kda_fwd"] == 2   # a call a run of KDA layers
    # A delta-rule layer's running log-decay is negative, a latent layer's 0.
    assert [float(floor) < 0 for floor in both["aux"]["decay_floor"]] == [
        kind.endswith("kda") for kind in CFG.layers]
    assert both["aux"]["group_sizes"].shape == (CFG.n_moe_layers,
                                                CFG.num_experts)
    shapes = jax.eval_shape(partial(kimi_linear.init, FLASH),
                            jax.random.PRNGKey(0))
    assert shapes["run02_moe_kda"]["w_up"].shape[1] == 3


@pytest.mark.parametrize("shares", [8, 2, 1])
def test_the_shares_add_up_to_the_uncut_layer(shares):
    """Eight shares of 2 experts each, as the cell's eight chips; two; one;
    at 8 experts a token."""
    family_cases.shares_add_up(reference, 16, shares, top_k=8, scale=2.446)


# -- models/deepseek.py, shared -------------------------------------------

def test_mla_use_nope_off_is_deepseek_as_it_was():
    """The flag's default leaves ``models/deepseek.py`` the rope it had, bit
    for bit: the latent layer written out as it stood before the flag."""
    cfg = deepseek.config("deepseek-tiny")
    assert cfg.mla_use_nope is False
    params = deepseek.init(cfg, jax.random.PRNGKey(0))
    layer = jax.tree.map(lambda a: a[0], params["dense_layers"])
    # Scores that spread: at 0.02 the softmax is flat and rope moves nothing.
    layer = dict(layer, wq=8.0 * layer["wq"], w_kv_a=8.0 * layer["w_kv_a"])
    x = jax.random.normal(jax.random.PRNGKey(1), (2, 32, cfg.hidden_size))
    positions = lm.positions_of(x[..., 0])

    def as_it_was(x):
        nope, rank = cfg.qk_nope_head_dim, cfg.kv_lora_rank
        q = jnp.einsum("bsd,dhk->bshk", x, layer["wq"])
        kv_a = jnp.einsum("bsd,dr->bsr", x, layer["w_kv_a"])
        latent = lm.rmsnorm(kv_a[..., :rank], layer["kv_norm_scale"],
                            cfg.rms_norm_eps)
        kv = jnp.einsum("bsr,rhk->bshk", latent, layer["w_kv_b"])
        q_rope = lm.rope_interleaved(q[..., nope:], positions, cfg.rope_theta)
        k_rope = lm.rope_interleaved(kv_a[..., None, rank:], positions,
                                     cfg.rope_theta)
        q = jnp.concatenate([q[..., :nope], q_rope], -1)
        k = jnp.concatenate(
            [kv[..., :nope], jnp.broadcast_to(k_rope, q_rope.shape)], -1)
        attn = lm.attention(q, k, kv[..., nope:], cfg)
        return jnp.einsum("bshk,hkd->bsd", attn, layer["wo"])

    got = jax.jit(lambda x: lm.mla(cfg, x, layer, positions))(x)
    assert (got == jax.jit(as_it_was)(x)).all()
    without = lm.mla(replace(cfg, mla_use_nope=True), x, layer, positions)
    assert float(jnp.abs(without - got).max()) > 1e-3


# -- the train step's gauge ---------------------------------------------------

def test_the_step_feeds_the_decays_gauge():
    """The gauge holds the most negative running log-decay of the step: 128
    steps of at most -1.6, and of at least -0.001 in some channel."""
    found = family_cases.trained(KIMI, 1)
    for metrics in found["metrics"]:
        assert -205.0 < metrics["kda_decay_floor"] < -1.0
    assert -205.0 < found["gauges"]["ray_tpu_train_kda_decay_floor"] < -1.0


# -- the cut configuration ------------------------------------------------

CUT = replace(kimi_linear.config("kimi-linear-48b-a3b"), num_hidden_layers=5,
              experts_held=(0, 32), vocab_size=20480)


def test_the_cut_configuration_is_four_runs():
    """The benchmark's cut: published layers 1-5, the dense layer a KDA
    layer, then one period of expert layers, 3 KDA : 1 latent."""
    assert lm.runs(CUT.layers) == (
        ("run00_dense_kda", "dense_kda", 1), ("run01_moe_kda", "moe_kda", 2),
        ("run02_moe_mla", "moe_mla", 1), ("run03_moe_kda", "moe_kda", 1))
    shapes = jax.eval_shape(partial(kimi_linear.init, CUT),
                            jax.random.PRNGKey(0))
    assert shapes["run01_moe_kda"]["w_gate"].shape == (2, 32, 2304, 1024)
    assert shapes["run01_moe_kda"]["router"].shape == (2, 2304, 256)
    assert shapes["run02_moe_mla"]["wq"].shape == (1, 2304, 32, 192)
    assert shapes["lm_head"].shape == (2304, 20480)
    held = sum(math.prod(a.shape) for a in jax.tree.leaves(shapes))
    assert 1.281e9 < held < 1.284e9
    whole = kimi_linear.config("kimi-linear-48b-a3b")
    assert whole.layers.count("moe_kda") == 19 and \
        whole.layers.count("moe_mla") == 7 and whole.layers[0] == "dense_kda"


def test_the_decay_starts_as_published():
    """``A_log`` = log U(1, 16) a head and ``dt_bias`` the inverse softplus
    of a log-uniform (0.001, 0.1) a channel: log-decays from -0.001 to -1.6
    a step where the projection adds nothing."""
    stack = drawn(KIMI, CFG)["run02_moe_kda"]
    rate = jnp.exp(stack["A_log"])
    dt = jax.nn.softplus(stack["dt_bias"])
    assert 1.0 <= float(rate.min()) and float(rate.max()) <= 16.0
    assert 0.000999 < float(dt.min()) and float(dt.max()) < 0.1001
    assert stack["dt_bias"].shape[1:] == (2, 128)


def test_a_published_config_reads_straight_in():
    """``linear_attn_config`` as ``config.json`` has it: a dict of lists."""
    cfg = kimi_linear.KimiLinearConfig(linear_attn_config={
        "kda_layers": [l for l in range(1, 28) if l % 4 and l != 27],
        "full_attn_layers": [4, 8, 12, 16, 20, 24, 27], "num_heads": 32,
        "head_dim": 128, "short_conv_kernel_size": 4})
    assert cfg == kimi_linear.config("kimi-linear-48b-a3b")
    assert hash(cfg) == hash(kimi_linear.config("kimi-linear-48b-a3b"))
