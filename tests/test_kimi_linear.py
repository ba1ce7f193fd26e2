"""models/kimi_linear.py (Kimi Delta Attention layers through ``ops/kda.py``,
a latent-attention layer without positions from ``models/deepseek.py``, a
chip's share of the experts) against a copy of the benchmark's plain
reference, whose delta rule is the literal recurrence; the eight shares of an
expert layer adding up to the uncut layer; the sliced head; ``mla_use_nope``
off being ``models/deepseek.py`` as it was; the counters and the decay's
gauge; ``lm.scan_blocks`` over the kinds of layer.

Everything runs on the CPU at tiny widths in float32 under the highest
matmul precision, the kernels interpreted, where both sides compute the same
sums in another order: tolerances of 1e-4 (relative, on gradients: of a
leaf's norm) leave room for float32 reassociation across a few hundred terms
and nothing else.
"""

import math
import zlib
from dataclasses import replace
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import reference_kimi_linear as reference
from ray_tpu.models import deepseek, kimi_linear, lm
from ray_tpu.ops import kda
from ray_tpu.ops.moe import routed_experts
from ray_tpu.parallel import MeshConfig, build_mesh
from ray_tpu.parallel.train_step import init_train_state, make_train_step
from ray_tpu.util import metrics as metrics_mod

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


def drawn(cfg, seed=0):
    """The init with every vector moved off its one or zero (the correction
    bias too: routing uneven). The decay's vectors are drawn by the init."""
    params = kimi_linear.init(cfg, jax.random.PRNGKey(seed))
    key = jax.random.PRNGKey(seed + 1)

    def moved(path, leaf):
        name = jax.tree_util.keystr(path)
        k = jax.random.fold_in(key, zlib.crc32(name.encode()) % (2 ** 31))
        if name.endswith("_scale']"):
            return leaf + 0.2 * jax.random.normal(k, leaf.shape)
        if "router_bias" in name:
            return 0.1 * jax.random.normal(k, leaf.shape)
        if "_mla']['wq']" in name or "w_kv_a" in name:
            # Scores that spread: at 0.02 a latent layer's softmax is flat
            # and a rotation of q and k would move nothing.
            return 8.0 * leaf
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
        got_logits, aux = jax.jit(partial(
            kimi_linear.forward_with_aux, cfg=cfg))(params, tokens=tokens)
        got_loss, got_grads = jax.jit(jax.value_and_grad(
            lambda p: kimi_linear.loss_fn(p, cfg, tokens, targets)[0]))(
                params)
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
        "dense_kda", "dense_mla", "moe_kda", "moe_mla"]
    # The delta rule's kernels run (interpreted): heads of 128, whole chunks.
    from ray_tpu.parallel.collectives import kernel_census
    tokens, _ = batch(CFG)
    census = kernel_census(jax.make_jaxpr(partial(
        kimi_linear.forward, cfg=CFG))(drawn(CFG), tokens=tokens))
    assert census["kda_fwd"] == 2   # a call a run of KDA layers


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
                    jax.eval_shape(partial(kimi_linear.init, CFG),
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


def _kda_with(monkeypatch, **changed):
    """``lm.delta_rule`` with some of (q, k, v, a, beta) changed on the way
    in."""
    plain = lm.delta_rule

    def patched(q, k, v, a, beta):
        args = dict(q=q, k=k, v=v, a=a, beta=beta)
        args.update({name: fn(args[name]) for name, fn in changed.items()})
        return plain(**args)

    monkeypatch.setattr(lm, "delta_rule", patched)


@pytest.mark.parametrize("dropped", [
    "delta_term", "decay", "beta", "qk_norm", "conv", "output_gate",
    "rope_on_mla", "routed_scaling_factor", "shared_expert"])
def test_a_dropped_term_shows(both, dropped, monkeypatch):
    """Each of the terms a fast path could lose moves the logits by far
    more than the agreement above allows."""
    params, cfg = drawn(CFG), CFG
    tokens, _ = batch(CFG)
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
        params = _in_every_run(params, CFG, lambda w: dict(
            w, w_gb=jnp.zeros_like(w["w_gb"])) if "w_gb" in w else w)
    elif dropped == "rope_on_mla":
        cfg = replace(CFG, mla_use_nope=False)
    elif dropped == "routed_scaling_factor":
        cfg = replace(CFG, routed_scaling_factor=1.0)
    elif dropped == "shared_expert":
        params = _in_every_run(params, CFG, lambda w: dict(
            w, shared_w_down=jnp.zeros_like(w["shared_w_down"]))
            if "router" in w else w)
    with jax.default_matmul_precision("highest"):
        got = kimi_linear.forward(params, cfg, tokens)
    _, want = both["logits"]
    assert float(jnp.abs(got - want).max()) > 0.05 * both["rms"]


# -- the share ------------------------------------------------------------

def _expert_layer(experts=16, tokens=96, d=32, f=16, seed=0):
    ks = jax.random.split(jax.random.PRNGKey(seed), 9)
    normal = jax.random.normal
    w = {"ln2_scale": jnp.ones((d,)),
         "router": normal(ks[0], (d, experts)) / math.sqrt(d),
         "router_bias": 0.2 * normal(ks[1], (experts,)),
         "w_gate": normal(ks[2], (experts, d, f)) / math.sqrt(d),
         "w_up": normal(ks[3], (experts, d, f)) / math.sqrt(d),
         "w_down": normal(ks[4], (experts, f, d)) / math.sqrt(f),
         "shared_w_gate": normal(ks[5], (d, f)) / math.sqrt(d),
         "shared_w_up": normal(ks[6], (d, f)) / math.sqrt(d),
         "shared_w_down": normal(ks[7], (f, d)) / math.sqrt(f)}
    return w, normal(ks[8], (1, tokens, d))


@pytest.mark.parametrize("shares", [8, 2, 1])
def test_the_shares_add_up_to_the_uncut_layer(shares):
    """The routed parts that the shares of an expert layer give (eight of 2
    experts each, as the cell's eight chips; two; one), plus the shared
    expert once, are the uncut layer of the reference at 8 experts a token;
    and every share computes exactly the assignments the router gave its
    experts."""
    w, h = _expert_layer()
    top_k, scale, count = 8, 2.446, 16 // shares
    kw = dict(top_k=top_k, scaling=scale, renormalize=True, eps=0.0,
              first_expert=0)
    x = reference._rmsnorm(h, w["ln2_scale"], 0.0)
    with jax.default_matmul_precision("highest"):
        want, picked = reference._ffn(h, w, **kw)
        total, computed = h, 0
        for first in range(0, 16, count):
            share = dict(w, **{name: w[name][first:first + count]
                               for name in ("w_gate", "w_up", "w_down")})
            routed, shared, aux = lm.expert_ffn(
                x, share, top_k=top_k, scaling=scale, normalize=True,
                held=(first, count))
            mine = ((picked >= first) & (picked < first + count)).sum()
            assert int(aux["group_sizes"].sum()) == int(mine) \
                == int(aux.get("asked", mine))
            # The shared expert is every chip's alike: counted once.
            total = total + routed + (shared if first == 0 else 0.0)
            computed += int(mine)
            ref_part = reference._ffn(h, share, **dict(
                kw, first_expert=first))[0]
            np.testing.assert_allclose(h + routed + shared, ref_part,
                                       atol=5e-5)
    assert computed == h.shape[1] * top_k
    np.testing.assert_allclose(total, want, atol=1e-4)


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
        got, metrics = kimi_linear.loss_fn(
            sliced, replace(CFG, vocab_size=held), tokens, targets)
        logits = kimi_linear.forward(params, CFG, tokens)[..., :held]
    logp = jax.nn.log_softmax(logits.astype(jnp.float32), axis=-1)
    want = -jnp.take_along_axis(logp, targets[..., None], -1).mean()
    np.testing.assert_allclose(got, want, rtol=1e-6)
    assert abs(float(got) - math.log(held)) < 1.0
    assert float(metrics["moe_routed"]) == tokens.size * 2 * 3


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


# -- the train step, its counters and the gauge ----------------------------

def _one_chip():
    return build_mesh(MeshConfig(dp=1, fsdp=1, tp=1),
                      devices=jax.devices()[:1])


def _series(name):
    for entry in metrics_mod.snapshot():
        if entry["name"] == name:
            return sum(entry["series"].values())
    return 0.0


COUNTERS = ("ray_tpu_train_moe_assignments_total",
            "ray_tpu_train_moe_tokens_total",
            "ray_tpu_train_moe_routed_total")


def test_trains_and_feeds_the_counters_and_the_gauge():
    """``make_train_step`` finds the model from ``type(cfg)``: the loss
    falls on a repeated batch (both kernel pairs, remat, the chunked loss, a
    share of the experts), the counters say what the share did, and the
    gauge holds the most negative running log-decay of the step."""
    import optax
    from ray_tpu.parallel.sharding import ShardingRules
    mesh = _one_chip()
    rules, optimizer = ShardingRules(), optax.adam(3e-3)
    state = init_train_state(FLASH, mesh, rules, optimizer, seed=0)
    step = make_train_step(FLASH, mesh, rules, optimizer)
    tokens, targets = batch(FLASH, rows=2, seq=FLASH_SEQ)
    routed = tokens.size * FLASH.num_experts_per_token * FLASH.n_moe_layers
    before = [_series(name) for name in COUNTERS]
    losses = []
    for _ in range(3):
        state, metrics = step(state, {"tokens": tokens, "targets": targets})
        losses.append(float(metrics["loss"]))
        assert float(metrics["moe_routed"]) == routed
        assert float(metrics["moe_assignments"]) == \
            float(metrics["moe_tokens"])
        assert 0 < float(metrics["moe_tokens"]) < routed
        # 128 steps of at most -1.6, and of at least -0.001 in some channel.
        assert -205.0 < float(metrics["kda_decay_floor"]) < -1.0
    assert all(np.isfinite(losses)) and losses[-1] < losses[0]
    assigned, asked, all_routed = (
        _series(name) - was for name, was in zip(COUNTERS, before))
    assert assigned == asked and all_routed in (2 * routed, 3 * routed)
    assert 0.2 < asked / all_routed < 0.6
    assert -205.0 < _series("ray_tpu_train_kda_decay_floor") < -1.0


def test_expert_parallel_mesh_is_refused():
    mesh = build_mesh(MeshConfig(dp=1, fsdp=1, tp=1, ep=2),
                      devices=jax.devices()[:2])
    step = make_train_step(CFG, mesh)
    state = init_train_state(CFG, mesh, seed=0)
    tokens, targets = batch(CFG)
    with pytest.raises(NotImplementedError, match="expert parallelism"):
        step(state, {"tokens": tokens, "targets": targets})


# -- the layer scan over the runs -----------------------------------------

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


@pytest.mark.parametrize("remat", [False, True])
def test_scan_blocks_over_the_runs(remat):
    """The runs scanned, one stack a run, are the layers applied one by one
    in order: hidden states and the layers' auxiliary outputs."""
    cfg = replace(CFG, remat=remat)
    params = drawn(cfg)
    tokens, _ = batch(cfg)
    with jax.default_matmul_precision("highest"):
        got, aux = kimi_linear.hidden_states(params, cfg, tokens)
        x = lm.embed(params["wte"], tokens, cfg.dtype)
        picked, floors = [], []
        for run, kind, depth in lm.runs(cfg.layers):
            for j in range(depth):
                x, one = kimi_linear._block(cfg, kind, x, jax.tree.map(
                    lambda a: a[j], params[run]), lm.positions_of(tokens))
                floors.append(one["decay_floor"])
                if "picked" in one:
                    picked.append(one["picked"])
    want = lm.rmsnorm(x, params["lnf_scale"], cfg.rms_norm_eps)
    np.testing.assert_allclose(got, want, atol=1e-4)
    assert (aux["picked"] == jnp.stack(picked)).all()
    np.testing.assert_allclose(aux["decay_floor"], jnp.stack(floors),
                               rtol=1e-6)
    assert [float(f) < 0 for f in floors] == [
        kind.endswith("kda") for kind in cfg.layers]
    assert aux["group_sizes"].shape == (cfg.n_moe_layers, cfg.num_experts)


def test_param_specs_match_init():
    from ray_tpu.parallel.sharding import ShardingRules
    for cfg in (CFG, FLASH):
        params = jax.eval_shape(partial(kimi_linear.init, cfg),
                                jax.random.PRNGKey(0))
        specs = kimi_linear.param_specs(cfg, ShardingRules())
        assert jax.tree.structure(params) == jax.tree.structure(
            specs, is_leaf=lambda s: isinstance(
                s, jax.sharding.PartitionSpec))
    assert params["run02_moe_kda"]["w_up"].shape[1] == 3


def test_the_decay_starts_as_published():
    """``A_log`` = log U(1, 16) a head and ``dt_bias`` the inverse softplus
    of a log-uniform (0.001, 0.1) a channel: log-decays from -0.001 to -1.6
    a step where the projection adds nothing."""
    stack = drawn(CFG)["run02_moe_kda"]
    rate = jnp.exp(stack["A_log"])
    dt = jax.nn.softplus(stack["dt_bias"])
    assert 1.0 <= float(rate.min()) and float(rate.max()) <= 16.0
    assert 0.000999 < float(dt.min()) and float(dt.max()) < 0.1001
    assert stack["dt_bias"].shape[1:] == (2, 128)


@pytest.mark.parametrize("wrong", [
    {"experts_held": (6, 4)}, {"experts_held": (0, 0)},
    {"num_hidden_layers": 6},
    {"linear_attn_config": {"kda_layers": [1, 2], "full_attn_layers": [2]}}])
def test_config_refuses_what_it_cannot_hold(wrong):
    with pytest.raises(ValueError):
        replace(CFG, **wrong)


def test_a_published_config_reads_straight_in():
    """``linear_attn_config`` as ``config.json`` has it: a dict of lists."""
    cfg = kimi_linear.KimiLinearConfig(linear_attn_config={
        "kda_layers": [l for l in range(1, 28) if l % 4 and l != 27],
        "full_attn_layers": [4, 8, 12, 16, 20, 24, 27], "num_heads": 32,
        "head_dim": 128, "short_conv_kernel_size": 4})
    assert cfg == kimi_linear.config("kimi-linear-48b-a3b")
    assert hash(cfg) == hash(kimi_linear.config("kimi-linear-48b-a3b"))
