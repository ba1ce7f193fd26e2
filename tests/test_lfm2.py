"""models/lfm2.py (the double-gated short convolution as the mixer, grouped-
query attention with a norm on q and k before rope, a chip's share of the
experts with no shared expert beside it, one table as embedding and head)
against the installed ``transformers``' ``Lfm2ForCausalLM`` where that has
the layers (every layer dense), and against a copy of the benchmark's plain
reference for the whole model; the share tied to the model; the sliced tied
head; the counters; ``lm.scan_blocks`` over the cut's seven runs.

Everything runs on the CPU at tiny widths in float32 under the highest
matmul precision, the kernels interpreted, where both sides compute the same
sums in another order: tolerances of 1e-4 (relative, on gradients: of a
leaf's norm) leave room for float32 reassociation across a few hundred terms
and nothing else. One comparison runs in bfloat16, loosely: it says that
the low-precision path is the same function, not how close it is.
"""

import math
from dataclasses import replace
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import reference_lfm2_moe as reference
from ray_tpu.models import lfm2, lm
from ray_tpu.ops import short_conv as short_conv_op
from ray_tpu.ops.moe import routed_experts
from ray_tpu.parallel import MeshConfig, build_mesh
from ray_tpu.parallel.train_step import init_train_state, make_train_step
from ray_tpu.util import metrics as metrics_mod

CFG = lfm2.config("lfm2-tiny")
SEQ = 64
# The kernels (interpreted: the flash three and the convolution's pair, at a
# width and a length that tile), remat, the chunked loss, and a share of the
# experts: 3 of 8, from the third.
FLASH = replace(CFG, hidden_size=128, attn_impl="flash", attn_blk_q=128,
                attn_blk_k=128, remat=True, loss_chunk=128,
                experts_held=(2, 3))
FLASH_SEQ = short_conv_op.ROWS


def published(cfg):
    out = {"layer_types": list(cfg.layer_types),
           "num_hidden_layers": cfg.num_hidden_layers,
           "num_dense_layers": cfg.num_dense_layers,
           "rope_parameters": {"rope_theta": cfg.rope_parameters.rope_theta,
                               "rope_type": "default"},
           "num_experts_per_tok": cfg.num_experts_per_tok,
           "routed_scaling_factor": cfg.routed_scaling_factor,
           "norm_topk_prob": cfg.norm_topk_prob, "norm_eps": cfg.norm_eps,
           "deployment": {"layers_run": {"first": cfg.first_layer,
                                         "count": cfg.num_hidden_layers}}}
    if cfg.experts_held is not None:
        first, count = cfg.experts_held
        out["deployment"]["experts_held"] = {
            "first": first, "count": count, "of": cfg.num_experts}
    return out


def drawn(cfg, seed=0):
    """The init with every vector moved off its one or zero (the expert
    bias too: routing uneven), and the q and k norms' scales doubled: the
    scores of a random model then spread by four units, so that a key
    wrongly seen, a missing rotation or a wrong KV head moves the
    softmax."""
    params = lfm2.init(cfg, jax.random.PRNGKey(seed))
    keys = iter(jax.random.split(jax.random.PRNGKey(seed + 1), 128))

    def moved(path, leaf):
        name = jax.tree_util.keystr(path)
        if "router_bias" in name:
            return 0.05 * jax.random.normal(next(keys), leaf.shape)
        if name.endswith("_scale']"):
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
        got_logits, aux = jax.jit(partial(lfm2.forward_with_aux, cfg=cfg))(
            params, tokens=tokens)
        got_loss, got_grads = jax.jit(jax.value_and_grad(
            lambda p: lfm2.loss_fn(p, cfg, tokens, targets)[0]))(params)
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
        "dense_conv", "dense_full_attention", "moe_conv",
        "moe_full_attention", "moe_conv"]


def test_the_flash_size_runs_the_convolutions_kernels():
    params = jax.eval_shape(partial(lfm2.init, FLASH), jax.random.PRNGKey(0))
    tokens, targets = batch(FLASH, seq=FLASH_SEQ)
    text = jax.jit(jax.grad(
        lambda p: lfm2.loss_fn(p, FLASH, tokens, targets)[0])).lower(
        params).as_text(debug_info=True)
    for kernel in ("short_conv_fwd", "short_conv_bwd", "flash_fwd"):
        assert kernel in text


# -- against the installed transformers -----------------------------------

def test_every_layer_dense_is_transformers_lfm2():
    """``Lfm2ForCausalLM`` has the mixer, the attention, the layer and the
    head, with a dense FFN in every layer: the model with
    ``num_dense_layers`` = ``num_hidden_layers`` is that model, weights
    copied over. The chunk order of the convolution's projection, the taps'
    order, the norms on q and k before the rotation, the rotation's
    pairing, ``embedding_norm`` last and the table as the head all show in
    the logits."""
    torch = pytest.importorskip("torch")
    try:
        from transformers.models.lfm2.configuration_lfm2 import \
            Lfm2Config as TorchConfig
        from transformers.models.lfm2.modeling_lfm2 import Lfm2ForCausalLM
    except ImportError as exc:
        pytest.skip(f"no lfm2 in transformers: {exc}")
    kinds = ("conv", "full_attention", "conv")
    cfg = replace(CFG, num_hidden_layers=3, num_dense_layers=3,
                  layer_types=kinds)
    theirs = TorchConfig(
        vocab_size=cfg.vocab_size, hidden_size=cfg.hidden_size,
        intermediate_size=cfg.intermediate_size, num_hidden_layers=3,
        num_attention_heads=cfg.num_attention_heads,
        num_key_value_heads=cfg.num_key_value_heads,
        max_position_embeddings=cfg.max_position_embeddings,
        norm_eps=cfg.norm_eps, rope_theta=cfg.rope_parameters.rope_theta,
        conv_bias=False, conv_L_cache=cfg.conv_L_cache,
        block_auto_adjust_ff_dim=False, layer_types=list(kinds),
        tie_word_embeddings=True, attn_implementation="eager")
    model = Lfm2ForCausalLM(theirs).float().eval()
    params = drawn(cfg)
    as_torch = lambda a: torch.from_numpy(np.array(a, np.float32))  # noqa: E731
    d = cfg.hidden_size
    with torch.no_grad():
        model.model.embed_tokens.weight.copy_(as_torch(params["wte"]))
        model.model.embedding_norm.weight.copy_(
            as_torch(params["embedding_norm_scale"]))
        for layer, (run, kind, _) in zip(model.model.layers,
                                         lm.runs(cfg.layers)):
            w = jax.tree.map(lambda a: a[0], params[run])
            layer.operator_norm.weight.copy_(
                as_torch(w["operator_norm_scale"]))
            layer.ffn_norm.weight.copy_(as_torch(w["ffn_norm_scale"]))
            layer.feed_forward.w1.weight.copy_(as_torch(w["w_gate"].T))
            layer.feed_forward.w3.weight.copy_(as_torch(w["w_up"].T))
            layer.feed_forward.w2.weight.copy_(as_torch(w["w_down"].T))
            if kind.endswith("conv"):
                layer.conv.in_proj.weight.copy_(as_torch(w["w_in"].T))
                layer.conv.out_proj.weight.copy_(as_torch(w["w_out"].T))
                layer.conv.conv.weight.copy_(
                    as_torch(w["conv_w"].T[:, None, :]))
            else:
                attn = layer.self_attn
                attn.q_proj.weight.copy_(as_torch(w["wq"].reshape(d, -1).T))
                attn.k_proj.weight.copy_(as_torch(w["wk"].reshape(d, -1).T))
                attn.v_proj.weight.copy_(as_torch(w["wv"].reshape(d, -1).T))
                attn.out_proj.weight.copy_(
                    as_torch(w["wo"].reshape(-1, d).T))
                attn.q_layernorm.weight.copy_(as_torch(w["q_norm_scale"]))
                attn.k_layernorm.weight.copy_(as_torch(w["k_norm_scale"]))
        tokens, _ = batch(cfg)
        want = model(torch.from_numpy(np.array(tokens, np.int64))
                     ).logits.numpy()
    with jax.default_matmul_precision("highest"):
        got = lfm2.forward(params, cfg, tokens)
    rms = float(np.sqrt((want ** 2).mean()))
    assert rms > 0.01
    np.testing.assert_allclose(got, want, atol=1e-4 * rms)


# -- against the reference ------------------------------------------------

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
                    jax.eval_shape(partial(lfm2.init, CFG),
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


def test_bfloat16_with_the_kernels_is_the_same_function():
    """The shipped precision on the CPU, every expert held so that no
    routing flip turns a branch: the logits stay within a few per cent of
    the float32 reference's RMS."""
    cfg = replace(FLASH, dtype=jnp.bfloat16, experts_held=None)
    params = drawn(cfg)
    tokens, targets = batch(cfg, seq=FLASH_SEQ)
    where = jnp.broadcast_to(jnp.arange(FLASH_SEQ, dtype=jnp.int32),
                             tokens.shape)
    want, _, rms = reference.forward(
        params, tokens, targets, where, **reference.arguments(published(cfg)))
    got = jax.jit(partial(lfm2.forward, cfg=cfg))(params, tokens=tokens)
    err = float(jnp.sqrt(((got.astype(jnp.float32) - want) ** 2).mean()))
    assert err < 0.05 * float(rms)


@pytest.mark.parametrize("dropped", [
    "gate_before", "gate_after", "a_tap", "rope", "qk_norm", "bias"])
def test_a_dropped_term_shows(both, dropped, monkeypatch):
    """Each term the equations have and a sibling family lacks, taken out
    of the program: the logits move by far more than the comparison
    allows."""
    params, cfg = drawn(CFG), CFG
    if dropped in ("gate_before", "gate_after"):
        d = CFG.hidden_size
        chunk = 0 if dropped == "gate_before" else 1
        plain = lm.short_conv
        monkeypatch.setattr(
            lm, "short_conv", lambda bcx, w: plain(
                bcx.at[..., chunk * d:(chunk + 1) * d].set(1.0), w))
    elif dropped == "a_tap":
        params = {name: dict(leaf, conv_w=leaf["conv_w"].at[:, 0].set(0.0))
                  if "conv_w" in leaf else leaf
                  for name, leaf in params.items()}
    elif dropped == "rope":
        monkeypatch.setattr(lm, "rope", lambda x, positions, theta: x)
    elif dropped == "qk_norm":
        plain = lm.rmsnorm
        monkeypatch.setattr(
            lm, "rmsnorm", lambda x, scale, eps:
            x if x.ndim == 4 else plain(x, scale, eps))
    elif dropped == "bias":
        cfg = replace(CFG, use_expert_bias=False)
    tokens, _ = batch(CFG)
    with jax.default_matmul_precision("highest"):
        got = lfm2.forward(params, cfg, tokens)
    want = both["logits"][1]
    assert float(jnp.abs(got - want).max()) > 0.05 * both["rms"]


# -- the share ------------------------------------------------------------

def _expert_layer(experts=16, tokens=96, d=32, f=16, seed=0):
    ks = jax.random.split(jax.random.PRNGKey(seed), 6)
    normal = jax.random.normal
    w = {"router": normal(ks[0], (d, experts)) / math.sqrt(d),
         "router_bias": 0.2 * normal(ks[1], (experts,)),
         "w_gate": normal(ks[2], (experts, d, f)) / math.sqrt(d),
         "w_up": normal(ks[3], (experts, d, f)) / math.sqrt(d),
         "w_down": normal(ks[4], (experts, f, d)) / math.sqrt(f)}
    return w, normal(ks[5], (tokens, d))


def _share_of(w, first, count):
    return dict(w, **{name: w[name][first:first + count]
                      for name in ("w_gate", "w_up", "w_down")})


def test_the_eight_shares_add_up_to_the_uncut_layer():
    """Nothing is computed alike on every chip here (no shared expert), so
    nothing is counted once: the eight shares' parts, each an eighth of the
    experts, add up to the uncut layer of the reference, and every share
    computes exactly the assignments the router gave its experts."""
    w, x = _expert_layer()
    top_k, count = 4, 2
    with jax.default_matmul_precision("highest"):
        want, picked = reference._ffn(x, w, top_k, 1.0, True, 0)
        total, computed = jnp.zeros_like(x), 0
        for first in range(0, 16, count):
            share = _share_of(w, first, count)
            part, aux = routed_experts(
                x, w["router"], w["router_bias"], share["w_gate"],
                share["w_up"], share["w_down"], top_k=top_k, scaling=1.0,
                held=(first, count))
            mine = ((picked >= first) & (picked < first + count)).sum()
            assert int(aux["group_sizes"].sum()) == int(mine) \
                == int(aux["asked"])
            total, computed = total + part, computed + int(mine)
            # The reference given the same share gives the same part.
            np.testing.assert_allclose(part, reference._ffn(
                x, share, top_k, 1.0, True, first)[0], atol=2e-5)
    assert computed == x.shape[0] * top_k
    np.testing.assert_allclose(total, want, atol=5e-5)


def test_a_token_with_no_held_expert_gets_exactly_zero():
    """No shared expert stands beside the share: where none of a token's
    experts is held the layer returns zeros, and the block the residual
    alone."""
    cfg = replace(CFG, num_hidden_layers=1, num_dense_layers=0,
                  layer_types=("conv",), experts_held=(6, 2))
    params = drawn(cfg)
    tokens, _ = batch(cfg, rows=4)
    layer = jax.tree.map(lambda a: a[0], params["run00_moe_conv"])
    h = lm.embed(params["wte"], tokens, cfg.dtype)
    mixed = h + lfm2._short_conv(cfg, lm.rmsnorm(
        h, layer["operator_norm_scale"], cfg.norm_eps), layer)
    out, aux = lfm2._block(cfg, "moe_conv", h, layer, lm.positions_of(tokens))
    unheld = ~((aux["picked"] >= 6) & (aux["picked"] < 8)).any(-1)
    assert 0 < int(unheld.sum()) < unheld.size
    assert np.array_equal(np.asarray(out)[np.asarray(unheld)],
                          np.asarray(mixed)[np.asarray(unheld)])
    assert not np.array_equal(np.asarray(out)[~np.asarray(unheld)],
                              np.asarray(mixed)[~np.asarray(unheld)])


def test_the_sliced_tied_heads_loss_is_the_whole_heads_on_the_slice():
    """A slice of the vocabulary is a smaller vocabulary: on ids of the
    slice, the loss of the model that holds the slice's rows of the one
    table (embedding and head) is the whole model's with its logits
    restricted to those columns."""
    held = 64
    params = drawn(CFG)
    tokens, targets = batch(replace(CFG, vocab_size=held))
    sliced = dict(params, wte=params["wte"][:held])
    with jax.default_matmul_precision("highest"):
        got, metrics = lfm2.loss_fn(sliced, replace(CFG, vocab_size=held),
                                    tokens, targets)
        logits = lfm2.forward(params, CFG, tokens)[..., :held]
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
            "ray_tpu_train_moe_routed_total",
            "ray_tpu_train_moe_calls_total",
            "ray_tpu_train_moe_calls_within_bound_total")


@pytest.mark.parametrize("accum_steps", [1, 2])
def test_trains_and_feeds_the_shares_counters(accum_steps):
    """``make_train_step`` finds the model from ``type(cfg)``: the loss
    falls on a repeated batch (flash, the convolution's kernels, remat, the
    chunked loss, a share of the experts), and the counters the share
    cells read say what the share did."""
    import optax
    from ray_tpu.parallel.sharding import ShardingRules
    mesh = _one_chip()
    rules, optimizer = ShardingRules(), optax.adam(3e-3)
    state = init_train_state(FLASH, mesh, rules, optimizer, seed=0)
    step = make_train_step(FLASH, mesh, rules, optimizer,
                           accum_steps=accum_steps)
    tokens, targets = batch(FLASH, rows=2, seq=FLASH_SEQ)
    routed = tokens.size * FLASH.num_experts_per_tok * FLASH.n_moe_layers
    calls = FLASH.n_moe_layers * accum_steps
    before = [_counter(name) for name in COUNTERS]
    losses = []
    for _ in range(3):
        state, metrics = step(state, {"tokens": tokens, "targets": targets})
        losses.append(float(metrics["loss"]))
        assert float(metrics["moe_routed"]) == routed
        assert float(metrics["moe_assignments"]) == \
            float(metrics["moe_tokens"])
        assert 0 < float(metrics["moe_tokens"]) < routed
        assert float(metrics["moe_calls"]) == calls \
            == float(metrics["moe_calls_within_bound"])
    assert all(np.isfinite(losses)) and losses[-1] < losses[0]
    assigned, asked, all_routed, in_all, within = (
        _counter(name) - was for name, was in zip(COUNTERS, before))
    # Fed one call late at most: after three blocking steps, two or three.
    assert assigned == asked and all_routed in (2 * routed, 3 * routed)
    assert in_all == within and in_all in (2 * calls, 3 * calls)
    # 3 of 8 experts held: about three eighths of the routing's work.
    assert 0.2 < asked / all_routed < 0.6


def test_expert_parallel_mesh_is_refused():
    mesh = build_mesh(MeshConfig(dp=1, fsdp=1, tp=1, ep=2),
                      devices=jax.devices()[:2])
    step = make_train_step(CFG, mesh)
    state = init_train_state(CFG, mesh, seed=0)
    tokens, targets = batch(CFG)
    with pytest.raises(NotImplementedError, match="expert parallelism"):
        step(state, {"tokens": tokens, "targets": targets})


def test_a_data_parallel_mesh_runs_the_kernels_per_shard():
    """Two shards of the batch: the convolution's kernels and the flash
    kernels run on each shard's own sequences, and the loss is the one
    chip's."""
    tokens, targets = batch(FLASH, rows=2, seq=FLASH_SEQ)
    losses = []
    for chips in (1, 2):
        mesh = build_mesh(MeshConfig(dp=chips, fsdp=1, tp=1),
                          devices=jax.devices()[:chips])
        state = init_train_state(FLASH, mesh, seed=0)
        _, metrics = make_train_step(FLASH, mesh)(
            state, {"tokens": tokens, "targets": targets})
        losses.append(float(metrics["loss"]))
    np.testing.assert_allclose(losses[1], losses[0], rtol=1e-5)


# -- the layer scan over the cut's seven runs -----------------------------

CUT = replace(lfm2.config("lfm2-24b-a2b"), num_hidden_layers=13,
              num_dense_layers=1, first_layer=1, experts_held=(0, 8),
              vocab_size=8192)


def test_the_cut_configuration_is_seven_runs():
    """ISSUE 37's cut, published layers 1-13 (``layer_types[1:14]``): the
    dense layer a convolution layer as published layer 1 is, then three
    whole periods of expert layers, 1 attention : 3 convolution. The
    benchmark's cell runs a fourth period (layers 1-17: nine runs)."""
    assert lm.runs(CUT.layers) == (
        ("run00_dense_conv", "dense_conv", 1),
        ("run01_moe_full_attention", "moe_full_attention", 1),
        ("run02_moe_conv", "moe_conv", 3),
        ("run03_moe_full_attention", "moe_full_attention", 1),
        ("run04_moe_conv", "moe_conv", 3),
        ("run05_moe_full_attention", "moe_full_attention", 1),
        ("run06_moe_conv", "moe_conv", 3))
    assert CUT.n_moe_layers == 12
    deeper = lm.runs(replace(CUT, num_hidden_layers=17).layers)
    assert deeper[:7] == lm.runs(CUT.layers) and deeper[7:] == (
        ("run07_moe_full_attention", "moe_full_attention", 1),
        ("run08_moe_conv", "moe_conv", 3))
    shapes = jax.eval_shape(partial(lfm2.init, CUT), jax.random.PRNGKey(0))
    assert [jax.tree.leaves(shapes[run])[0].shape[0]
            for run, _, _ in lm.runs(CUT.layers)] == [1, 1, 3, 1, 3, 1, 3]
    assert shapes["run02_moe_conv"]["w_gate"].shape == (3, 8, 2048, 1536)
    assert shapes["run02_moe_conv"]["router"].shape == (3, 2048, 64)
    assert shapes["run02_moe_conv"]["w_in"].shape == (3, 2048, 6144)
    assert shapes["run02_moe_conv"]["conv_w"].shape == (3, 3, 2048)
    assert shapes["run01_moe_full_attention"]["wk"].shape == (1, 2048, 8, 64)
    assert shapes["run00_dense_conv"]["w_gate"].shape == (1, 2048, 11776)
    assert shapes["wte"].shape == (8192, 2048) and "lm_head" not in shapes
    # Without the offset the first thirteen published layers are eight
    # runs: two convolution layers lead, and the pattern falls a layer late.
    assert len(lm.runs(replace(CUT, first_layer=0).layers)) == 8


@pytest.mark.parametrize("remat", [False, True])
def test_scan_blocks_over_the_runs_is_the_layers_one_by_one(remat):
    cfg = replace(CFG, remat=remat)
    params = drawn(cfg)
    tokens, _ = batch(cfg)
    with jax.default_matmul_precision("highest"):
        got, _ = lfm2.hidden_states(params, cfg, tokens)
        x = lm.embed(params["wte"], tokens, cfg.dtype)
        for run, kind, depth in lm.runs(cfg.layers):
            for i in range(depth):
                x, _ = lfm2._block(cfg, kind, x, jax.tree.map(
                    lambda a: a[i], params[run]), lm.positions_of(tokens))
        want = lm.rmsnorm(x, params["embedding_norm_scale"], cfg.norm_eps)
    np.testing.assert_allclose(got, want, atol=1e-5)


def test_param_specs_match_init():
    from ray_tpu.parallel.sharding import ShardingRules
    shapes = jax.eval_shape(partial(lfm2.init, CFG), jax.random.PRNGKey(0))
    specs = lfm2.param_specs(CFG, ShardingRules())
    assert jax.tree.structure(shapes) == jax.tree.structure(
        specs, is_leaf=lambda s: isinstance(s, jax.sharding.PartitionSpec))
    for leaf, spec in zip(jax.tree.leaves(shapes), jax.tree.leaves(
            specs, is_leaf=lambda s: isinstance(
                s, jax.sharding.PartitionSpec))):
        assert len(spec) <= leaf.ndim


@pytest.mark.parametrize("wrong", [
    dict(experts_held=(6, 4)), dict(num_hidden_layers=6),
    dict(first_layer=1), dict(layer_types=("conv", "mamba") * 3),
    dict(conv_bias=True), dict(tie_word_embeddings=False),
    dict(rope_parameters={"rope_theta": 1e6, "rope_type": "yarn"})])
def test_config_refuses_what_it_cannot_hold(wrong):
    with pytest.raises((ValueError, NotImplementedError)):
        replace(CFG, **wrong)


def test_the_reference_is_the_benchmarks_byte_for_byte():
    import os
    here = os.path.dirname(os.path.abspath(__file__))
    with open(os.path.join(here, "reference_lfm2_moe.py"), "rb") as mine, \
            open(os.path.join(here, "..", "benchmark", "reference",
                              "lfm2_moe.py"), "rb") as theirs:
        assert mine.read() == theirs.read()
