"""models/glm_moe_dsa.py (latent attention with a low-rank query over the
keys an indexer selects, the selection shared by the layers behind the one
that made it, the indexers' own loss, a chip's share of the experts) against
a copy of the benchmark's plain reference, which selects with
``jax.lax.top_k`` on whole rows and attends under an explicit mask, through
``family_cases.py``; the selections themselves; which leaves each term of the
loss moves; the 32 shares of an expert layer adding up to the uncut layer;
the gauges; an integer value handed on through ``lm.scan_blocks``.
"""

import math
from dataclasses import replace
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import family_cases
import reference_glm_moe_dsa as reference
from family_cases import batch, by_name, drawn, in_every_run
from ray_tpu.models import deepseek, glm_moe_dsa, kimi_linear, lm
from ray_tpu.ops import dsa
from ray_tpu.parallel import MeshConfig, build_mesh
from ray_tpu.parallel.train_step import init_train_state, make_train_step

CFG = glm_moe_dsa.config("glm-tiny")
SEQ = 64    # the top 24 of up to 64 keys
# The selection's kernels (interpreted), remat, the chunked loss, and a
# share of the experts: 3 of 8, from the third; the top 100 of up to 256.
FLASH = replace(CFG, attn_impl="flash", attn_blk_q=128, attn_blk_k=128,
                remat=True, loss_chunk=128, experts_held=(2, 3),
                index_topk=100)
FLASH_SEQ = 256
INDEXER = ("w_iq", "w_ik", "ik_norm_scale", "ik_norm_bias", "w_iw")


def published(cfg):
    run = range(cfg.first_layer, cfg.first_layer + cfg.num_hidden_layers)
    out = {"num_hidden_layers": cfg.num_hidden_layers,
           "layers_run": list(run),
           "first_k_dense_replace": cfg.first_k_dense_replace,
           "indexer_types": list(cfg.indexer_types),
           "qk_nope_head_dim": cfg.qk_nope_head_dim,
           "qk_rope_head_dim": cfg.qk_rope_head_dim,
           "kv_lora_rank": cfg.kv_lora_rank,
           "rope_parameters": {"rope_theta": cfg.rope_theta},
           "index_topk": cfg.index_topk,
           "num_experts_per_tok": cfg.num_experts_per_tok,
           "routed_scaling_factor": cfg.routed_scaling_factor,
           "norm_topk_prob": cfg.norm_topk_prob,
           "rms_norm_eps": cfg.rms_norm_eps,
           "assumed": {"sizes": {
               "indexer_loss_coef": cfg.indexer_loss_coef,
               "index_norm_eps": cfg.index_norm_eps}}}
    if cfg.experts_held is not None:
        first, count = cfg.experts_held
        out["deployment"] = {"experts_held": {
            "first": first, "count": count, "of": cfg.n_routed_experts}}
    return out


def moved(name, leaf, key):
    """Every vector off its one or zero (the correction bias too: routing
    uneven), and the queries' second matrices larger: at 0.02 the main
    softmax and the indexer's are flat, and which keys a query attends over
    would move nothing."""
    if name.endswith("_scale']") or "ik_norm_bias" in name:
        return leaf + 0.2 * jax.random.normal(key, leaf.shape)
    if "router_bias" in name:
        return 0.1 * jax.random.normal(key, leaf.shape)
    if "w_q_b" in name or "w_iq" in name or "w_iw" in name:
        return 6.0 * leaf
    return leaf


_rmsnorm = lm.rmsnorm


def drop(dropped, params, cfg, monkeypatch):
    if dropped == "selection":
        monkeypatch.setattr(dsa, "select", lambda scores, topk: jnp.tril(
            jnp.ones(scores.shape, jnp.int8)))
    elif dropped == "relu":
        monkeypatch.setattr(jax.nn, "relu", lambda x: x)
    elif dropped == "index_rope":
        monkeypatch.setattr(glm_moe_dsa, "_partly_rotated",
                            lambda x, positions, cfg: x)
    elif dropped == "shared_selection":
        plain = glm_moe_dsa._block

        def block(cfg, kind, h, layer, positions, shared):
            if kind.endswith("shared"):
                shared = {glm_moe_dsa.SELECTION: jnp.tril(jnp.ones_like(
                    shared[glm_moe_dsa.SELECTION]))}
            return plain(cfg, kind, h, layer, positions, shared)
        monkeypatch.setattr(glm_moe_dsa, "_block", block)
    elif dropped == "q_norm":
        params = in_every_run(params, lambda stack: dict(
            stack, q_norm_scale=jnp.ones_like(stack["q_norm_scale"])))
    else:
        monkeypatch.setattr(lm, "rmsnorm", lambda x, scale, eps: x if
                            scale.shape[-1] == cfg.q_lora_rank else
                            _rmsnorm(x, scale, eps))
    return params, cfg


GLM = family_cases.Family(
    module=glm_moe_dsa, reference=reference, cfg=CFG, seq=SEQ, flash=FLASH,
    flash_seq=FLASH_SEQ, published=published, moved=moved,
    extras=("picked", "selections"), drop=drop, dropped=(
        "selection", "relu", "index_rope", "shared_selection", "q_norm",
        "q_lora_norm"),
    top_k=CFG.num_experts_per_tok, sliced_vocab=32, accum_steps=(1,),
    train_drawn=True,
    wrong=(dict(first_layer=3),                 # shares what no layer makes
           dict(first_layer=5, num_hidden_layers=5),  # past the depth
           dict(indexer_types=("full", "none") + ("shared",) * 6),
           dict(experts_held=(6, 4))))
globals().update(family_cases.cases(GLM))


def program_selections(cfg, params, tokens):
    """Every layer's selection as the program's forward pass attends over
    it, in layer order: called back from inside the layer scans."""
    seen = []
    plain = lm.selected_attention

    def attend(cfg, q, k, v, selection):
        jax.debug.callback(lambda s: seen.append(np.asarray(s)), selection,
                           ordered=True)
        return plain(cfg, q, k, v, selection)

    lm.selected_attention = attend
    try:
        jax.block_until_ready(jax.jit(lambda p: glm_moe_dsa.hidden_states(
            p, replace(cfg, remat=False), tokens)[0])(params))
    finally:
        lm.selected_attention = plain
    return seen


@pytest.fixture(scope="module")
def selections():
    tokens, _ = batch(CFG, SEQ)
    return program_selections(CFG, drawn(GLM, CFG), tokens)


@pytest.fixture(scope="module")
def ce_grads():
    """The cross-entropy's gradient alone: the indexers' term left out."""
    tokens, targets = batch(CFG, SEQ)
    with jax.default_matmul_precision("highest"):
        return jax.jit(jax.grad(lambda p: glm_moe_dsa.loss_fn(
            p, replace(CFG, indexer_loss_coef=0.0), tokens, targets)[0]))(
                drawn(GLM, CFG))


def test_the_tiny_stack_is_the_published_pattern():
    assert [(kind, n) for _, kind, n in lm.runs(CFG.layers)] == [
        ("dense_full", 1), ("moe_shared", 3), ("moe_full", 1)]
    assert CFG.n_moe_layers == 4 and CFG.index_topk < SEQ
    # The selection's kernels run (interpreted) under ``flash``: a forward
    # call a run, and the indexer's scores and the head-summed probabilities
    # where an indexer is.
    from ray_tpu.parallel.collectives import kernel_census
    tokens, targets = batch(FLASH, FLASH_SEQ)
    census = kernel_census(jax.make_jaxpr(lambda p: glm_moe_dsa.loss_fn(
        p, replace(FLASH, remat=False), tokens, targets)[0])(
            drawn(GLM, FLASH)))
    assert census["dsa_fwd"] == 3 and census["dsa_probs"] == 2 \
        and census["dsa_index_fwd"] == 2


@pytest.mark.parametrize("which", ["both", "both_flash"])
def test_the_indexers_loss_is_the_references(which, request):
    found = request.getfixturevalue(which)
    metrics, want = found["metrics"], found["extras"][2].mean()
    np.testing.assert_allclose(metrics["dsa_index_loss"], want, rtol=1e-4)
    # The second term is there, and ``loss`` stays the cross-entropy.
    assert float(want) > 1e-3
    np.testing.assert_allclose(
        metrics["total_loss"],
        metrics["loss"] + metrics["dsa_index_loss"], rtol=1e-6)


def test_the_selections_are_top_k_and_shared_as_published(both, selections):
    """Every row keeps exactly ``min(t + 1, k)`` keys, all causal; the
    three layers behind the dense layer attend over its selection bit for
    bit; the last layer makes its own; and all are the reference's."""
    got, want = selections, np.asarray(both["extras"][1])
    assert len(got) == 5
    rows = np.minimum(np.arange(SEQ) + 1, CFG.index_topk)
    causal = np.tril(np.ones((SEQ, SEQ), bool))
    for selection in got:
        assert (selection.sum(-1) == rows).all()
        assert not selection[:, ~causal].any()
    for shared in got[1:4]:
        assert (shared == got[0]).all()
    assert (got[4] != got[0]).any()
    for mine, theirs in zip(got, want):
        assert ((mine != 0) == theirs).all()


@pytest.mark.parametrize("which", ["both", "both_flash"])
def test_the_selected_share_is_the_closed_form(which, request):
    found = request.getfixturevalue(which)
    seq, topk = found["seq"], found["cfg"].index_topk
    kept = topk * (topk + 1) // 2 + (seq - topk) * topk
    np.testing.assert_allclose(found["metrics"]["dsa_selected_share"],
                               kept / (seq * (seq + 1) // 2), rtol=1e-6)


@pytest.mark.parametrize("leaf", family_cases.leaves(GLM))
def test_each_term_of_the_loss_moves_its_own_leaves(both, ce_grads, leaf):
    """The indexer's leaves get their gradient from ``L_I`` alone, and no
    other leaf gets any from it."""
    whole, ce = by_name(both["grads"][0])[leaf], by_name(ce_grads)[leaf]
    if any(f"['{name}']" in leaf for name in INDEXER):
        assert not np.any(ce) and np.any(whole)
    else:
        np.testing.assert_array_equal(whole, ce)


# -- the share ------------------------------------------------------------

@pytest.mark.parametrize("shares", [32, 4, 1])
def test_the_shares_add_up_to_the_uncut_layer(shares):
    """Thirty-two shares of one expert each, as the cell's thirty-two
    chips; four; one; at 8 experts a token."""
    family_cases.shares_add_up(reference, 32, shares, top_k=8, scale=2.5)


def test_a_mask_weighs_the_indexers_term_by_sequence():
    """``loss_of_hidden`` under a mask of one row is that row's loss, both
    terms (the benchmark's comparison takes a sequence at a time)."""
    params = drawn(GLM, CFG)
    tokens, targets = batch(CFG, SEQ)
    hidden, aux = jax.jit(partial(glm_moe_dsa.hidden_states, cfg=CFG))(
        params, tokens=tokens)
    per_row = [glm_moe_dsa.loss_of_hidden(
        params, CFG, hidden, aux, targets,
        mask=jnp.zeros(tokens.shape).at[i].set(1.0)) for i in range(2)]
    whole = glm_moe_dsa.loss_of_hidden(params, CFG, hidden, aux, targets)
    np.testing.assert_allclose(
        (per_row[0][0] + per_row[1][0]) / 2, whole[0], rtol=1e-6)
    index = [float(m["total_loss"] - m["loss"]) for _, m in per_row]
    np.testing.assert_allclose(index, aux["index_loss"].sum(0), rtol=1e-4)
    assert index[0] != index[1]


# -- models/lm.py's latent attention, shared -------------------------------

@pytest.mark.parametrize("family,preset,stack", [
    (deepseek, "deepseek-tiny", "dense_layers"),
    (kimi_linear, "kimi-linear-tiny", "run01_dense_mla")])
def test_a_null_rank_leaves_the_other_families_as_they_were(family, preset,
                                                            stack):
    """``q_lora_rank`` null, or a config without the key: one matrix ``wq``
    in the place it had, the same leaves in the same order (a seed's draw is
    a contract), and ``mla_qkv`` hands back no low-rank query."""
    cfg = family.config(preset)
    assert getattr(cfg, "q_lora_rank", None) is None
    assert list(lm.mla_leaves(cfg)) == [
        "wq", "w_kv_a", "kv_norm_scale", "w_kv_b", "wo"]
    params = jax.jit(partial(family.init, cfg))(jax.random.PRNGKey(0))
    layer = jax.tree.map(lambda a: a[0], params[stack])
    x = jax.random.normal(jax.random.PRNGKey(1), (1, 16, cfg.hidden_size))
    q, k, v, c_q = lm.mla_qkv(cfg, x, layer, lm.positions_of(x[..., 0]))
    assert c_q is None and q.shape == k.shape and v.shape[:3] == q.shape[:3]


def test_a_rank_makes_the_query_two_matrices_and_a_norm():
    leaves = lm.mla_leaves(CFG)
    assert list(leaves) == ["w_q_a", "q_norm_scale", "w_q_b", "w_kv_a",
                            "kv_norm_scale", "w_kv_b", "wo"]
    assert leaves["w_q_a"][0] == (CFG.hidden_size, CFG.q_lora_rank)
    assert leaves["w_q_b"][0] == (CFG.q_lora_rank, CFG.num_attention_heads,
                                  CFG.qk_nope_head_dim + CFG.qk_rope_head_dim)
    params = drawn(GLM, CFG)
    layer = jax.tree.map(lambda a: a[0], params["run00_dense_full"])
    x = jax.random.normal(jax.random.PRNGKey(1), (1, 16, CFG.hidden_size))
    q, _, _, c_q = lm.mla_qkv(CFG, x, layer, lm.positions_of(x[..., 0]))
    want = lm.rmsnorm(x @ layer["w_q_a"], layer["q_norm_scale"],
                      CFG.rms_norm_eps)
    np.testing.assert_allclose(c_q, want, rtol=1e-6)
    nope = CFG.qk_nope_head_dim
    np.testing.assert_allclose(
        q[..., :nope], jnp.einsum("bsr,rhk->bshk", want,
                                  layer["w_q_b"])[..., :nope], rtol=1e-5,
        atol=1e-6)


# -- the train step's gauges ---------------------------------------------------

def test_the_step_feeds_the_selections_gauges():
    """Both terms fall on a repeated batch, and the gauges hold the selected
    share (the closed form) and ``L_I``."""
    found = family_cases.trained(GLM, 1)
    topk = FLASH.index_topk
    share = (topk * (topk + 1) // 2 + (FLASH_SEQ - topk) * topk) / (
        FLASH_SEQ * (FLASH_SEQ + 1) // 2)
    index_losses = [m["dsa_index_loss"] for m in found["metrics"]]
    assert index_losses[-1] < index_losses[0]
    for metrics in found["metrics"]:
        np.testing.assert_allclose(metrics["dsa_selected_share"], share,
                                   rtol=1e-6)
    np.testing.assert_allclose(
        found["gauges"]["ray_tpu_train_dsa_selected_share"], share, rtol=1e-6)
    assert found["gauges"]["ray_tpu_train_dsa_index_loss"] in index_losses


def test_the_kernels_refuse_a_mesh_of_several_devices():
    mesh = build_mesh(MeshConfig(dp=2, fsdp=1, tp=1),
                      devices=jax.devices()[:2])
    step = make_train_step(FLASH, mesh)
    state = init_train_state(FLASH, mesh, seed=0)
    tokens, targets = batch(FLASH, FLASH_SEQ)
    with pytest.raises(NotImplementedError, match="one\n?\\s*device"):
        step(state, {"tokens": tokens, "targets": targets})


# -- the layer scan over the runs -----------------------------------------

CUT = replace(glm_moe_dsa.config("glm-5.2"), num_hidden_layers=5,
              first_layer=2, experts_held=(0, 8), vocab_size=19360)


def test_the_cut_configuration_is_three_runs():
    """Published layers 2 to 6: the last leading dense layer and one whole
    period of expert layers, indexers to sharing layers 1 : 3."""
    assert CUT.layers == ("dense_full", "moe_shared", "moe_shared",
                          "moe_shared", "moe_full")
    assert [(kind, n) for _, kind, n in lm.runs(CUT.layers)] == [
        ("dense_full", 1), ("moe_shared", 3), ("moe_full", 1)]
    whole = glm_moe_dsa.config("glm-5.2")
    assert whole.indexer_types[:11] == (
        "full", "full", "full", "shared", "shared", "shared", "full",
        "shared", "shared", "shared", "full")
    assert len(whole.indexer_types) == 78 == whole.num_hidden_layers
    assert sum(t == "full" for t in whole.indexer_types) == 21
    shapes = jax.eval_shape(partial(glm_moe_dsa.init, CUT),
                            jax.random.PRNGKey(0))
    # Every matrix: the tables, and a stack's leaves behind its layers axis.
    held = sum(math.prod(leaf.shape) for path, leaf in
               jax.tree_util.tree_leaves_with_path(shapes)
               if leaf.ndim >= (2 if len(path) == 1 else 3))
    assert abs(held / 1e9 - 2.674) < 0.002
    assert shapes["run01_moe_shared"]["w_gate"].shape == (3, 8, 6144, 2048)
    assert "w_iq" not in shapes["run01_moe_shared"]
    assert shapes["run02_moe_full"]["w_iq"].shape == (1, 2048, 32, 128)


def test_an_integer_value_handed_on_carries_no_cotangent(both):
    """The selection is integers: handed on through ``lm.scan_blocks`` it
    reaches the sharing run as a constant of its scan, gradients are the
    same with the blocks rematerialised (the selection kept by name, not
    searched again) as without, and nothing flows back through it."""
    cfg = replace(CFG, remat=True)
    params = drawn(GLM, cfg)
    tokens, targets = batch(cfg, SEQ)
    with jax.default_matmul_precision("highest"):
        grads = jax.jit(jax.grad(lambda p: glm_moe_dsa.loss_fn(
            p, cfg, tokens, targets)[0]))(params)
    for a, b in zip(jax.tree.leaves(grads),
                    jax.tree.leaves(both["grads"][0])):
        np.testing.assert_allclose(a, b, rtol=1e-4, atol=1e-7)
    jaxpr = jax.make_jaxpr(lambda p: glm_moe_dsa.hidden_states(
        p, cfg, tokens))(params)
    scans = [e for e in jaxpr.jaxpr.eqns if e.primitive.name == "scan"]
    assert [e.params["length"] for e in scans] == [1, 3, 1]
    consts = [(v.aval.shape, v.aval.dtype) for v in scans[1].invars[
        :scans[1].params["num_consts"]]]
    assert ((2, SEQ, SEQ), jnp.int8) in consts


def test_deepseek_takes_a_rank_from_its_config():
    """``q_lora_rank`` is a published key of ``deepseek_v3``: set, Moonlight's
    family draws the query's two matrices and its norm in ``wq``'s place and
    runs them."""
    cfg = replace(deepseek.config("deepseek-tiny"), q_lora_rank=24)
    params = jax.jit(partial(deepseek.init, cfg))(jax.random.PRNGKey(0))
    stack = params["moe_layers"]
    assert "wq" not in stack and stack["w_q_a"].shape == (2, 64, 24) \
        and stack["q_norm_scale"].shape == (2, 24)
    tokens = jnp.arange(32, dtype=jnp.int32).reshape(2, 16)
    logits = jax.jit(partial(deepseek.forward, cfg=cfg))(params,
                                                         tokens=tokens)
    assert logits.shape == (2, 16, cfg.vocab_size) \
        and bool(jnp.isfinite(logits).all())
