"""models/dots3_note.py (two latent-attention geometries in one decoder:
full layers over an indexer's selection beside window layers with ranks,
head count and head sizes of their own, a sigmoid gate a head, rescaled
latents, a chip's share of the experts) against a copy of the benchmark's
plain reference, which selects with ``jax.lax.top_k`` on whole rows and
attends under explicit masks, through ``family_cases.py``; the two
geometries and rope bases each on their own kind of layer; the window's edge
one key past a tile; each gate and each rescale planted wrong; the 32 shares
of an expert layer adding up; the gauges; ``ops/dsa.py`` and
``ops/flash_attention.py`` (interpreted) at heads of 192 | 128 and 256 | 128.
"""

import math
from dataclasses import replace
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import family_cases
import reference_dots3_note as reference
from family_cases import batch, drawn, forward_alone
from ray_tpu.models import dots3_note, lm
from ray_tpu.ops import dsa
from ray_tpu.ops.flash_attention import flash_attention, window_tile_census
from ray_tpu.parallel import MeshConfig, build_mesh
from ray_tpu.parallel.train_step import (abstract_train_state,
                                         make_train_step)

CFG = dots3_note.config("dots3-tiny")
SEQ = 64    # the top 24 of up to 64 keys; a window of 20
# The kernels (interpreted), remat, the chunked loss, a share of the experts
# (3 of 8, from the third), the top 100 of up to 256, and a window one key
# longer than a tile, as 513 is at tiles of 512.
FLASH = replace(CFG, attn_impl="flash", attn_blk_q=128, attn_blk_k=128,
                remat=True, loss_chunk=128, experts_held=(2, 3),
                index_topk=100, sliding_window_size=129)
FLASH_SEQ = 256
PLAIN = ("num_attention_heads", "q_lora_rank", "kv_lora_rank",
         "qk_nope_head_dim", "qk_rope_head_dim", "v_head_dim", "rope_theta")


def published(cfg):
    run = range(cfg.first_layer, cfg.first_layer + cfg.num_hidden_layers)
    out = {"hidden_size": cfg.hidden_size,
           "num_hidden_layers": cfg.num_hidden_layers,
           "layers_run": list(run),
           "first_k_dense_replace": cfg.first_k_dense_replace,
           "layer_types": list(cfg.layer_types),
           **{prefix + key: getattr(cfg, prefix + key)
              for prefix in ("", "swa_") for key in PLAIN},
           "sliding_window_size": cfg.sliding_window_size,
           "apply_mla_qkv_lora_rescale": cfg.apply_mla_qkv_lora_rescale,
           "attention_gate_type": cfg.attention_gate_type,
           "swa_attention_gate_type": cfg.swa_attention_gate_type,
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
    uneven), the queries' second matrices and the gates' larger: at 0.02 the
    softmaxes are flat and every gate a half, and which keys a query attends
    over, or a gate that is one, would move little."""
    if name.endswith("_scale']") or "ik_norm_bias" in name:
        return leaf + 0.2 * jax.random.normal(key, leaf.shape)
    if "router_bias" in name:
        return 0.1 * jax.random.normal(key, leaf.shape)
    if "w_q_b" in name:
        return 30.0 * leaf
    if "w_iq" in name or "w_iw" in name:
        return 6.0 * leaf
    if "w_attn_gate" in name:
        return 12.0 * leaf
    return leaf


def _without_scale(monkeypatch, scale):
    """Both kinds of layer's latent without one of the two scalars."""
    plain = dots3_note.Dots3NoteConfig.latent
    monkeypatch.setattr(
        dots3_note.Dots3NoteConfig, "latent", lambda self, kind:
        replace(plain(self, kind), **{scale: None}))


def drop(dropped, params, cfg, monkeypatch):
    if dropped == "selection":
        monkeypatch.setattr(dsa, "select", lambda scores, topk: jnp.tril(
            jnp.ones(scores.shape, jnp.int8)))
    elif dropped in ("gate_full", "gate_window"):
        plain = dots3_note._gated
        monkeypatch.setattr(
            dots3_note, "_gated", lambda x, attn, w_gate, scope:
            (attn, jnp.float32(1.0)) if scope.endswith(dropped[5:])
            else plain(x, attn, w_gate, scope))
    elif dropped in ("s_q", "s_kv"):     # in both kinds of layer
        _without_scale(monkeypatch, dropped[2:] + "_lora_scale")
    elif dropped == "window_shorter":
        cfg = replace(cfg, sliding_window_size=cfg.sliding_window_size - 1)
    elif dropped == "rope_bases_swapped":
        cfg = replace(cfg, rope_theta=cfg.swa_rope_theta,
                      swa_rope_theta=cfg.rope_theta)
    else:
        raise ValueError(dropped)
    return params, cfg


DOTS3 = family_cases.Family(
    module=dots3_note, reference=reference, cfg=CFG, seq=SEQ, flash=FLASH,
    flash_seq=FLASH_SEQ, published=published, moved=moved,
    extras=("picked", "selections"), drop=drop, dropped=(
        "selection", "gate_full", "gate_window", "s_q", "s_kv",
        "rope_bases_swapped"),
    top_k=CFG.num_experts_per_tok, sliced_vocab=32, accum_steps=(1,),
    train_drawn=True, rows=1,
    wrong=(dict(first_layer=5, num_hidden_layers=5),   # past the depth
           dict(layer_types=("full_attention", "mamba") * 4),
           dict(attention_gate_type="elementwise"),
           dict(swa_attention_gate_type="none"),
           dict(experts_held=(6, 4))))
globals().update(family_cases.cases(DOTS3))


# -- the stack, the two geometries ----------------------------------------

def test_the_tiny_stack_is_the_published_pattern():
    assert [(kind, n) for _, kind, n in lm.runs(CFG.layers)] == [
        ("dense_full", 1), ("moe_full", 1), ("moe_window", 2)]
    assert CFG.n_moe_layers == 3 and CFG.index_topk < SEQ \
        and CFG.sliding_window_size < SEQ
    # One key past a tile, as 513 is at 512.
    assert FLASH.sliding_window_size == FLASH.attn_blk_k + 1
    # Every full layer owns an indexer: a run's forward kernel, its two
    # backward kernels, the probabilities' and the indexer's pair each, the
    # window layers the flash kernels.
    from ray_tpu.parallel.collectives import kernel_census
    tokens, targets = batch(FLASH, FLASH_SEQ, rows=1)
    census = kernel_census(jax.make_jaxpr(jax.grad(
        lambda p: dots3_note.loss_fn(p, replace(FLASH, remat=False), tokens,
                                     targets)[0]))(drawn(DOTS3, FLASH)))
    assert {name: census[name] for name in census
            if name.startswith(("dsa_", "flash_"))} == {
        "dsa_fwd": 2, "dsa_probs": 2, "dsa_bwd_dq": 2, "dsa_bwd_dkv": 2,
        "dsa_index_fwd": 2, "dsa_index_bwd": 2,
        "flash_fwd_win": 1, "flash_bwd_win": 1}


def test_the_two_geometries_lie_side_by_side():
    """Unlike head counts, ranks and head sizes in one tree; the indexer's
    leaves in the full layers alone, the gate a head in both."""
    full, window = CFG.latent("full"), CFG.latent("window")
    assert (full.num_attention_heads, full.q_lora_rank, full.kv_lora_rank,
            full.qk_nope_head_dim) != (
        window.num_attention_heads, window.q_lora_rank, window.kv_lora_rank,
        window.qk_nope_head_dim)
    assert full.window is None and window.window == CFG.sliding_window_size
    shapes = jax.eval_shape(partial(dots3_note.init, CFG),
                            jax.random.PRNGKey(0))
    for run, latent in (("run01_moe_full", full),
                        ("run02_moe_window", window)):
        stack, h = shapes[run], latent.num_attention_heads
        qk = latent.qk_nope_head_dim + latent.qk_rope_head_dim
        assert stack["w_q_b"].shape[1:] == (latent.q_lora_rank, h, qk)
        assert stack["w_kv_a"].shape[1:] == (
            CFG.hidden_size, latent.kv_lora_rank + latent.qk_rope_head_dim)
        assert stack["w_kv_b"].shape[1:] == (
            latent.kv_lora_rank, h,
            latent.qk_nope_head_dim + latent.v_head_dim)
        assert stack["wo"].shape[1:] == (h, latent.v_head_dim,
                                         CFG.hidden_size)
        assert stack["w_attn_gate"].shape[1:] == (CFG.hidden_size, h)
        assert ("w_iq" in stack) == (run == "run01_moe_full")
    # The scalars: sqrt(hidden / rank), and none without the published flag.
    assert full.q_lora_scale == math.sqrt(64 / 48) \
        and full.kv_lora_scale == math.sqrt(64 / 32) \
        and window.q_lora_scale == math.sqrt(64 / 40) \
        and window.kv_lora_scale == math.sqrt(64 / 48)
    off = replace(CFG, apply_mla_qkv_lora_rescale=False).latent("window")
    assert off.q_lora_scale is None and off.kv_lora_scale is None


def test_the_published_pattern_and_the_cut():
    """46 layers, 13 of them full (0, 1, 5, 9, ..., 45); published layers
    0-4 with 8 of 256 experts and an eighth of the vocabulary are 1.82 B
    parameters in three runs."""
    whole = dots3_note.config("dots3-note-prev")
    full = [l for l, kind in enumerate(whole.layer_types)
            if kind == "full_attention"]
    assert full == [0] + list(range(1, 46, 4)) and len(full) == 13
    assert len(whole.layer_types) == 46 == whole.num_hidden_layers
    cut = replace(whole, num_hidden_layers=5, experts_held=(0, 8),
                  vocab_size=19008)
    assert cut.layers == ("dense_full", "moe_full", "moe_window",
                          "moe_window", "moe_window")
    shapes = jax.eval_shape(partial(dots3_note.init, cut),
                            jax.random.PRNGKey(0))
    held = sum(math.prod(leaf.shape) for path, leaf in
               jax.tree_util.tree_leaves_with_path(shapes)
               if leaf.ndim >= (2 if len(path) == 1 else 3))
    assert abs(held / 1e9 - 1.822) < 0.002
    assert shapes["run01_moe_full"]["w_q_b"].shape == (1, 1024, 128, 192)
    assert shapes["run01_moe_full"]["w_iq"].shape == (1, 1024, 64, 128)
    assert shapes["run02_moe_window"]["w_kv_b"].shape == (3, 1024, 64, 320)
    assert shapes["run02_moe_window"]["w_gate"].shape == (3, 8, 5120, 1536)
    assert cut.latent("full").q_lora_scale == math.sqrt(5) \
        and cut.latent("full").kv_lora_scale == math.sqrt(10) \
        and cut.latent("window").kv_lora_scale == math.sqrt(5)


# -- the window -------------------------------------------------------------

@pytest.mark.parametrize("which,cfg,seq", [("both", CFG, SEQ),
                                           ("both_flash", FLASH, FLASH_SEQ)])
def test_the_masks_are_the_selection_and_the_window(which, cfg, seq, request):
    """What the reference attends over: a full layer's rows keep ``min(t +
    1, index_topk)`` causal keys, a window layer's the query's own key and
    the ``sliding_window_size - 1`` before it."""
    attended = np.asarray(request.getfixturevalue(which)["extras"][1])
    t, s = np.arange(seq)[:, None], np.arange(seq)[None]
    for pairs in attended[:2]:
        assert (pairs.sum(-1) == np.minimum(t[:, 0] + 1,
                                            cfg.index_topk)).all()
        assert not pairs[:, s > t].any()
    want = (s <= t) & (t - s < cfg.sliding_window_size)
    for pairs in attended[2:]:
        assert (pairs == want).all()


def test_a_window_one_key_shorter_moves_the_logits(both, both_flash,
                                                   monkeypatch):
    """``t - s < window - 1``: one key of 20 a query at the small size, and
    at the kernels' size the one key that lies past the tile's edge (a
    window of 128 for 129): less than a dropped term moves, and still far
    outside the agreement."""
    for found, cfg, seq in ((both, CFG, SEQ), (both_flash, FLASH,
                                               FLASH_SEQ)):
        params, short = drop("window_shorter", drawn(DOTS3, cfg), cfg,
                             monkeypatch)
        tokens, _ = batch(cfg, seq, rows=1)
        got = forward_alone(DOTS3, params, short, tokens)
        _, want = found["logits"]
        assert float(jnp.abs(got - want).max()) > 10 * DOTS3.logits_tol \
            * found["rms"]


@pytest.mark.parametrize("S,window,tile,executed,fill", [
    (8192, 513, 512, 31, 0.5010), (8192, 513, 256, 93, 0.6680),
    (4096, 513, 512, 15, 0.5010)])
def test_the_cells_window_tile_fill(S, window, tile, executed, fill):
    """A window one key longer than the tile: two tiles a row of tiles at
    512, each cut by an edge (the diagonal's, and the window's, which leaves
    of the tile before the diagonal's the triangle the diagonal's lacks and
    one key more a row), so half of what runs is kept; three at 256, the
    middle one whole."""
    cfg = replace(dots3_note.config("dots3-note-prev"), num_hidden_layers=5,
                  attn_impl="flash", attn_blk_q=tile, attn_blk_k=tile)
    assert window_tile_census(S, window, tile, tile)["executed"] == executed
    assert dots3_note.window_tile_fill(cfg, S) == pytest.approx(fill,
                                                                abs=1e-4)
    assert dots3_note.window_tile_fill(
        replace(cfg, attn_impl="dot"), S) is None
    assert dots3_note.window_tile_fill(
        replace(cfg, num_hidden_layers=2), S) is None   # no window layer


# -- the gates, the loss's terms --------------------------------------------

@pytest.mark.parametrize("which", ["both", "both_flash"])
def test_the_gates_means_and_the_indexers_loss_are_the_references(which,
                                                                  request):
    found = request.getfixturevalue(which)
    metrics = found["metrics"]
    _, _, index_loss, gates = found["extras"]
    np.testing.assert_allclose(metrics["attn_gate_mean_full"],
                               gates[:2].mean(), rtol=1e-5)
    np.testing.assert_allclose(metrics["attn_gate_mean_window"],
                               gates[2:].mean(), rtol=1e-5)
    np.testing.assert_allclose(found["aux"]["gate_window"], gates[2:],
                               rtol=1e-5)
    # The gates are neither dropped nor dead: off a half, well under one.
    assert 0.3 < float(gates.min()) and float(gates.max()) < 0.7 \
        and float(jnp.abs(gates - 0.5).max()) > 1e-3
    np.testing.assert_allclose(metrics["dsa_index_loss"], index_loss.mean(),
                               rtol=1e-4)
    assert float(index_loss.mean()) > 1e-3
    np.testing.assert_allclose(
        metrics["total_loss"],
        metrics["loss"] + metrics["dsa_index_loss"], rtol=1e-6)
    seq, topk = found["seq"], found["cfg"].index_topk
    kept = topk * (topk + 1) // 2 + (seq - topk) * topk
    np.testing.assert_allclose(metrics["dsa_selected_share"],
                               kept / (seq * (seq + 1) // 2), rtol=1e-6)


def test_the_rope_bases_are_each_on_their_own_kind(monkeypatch):
    """``lm.mla_qkv`` rotates a full layer by ``rope_theta`` and a window
    layer by ``swa_rope_theta``."""
    seen = []
    plain = lm.rope_interleaved
    monkeypatch.setattr(lm, "rope_interleaved", lambda x, positions, theta:
                        seen.append(theta) or plain(x, positions, theta))
    tokens, _ = batch(CFG, SEQ)
    jax.eval_shape(partial(dots3_note.forward, cfg=CFG),
                   drawn(DOTS3, CFG), tokens=tokens)
    # q and k of the latent, and the indexer's q and k, a full layer (two
    # runs of one); q and k a window layer (one trace a run).
    assert seen == [CFG.rope_theta] * 8 + [CFG.swa_rope_theta] * 2
    assert CFG.rope_theta != CFG.swa_rope_theta


# -- the share ------------------------------------------------------------

def test_the_shares_add_up_to_the_uncut_layer():
    """Thirty-two shares of one expert each, as the cell's thirty-two
    chips, at 8 experts a token and a scaling factor of 1."""
    family_cases.shares_add_up(reference, 32, 32, top_k=8, scale=1.0)


# -- the train step's gauges ---------------------------------------------------

def test_the_step_feeds_the_gauges():
    """Both terms fall on a repeated batch, and the gauges hold the selected
    share, ``L_I``, the window's tile fill and the gates' means, one series
    a kind of layer."""
    from ray_tpu.util import metrics as metrics_mod
    found = family_cases.trained(DOTS3, 1)
    topk = FLASH.index_topk
    share = (topk * (topk + 1) // 2 + (FLASH_SEQ - topk) * topk) / (
        FLASH_SEQ * (FLASH_SEQ + 1) // 2)
    index_losses = [m["dsa_index_loss"] for m in found["metrics"]]
    assert all(0.0 < loss < 10.0 for loss in index_losses)
    gauges = found["gauges"]
    np.testing.assert_allclose(gauges["ray_tpu_train_dsa_selected_share"],
                               share, rtol=1e-6)
    assert gauges["ray_tpu_train_dsa_index_loss"] in index_losses
    fill = dots3_note.window_tile_fill(FLASH, FLASH_SEQ)
    assert 0.4 < fill < 0.7
    np.testing.assert_allclose(
        gauges["ray_tpu_train_attn_window_tile_fill"], fill, rtol=1e-6)
    series = {entry["name"]: entry["series"]
              for entry in metrics_mod.snapshot()}[
        "ray_tpu_train_attn_gate_mean"]
    assert sorted(key[0] for key in series) == ["full", "window"]
    for (kind,), value in series.items():
        assert value in [m["attn_gate_mean_" + kind]
                         for m in found["metrics"]]
        assert 0.3 < value < 0.7


def test_not_a_number_sets_no_gauge():
    from ray_tpu.util import metrics as metrics_mod
    record = dots3_note.RECORDED_METRICS["attn_gate_mean_window"]
    record(0.25)
    record(float("nan"))
    series = {entry["name"]: entry["series"]
              for entry in metrics_mod.snapshot()}[
        "ray_tpu_train_attn_gate_mean"]
    assert series[("window",)] == 0.25
    for name in ("dsa_selected_share", "dsa_index_loss"):
        dots3_note.RECORDED_METRICS[name](float("nan"))
    assert all(value == value for entry in metrics_mod.snapshot()
               for value in entry["series"].values()
               if entry["name"].startswith("ray_tpu_train_dsa"))


def test_the_kernels_refuse_a_mesh_of_several_devices():
    mesh = build_mesh(MeshConfig(dp=2, fsdp=1, tp=1),
                      devices=jax.devices()[:2])
    step = make_train_step(FLASH, mesh)
    state = abstract_train_state(FLASH, mesh)
    tokens, targets = batch(FLASH, FLASH_SEQ)
    with pytest.raises(NotImplementedError, match="one\n?\\s*device"):
        step.lower(state, {"tokens": tokens, "targets": targets})


# -- the kernels at this family's head sizes -----------------------------------

def _qkv(heads, d, dv, seq=256, seed=1):
    ks = jax.random.split(jax.random.PRNGKey(seed), 4)
    return (jax.random.normal(ks[0], (1, seq, heads, d)) * d ** -0.25,
            jax.random.normal(ks[1], (1, seq, heads, d)) * d ** -0.25,
            jax.random.normal(ks[2], (1, seq, heads, dv)),
            jax.random.normal(ks[3], (1, seq, heads, dv)))


def test_the_selections_kernels_at_192_and_128():
    """``dsa_fwd``, ``dsa_bwd_dq``, ``dsa_bwd_dkv`` and ``dsa_probs`` with
    query and value widths that differ (GLM's are equal), against the
    masked attention written out."""
    q, k, v, g = _qkv(1, 192, 128)
    ks = jax.random.split(jax.random.PRNGKey(0), 3)
    selection = dsa.select(dsa.dot_index_scores(
        jax.random.normal(ks[0], (1, 256, 32, 16)),
        jax.random.normal(ks[1], (1, 256, 16)),
        jax.random.normal(ks[2], (1, 256, 32))), 100)
    (out, lse), vjp = jax.vjp(lambda *a: dsa.selected_attention(
        *a, selection, 128, 128, None), q, k, v)
    (want, want_lse), want_vjp = jax.vjp(lambda *a: dsa.dot_selected_attention(
        *a, selection), q, k, v)
    assert out.shape == (1, 256, 1, 128)
    np.testing.assert_allclose(out, want, atol=2e-5)
    np.testing.assert_allclose(lse, want_lse, atol=2e-5)
    for a, b in zip(vjp((g, jnp.zeros_like(lse))),
                    want_vjp((g, jnp.zeros_like(lse)))):
        np.testing.assert_allclose(
            a, b, atol=5e-5 * float(jnp.abs(b).max()) + 1e-6)
    np.testing.assert_allclose(
        dsa.head_probs(q, k, lse, selection, 128, 128),
        dsa.dot_head_probs(q, k, want_lse, selection), atol=2e-5)


def test_the_window_kernels_at_256_and_128():
    """``flash_fwd_win`` and its two backward kernels at heads of 256 | 128
    under a window one key longer than the tile, against
    ``lm.dot_attention`` with the literal mask."""
    q, k, v, g = _qkv(1, 256, 128, seq=512)
    window = 129
    want, want_vjp = jax.vjp(partial(lm.dot_attention, window=window),
                             q, k, v)
    got, got_vjp = jax.vjp(lambda q, k, v: flash_attention(
        q, k, v, True, 128, 128, None, window), q, k, v)
    assert got.shape == (1, 512, 1, 128)
    np.testing.assert_allclose(got, want, atol=1e-4)
    for a, b in zip(got_vjp(g), want_vjp(g)):
        np.testing.assert_allclose(
            a, b, atol=1e-5 + 2e-4 * float(jnp.abs(b).max()))
    # A window of the tile's own length differs by one key a row, and the
    # kernels see it.
    other = flash_attention(q, k, v, True, 128, 128, None, window - 1)
    assert float(jnp.abs(other - want).max()) > 1e-3
