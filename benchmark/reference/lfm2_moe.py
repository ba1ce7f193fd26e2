"""LFM2's expert models' forward pass and loss, plainly, as the yardstick for
``correct`` of the ``lfm2_moe`` family (LFM2-24B-A2B is one).

Written from ``transformers``' ``modeling_lfm2.py`` (``Lfm2ShortConv``,
``Lfm2Attention``, ``Lfm2DecoderLayer``) and, for the expert block, from the
published keys (sigmoid scores, ``norm_topk_prob``, ``use_expert_bias``,
``routed_scaling_factor``, no shared expert), in ``jax.numpy`` and float32
under ``jax.default_matmul_precision("highest")``, with no kernel, layer
scan, remat, sort or grouped matmul, independent of
``ray_tpu/models/lfm2.py``::

    h        = wte[tokens]
    layer l of the layers that run, dense iff l < num_dense_layers:
    x        = RMSNorm(h; g_op)
    conv:    B | C | u = x W_in
             z_t = sum_k w_k (B * u)_(t-K+1+k)          the literal K-term sum, zeros before the first token
             m   = (C * z) W_out
    attn:    q | k | v = x Wq | x Wk | x Wv
             q, k = RMSNorm(q; g_q), RMSNorm(k; g_k)     over a head's width
             q, k = rope(q), rope(k)                     x cos + rotate_half(x) sin
             m   = softmax(causal(q k^T / sqrt(head_dim))) v Wo     query head i reads KV head i // (heads / kv heads)
    h        = h + m
    x        = RMSNorm(h; g_ffn)
    dense:   W_down(silu(W_gate x) * W_up x)
    experts: s = sigmoid(x W_r) ;  pick top_k of s + b   (b: selection only)
             w = s[picked] / (sum s[picked] + 1e-20) * routed_scaling_factor
             sum_{i picked and held} w_i Expert_i(x)
    h        = h + that
    logits   = RMSNorm(h_L; g_emb) wte^T ;  loss = mean_t -log softmax(logits_t)[target_t]

**The share.** The parameters hold the experts ``first_expert`` to
``first_expert`` + (how many the stacks hold) of the router's width alone: a
chip's share of a layer. Every held expert runs on every token, one after
the other in a counted loop, weighted by ``w`` (zero where the token did
not pick it); what the absent experts would have added is left out, as the
program leaves it out, and a token that picked no held expert gets zero.
With every expert held this is the whole layer.

A convolution layer's mixer goes by stretches of ``SEGMENT`` positions for
its two projections, with the gated product ``B * u`` of the whole sequence
between them, shifted a row a tap; attention goes by blocks of
``QUERY_ROWS`` query rows against the keys and values of the whole context;
the FFN goes with those rows, and the head by blocks of positions, so
neither S x S scores for all heads nor [S, vocab] logits nor a [S,
intermediate] array exist whole.

It takes the program's parameter tree as it sits on the device (bf16, one
stack a run of layers of one kind, ``run00_dense_conv``, ...) and upcasts
one layer, and inside an expert layer one expert, at a time.
"""

from __future__ import annotations

from functools import partial
from typing import Any, Dict, Tuple

import jax
import jax.numpy as jnp
import numpy as np

F32 = jnp.float32
QUERY_ROWS = 128   # attention and the FFN: rows a block
SEGMENT = 1024     # projections, keys and values: positions a block
HEAD_ROWS = 1024   # head: positions a block

_STATIC = ("attention", "theta", "top_k", "scaling", "normalize", "eps",
           "first_expert")


def arguments(config: Dict[str, Any]) -> Dict[str, Any]:
    """What ``forward`` and ``loss`` take besides arrays, from the published
    keys of a configuration file and, for the cut, its ``deployment``: the
    published layer the first layer is (``layers_run.first``) and the first
    expert held here; how many are held the parameters say."""
    deployment = config.get("deployment", {})
    first = deployment.get("layers_run", {}).get("first", 0)
    n = config["num_hidden_layers"]
    return {"layer_types": tuple(config["layer_types"][first:first + n]),
            "num_dense_layers": config["num_dense_layers"],
            "theta": float(config["rope_parameters"]["rope_theta"]),
            "top_k": config["num_experts_per_tok"],
            "scaling": float(config["routed_scaling_factor"]),
            "normalize": config["norm_topk_prob"],
            "eps": config["norm_eps"],
            "first_expert": deployment.get("experts_held", {}).get(
                "first", 0)}


def _rmsnorm(x, scale, eps):
    return x / jnp.sqrt((x * x).mean(-1, keepdims=True) + eps) * scale


def _segments(a, rows):
    """[B, S, ...] -> [S / rows, B, rows, ...]: stretches of a sequence."""
    batch, seq = a.shape[:2]
    assert seq % rows == 0, (seq, rows)
    return a.reshape(batch, seq // rows, rows, *a.shape[2:]).swapaxes(0, 1)


def _whole(a):
    """The inverse of ``_segments``."""
    n, batch, rows = a.shape[:3]
    return a.swapaxes(0, 1).reshape(batch, n * rows, *a.shape[3:])


def _rope(x, start, theta):
    """x [B, rows, H, D] at positions start..: ``x cos + rotate_half(x)
    sin``, angle pos * theta^(-2i/D) for dimensions i and i + D / 2."""
    rows, width = x.shape[1], x.shape[-1]
    inv_freq = 1.0 / (theta ** (jnp.arange(0, width, 2, dtype=F32) / width))
    angles = (start + jnp.arange(rows)).astype(F32)[:, None] * inv_freq
    angles = jnp.concatenate([angles, angles], axis=-1)[None, :, None, :]
    rotated = jnp.concatenate([-x[..., width // 2:], x[..., :width // 2]],
                              axis=-1)
    return x * jnp.cos(angles) + rotated * jnp.sin(angles)


def _swiglu(x, w_gate, w_up, w_down):
    return (jax.nn.silu(x @ w_gate) * (x @ w_up)) @ w_down


def _routing(x, router, bias, top_k, scaling, normalize):
    """(picked [.., K], weight of every expert for every token [.., E])."""
    scores = jax.nn.sigmoid(x @ router)
    _, picked = jax.lax.top_k(scores + jax.lax.stop_gradient(bias), top_k)
    chosen = jax.nn.one_hot(picked, scores.shape[-1], dtype=F32).sum(-2)
    weights = scores * chosen
    if normalize and top_k > 1:
        weights = weights / (weights.sum(-1, keepdims=True) + 1e-20)
    return picked, weights * scaling


_EXPERT_LEAVES = ("w_gate", "w_up", "w_down")


def _ffn(x, w, top_k, scaling, normalize, first_expert):
    """The dense SwiGLU, or the held experts' part of the routed sum.
    Returns (m, picked or None)."""
    if "router" not in w:
        return _swiglu(x, w["w_gate"], w["w_up"], w["w_down"]), None
    picked, weights = _routing(x, w["router"], w["router_bias"], top_k,
                               scaling, normalize)

    def add_expert(e, m):
        """m + w_e Expert_e(x), on held expert e's weights upcast alone."""
        w_gate, w_up, w_down = (
            jax.lax.dynamic_index_in_dim(w[name], e, 0, keepdims=False
                                         ).astype(F32)
            for name in _EXPERT_LEAVES)
        return m + jnp.take(weights, first_expert + e, axis=-1)[..., None] \
            * _swiglu(x, w_gate, w_up, w_down)

    # A counted loop, one held expert after the other, from zero: there is
    # no shared expert.
    return jax.lax.fori_loop(0, w["w_gate"].shape[0], add_expert,
                             jnp.zeros_like(x)), picked


def _short_conv(h, w, eps):
    """h + the double-gated short convolution of one layer, [B, S, d]."""
    seq, d = h.shape[1], h.shape[2]
    stretch = min(SEGMENT, seq)

    def gates(h_s):
        bcx = _rmsnorm(h_s, w["operator_norm_scale"], eps) @ w["w_in"]
        return bcx[..., :d] * bcx[..., 2 * d:], bcx[..., d:2 * d]

    bu, gate_c = (_whole(a) for a in jax.lax.map(gates, _segments(h, stretch)))
    taps = w["conv_w"].shape[0]
    # z_t = sum_k w_k (B u)_(t - (K - 1 - k)), rows before the first zero.
    z = sum(w["conv_w"][k] * jnp.pad(
        bu, ((0, 0), (taps - 1 - k, 0), (0, 0)))[:, :seq]
        for k in range(taps))
    return h + _whole(jax.lax.map(
        lambda cz: cz @ w["w_out"], _segments(gate_c * z, stretch)))


def block(h, w: Dict[str, jax.Array], *, attention, theta, top_k, scaling,
          normalize, eps, first_expert):
    """One layer on one layer's weights (the program's names; float32 but
    for an expert layer's ``w_gate`` / ``w_up`` / ``w_down``, upcast an
    expert at a time). Returns (h, picked [B, S, K] or None)."""
    seq = h.shape[1]
    rows = min(QUERY_ROWS, seq)

    def ffn_rows(h_s):
        m, picked = _ffn(_rmsnorm(h_s, w["ffn_norm_scale"], eps), w, top_k,
                         scaling, normalize, first_expert)
        return h_s + m, picked

    if not attention:
        h, picked = jax.lax.map(jax.checkpoint(ffn_rows),
                                _segments(_short_conv(h, w, eps), rows))
        return _whole(h), None if picked is None else _whole(picked)

    stretch = min(SEGMENT, seq)

    def keys_values(at):
        start, h_s = at
        x = _rmsnorm(h_s, w["operator_norm_scale"], eps)
        k = _rmsnorm(jnp.einsum("bsd,dgk->bsgk", x, w["wk"]),
                     w["k_norm_scale"], eps)
        return _rope(k, start, theta), jnp.einsum("bsd,dgk->bsgk", x,
                                                  w["wv"])

    k, v = (_whole(a) for a in jax.lax.map(
        keys_values, (jnp.arange(0, seq, stretch), _segments(h, stretch))))
    kv_heads, width = k.shape[2], k.shape[3]

    def queries(at):
        start, h_s = at
        x = _rmsnorm(h_s, w["operator_norm_scale"], eps)
        q = _rope(_rmsnorm(jnp.einsum("bsd,dhk->bshk", x, w["wq"]),
                           w["q_norm_scale"], eps), start, theta)
        # Query heads as [kv heads, heads a kv head]: head i reads i // rep.
        q = q.reshape(q.shape[:2] + (kv_heads, -1, width))
        scores = jnp.einsum("bqgjk,btgk->bgjqt", q, k) / np.sqrt(width)
        query, key = start + jnp.arange(rows)[:, None], jnp.arange(seq)[None]
        scores = jnp.where(key <= query, scores, -jnp.inf)
        a = jnp.einsum("bgjqt,btgk->bqgjk", jax.nn.softmax(scores, axis=-1),
                       v)
        a = a.reshape(x.shape[:2] + (-1, width))
        return ffn_rows(h_s + jnp.einsum("bqhk,hkd->bqd", a, w["wo"]))

    # Rematerialised a block at a time, so that a backward pass through
    # this holds one block's [rows, S] scores, as the forward pass does.
    h, picked = jax.lax.map(jax.checkpoint(queries), (
        jnp.arange(0, seq, rows), _segments(h, rows)))
    return _whole(h), None if picked is None else _whole(picked)


def _layer(stack, index, dynamic: bool):
    """Layer ``index`` of a stack, float32 but for an expert layer's expert
    weights, which ``block`` upcasts one expert at a time."""
    is_moe = "router" in stack

    def pick(name, a):
        a = jax.lax.dynamic_index_in_dim(a, index, 0, keepdims=False) \
            if dynamic else a[index]
        return a if is_moe and name in _EXPERT_LEAVES else a.astype(F32)

    return {name: pick(name, a) for name, a in stack.items()}


@partial(jax.jit, static_argnames=_STATIC, donate_argnums=(0,))
def _block_at(h, stack, index, **kw):
    return block(h, _layer(stack, index, dynamic=True), **kw)


@jax.jit
def _embed(wte, tokens):
    return jnp.take(wte, tokens, axis=0).astype(F32)


@partial(jax.jit, static_argnames=("eps",))
def _head_block(h, params, targets, local, inside, *, eps):
    """Final RMSNorm and the tied head on a block of positions: (the logits
    at the block's own rows ``local`` [B, P] where ``inside``, else 0; sum
    of nll; sum of logits squared). The block's [rows, vocab] logits stay
    inside."""
    logits = _rmsnorm(h, params["embedding_norm_scale"].astype(F32), eps) \
        @ params["wte"].astype(F32).T
    logp = jax.nn.log_softmax(logits, axis=-1)
    nll = -jnp.take_along_axis(logp, targets[..., None], axis=-1)[..., 0]
    sampled = jnp.where(inside[..., None], jnp.take_along_axis(
        logits, local[..., None], axis=1), 0.0)
    return sampled, nll.sum(-1), (logits ** 2).sum()


def _walk(layer_types, num_dense_layers):
    """(attention?, the name of its run's stack, index within it) of every
    layer in order; a run is a stretch of layers of one kind, a kind the
    FFN (dense in the leading layers, else experts) and the mixer."""
    kinds = [("dense_" if i < num_dense_layers else "moe_") + kind
             for i, kind in enumerate(layer_types)]
    run, index = -1, 0
    for i, kind in enumerate(kinds):
        if i == 0 or kinds[i - 1] != kind:
            run, index = run + 1, 0
        yield layer_types[i] == "full_attention", \
            f"run{run:02d}_{kind}", index
        index += 1


def forward(params: Dict[str, Any], tokens, targets, positions, *,
            layer_types, num_dense_layers, with_picked: bool = False, **kw
            ) -> Tuple[jax.Array, ...]:
    """(logits at ``positions`` [B, P, vocab], loss per sequence [B], RMS of
    all logits), float32; with ``with_picked`` also the experts picked
    [L_moe, B, S, K]. ``params`` is the program's tree."""
    eps = kw["eps"]
    with jax.default_matmul_precision("highest"):
        h = _embed(params["wte"], tokens)
        picked = []
        for attention, stack, index in _walk(layer_types, num_dense_layers):
            h, p = _block_at(h, params[stack], jnp.int32(index),
                             attention=attention, **kw)
            if p is not None:
                picked.append(p)
        seq = tokens.shape[1]
        nll, squares, sampled = 0.0, 0.0, 0.0
        for start in range(0, seq, HEAD_ROWS):
            rows = slice(start, min(start + HEAD_ROWS, seq))
            inside = (positions >= rows.start) & (positions < rows.stop)
            local = jnp.clip(positions - rows.start, 0,
                             rows.stop - rows.start - 1)
            at_rows, nll_sum, square_sum = _head_block(
                h[:, rows], params, targets[:, rows], local, inside, eps=eps)
            nll, squares = nll + nll_sum, squares + square_sum
            sampled = sampled + at_rows
        vocab = params["wte"].shape[0]
        out = (sampled, nll / seq,
               jnp.sqrt(squares / (float(tokens.size) * vocab)))
        return out + (jnp.stack(picked),) if with_picked else out


def loss(params: Dict[str, Any], tokens, targets, *, layer_types,
         num_dense_layers, **kw) -> jax.Array:
    """Mean loss over all positions, differentiable in ``params``: what the
    gradient check takes the reference's gradients of. One program, the
    layers walked in Python, each rematerialised in the backward pass; for
    small depths and short sequences only."""
    with jax.default_matmul_precision("highest"):
        h = jnp.take(params["wte"], tokens, axis=0).astype(F32)
        for attention, stack, index in _walk(layer_types, num_dense_layers):
            h = jax.checkpoint(
                lambda h, w, attention=attention: block(
                    h, w, attention=attention, **kw)[0])(
                h, _layer(params[stack], index, dynamic=False))
        logits = _rmsnorm(
            h, params["embedding_norm_scale"].astype(F32), kw["eps"]) \
            @ params["wte"].astype(F32).T
        logp = jax.nn.log_softmax(logits, axis=-1)
        return -jnp.take_along_axis(
            logp, targets[..., None], axis=-1)[..., 0].mean()
