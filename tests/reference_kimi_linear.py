"""Kimi Linear's forward pass and loss, plainly, as the yardstick for
``correct`` of the ``kimi_linear`` family (Kimi-Linear-48B-A3B-Instruct is
one).

Written from the published description of ``KimiLinearForCausalLM`` (Kimi
Delta Attention with a short convolution on q, k and v, latent attention
without positions, sigmoid scores renormalised over the picked experts, one
group, no bias anywhere), in ``jax.numpy`` and float32 under
``jax.default_matmul_precision("highest")``, with no kernel, chunk, layer
scan, remat, sort or grouped matmul, independent of
``ray_tpu/models/kimi_linear.py`` and of ``ray_tpu/ops/kda.py``::

    h        = wte[tokens]
    layer l (1-based), KDA iff l in kda_layers, dense iff l <= first_k_dense_replace:
    x        = RMSNorm(h; g_in)
    KDA:     q | k | v = silu(conv(x Wq)) | silu(conv(x Wk)) | silu(conv(x Wv))     depthwise, causal
             q, k   = q / max(|q|, 1e-6), k / max(|k|, 1e-6) ;  q = q * head_dim^-0.5
             a      = -exp(A_log) * softplus((x W_fa) W_fb + dt_bias) ;  beta = sigmoid(x W_beta)
             token by token, S [keys, values] zero before the first:
               S    = exp(a_t)[:, None] * S
               S    = S + beta_t k_t (v_t - S^T k_t)^T
               o_t  = S^T q_t
             m      = (RMSNorm(o; g_o) * sigmoid((x W_ga) W_gb)) Wo
    MLA:     q = x Wq ;  c | k_r = x W_kv_a ;  c = RMSNorm(c; g_kv) ;  k_n | v = c W_kv_b
             m = softmax(causal([q_n|q_r] [k_n|k_r]^T / sqrt(nope + rope))) v Wo       no rotation: no positions
    h        = h + m
    x        = RMSNorm(h; g_2)
    dense:   W_down(silu(W_gate x) * W_up x)
    experts: s = sigmoid(x W_r) ;  pick top_k of s + b   (b: selection only)
             w = s[picked] / (sum s[picked] + 1e-20) * scaling
             Shared(x) + sum_{i picked and held} w_i Expert_i(x)
    h        = h + that
    logits   = RMSNorm(h_L; g_f) W_head ;  loss = mean_t -log softmax(logits_t)[target_t]

**The recurrence is the literal one**: a ``lax.scan`` over tokens, one
decay, one read, one write and one query a step. A KDA layer goes a stretch
of ``SEGMENT`` positions after the other; the convolutions' last inputs and
the state pass from stretch to stretch.

**The share.** The parameters hold the experts ``first_expert`` to
``first_expert`` + (how many the stacks hold) of the router's width alone: a
chip's share of a layer. Every held expert runs on every token, one after
the other in a counted loop, weighted by ``w`` (zero where the token did not
pick it); what the absent experts would have added is left out, as the
program leaves it out. With every expert held this is the whole layer.

Latent attention goes by blocks of ``QUERY_ROWS`` query rows against the
keys and values of the whole context, the causal edge one mask over the
block's whole [rows, S] scores, and the head by blocks of positions, so
neither S x S scores for all heads nor [S, vocab] logits exist whole.

It takes the program's parameter tree as it sits on the device (bf16, one
stack a run of layers of one kind, ``run00_dense_kda``, ...) and upcasts one
layer, and inside an expert layer one expert, at a time.
"""

from __future__ import annotations

from functools import partial
from typing import Any, Dict, Tuple

import jax
import jax.numpy as jnp
import numpy as np

F32 = jnp.float32
QUERY_ROWS = 128   # latent attention: query rows a block
SEGMENT = 1024     # a KDA layer, keys and values, the FFN: positions a block
HEAD_ROWS = 1024   # head: positions a block
GROUP = 64         # the recurrence: tokens a rematerialised stretch

_STATIC = ("kda", "nope", "rank", "top_k", "scaling", "renormalize", "eps",
           "first_expert")


def arguments(config: Dict[str, Any]) -> Dict[str, Any]:
    """What ``forward`` and ``loss`` take besides arrays, from the published
    keys of a configuration file (and, for the share, its ``deployment``:
    the first expert held here; how many are held the parameters say)."""
    n = config["num_hidden_layers"]
    held = config.get("deployment", {}).get("experts_held", {})
    kda = set(config["linear_attn_config"]["kda_layers"])
    return {"kda_layers": tuple(l in kda for l in range(1, n + 1)),
            "first_k_dense_replace": config["first_k_dense_replace"],
            "nope": config["qk_nope_head_dim"],
            "rank": config["kv_lora_rank"],
            "top_k": config["num_experts_per_token"],
            "scaling": config["routed_scaling_factor"],
            "renormalize": config["moe_renormalize"],
            "eps": config["rms_norm_eps"],
            "first_expert": held.get("first", 0)}


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


def _swiglu(x, w_gate, w_up, w_down):
    return (jax.nn.silu(x @ w_gate) * (x @ w_up)) @ w_down


def _routing(x, router, bias, top_k, scaling, renormalize):
    """(picked [.., K], weight of every expert for every token [.., E])."""
    scores = jax.nn.sigmoid(x @ router)
    _, picked = jax.lax.top_k(scores + jax.lax.stop_gradient(bias), top_k)
    chosen = jax.nn.one_hot(picked, scores.shape[-1], dtype=F32).sum(-2)
    weights = scores * chosen
    if renormalize and top_k > 1:
        weights = weights / (weights.sum(-1, keepdims=True) + 1e-20)
    return picked, weights * scaling


_EXPERT_LEAVES = ("w_gate", "w_up", "w_down")


def _ffn(h, w, top_k, scaling, renormalize, eps, first_expert):
    """h + the dense SwiGLU, or + the shared expert and the held experts'
    part of the routed sum, of RMSNorm(h). Returns (h, picked or None)."""
    x = _rmsnorm(h, w["ln2_scale"], eps)
    if "router" not in w:
        return h + _swiglu(x, w["w_gate"], w["w_up"], w["w_down"]), None
    picked, weights = _routing(x, w["router"], w["router_bias"], top_k,
                               scaling, renormalize)
    m = _swiglu(x, w["shared_w_gate"], w["shared_w_up"], w["shared_w_down"])

    def add_expert(e, m):
        """m + w_e Expert_e(x), on held expert e's weights upcast alone."""
        w_gate, w_up, w_down = (
            jax.lax.dynamic_index_in_dim(w[name], e, 0, keepdims=False
                                         ).astype(F32)
            for name in _EXPERT_LEAVES)
        return m + jnp.take(weights, first_expert + e, axis=-1)[..., None] \
            * _swiglu(x, w_gate, w_up, w_down)

    # A counted loop, one held expert after the other.
    return h + jax.lax.fori_loop(0, w["w_gate"].shape[0], add_expert, m), \
        picked


def _delta_rule(state, q, k, v, a, beta):
    """(state after the last position, o [B, S, H, V]) of the gated delta
    rule from ``state`` [B, H, K, V], one position a step. q, k, a [B, S,
    H, K]; v [B, S, H, V]; beta [B, S, H]."""

    def step(state, at):
        q_t, k_t, v_t, a_t, beta_t = at
        state = jnp.exp(a_t)[..., None] * state
        read = (state * k_t[..., None]).sum(-2)                 # S^T k_t
        state = state + (beta_t[..., None] * k_t)[..., None] \
            * (v_t - read)[..., None, :]
        return state, (state * q_t[..., None]).sum(-2)

    # Token by token, ``GROUP`` tokens a rematerialised stretch: a backward
    # pass through this holds a group's states, not a sequence's.
    seq = q.shape[1]
    group = GROUP if seq % GROUP == 0 else seq
    by_group = tuple(
        x.swapaxes(0, 1).reshape((seq // group, group) + x.shape[:1]
                                 + x.shape[2:]) for x in (q, k, v, a, beta))
    state, o = jax.lax.scan(
        jax.checkpoint(lambda state, xs: jax.lax.scan(step, state, xs)),
        state, by_group)
    return state, o.reshape((seq,) + o.shape[2:]).swapaxes(0, 1)


def _kda_layer(h, w, ffn):
    """A KDA layer with its FFN on h [B, S, d], a stretch of ``SEGMENT``
    positions after the other: the three convolutions' last inputs and the
    state pass from stretch to stretch, zero before the first."""
    eps = ffn.keywords["eps"]
    taps, (_, heads, width) = w["conv_q"].shape[0], w["wq"].shape
    batch, rows = h.shape[0], min(SEGMENT, h.shape[1])
    flat = lambda m: m.reshape(m.shape[0], -1)

    def stretch(carry, h_s):
        tails, state = carry
        x = _rmsnorm(h_s, w["ln_in_scale"], eps)
        new_tails, convolved = [], []
        for name, tail in zip("qkv", tails):
            padded = jnp.concatenate([tail, x @ flat(w["w" + name])], axis=1)
            new_tails.append(padded[:, rows:])
            convolved.append(jax.nn.silu(sum(
                w["conv_" + name][t] * padded[:, t:t + rows]
                for t in range(taps))).reshape(batch, rows, heads, width))
        q, k, v = convolved
        q = q / jnp.maximum(jnp.linalg.norm(q, axis=-1, keepdims=True), 1e-6)
        k = k / jnp.maximum(jnp.linalg.norm(k, axis=-1, keepdims=True), 1e-6)
        a = -jnp.exp(w["A_log"])[:, None] * jax.nn.softplus(
            ((x @ w["w_fa"]) @ flat(w["w_fb"])).reshape(q.shape)
            + w["dt_bias"])
        state, o = _delta_rule(state, q * width ** -0.5, k, v, a,
                               jax.nn.sigmoid(x @ w["w_beta"]))
        gate = jax.nn.sigmoid(
            ((x @ w["w_ga"]) @ flat(w["w_gb"])).reshape(o.shape))
        m = (_rmsnorm(o, w["o_norm_scale"], eps) * gate).reshape(
            batch, rows, -1) @ w["wo"].reshape(-1, w["wo"].shape[-1])
        return (tuple(new_tails), state), ffn(h_s + m, w)

    start = (tuple(jnp.zeros((batch, taps - 1, heads * width), F32)
                   for _ in "qkv"),
             jnp.zeros((batch, heads, width, width), F32))
    h, picked = jax.lax.scan(stretch, start, _segments(h, rows))[1]
    return _whole(h), None if picked is None else _whole(picked)


def _mla_layer(h, w, ffn, nope, rank):
    """A latent-attention layer with its FFN on h [B, S, d]: keys and values
    of the whole context first, then ``QUERY_ROWS`` query rows at a time
    against all of them. No rotation anywhere: the layer has no positions."""
    eps = ffn.keywords["eps"]
    seq = h.shape[1]

    def keys_values(h_s):
        kv_a = _rmsnorm(h_s, w["ln_in_scale"], eps) @ w["w_kv_a"]
        kv = jnp.einsum("bsr,rhk->bshk", _rmsnorm(
            kv_a[..., :rank], w["kv_norm_scale"], eps), w["w_kv_b"])
        shared = jnp.broadcast_to(
            kv_a[..., None, rank:], kv.shape[:3] + (kv_a.shape[-1] - rank,))
        return jnp.concatenate([kv[..., :nope], shared], -1), kv[..., nope:]

    k, v = (_whole(a) for a in jax.lax.map(
        keys_values, _segments(h, min(SEGMENT, seq))))
    rows = min(QUERY_ROWS, seq)

    def queries(at):
        start, h_s = at
        q = jnp.einsum("bsd,dhk->bshk",
                       _rmsnorm(h_s, w["ln_in_scale"], eps), w["wq"])
        scores = jnp.einsum("bqhk,bthk->bhqt", q, k) / np.sqrt(q.shape[-1])
        allowed = jnp.arange(seq)[None] <= start + jnp.arange(rows)[:, None]
        a = jnp.einsum("bhqt,bthk->bqhk", jax.nn.softmax(
            jnp.where(allowed, scores, -jnp.inf), axis=-1), v)
        return ffn(h_s + jnp.einsum("bqhk,hkd->bqd", a, w["wo"]), w)

    # Rematerialised a block at a time, so that a backward pass through
    # this holds one block's [rows, S] scores, as the forward pass does.
    h, picked = jax.lax.map(jax.checkpoint(queries), (
        jnp.arange(0, seq, rows), _segments(h, rows)))
    return _whole(h), None if picked is None else _whole(picked)


def block(h, w: Dict[str, jax.Array], *, kda, nope, rank, top_k, scaling,
          renormalize, eps, first_expert):
    """One layer on one layer's weights (the program's names; float32 but
    for an expert layer's ``w_gate`` / ``w_up`` / ``w_down``, upcast an
    expert at a time). Returns (h, picked [B, S, K] or None)."""
    ffn = partial(_ffn, top_k=top_k, scaling=scaling, renormalize=renormalize,
                  eps=eps, first_expert=first_expert)
    return _kda_layer(h, w, ffn) if kda else _mla_layer(h, w, ffn, nope, rank)


def _layer(stack, index, dynamic: bool):
    """Layer ``index`` of a stack, float32 but for an expert layer's expert
    weights, which ``_ffn`` upcasts one expert at a time."""
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
    """Final RMSNorm and head on a block of positions: (the logits at the
    block's own rows ``local`` [B, P] where ``inside``, else 0; sum of nll;
    sum of logits squared). The block's [rows, vocab] logits stay inside."""
    logits = _rmsnorm(h, params["lnf_scale"].astype(F32), eps) \
        @ params["lm_head"].astype(F32)
    logp = jax.nn.log_softmax(logits, axis=-1)
    nll = -jnp.take_along_axis(logp, targets[..., None], axis=-1)[..., 0]
    sampled = jnp.where(inside[..., None], jnp.take_along_axis(
        logits, local[..., None], axis=1), 0.0)
    return sampled, nll.sum(-1), (logits ** 2).sum()


def _walk(kda_layers, first_k_dense_replace):
    """(KDA?, the name of its run's stack, index within it) of every layer
    in order; a run is a stretch of layers of one kind, a kind the FFN
    (dense in the leading layers, else experts) and the mixer."""
    kinds = [("dense_" if i < first_k_dense_replace else "moe_")
             + ("kda" if kda else "mla") for i, kda in enumerate(kda_layers)]
    run, index = -1, 0
    for i, kind in enumerate(kinds):
        if i == 0 or kinds[i - 1] != kind:
            run, index = run + 1, 0
        yield kda_layers[i], f"run{run:02d}_{kind}", index
        index += 1


def forward(params: Dict[str, Any], tokens, targets, positions, *,
            kda_layers, first_k_dense_replace, with_picked: bool = False,
            **kw) -> Tuple[jax.Array, ...]:
    """(logits at ``positions`` [B, P, vocab], loss per sequence [B], RMS of
    all logits), float32; with ``with_picked`` also the experts picked
    [L_moe, B, S, K]. ``params`` is the program's tree."""
    eps = kw["eps"]
    with jax.default_matmul_precision("highest"):
        h = _embed(params["wte"], tokens)
        picked = []
        for kda, stack, index in _walk(kda_layers, first_k_dense_replace):
            h, p = _block_at(h, params[stack], jnp.int32(index), kda=kda,
                             **kw)
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
        vocab = params["lm_head"].shape[-1]
        out = (sampled, nll / seq,
               jnp.sqrt(squares / (float(tokens.size) * vocab)))
        return out + (jnp.stack(picked),) if with_picked else out


def loss(params: Dict[str, Any], tokens, targets, *, kda_layers,
         first_k_dense_replace, **kw) -> jax.Array:
    """Mean loss over all positions, differentiable in ``params``: what the
    gradient check takes the reference's gradients of. One program, the
    layers walked in Python, each rematerialised in the backward pass; for
    small depths and short sequences only."""
    with jax.default_matmul_precision("highest"):
        h = jnp.take(params["wte"], tokens, axis=0).astype(F32)
        for kda, stack, index in _walk(kda_layers, first_k_dense_replace):
            h = jax.checkpoint(
                lambda h, w, kda=kda: block(h, w, kda=kda, **kw)[0])(
                h, _layer(params[stack], index, dynamic=False))
        logits = _rmsnorm(h, params["lnf_scale"].astype(F32), kw["eps"]) \
            @ params["lm_head"].astype(F32)
        logp = jax.nn.log_softmax(logits, axis=-1)
        return -jnp.take_along_axis(
            logp, targets[..., None], axis=-1)[..., 0].mean()
