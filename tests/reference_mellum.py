"""Mellum's forward pass and loss, plainly, as the yardstick for ``correct`` of
the ``mellum`` family (Mellum2-12B-A2.5B is one).

Written from the published config's keys and the implementations they name
(``transformers``' ``_compute_yarn_parameters`` for the full layers' rope,
``Qwen3MoeSparseMoeBlock`` for the router), in ``jax.numpy`` and float32
under ``jax.default_matmul_precision("highest")``, with no kernel, pair
table, layer scan, remat, sort or grouped matmul, and importing nothing of
``ray_tpu``::

    h        = wte[tokens]
    layer l, kind layer_types[l]:
    x        = RMSNorm(h; g_in)
    q | k | v = x Wq | x Wk | x Wv
    q, k     = RMSNorm(q; g_q), RMSNorm(k; g_k)          over a head's width (assumed: no key says so)
    q, k     = rope_kind(q), rope_kind(k)                m (x cos + rotate_half(x) sin), angle pos * f[i]
               rope_type default: f[i] = theta^(-2i/D), m = 1
               rope_type yarn:    e[i] = theta^(-2i/D); d(n) = D ln(original / (2 pi n)) / (2 ln theta)
                                  low = floor(d(beta_fast)), high = ceil(d(beta_slow)), clipped to [0, D - 1]
                                  r[i] = clip((i - low) / (high - low), 0, 1)
                                  f[i] = (1 - r[i]) e[i] + r[i] e[i] / factor,  m = attention_factor
    a        = softmax(mask(q k^T / sqrt(head_dim))) v   query head i reads KV head i // (heads / kv heads)
               mask: key j <= query i, on sliding_attention also i - j < sliding_window
    h        = h + a Wo
    x        = RMSNorm(h; g_post)
    p        = softmax(x W_r) over all the experts ;  pick top_k of p
    w        = p[picked] / sum p[picked]                 (norm_topk_prob)
    h        = h + sum_{i picked and held} w_i Expert_i(x)
    logits   = RMSNorm(h_L; g_f) W_head ;  loss = mean_t -log softmax(logits_t)[target_t]

**The share.** The parameters hold the experts ``first_expert`` to
``first_expert`` + (how many the stacks hold) of the router's width alone: a
chip's share of a layer. Every held expert runs on every token, one after
the other in a counted loop, weighted by a dense ``[tokens, experts]``
matrix ``w`` (zero where the token did not pick the expert); what the absent
experts would have added is left out, as the program leaves it out. With
every expert held this is the whole layer.

Attention goes by blocks of ``QUERY_ROWS`` query rows against the keys and
values of the whole context, the causal edge and the window one mask over
the block's whole [rows, S] scores; the rest of a layer goes with those
rows, and the head by blocks of positions, so neither S x S scores for all
heads nor [S, vocab] logits exist whole.

It takes the program's parameter tree as it sits on the device (bf16, one
stack a run of layers of one kind, ``run00_sliding_attention``, ...) and
upcasts one layer, and inside it one expert, at a time.
"""

from __future__ import annotations

import math
from functools import partial
from typing import Any, Dict, Tuple

import jax
import jax.numpy as jnp
import numpy as np

F32 = jnp.float32
QUERY_ROWS = 128   # attention and the layer's rest: query rows a block
SEGMENT = 1024     # keys and values: positions a block
HEAD_ROWS = 1024   # head: positions a block

_STATIC = ("sliding", "window", "rope", "top_k", "norm_topk_prob", "eps",
           "first_expert")


def rope_table(parameters: Dict[str, Any], head_dim: int
               ) -> Tuple[Tuple[float, ...], float]:
    """(inverse frequencies of the head_dim / 2 pairs, factor on cos and
    sin) of one kind of layer's ``rope_parameters``, in float64 rounded to
    float32 (the module text)."""
    theta = float(parameters["rope_theta"])
    pairs = np.arange(0, head_dim, 2, dtype=np.float64) / head_dim
    plain = theta ** -pairs
    kind = parameters.get("rope_type", "default")
    if kind == "default":
        return tuple(float(f) for f in plain.astype(np.float32)), 1.0
    assert kind == "yarn", kind
    factor = float(parameters["factor"])
    original = parameters["original_max_position_embeddings"]

    def pair_of(rotations):
        return head_dim * math.log(original / (rotations * 2 * math.pi)) \
            / (2 * math.log(theta))

    low = max(math.floor(pair_of(parameters.get("beta_fast", 32))), 0)
    high = min(math.ceil(pair_of(parameters.get("beta_slow", 1))),
               head_dim - 1)
    ramp = np.clip((np.arange(head_dim // 2) - low) / max(high - low, 0.001),
                   0.0, 1.0)
    scaled = parameters.get("attention_factor")
    if scaled is None:
        scaled = 0.1 * math.log(factor) + 1.0 if factor > 1 else 1.0
    table = plain * (1.0 - ramp) + plain / factor * ramp
    return tuple(float(f) for f in table.astype(np.float32)), float(scaled)


def arguments(config: Dict[str, Any]) -> Dict[str, Any]:
    """What ``forward`` and ``loss`` take besides arrays, from the published
    keys of a configuration file (and, for a share, its ``deployment``: the
    first expert held here; how many are held the parameters say)."""
    n = config["num_hidden_layers"]
    held = config.get("deployment", {}).get("experts_held", {})
    return {"layer_types": tuple(config["layer_types"][:n]),
            "window": config["sliding_window"],
            "ropes": {kind: rope_table(parameters, config["head_dim"])
                      for kind, parameters
                      in config["rope_parameters"].items()},
            "top_k": config["num_experts_per_tok"],
            "norm_topk_prob": config["norm_topk_prob"],
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


def _rope(x, start, rope):
    """x [B, rows, H, D] at positions start..: ``m (x cos + rotate_half(x)
    sin)``, angle pos * f[i] for dimensions i and i + D / 2, (f, m) =
    ``rope``."""
    inv_freq, factor = rope
    rows, width = x.shape[1], x.shape[-1]
    angles = (start + jnp.arange(rows)).astype(F32)[:, None] \
        * jnp.asarray(inv_freq, F32)
    angles = jnp.concatenate([angles, angles], axis=-1)[None, :, None, :]
    rotated = jnp.concatenate([-x[..., width // 2:], x[..., :width // 2]],
                              axis=-1)
    return x * (jnp.cos(angles) * factor) + rotated * (jnp.sin(angles)
                                                       * factor)


def _swiglu(x, w_gate, w_up, w_down):
    return (jax.nn.silu(x @ w_gate) * (x @ w_up)) @ w_down


def _routing(x, router, top_k, norm_topk_prob):
    """(picked [.., K], weight of every expert for every token [.., E])."""
    probs = jax.nn.softmax(x @ router, axis=-1)
    _, picked = jax.lax.top_k(probs, top_k)
    chosen = jax.nn.one_hot(picked, probs.shape[-1], dtype=F32).sum(-2)
    weights = probs * chosen
    if norm_topk_prob:
        weights = weights / weights.sum(-1, keepdims=True)
    return picked, weights


_EXPERT_LEAVES = ("w_gate", "w_up", "w_down")


def _ffn(x, w, top_k, norm_topk_prob, first_expert):
    """The held experts' part of the routed sum. Returns (m, picked)."""
    picked, weights = _routing(x, w["router"], top_k, norm_topk_prob)

    def add_expert(e, m):
        """m + w_e Expert_e(x), on held expert e's weights upcast alone."""
        w_gate, w_up, w_down = (
            jax.lax.dynamic_index_in_dim(w[name], e, 0, keepdims=False
                                         ).astype(F32)
            for name in _EXPERT_LEAVES)
        return m + jnp.take(weights, first_expert + e, axis=-1)[..., None] \
            * _swiglu(x, w_gate, w_up, w_down)

    # A counted loop, one held expert after the other.
    return jax.lax.fori_loop(0, w["w_gate"].shape[0], add_expert,
                             jnp.zeros_like(x)), picked


def block(h, w: Dict[str, jax.Array], *, sliding, window, rope, top_k,
          norm_topk_prob, eps, first_expert):
    """One layer on one layer's weights (the program's names; float32 but
    for ``w_gate`` / ``w_up`` / ``w_down``, upcast an expert at a time):
    keys and values of the whole context first, then ``QUERY_ROWS`` query
    rows at a time against all of them and on through the expert layer.
    Returns (h, picked [B, S, K])."""
    seq = h.shape[1]
    stretch = min(SEGMENT, seq)

    def keys_values(at):
        start, h_s = at
        x = _rmsnorm(h_s, w["ln_in_scale"], eps)
        k = _rmsnorm(jnp.einsum("bsd,dgk->bsgk", x, w["wk"]),
                     w["k_norm_scale"], eps)
        return _rope(k, start, rope), jnp.einsum("bsd,dgk->bsgk", x, w["wv"])

    k, v = (_whole(a) for a in jax.lax.map(
        keys_values, (jnp.arange(0, seq, stretch), _segments(h, stretch))))
    kv_heads, width = k.shape[2], k.shape[3]
    rows = min(QUERY_ROWS, seq)

    def queries(at):
        start, h_s = at
        x = _rmsnorm(h_s, w["ln_in_scale"], eps)
        q = _rope(_rmsnorm(jnp.einsum("bsd,dhk->bshk", x, w["wq"]),
                           w["q_norm_scale"], eps), start, rope)
        # Query heads as [kv heads, heads a kv head]: head i reads i // rep.
        q = q.reshape(q.shape[:2] + (kv_heads, -1, width))
        scores = jnp.einsum("bqgjk,btgk->bgjqt", q, k) / np.sqrt(width)
        query, key = start + jnp.arange(rows)[:, None], jnp.arange(seq)[None]
        allowed = key <= query
        if sliding:
            allowed = allowed & (query - key < window)
        scores = jnp.where(allowed, scores, -jnp.inf)
        a = jnp.einsum("bgjqt,btgk->bqgjk", jax.nn.softmax(scores, axis=-1),
                       v)
        a = a.reshape(x.shape[:2] + (-1, width))
        h_s = h_s + jnp.einsum("bqhk,hkd->bqd", a, w["wo"])
        m, picked = _ffn(_rmsnorm(h_s, w["ln_post_scale"], eps), w, top_k,
                         norm_topk_prob, first_expert)
        return h_s + m, picked

    # Rematerialised a block at a time, so that a backward pass through
    # this holds one block's [rows, S] scores, as the forward pass does.
    h, picked = jax.lax.map(jax.checkpoint(queries), (
        jnp.arange(0, seq, rows), _segments(h, rows)))
    return _whole(h), _whole(picked)


def _layer(stack, index, dynamic: bool):
    """Layer ``index`` of a stack, float32 but for the expert weights, which
    ``block`` upcasts one expert at a time."""
    def pick(name, a):
        a = jax.lax.dynamic_index_in_dim(a, index, 0, keepdims=False) \
            if dynamic else a[index]
        return a if name in _EXPERT_LEAVES else a.astype(F32)

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


def _walk(layer_types):
    """(kind, the name of its run's stack, index within it) of every layer
    in order; a run is a stretch of layers of one kind."""
    run, index = -1, 0
    for i, kind in enumerate(layer_types):
        if i == 0 or layer_types[i - 1] != kind:
            run, index = run + 1, 0
        yield kind, f"run{run:02d}_{kind}", index
        index += 1


def forward(params: Dict[str, Any], tokens, targets, positions, *,
            layer_types, ropes, with_picked: bool = False, **kw
            ) -> Tuple[jax.Array, ...]:
    """(logits at ``positions`` [B, P, vocab], loss per sequence [B], RMS of
    all logits), float32; with ``with_picked`` also the experts picked [L,
    B, S, K]. ``params`` is the program's tree."""
    eps = kw["eps"]
    with jax.default_matmul_precision("highest"):
        h = _embed(params["wte"], tokens)
        picked = []
        for kind, stack, index in _walk(layer_types):
            h, p = _block_at(h, params[stack], jnp.int32(index),
                             sliding=kind == "sliding_attention",
                             rope=ropes[kind], **kw)
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


def loss(params: Dict[str, Any], tokens, targets, *, layer_types, ropes,
         **kw) -> jax.Array:
    """Mean loss over all positions, differentiable in ``params``: what the
    gradient check takes the reference's gradients of. One program, the
    layers walked in Python, each rematerialised in the backward pass; for
    small depths and short sequences only."""
    with jax.default_matmul_precision("highest"):
        h = jnp.take(params["wte"], tokens, axis=0).astype(F32)
        for kind, stack, index in _walk(layer_types):
            h = jax.checkpoint(
                lambda h, w, kind=kind: block(
                    h, w, sliding=kind == "sliding_attention",
                    rope=ropes[kind], **kw)[0])(
                h, _layer(params[stack], index, dynamic=False))
        logits = _rmsnorm(h, params["lnf_scale"].astype(F32), kw["eps"]) \
            @ params["lm_head"].astype(F32)
        logp = jax.nn.log_softmax(logits, axis=-1)
        return -jnp.take_along_axis(
            logp, targets[..., None], axis=-1)[..., 0].mean()
