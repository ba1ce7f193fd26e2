"""DeepSeek-V3's forward pass and loss, plainly, as the yardstick for
``correct`` of the ``deepseek_v3`` family (Moonlight-16B-A3B is one).

Written from the published implementation (``DeepseekV3ForCausalLM`` with
``q_lora_rank`` null, ``topk_method`` ``noaux_tc``, ``n_group`` 1, sigmoid
scores, no rope scaling), in ``jax.numpy`` and float32 under
``jax.default_matmul_precision("highest")``, with no kernel, layer scan,
remat, sort or grouped matmul, independent of ``ray_tpu/models/deepseek.py``::

    h        = wte[tokens]
    x        = RMSNorm(h; g1)                                 eps, no bias anywhere
    q        = x Wq              -> heads x (nope | rope)
    c | k_r  = x W_kv_a          -> kv_lora_rank | rope ;  c = RMSNorm(c; g_kv)
               k_r is one head, shared by all query heads
    k_n | v  = c W_kv_b          -> heads x (nope | v_head)
    q_r, k_r = rope(q_r), rope(k_r)   pairs (2i, 2i+1), angle pos * theta^(-2i/rope)
    a        = softmax(causal([q_n|q_r] [k_n|k_r]^T / sqrt(nope + rope))) v ;  h += a Wo
    x        = RMSNorm(h; g2)
    dense layers:  m = W_down(silu(W_gate x) * W_up x)
    expert layers: s = sigmoid(x W_r) ;  pick top_k of s + b   (b: selection only)
                   w = s[picked] / (sum s[picked] + 1e-20) * scaling
                   m = sum_i w_i Expert_i(x) + Shared(x)
    h       += m
    logits   = RMSNorm(h_L; gf) W_head ;  loss = mean_t -log softmax(logits_t)[target_t]

Every expert runs on every token, one expert after the other in a counted
loop, and is weighted by ``w`` (zero where the token did not pick it). Attention goes by blocks of query rows and the head
by blocks of positions, so neither S x S scores for all heads nor
[S, vocab] logits exist whole. The published rope de-interleaves q and k
(first members of the pairs, then second) before rotating halves; a
permutation applied to q and k alike leaves the scores unchanged, so the
pairs are rotated in place here.

It takes the program's parameter tree as it sits on the device (bf16, the
two stacks ``dense_layers`` and ``moe_layers`` stacked over layers) and
upcasts one layer, and inside an expert layer one expert, at a time.
"""

from __future__ import annotations

from functools import partial
from typing import Any, Dict, Tuple

import jax
import jax.numpy as jnp
import numpy as np

F32 = jnp.float32
QUERY_ROWS = 1024  # attention: query rows a block
HEAD_ROWS = 1024   # head: positions a block


def arguments(config: Dict[str, Any]) -> Dict[str, Any]:
    """What ``forward`` and ``loss`` take besides arrays, from the published
    keys of a configuration file."""
    return {"nope": config["qk_nope_head_dim"],
            "rank": config["kv_lora_rank"],
            "theta": float(config["rope_theta"]),
            "top_k": config["num_experts_per_tok"],
            "scaling": config["routed_scaling_factor"],
            "norm_topk": config["norm_topk_prob"],
            "eps": config["rms_norm_eps"]}


def _rmsnorm(x, scale, eps):
    return x / jnp.sqrt((x * x).mean(-1, keepdims=True) + eps) * scale


def _rope(x, theta):
    """x [B, S, H, R]: rotate each pair (2i, 2i+1) by pos * theta^(-2i/R)."""
    seq, width = x.shape[1], x.shape[-1]
    inv_freq = 1.0 / (theta ** (jnp.arange(0, width, 2, dtype=F32) / width))
    angles = jnp.arange(seq, dtype=F32)[:, None] * inv_freq[None, :]
    cos, sin = jnp.cos(angles)[None, :, None, :], jnp.sin(angles)[None, :, None, :]
    pairs = x.reshape(x.shape[:-1] + (width // 2, 2))
    even, odd = pairs[..., 0], pairs[..., 1]
    return jnp.stack([even * cos - odd * sin, odd * cos + even * sin],
                     axis=-1).reshape(x.shape)


def _attention(q, k, v):
    """Causal softmax attention, a block of query rows at a time."""
    seq = q.shape[1]
    scale = 1.0 / np.sqrt(q.shape[-1])
    out = []
    for start in range(0, seq, QUERY_ROWS):
        rows = slice(start, min(start + QUERY_ROWS, seq))
        scores = jnp.einsum("bqhk,bthk->bhqt", q[:, rows], k) * scale
        allowed = (jnp.arange(seq)[None, :]
                   <= jnp.arange(rows.start, rows.stop)[:, None])
        scores = jnp.where(allowed[None, None], scores, -jnp.inf)
        out.append(jnp.einsum("bhqt,bthk->bqhk",
                              jax.nn.softmax(scores, axis=-1), v))
    return jnp.concatenate(out, axis=1)


def _swiglu(x, w_gate, w_up, w_down):
    return (jax.nn.silu(x @ w_gate) * (x @ w_up)) @ w_down


def _routing(x, router, bias, top_k, scaling, norm_topk):
    """(picked [.., K], weight of every expert for every token [.., E])."""
    scores = jax.nn.sigmoid(x @ router)
    _, picked = jax.lax.top_k(scores + jax.lax.stop_gradient(bias), top_k)
    chosen = jax.nn.one_hot(picked, scores.shape[-1], dtype=F32).sum(-2)
    weights = scores * chosen
    if norm_topk and top_k > 1:
        weights = weights / (weights.sum(-1, keepdims=True) + 1e-20)
    return picked, weights * scaling


_EXPERT_LEAVES = ("w_gate", "w_up", "w_down")


def block(h, w: Dict[str, jax.Array], *, nope, rank, theta, top_k, scaling,
          norm_topk, eps):
    """One layer on one layer's weights (program's names). The attention
    and norm leaves are float32; an expert layer's ``w_gate``/``w_up``/
    ``w_down`` may be stored narrower and are upcast an expert at a time.
    Returns (h, picked or None)."""
    x = _rmsnorm(h, w["ln1_scale"], eps)
    q = jnp.einsum("bsd,dhk->bshk", x, w["wq"])
    kv_a = x @ w["w_kv_a"]
    latent = _rmsnorm(kv_a[..., :rank], w["kv_norm_scale"], eps)
    kv = jnp.einsum("bsr,rhk->bshk", latent, w["w_kv_b"])
    q_rope = _rope(q[..., nope:], theta)
    k_rope = _rope(kv_a[..., None, rank:], theta)
    q = jnp.concatenate([q[..., :nope], q_rope], axis=-1)
    k = jnp.concatenate([kv[..., :nope],
                         jnp.broadcast_to(k_rope, q_rope.shape)], axis=-1)
    attn = _attention(q, k, kv[..., nope:])
    h = h + jnp.einsum("bqhk,hkd->bqd", attn, w["wo"])
    x = _rmsnorm(h, w["ln2_scale"], eps)
    if "router" not in w:
        return h + _swiglu(x, w["w_gate"], w["w_up"], w["w_down"]), None
    picked, weights = _routing(x, w["router"], w["router_bias"], top_k,
                               scaling, norm_topk)
    m = _swiglu(x, w["shared_w_gate"], w["shared_w_up"], w["shared_w_down"])

    def add_expert(e, m):
        """m + w_e Expert_e(x), on expert e's weights upcast alone."""
        w_gate, w_up, w_down = (
            jax.lax.dynamic_index_in_dim(w[name], e, 0, keepdims=False
                                         ).astype(F32) for name in _EXPERT_LEAVES)
        return m + jnp.take(weights, e, axis=-1)[..., None] * _swiglu(
            x, w_gate, w_up, w_down)

    # A counted loop, one expert after the other, in the order of a Python
    # loop: 64 experts unrolled are one program of 192 float32 products,
    # minutes to compile and a quarter of a gigabyte to keep loaded.
    m = jax.lax.fori_loop(0, w["router"].shape[-1], add_expert, m)
    return h + m, picked


def _layer(stack, index, dynamic: bool):
    """Layer ``index`` of a stack, float32 but for an expert layer's expert
    weights, which ``block`` upcasts one expert at a time."""
    is_moe = "router" in stack

    def pick(name, a):
        a = jax.lax.dynamic_index_in_dim(a, index, 0, keepdims=False) \
            if dynamic else a[index]
        return a if is_moe and name in _EXPERT_LEAVES else a.astype(F32)

    return {name: pick(name, a) for name, a in stack.items()}


@partial(jax.jit, static_argnames=("nope", "rank", "theta", "top_k",
                                   "scaling", "norm_topk", "eps"))
def _block_at(h, stack, index, **kw):
    return block(h, _layer(stack, index, dynamic=True), **kw)


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


def _depth(stack) -> int:
    return jax.tree.leaves(stack)[0].shape[0]


def forward(params: Dict[str, Any], tokens, targets, positions, *,
            with_picked: bool = False, **kw
            ) -> Tuple[jax.Array, ...]:
    """(logits at ``positions`` [B, P, vocab], loss per sequence [B], RMS of
    all logits), float32; with ``with_picked`` also the experts picked
    [L_moe, B, S, K]. ``params`` is the program's tree."""
    eps = kw["eps"]
    with jax.default_matmul_precision("highest"):
        h = jnp.take(params["wte"], tokens, axis=0).astype(F32)
        picked = []
        for name in ("dense_layers", "moe_layers"):
            for index in range(_depth(params[name])):
                h, p = _block_at(h, params[name], jnp.int32(index), **kw)
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
               jnp.sqrt(squares / (tokens.size * vocab)))
        return out + (jnp.stack(picked),) if with_picked else out


def loss(params: Dict[str, Any], tokens, targets, **kw) -> jax.Array:
    """Mean loss over all positions, differentiable in ``params``: what the
    gradient check takes the reference's gradients of. One program, the
    layers walked in Python; for small depths and short sequences only."""
    with jax.default_matmul_precision("highest"):
        h = jnp.take(params["wte"], tokens, axis=0).astype(F32)
        for name in ("dense_layers", "moe_layers"):
            for index in range(_depth(params[name])):
                h, _ = block(h, _layer(params[name], index, dynamic=False),
                             **kw)
        logits = _rmsnorm(h, params["lnf_scale"].astype(F32), kw["eps"]) \
            @ params["lm_head"].astype(F32)
        logp = jax.nn.log_softmax(logits, axis=-1)
        return -jnp.take_along_axis(
            logp, targets[..., None], axis=-1)[..., 0].mean()
