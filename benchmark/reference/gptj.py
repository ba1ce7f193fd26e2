"""GPT-J's forward pass and loss, plainly, as the yardstick for ``correct``.

Written from the published description of EleutherAI/gpt-j-6b (Wang and
Komatsuzaki 2021, "GPT-J-6B: A 6 Billion Parameter Autoregressive Language
Model"; the equations are those of ``GPTJForCausalLM``), in ``jax.numpy``
and float32 under ``jax.default_matmul_precision("highest")``, with no
kernel, scan, remat or cache, independent of ``ray_tpu/models/gpt.py``::

    h_0     = wte[tokens]
    x       = LayerNorm(h_l; g1, b1)                      eps 1e-5
    q, k, v = x Wq, x Wk, x Wv                            no bias
    q, k    = rotary(q), rotary(k)     first rotary_dim dims of each head,
              pairs (2i, 2i+1), angle pos * 10000^(-2i / rotary_dim)
    a       = softmax(causal(q k^T / sqrt(head_dim))) v   then  a Wo, no bias
    m       = gelu_tanh(x W_in + b_in) W_out + b_out
    h_{l+1} = h_l + a + m                                 one LayerNorm feeds both
    logits  = LayerNorm(h_L; gf, bf) W_head + b_head      head not tied to wte
    loss    = mean_t -log softmax(logits_t)[target_t]

It takes the program's parameter tree as it sits on the device (bf16,
stacked over layers, sharded or not) and upcasts one layer at a time, so it
fits beside the train state. One jitted program serves every layer.

``models/gpt.py`` pairs rotary dimension i with i + rotary_dim/2 where the
published model pairs 2i with 2i+1. The two agree up to a fixed permutation
of the columns of Wq and Wk inside each head, which ``_published_heads``
applies to the program's weights; every other equation is taken as
published. The departures are listed in each configuration's file.
"""

from __future__ import annotations

from functools import partial
from typing import Any, Dict, Tuple

import jax
import jax.numpy as jnp
import numpy as np

F32 = jnp.float32


def arguments(config: Dict[str, Any]) -> Dict[str, Any]:
    """What ``forward`` and ``loss`` take besides arrays, from the published
    keys of a configuration file."""
    return {"rotary_dim": config["rotary_dim"],
            "eps": config["layer_norm_epsilon"]}


def _published_heads(w: jax.Array, rotary_dim: int) -> jax.Array:
    """Reorder the last (head_dim) axis of the program's Wq or Wk so that
    the published pairing (2i, 2i+1) rotates what the program's pairing
    (i, i + rotary_dim/2) rotates."""
    head_dim, half = w.shape[-1], rotary_dim // 2
    perm = np.arange(head_dim)
    perm[0:rotary_dim:2] = np.arange(half)
    perm[1:rotary_dim:2] = np.arange(half) + half
    return w[..., perm]


def _layernorm(x, scale, bias, eps):
    mean = x.mean(-1, keepdims=True)
    var = ((x - mean) ** 2).mean(-1, keepdims=True)
    return (x - mean) / jnp.sqrt(var + eps) * scale + bias


def _rotary(x, rotary_dim):
    """x [B, S, H, D]: rotate_every_two on the first rotary_dim dims."""
    seq = x.shape[1]
    inv_freq = 1.0 / (10000.0 ** (jnp.arange(0, rotary_dim, 2, dtype=F32)
                                  / rotary_dim))
    angles = jnp.arange(seq, dtype=F32)[:, None] * inv_freq[None, :]
    sin = jnp.repeat(jnp.sin(angles), 2, axis=-1)[None, :, None, :]
    cos = jnp.repeat(jnp.cos(angles), 2, axis=-1)[None, :, None, :]
    rot, rest = x[..., :rotary_dim], x[..., rotary_dim:]
    even, odd = rot[..., 0::2], rot[..., 1::2]
    rotated = jnp.stack([-odd, even], axis=-1).reshape(rot.shape)
    return jnp.concatenate([rot * cos + rotated * sin, rest], axis=-1)


def _gelu_tanh(x):
    return 0.5 * x * (1.0 + jnp.tanh(
        np.sqrt(2.0 / np.pi) * (x + 0.044715 * x ** 3)))


def block(h, w: Dict[str, jax.Array], *, rotary_dim: int, eps: float):
    """One GPT-J block on float32 weights of one layer (program's names)."""
    x = _layernorm(h, w["ln1_scale"], w["ln1_bias"], eps)
    q = jnp.einsum("bsd,dhk->bshk", x, _published_heads(w["wq"], rotary_dim))
    k = jnp.einsum("bsd,dhk->bshk", x, _published_heads(w["wk"], rotary_dim))
    v = jnp.einsum("bsd,dhk->bshk", x, w["wv"])
    q, k = _rotary(q, rotary_dim), _rotary(k, rotary_dim)
    seq, head_dim = q.shape[1], q.shape[-1]
    scores = jnp.einsum("bqhk,bthk->bhqt", q, k) / np.sqrt(head_dim)
    causal = jnp.tril(jnp.ones((seq, seq), bool))
    scores = jnp.where(causal[None, None], scores, -jnp.inf)
    attn = jnp.einsum("bhqt,bthk->bqhk", jax.nn.softmax(scores, axis=-1), v)
    a = jnp.einsum("bqhk,hkd->bqd", attn, w["wo"])
    m = _gelu_tanh(x @ w["w_in"] + w["b_in"]) @ w["w_out"] + w["b_out"]
    return h + a + m


@partial(jax.jit, static_argnames=("rotary_dim", "eps"))
def _block_at(h, layers, index, *, rotary_dim, eps):
    """Layer ``index`` of the stacked weights, upcast alone."""
    w = jax.tree.map(
        lambda a: jax.lax.dynamic_index_in_dim(
            a, index, axis=0, keepdims=False).astype(F32), layers)
    return block(h, w, rotary_dim=rotary_dim, eps=eps)


@partial(jax.jit, static_argnames=("eps",))
def _head_stats(h, params, targets, positions, *, eps):
    """Final LayerNorm and head: logits at ``positions`` [B, P], their RMS
    over all positions, and the loss of each sequence."""
    x = _layernorm(h, params["lnf_scale"].astype(F32),
                   params["lnf_bias"].astype(F32), eps)
    logits = x @ params["lm_head"].astype(F32) \
        + params["lm_head_bias"].astype(F32)
    logp = jax.nn.log_softmax(logits, axis=-1)
    nll = -jnp.take_along_axis(logp, targets[..., None], axis=-1)[..., 0]
    sampled = jnp.take_along_axis(logits, positions[..., None], axis=1)
    return sampled, nll.mean(-1), jnp.sqrt((logits ** 2).mean())


def forward(params: Dict[str, Any], tokens, targets, positions, *,
            rotary_dim: int, eps: float = 1e-5
            ) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """(logits at ``positions`` [B, P, vocab], loss per sequence [B], RMS of
    all logits), float32. ``params`` is the program's tree: ``wte``,
    ``layers`` (each leaf stacked over layers), ``lnf_*``, ``lm_head*``."""
    with jax.default_matmul_precision("highest"):
        h = jnp.take(params["wte"], tokens, axis=0).astype(F32)
        n_layers = jax.tree.leaves(params["layers"])[0].shape[0]
        for index in range(n_layers):
            h = _block_at(h, params["layers"], jnp.int32(index),
                          rotary_dim=rotary_dim, eps=eps)
        return _head_stats(h, params, targets, positions, eps=eps)


def loss(params: Dict[str, Any], tokens, targets, *, rotary_dim: int,
         eps: float = 1e-5) -> jax.Array:
    """Mean loss over all positions, differentiable in ``params``: what
    ``check_grads.py`` takes the reference's gradients of. Walks the
    layers in Python; for small depths only."""
    with jax.default_matmul_precision("highest"):
        h = jnp.take(params["wte"], tokens, axis=0).astype(F32)
        n_layers = jax.tree.leaves(params["layers"])[0].shape[0]
        for index in range(n_layers):
            w = jax.tree.map(lambda a: a[index].astype(F32),
                             params["layers"])
            h = block(h, w, rotary_dim=rotary_dim, eps=eps)
        x = _layernorm(h, params["lnf_scale"].astype(F32),
                       params["lnf_bias"].astype(F32), eps)
        logits = x @ params["lm_head"].astype(F32) \
            + params["lm_head_bias"].astype(F32)
        logp = jax.nn.log_softmax(logits, axis=-1)
        return -jnp.take_along_axis(
            logp, targets[..., None], axis=-1)[..., 0].mean()
