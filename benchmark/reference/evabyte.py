"""EvaByte's forward pass, eight-head loss and gradients, plainly, as the
yardstick for ``correct`` of the ``evabyte`` family (EvaByte 6.5B is one).

Written from the paper (Zheng, Yuan, Wang, Kong: "Efficient Attention via
Control Variates", ICLR 2023) and the EvaByte release's description of its
``attention_class: eva`` (https://huggingface.co/EvaByte/EvaByte): the
release is remote code and the installed ``transformers`` (4.57.6) has no
``evabyte`` model to hold this file to. ``jax.numpy`` and float32 under
``jax.default_matmul_precision("highest")``, no kernel, layer scan, remat or
cache, independent of ``ray_tpu/models/`` and ``ray_tpu/ops/``. With ``N(h;
g) = h / sqrt(mean(h^2) + eps) * (1 + g)``, chunks ``C_j = {c j, .., c j + c
- 1}`` (c = ``chunk_size``) and block windows of W = ``window_size``::

    h_0      = wte[bytes]
    x        = N(h; g1)
    q, k, v  = x W_q, x W_k, x W_v                    heads of hidden_size / num_attention_heads, no bias
    q, k     = rope(q), rope(k)                       theta rope_theta, dimension i with i + head_dim / 2
    a[m]     = softmax_{m in C_j}(k[m] . phi)         phi, mu: a vector a head and layer
    kc[j]    = sum_{m in C_j} a[m] k[m] + mu   ;   vc[j] = sum_{m in C_j} a[m] v[m]
    query t, s = W (t // W):
      scores = (q[t] . kc[j] for every j | q[t] . k[m] for every m) / sqrt(head_dim)
      mask   = (c j < s | s <= m <= t)                the explicit [S, S / c + S] mask
      o[t]   = softmax(scores under mask) (vc | v)
    h        = h + o W_o
    h        = h + W_down(silu(W_gate N(h; g2)) * W_up N(h; g2))
    z        = N(h_L; g_f) W_head  -> [num_pred_heads, vocab]; head i at t predicts byte t + 1 + i
    loss     = mean_i mean_{t : t + i < S} -log softmax(z[t, i])[target[t + i]]     (target[t] = byte t + 1)

The five readings the published config does not decide, each as
``ray_tpu/models/evabyte.py`` and the configuration's ``assumed`` have it:
(i) ``+ mu`` on the pooled key, a learned vector a head (the release's
``adaptive_mu_k``; the 2023 paper has a small network on the chunk's mean
key there); (ii) no further scale on ``k . phi``; (iii) the rotate-half
pairing of rope; (iv) equal weights on the heads' losses; (v)
``fp32_skip_add`` as the residual sum in float32 (here everything is).

``forward`` fits beside the train state at the timed size (one sequence of
32768 at width 4096): a layer first pools every chunk a stretch of
``SEGMENT`` positions at a time (k and v of a stretch exist, never those of
the sequence), then attends a window at a time, ``QUERY_ROWS`` query rows
against the explicit mask's columns that can be allowed for them (every
summary, and the keys of the rows' own window, made again from h); the head
goes by blocks of positions. ``loss`` (small sizes: the gradient check and
the tests) attends over the whole ``[S, S / c + S]`` mask at once;
``tests/test_evabyte.py`` holds the two to each other.

It takes the program's parameter tree as it sits on the device (bf16, the
layers stacked under ``run00_eva``) and upcasts one layer at a time.
"""

from __future__ import annotations

from functools import partial
from typing import Any, Dict, Tuple

import jax
import jax.numpy as jnp

F32 = jnp.float32
SEGMENT = 1024     # pooling: positions a stretch
QUERY_ROWS = 64    # attention: query rows a block
HEAD_ROWS = 1024   # head: positions a block
STACK = "run00_eva"
_STATIC = ("window", "chunk", "theta", "eps")


def arguments(config: Dict[str, Any]) -> Dict[str, Any]:
    """What ``forward`` and ``loss`` take besides arrays, from the published
    keys of a configuration file."""
    return {"window": config["window_size"], "chunk": config["chunk_size"],
            "theta": float(config["rope_theta"]),
            "eps": config["rms_norm_eps"],
            "pred_heads": config["num_pred_heads"]}


def _norm(x, g, eps):
    return x / jnp.sqrt((x * x).mean(-1, keepdims=True) + eps) * (1.0 + g)


def _rope(x, positions, theta):
    """x [B, S, H, D] at ``positions`` [S]: dimension i with i + D / 2,
    angle pos * theta^(-2 i / D)."""
    half = x.shape[-1] // 2
    angles = positions.astype(F32)[:, None] \
        * theta ** (-jnp.arange(half, dtype=F32) / half)
    cos, sin = jnp.cos(angles)[:, None, :], jnp.sin(angles)[:, None, :]
    a, b = x[..., :half], x[..., half:]
    return jnp.concatenate([a * cos - b * sin, b * cos + a * sin], -1)


def _qkv(h, w, positions, theta, eps):
    x = _norm(h, w["ln1_scale"], eps)
    q, k, v = (jnp.einsum("bsd,dhk->bshk", x, w[name])
               for name in ("wq", "wk", "wv"))
    return _rope(q, positions, theta), _rope(k, positions, theta), v


def _pooled(k, v, w, chunk):
    """k, v [B, n chunk, H, D] -> kc, vc [B, n, H, D]."""
    batch, rows, heads, dim = k.shape
    k_c = k.reshape(batch, rows // chunk, chunk, heads, dim)
    v_c = v.reshape(batch, rows // chunk, chunk, heads, dim)
    a = jax.nn.softmax((k_c * w["eva_phi"]).sum(-1), axis=2)[..., None]
    return (a * k_c).sum(2) + w["eva_mu"], (a * v_c).sum(2)


def _attend(q, rows, keys, key_at, values, kc, vc, window, chunk):
    """Queries q [B, R, H, D] at positions ``rows`` [R] over the summaries
    kc, vc [B, J, H, D] and the keys [B, M, H, D] at positions ``key_at``
    [M], under the explicit mask."""
    start = (rows - rows % window)[:, None]
    seen = jnp.concatenate([
        jnp.arange(kc.shape[1])[None, :] * chunk < start,
        (start <= key_at[None, :]) & (key_at[None, :] <= rows[:, None])], 1)
    scores = jnp.einsum(
        "bqhd,bkhd->bhqk", q, jnp.concatenate([kc, keys], 1)) \
        / jnp.sqrt(F32(q.shape[-1]))
    probs = jax.nn.softmax(jnp.where(seen, scores, -jnp.inf), axis=-1)
    return jnp.einsum("bhqk,bkhd->bqhd", probs,
                      jnp.concatenate([vc, values], 1))


def _mlp(h, w, eps):
    x = _norm(h, w["ln2_scale"], eps)
    return h + (jax.nn.silu(x @ w["w_gate"]) * (x @ w["w_up"])) @ w["w_down"]


def block_whole(h, w, *, window, chunk, theta, eps):
    """One layer on h [B, S, d] over the whole [S, S / chunk + S] mask at
    once: small sizes only."""
    at = jnp.arange(h.shape[1])
    q, k, v = _qkv(h, w, at, theta, eps)
    kc, vc = _pooled(k, v, w, chunk)
    o = _attend(q, at, k, at, v, kc, vc, window, chunk)
    return _mlp(h + jnp.einsum("bshk,hkd->bsd", o, w["wo"]), w, eps)


def _stretches(a, rows):
    """[B, S, ...] -> [S / rows, B, rows, ...]."""
    batch, seq = a.shape[:2]
    return a.reshape(batch, seq // rows, rows, *a.shape[2:]).swapaxes(0, 1)


def _whole(a):
    n, batch, rows = a.shape[:3]
    return a.swapaxes(0, 1).reshape(batch, n * rows, *a.shape[3:])


def block(h, w, *, window, chunk, theta, eps):
    """The same layer a stretch at a time (module text)."""
    seq = h.shape[1]
    stretch = min(SEGMENT, seq)

    def pooled(at):
        first, h_s = at
        _, k, v = _qkv(h_s, w, first + jnp.arange(stretch), theta, eps)
        return _pooled(k, v, w, chunk)

    kc, vc = (_whole(a) for a in jax.lax.map(
        pooled, (jnp.arange(0, seq, stretch), _stretches(h, stretch))))
    span = min(window, seq)
    rows = min(QUERY_ROWS, span)

    def one_window(at):
        first, h_w = at
        key_at = first + jnp.arange(span)
        q, k, v = _qkv(h_w, w, key_at, theta, eps)

        def some_rows(at):
            row_at, q_r, h_r = at
            o = _attend(q_r, row_at, k, key_at, v, kc, vc, window, chunk)
            return _mlp(h_r + jnp.einsum("bshk,hkd->bsd", o, w["wo"]), w,
                        eps)

        return _whole(jax.lax.map(some_rows, (
            key_at.reshape(-1, rows), _stretches(q, rows),
            _stretches(h_w, rows))))

    return _whole(jax.lax.map(
        one_window, (jnp.arange(0, seq, span), _stretches(h, span))))


def _layer(stack, index, dynamic: bool):
    """Layer ``index`` of the stack, float32."""
    return {name: (jax.lax.dynamic_index_in_dim(a, index, 0, keepdims=False)
                   if dynamic else a[index]).astype(F32)
            for name, a in stack.items()}


@partial(jax.jit, static_argnames=_STATIC, donate_argnums=(0,))
def _block_at(h, stack, index, **kw):
    return block(h, _layer(stack, index, dynamic=True), **kw)


def _head_targets(targets, pred_heads):
    """(targets [B, S, heads], valid [B, S, heads]): head i at t is held to
    ``targets[t + i]`` where t + i is inside the sequence."""
    seq = targets.shape[1]
    at = jnp.arange(seq)[:, None] + jnp.arange(pred_heads)[None, :]
    return jnp.take(targets, jnp.minimum(at, seq - 1), axis=1), \
        jnp.broadcast_to(at < seq, (targets.shape[0],) + at.shape)


def _logits(h, params, eps, pred_heads):
    """[B, R, heads, vocab] of a block of final hidden states."""
    z = _norm(h, params["lnf_scale"].astype(F32), eps) \
        @ params["lm_head"].astype(F32)
    return z.reshape(*z.shape[:-1], pred_heads, -1)


@partial(jax.jit, static_argnames=("eps", "pred_heads"))
def _head_block(h, params, targets, valid, local, inside, *, eps,
                pred_heads):
    """Final norm and the heads on a block of positions: (the logits [B, P,
    heads x vocab] at the block's own rows ``local`` where ``inside``, else
    0; each head's sum of nll [B, heads]; the sum of logits squared)."""
    z = _logits(h, params, eps, pred_heads)
    nll = -jnp.take_along_axis(jax.nn.log_softmax(z, axis=-1),
                               targets[..., None], axis=-1)[..., 0]
    flat = z.reshape(*z.shape[:2], -1)
    sampled = jnp.where(inside[..., None], jnp.take_along_axis(
        flat, local[..., None], axis=1), 0.0)
    return sampled, jnp.where(valid, nll, 0.0).sum(1), (z ** 2).sum()


def forward(params: Dict[str, Any], tokens, targets, positions, *,
            pred_heads, **kw) -> Tuple[jax.Array, ...]:
    """(logits at ``positions`` [B, P, heads x vocab], loss per sequence
    [B], RMS of all logits), float32. ``params`` is the program's tree."""
    eps = kw["eps"]
    with jax.default_matmul_precision("highest"):
        h = jnp.take(params["wte"], tokens, axis=0).astype(F32)
        depth = jax.tree.leaves(params[STACK])[0].shape[0]
        for index in range(depth):
            h = _block_at(h, params[STACK], jnp.int32(index), **kw)
        seq = tokens.shape[1]
        wanted, valid = _head_targets(targets, pred_heads)
        nll, squares, sampled = 0.0, 0.0, 0.0
        for first in range(0, seq, HEAD_ROWS):
            rows = slice(first, min(first + HEAD_ROWS, seq))
            inside = (positions >= rows.start) & (positions < rows.stop)
            local = jnp.clip(positions - rows.start, 0,
                             rows.stop - rows.start - 1)
            at_rows, nll_sum, square_sum = _head_block(
                h[:, rows], params, wanted[:, rows], valid[:, rows], local,
                inside, eps=eps, pred_heads=pred_heads)
            nll, squares = nll + nll_sum, squares + square_sum
            sampled = sampled + at_rows
        per_head = nll / (seq - jnp.arange(pred_heads, dtype=F32))
        width = params["lm_head"].shape[1]
        return (sampled, per_head.mean(-1),
                jnp.sqrt(squares / (float(tokens.size) * width)))


def head_losses(params: Dict[str, Any], tokens, targets, *, pred_heads,
                **kw) -> jax.Array:
    """Every head's cross-entropy [heads] over the whole batch,
    differentiable in ``params``; the layers walked in Python over the whole
    mask, each rematerialised in the backward pass. Small sizes only."""
    with jax.default_matmul_precision("highest"):
        h = jnp.take(params["wte"], tokens, axis=0).astype(F32)
        depth = jax.tree.leaves(params[STACK])[0].shape[0]
        for index in range(depth):
            h = jax.checkpoint(partial(block_whole, **kw))(
                h, _layer(params[STACK], index, dynamic=False))
        z = _logits(h, params, kw["eps"], pred_heads)
        wanted, valid = _head_targets(targets, pred_heads)
        nll = -jnp.take_along_axis(jax.nn.log_softmax(z, axis=-1),
                                   wanted[..., None], axis=-1)[..., 0]
        return jnp.where(valid, nll, 0.0).sum((0, 1)) / valid.sum((0, 1))


def loss(params: Dict[str, Any], tokens, targets, **kw) -> jax.Array:
    """The mean of the heads' losses: what the gradient check takes the
    reference's gradients of."""
    return head_losses(params, tokens, targets, **kw).mean()


def logits(params: Dict[str, Any], tokens, **kw) -> jax.Array:
    """[B, S, heads, vocab] over the whole mask at once: small sizes."""
    pred_heads = kw.pop("pred_heads")
    with jax.default_matmul_precision("highest"):
        h = jnp.take(params["wte"], tokens, axis=0).astype(F32)
        depth = jax.tree.leaves(params[STACK])[0].shape[0]
        for index in range(depth):
            h = block_whole(h, _layer(params[STACK], index, dynamic=False),
                            **kw)
        return _logits(h, params, kw["eps"], pred_heads)
