"""MiniCPM-SALA's forward pass, loss and gradients, plainly, as the yardstick
for ``correct`` of the ``minicpm_sala`` family (MiniCPM-SALA 9B is one).

The release (https://huggingface.co/openbmb/MiniCPM-SALA) is remote code and
the installed ``transformers`` has no ``minicpm_sala`` model to hold this
file to, so it is written from the published descriptions: the config's own
keys, the MiniCPM4 report (arXiv:2506.07900) and InfLLM-V2
(arXiv:2509.24663) for the ``minicpm4`` mixer, Lightning Attention-2
(arXiv:2401.04658) and MiniMax-01's use of it for ``lightning-attn``.
``jax.numpy`` and float32 under ``jax.default_matmul_precision("highest")``,
no kernel, layer scan, remat or cache, independent of ``ray_tpu/``. With
``N(h; g) = h / sqrt(mean(h^2) + eps) * g``, ``r = scale_depth /
sqrt(len(mixer_types))``, L = ``len(mixer_types)`` and l a layer's published
index::

    h_0     = scale_emb * wte[token]
    h       = h + r * Mixer(N(h; g1))
    h       = h + r * W_down(silu(W_gate x) * W_up x),  x = N(h; g2)
    logits  = (N(h_last; g_f) / (hidden_size / dim_model_base)) W_head
    loss    = mean_t -log softmax(logits[t])[token t + 1]

    minicpm4 (32 query heads, head h reads KV head g = h // 16; every head's q and k normed over its 128
    with a learned scale; no positions):
      Kc_g[j]  = mean(k_g[16 j : 16 j + 32])                       every whole kernel, j = 0 .. S / 16 - 2
      p_h[t]   = softmax over {j : 16 j + 31 <= t} of q_h[t] . Kc_g[j] / sqrt(128)     (none visible: zeros)
      P_g[t]   = sum of p_h[t] over the group's heads
      B_g[t,b] = max of P_g[t, j] over j in 4 b - 1 .. 4 b + 3 that exist and are visible
      forced   = block 0, and blocks t // 64 - 31 .. t // 64
      Sel_g[t] = the forced blocks, then the best-scored up to 64 in all, among blocks 0 .. t // 64;
                 **a tie falls to the lowest block index** (a stable sort by descending score)
      a_h[t]   = softmax over {s <= t : s // 64 in Sel_g[t]} of q_h[t] . k_g[s] / sqrt(128), applied to v_g[s]
      a sequence of at most dense_len positions attends over every s <= t
      Mixer    = (a * sigmoid(x W_g)) W_o
    lightning-attn (32 heads of 128; the same norms on q and k, then rope on both: rotate-half, theta 10000):
      lambda_h = exp(-2^(-8 (h + 1) / H) (1 - l / (L - 1) + 1e-5))
      o_h[t]   = sum_{s <= t} lambda_h^(t - s) (q_h[t] . k_h[s] / sqrt(128)) v_h[s]        the literal weights
      Mixer    = (N_head(o; g_o) * sigmoid(x W_g)) W_o             N_head over a head's 128, g_o shared by heads

The readings the sources leave open, each as ``ray_tpu/models/
minicpm_sala.py`` and the configuration's ``assumed.readings`` have it: the
``sparse_config`` sizes, the window forced by whole blocks and counted among
the 64, kernels lying partly beyond t invisible and no partial last kernel,
the q/k norm with a learned scale before rope, the decay's layer factor at
the published index, no activation on the linear mixer's q, k, v, the
output norm a group a head, ``mup_denominator`` and ``rand_init`` unused.

Nothing here is chunked beyond what memory forces at the timed size (one
sequence of 16384 at width 4096, beside the train state): a mixer goes
``ROWS`` query rows at a time against the whole sequence's keys (the
``[heads, ROWS, S]`` scores and decay weights of one block exist), the
SwiGLU and the head by blocks of positions. The same code runs the tests'
small sizes and is differentiable there (``loss``).

It takes the program's parameter tree as it sits on the device (bf16, the
runs of one kind of layer stacked under ``run<i>_<kind>``, in order) and
upcasts one layer at a time.
"""

from __future__ import annotations

import math
from functools import partial
from typing import Any, Dict, Tuple

import jax
import jax.numpy as jnp

F32 = jnp.float32
ROWS = 64          # mixers: query rows a block
MLP_ROWS = 1024    # SwiGLU: positions a block
HEAD_ROWS = 512    # head: positions a block
_STATIC = ("kind", "eps", "theta", "r", "sparse", "published_layers")


def arguments(config: Dict[str, Any]) -> Dict[str, Any]:
    """What ``forward`` and ``loss`` take besides arrays, from the published
    keys of a configuration file (and its ``assumed.sparse_config``)."""
    sparse = config["assumed"]["sparse_config"]
    return {
        "eps": config["rms_norm_eps"], "theta": float(config["rope_theta"]),
        "scale_emb": float(config["scale_emb"]),
        "r": config["scale_depth"] / math.sqrt(len(config["mixer_types"])),
        "divisor": config["hidden_size"] / config["dim_model_base"],
        "published_layers": len(config["mixer_types"]),
        "sparse": tuple(sparse[key] for key in (
            "kernel_size", "kernel_stride", "block_size", "topk",
            "init_blocks", "window_size", "dense_len")),
    }


def _norm(x, g, eps):
    return x / jnp.sqrt((x * x).mean(-1, keepdims=True) + eps) * g


def _rope(x, positions, theta):
    """x [B, S, H, D] at ``positions`` [S]: dimension i with i + D / 2,
    angle pos * theta^(-2 i / D)."""
    half = x.shape[-1] // 2
    angles = positions.astype(F32)[:, None] \
        * theta ** (-jnp.arange(half, dtype=F32) / half)
    cos, sin = jnp.cos(angles)[:, None, :], jnp.sin(angles)[:, None, :]
    a, b = x[..., :half], x[..., half:]
    return jnp.concatenate([a * cos - b * sin, b * cos + a * sin], -1)


def _row_blocks(a, rows):
    """[B, S, ...] -> [S / rows, B, rows, ...]."""
    batch, seq = a.shape[:2]
    return a.reshape(batch, seq // rows, rows, *a.shape[2:]).swapaxes(0, 1)


def _whole(a):
    n, batch, rows = a.shape[:3]
    return a.swapaxes(0, 1).reshape(batch, n * rows, *a.shape[3:])


def _by_rows(fn, rows, seq, *arrays):
    """``fn(first row, *blocks)`` over blocks of ``rows`` positions of
    [B, S, ...] arrays, the results laid back along S (a backward pass makes
    a block's scores again: those of a sequence are never kept)."""
    rows = min(rows, seq)
    return _whole(jax.lax.map(
        jax.checkpoint(lambda at: fn(*at)), (jnp.arange(0, seq, rows),
                             *(_row_blocks(a, rows) for a in arrays))))


def _selected_blocks(q, kc, t, sparse):
    """q [B, R, G, Hg, D] at positions t [R], kc [B, n, G, D] -> [B, R, G, S
    / block] bool: the blocks each query and group keeps."""
    kernel, stride, block, topk, init, window, _ = sparse
    n = kc.shape[1]
    blocks = (n + kernel // stride - 1) * stride // block
    visible = (jnp.arange(n) * stride + kernel - 1)[None, :] <= t[:, None]
    scores = jnp.einsum("brghd,bjgd->brghj", q, kc) \
        / jnp.sqrt(F32(q.shape[-1]))
    scores = jnp.where(visible[None, :, None, None, :], scores, -jnp.inf)
    top = jnp.max(scores, -1, keepdims=True)
    e = jnp.where(jnp.isfinite(scores),
                  jnp.exp(scores - jnp.where(jnp.isfinite(top), top, 0.0)),
                  0.0)
    total = e.sum(-1, keepdims=True)
    p = jnp.where(total > 0, e / jnp.where(total > 0, total, 1.0), 0.0)
    summed = p.sum(3)                                    # [B, R, G, n]
    per, ratio = kernel // stride, block // stride
    b = jnp.arange(blocks)
    over = b[:, None] * ratio - (per - 1) + jnp.arange(ratio + per - 1)
    exists = (over >= 0) & (over < n)                    # [blocks, 5]
    at = jnp.clip(over, 0, n - 1)
    seen = exists[None] & visible[:, at]                 # [R, blocks, 5]
    pooled = jnp.where(seen[None, :, None], summed[..., at], -jnp.inf).max(-1)
    own = (t // block)[:, None]
    must = (b[None, :] < init) | (b[None, :] > own - window // block)
    causal = b[None, :] <= own                           # [R, blocks]
    key = jnp.where(must[None, :, None], jnp.inf, pooled)
    key = jnp.where(causal[None, :, None], key, -jnp.inf)
    order = jnp.argsort(-key, axis=-1, stable=True)
    rank = jnp.argsort(order, axis=-1, stable=True)
    return (rank < topk) & causal[None, :, None]


def _sparse_mixer(x, w, *, eps, sparse, **_):
    """The ``minicpm4`` mixer on normed x [B, S, d] -> [B, S, H, D]."""
    kernel, stride, block, topk, init, window, dense_len = sparse
    batch, seq, _ = x.shape
    groups = w["wk"].shape[1]
    k = _norm(jnp.einsum("bsd,dgk->bsgk", x, w["wk"]), w["k_norm_scale"],
              eps)
    v = jnp.einsum("bsd,dgk->bsgk", x, w["wv"])
    width = k.shape[-1]
    dense = seq <= dense_len
    if not dense:
        n = (seq - kernel) // stride + 1
        at = jnp.arange(n)[:, None] * stride + jnp.arange(kernel)[None, :]
        kc = k[:, at].mean(2)                            # [B, n, G, D]
    s = jnp.arange(seq)

    def some_rows(first, x_r):
        rows = x_r.shape[1]
        t = first + jnp.arange(rows)
        q = _norm(jnp.einsum("bsd,dhk->bshk", x_r, w["wq"]),
                  w["q_norm_scale"], eps)
        q = q.reshape(batch, rows, groups, -1, width)
        seen = s[None, :] <= t[:, None]                  # [R, S]
        if dense:
            seen = seen[None, :, None, :]
        else:
            kept = _selected_blocks(q, kc, t, sparse)    # [B, R, G, nb]
            seen = seen[None, :, None, :] & jnp.repeat(kept, block, axis=-1)
        scores = jnp.einsum("brghd,bsgd->brghs", q, k) / jnp.sqrt(F32(width))
        probs = jax.nn.softmax(
            jnp.where(seen[:, :, :, None, :], scores, -jnp.inf), axis=-1)
        a = jnp.einsum("brghs,bsgd->brghd", probs, v)
        return a.reshape(batch, rows, -1, width)

    return _by_rows(some_rows, ROWS, seq, x)


def _lightning_mixer(x, w, layer, *, eps, theta, published_layers, **_):
    """The ``lightning-attn`` mixer of the layer at published index
    ``layer`` (an int32 scalar) on normed x -> [B, S, H, D], output norm
    included."""
    batch, seq, _ = x.shape
    heads, width = w["wq"].shape[1:]
    at = jnp.arange(seq)
    k = _rope(_norm(jnp.einsum("bsd,dhk->bshk", x, w["wk"]),
                    w["k_norm_scale"], eps), at, theta)
    v = jnp.einsum("bsd,dhk->bshk", x, w["wv"])
    slope = 2.0 ** (-8.0 * jnp.arange(1, heads + 1, dtype=F32) / heads) \
        * (1.0 - layer.astype(F32) / max(published_layers - 1, 1) + 1e-5)

    def some_rows(first, x_r):
        t = first + jnp.arange(x_r.shape[1])
        q = _rope(_norm(jnp.einsum("bsd,dhk->bshk", x_r, w["wq"]),
                        w["q_norm_scale"], eps), t, theta)
        gap = (t[:, None] - at[None, :]).astype(F32)     # [R, S]
        weight = jnp.where(gap >= 0, jnp.exp(
            -slope[:, None, None] * jnp.maximum(gap, 0.0)), 0.0)
        scores = jnp.einsum("brhd,bshd->bhrs", q, k) / jnp.sqrt(F32(width))
        return jnp.einsum("bhrs,bshd->brhd", scores * weight[None], v)

    o = _by_rows(some_rows, ROWS, seq, x)
    return _norm(o, w["o_norm_scale"], eps)


def _mlp(h, w, eps, r):
    def some_rows(first, h_r):
        x = _norm(h_r, w["ln2_scale"], eps)
        return h_r + r * (
            (jax.nn.silu(x @ w["w_gate"]) * (x @ w["w_up"])) @ w["w_down"])

    return _by_rows(some_rows, MLP_ROWS, h.shape[1], h)


def block(h, w, layer, *, kind, eps, theta, r, sparse, published_layers):
    """One layer of ``kind`` at published index ``layer`` on h [B, S, d]."""
    x = _norm(h, w["ln1_scale"], eps)
    kw = dict(eps=eps, theta=theta, sparse=sparse,
              published_layers=published_layers)
    a = _sparse_mixer(x, w, **kw) if kind == "sparse" \
        else _lightning_mixer(x, w, layer, **kw)
    gate = jax.nn.sigmoid(x @ w["w_g"])
    mixed = jnp.einsum("bshk,hkd->bsd", a * gate.reshape(a.shape), w["wo"])
    return _mlp(h + r * mixed, w, eps, r)


def _layer(stack, index, dynamic: bool):
    """Layer ``index`` of a stack, float32."""
    return {name: (jax.lax.dynamic_index_in_dim(a, index, 0, keepdims=False)
                   if dynamic else a[index]).astype(F32)
            for name, a in stack.items()}


@partial(jax.jit, static_argnames=_STATIC, donate_argnums=(0,))
def _block_at(h, stack, index, layer, **kw):
    return block(h, _layer(stack, index, dynamic=True), layer, **kw)


def _runs(params):
    """[(the stack, its kind, the published index of its first layer)] in
    layer order."""
    out, at = [], 0
    for name in sorted(key for key in params if key.startswith("run")):
        depth = jax.tree.leaves(params[name])[0].shape[0]
        out.append((params[name], name.split("_", 1)[1], at))
        at += depth
    return out


def _embedded(params, tokens, scale_emb):
    return scale_emb * jnp.take(params["wte"], tokens, axis=0).astype(F32)


def _logits(h, params, eps, divisor):
    x = _norm(h, params["lnf_scale"].astype(F32), eps) / divisor
    return x @ params["lm_head"].astype(F32)


@partial(jax.jit, static_argnames=("eps", "divisor"))
def _head_block(h, params, targets, local, inside, *, eps, divisor):
    """Final norm and head on a block of positions: (the logits [B, P,
    vocab] at the block's own rows ``local`` where ``inside``, else 0; the
    sum of nll [B]; the sum of logits squared)."""
    z = _logits(h, params, eps, divisor)
    nll = -jnp.take_along_axis(jax.nn.log_softmax(z, axis=-1),
                               targets[..., None], axis=-1)[..., 0]
    sampled = jnp.where(inside[..., None], jnp.take_along_axis(
        z, local[..., None], axis=1), 0.0)
    return sampled, nll.sum(1), (z ** 2).sum()


def forward(params: Dict[str, Any], tokens, targets, positions, *,
            scale_emb, divisor, **kw) -> Tuple[jax.Array, ...]:
    """(logits at ``positions`` [B, P, vocab], loss per sequence [B], RMS of
    all logits), float32. ``params`` is the program's tree."""
    eps = kw["eps"]
    with jax.default_matmul_precision("highest"):
        h = _embedded(params, tokens, scale_emb)
        for stack, kind, first in _runs(params):
            for index in range(jax.tree.leaves(stack)[0].shape[0]):
                h = _block_at(h, stack, jnp.int32(index),
                              jnp.int32(first + index), kind=kind, **kw)
        seq = tokens.shape[1]
        nll, squares, sampled = 0.0, 0.0, 0.0
        for first in range(0, seq, HEAD_ROWS):
            rows = slice(first, min(first + HEAD_ROWS, seq))
            inside = (positions >= rows.start) & (positions < rows.stop)
            local = jnp.clip(positions - rows.start, 0,
                             rows.stop - rows.start - 1)
            at_rows, nll_sum, square_sum = _head_block(
                h[:, rows], params, targets[:, rows], local, inside,
                eps=eps, divisor=divisor)
            nll, squares = nll + nll_sum, squares + square_sum
            sampled = sampled + at_rows
        width = params["lm_head"].shape[1]
        return (sampled, nll / seq,
                jnp.sqrt(squares / (float(tokens.size) * width)))


def logits(params: Dict[str, Any], tokens, *, scale_emb, divisor, **kw
           ) -> jax.Array:
    """[B, S, vocab], differentiable in ``params``; the layers walked in
    Python, each rematerialised in a backward pass. Small sizes."""
    with jax.default_matmul_precision("highest"):
        h = _embedded(params, tokens, scale_emb)
        for stack, kind, first in _runs(params):
            for index in range(jax.tree.leaves(stack)[0].shape[0]):
                h = jax.checkpoint(partial(block, kind=kind, **kw))(
                    h, _layer(stack, index, dynamic=False),
                    jnp.int32(first + index))
        return _logits(h, params, kw["eps"], divisor)


def loss(params: Dict[str, Any], tokens, targets, **kw) -> jax.Array:
    """The mean next-token cross-entropy: what the gradient check takes the
    reference's gradients of."""
    z = logits(params, tokens, **kw)
    with jax.default_matmul_precision("highest"):
        nll = -jnp.take_along_axis(jax.nn.log_softmax(z, axis=-1),
                                   targets[..., None], axis=-1)[..., 0]
        return nll.mean()
