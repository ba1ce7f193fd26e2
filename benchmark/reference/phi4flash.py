"""Phi-4-mini-flash's forward pass and loss, plainly, as the yardstick for
``correct`` of the ``phi4flash`` family (Phi-4-mini-flash-reasoning is one).

Written from the equations of ISSUE 42 (the installed ``transformers`` has no
``phi4flash``; its ``MambaMixer.slow_forward`` is this Mamba layer term for
term and its ``DiffLlamaAttention`` this attention up to a permutation of
heads), in ``jax.numpy`` and float32 under
``jax.default_matmul_precision("highest")``, with no kernel, no scan over
layers and no remat, independent of ``ray_tpu/models/phi4flash.py``::

    h = wte[tokens];  M = depth // 2
    layer i of the published depth (those of ``layers_run`` run, under their own i):
      x = LN(h; g1, b1);  h = h + mixer_i(x)
      x = LN(h; g2, b2);  h = h + (silu(x W_gate) * x W_up) W_down
    mixer_i:  i even, i <= M   mamba          (i = M also hands on its memory m)
              i odd,  i <  M   differential attention over the window: key t for query s iff s - window < t <= s
              i = M + 1        differential attention, causal; hands on its k, v
              i even, i >  M   gated memory unit on m
              i odd,  i >  M + 1   differential attention of this layer's q onto layer M + 1's k, v, causal
    mamba:    xs | z = x W_in;   xs = silu(sum_k w_k xs_(t-K+1+k) + b_c)      the literal K-term sum, zeros before the first token
              dtr | B | C = xs W_x;   delta = softplus(dtr W_dt + b_dt);   A = -exp(A_log)
              H_t = exp(delta_t (x) A) * H_(t-1) + (delta_t * xs_t) (x) B_t    a literal loop over time
              y_t = H_t C_t + D * xs_t ;   m = y ;   out = (y * silu(z)) W_out
    gmu:      out = (silu(x W_g) * m) W_o
    attention: q = x Wq + bq;  k, v = x Wk + bk, x Wv + bv
              differential head j: q heads 2j, 2j+1;  g = j // 2: k heads 2g, 2g+1;  V_g = [v_2g | v_2g+1]
              P1 = softmax(mask(q_2j k_2g^T / sqrt(hd)));   P2 = softmax(mask(q_2j+1 k_2g+1^T / sqrt(hd)))     explicit masked softmaxes
              lambda = exp(lq1 . lk1) - exp(lq2 . lk2) + l0(i);   l0(i) = 0.8 - 0.6 exp(-0.3 i)
              o_j = (1 - l0(i)) RMSNorm((P1 - lambda P2) V_g; g_sub, eps 1e-5);   out = concat_j(o_j) Wo + bo
    logits = LN(h_last; gf, bf) wte^T ;  loss = mean_t -log softmax(logits_t)[target_t]

Projections and the SwiGLU go by stretches of ``SEGMENT`` positions, attention
by blocks of ``QUERY_ROWS`` query rows against the keys and values of the whole
context, and the head by blocks of positions, so neither S x S scores for all
heads nor [S, vocab] logits nor a [S, intermediate] array exist whole.

It takes the program's parameter tree as it sits on the device (bf16; one
stack a run of pairs of one kind, ``run00_self``, ``run01_middle``,
``run02_cross``, a pair's first layer under ``a_`` and its second under
``b_``) and upcasts one layer at a time.

Departures from the published module, each also in the configuration's file:
the SwiGLU's ``W1`` is held as its two halves ``w_gate`` | ``w_up`` (gate
first; the same product), the heads are paired striped (2j with 2j + 1: a
permutation of Wq, Wk, Wv's columns against a chunked pairing), and the
attention projections carry biases.
"""

from __future__ import annotations

import math
from functools import partial
from typing import Any, Dict, Tuple

import jax
import jax.numpy as jnp
import numpy as np

F32 = jnp.float32
QUERY_ROWS = 128   # attention: query rows a block
SEGMENT = 1024     # projections and the SwiGLU: positions a block
HEAD_ROWS = 512    # head: positions a block
SAMPLE_COLUMNS = 16384  # sampled logits: tokens of the vocabulary a block
SUBLN_EPS = 1e-5

_STATIC = ("kind", "heads", "kv_heads", "window", "eps", "d_state",
           "dt_rank")


def arguments(config: Dict[str, Any]) -> Dict[str, Any]:
    """What ``forward`` and ``loss`` take besides arrays, from the published
    keys of a configuration file, its ``assumed`` Mamba-1 sizes and the
    layers it runs (``layers_run``: published indices; ``reduced`` holds the
    published depth)."""
    depth = config.get("reduced", {}).get("num_hidden_layers", {}).get(
        "published", config["num_hidden_layers"])
    sizes = config.get("assumed", {}).get("mamba_sizes", {})
    d = config["hidden_size"]
    return {"layers_run": tuple(config.get("layers_run", range(depth))),
            "depth": depth,
            "heads": config["num_attention_heads"],
            "kv_heads": config["num_key_value_heads"],
            "window": config["sliding_window"],
            "eps": config["layer_norm_eps"],
            "d_state": sizes.get("mamba_d_state", 16),
            "dt_rank": sizes.get("mamba_dt_rank") or math.ceil(d / 16)}


def lambda_init(index: int) -> float:
    return 0.8 - 0.6 * math.exp(-0.3 * index)


def _layernorm(x, scale, bias, eps):
    mean = x.mean(-1, keepdims=True)
    var = ((x - mean) ** 2).mean(-1, keepdims=True)
    return (x - mean) / jnp.sqrt(var + eps) * scale + bias


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


def _by_segments(fn, *arrays):
    """``fn`` on stretches of ``SEGMENT`` positions of [B, S, ...] arrays,
    put together again."""
    rows = min(SEGMENT, arrays[0].shape[1])
    out = jax.lax.map(jax.checkpoint(lambda a: fn(*a)),
                      tuple(_segments(a, rows) for a in arrays))
    return jax.tree.map(_whole, out)


def _mamba(x, w, d_state, dt_rank):
    """The Mamba-1 mixer on normed x [B, S, d] -> (out, y before the
    gate)."""
    seq = x.shape[1]
    d_inner, taps = w["conv_w"].shape[1], w["conv_w"].shape[0]
    proj = _by_segments(lambda x_s: x_s @ w["w_in"], x)
    pre, z = proj[..., :d_inner], proj[..., d_inner:]
    xs = jax.nn.silu(sum(
        w["conv_w"][k] * jnp.pad(pre, ((0, 0), (taps - 1 - k, 0), (0, 0))
                                 )[:, :seq] for k in range(taps))
        + w["conv_b"])
    low = xs @ w["w_x"]
    delta = jax.nn.softplus(low[..., :dt_rank] @ w["w_dt"] + w["b_dt"])
    b_in = low[..., dt_rank:dt_rank + d_state]
    c_out = low[..., dt_rank + d_state:]
    a_rate = -jnp.exp(w["A_log"])                        # [d_inner, N]

    def token(state, at_t):
        x_t, dt_t, b_t, c_t = at_t                       # [B, d_inner | N]
        state = jnp.exp(dt_t[..., None] * a_rate) * state \
            + (dt_t * x_t)[..., None] * b_t[:, None, :]
        return state, (state * c_t[:, None, :]).sum(-1) + w["D"] * x_t

    _, y = jax.lax.scan(
        token, jnp.zeros((x.shape[0],) + a_rate.shape, F32),
        tuple(a.swapaxes(0, 1) for a in (xs, delta, b_in, c_out)))
    y = y.swapaxes(0, 1)
    out = _by_segments(lambda y_s, z_s: (y_s * jax.nn.silu(z_s))
                       @ w["w_out"], y, z)
    return out, y


def _attention(x, w, l0, kv, heads, kv_heads, window):
    """Differential attention on normed x [B, S, d] -> (out, (k, v)).
    ``kv``: layer M + 1's (k, v) for a cross layer, else None; ``window``:
    the keys a query sees, itself among them, or None for all before it."""
    seq, width = x.shape[1], x.shape[2] // heads
    if kv is None:
        kv = tuple(_by_segments(
            lambda x_s, n=n: jnp.einsum("bsd,dgk->bsgk", x_s, w["w" + n])
            + w["b" + n], x) for n in "kv")
    k, v = kv
    groups = kv_heads // 2
    # k heads (2g, 2g + 1) apart, V_g their two values side by side.
    k_g = k.reshape(k.shape[:2] + (groups, 2, width))
    v_g = v.reshape(v.shape[:2] + (groups, 2 * width))
    lam = jnp.exp((w["lambda_q1"] * w["lambda_k1"]).sum()) \
        - jnp.exp((w["lambda_q2"] * w["lambda_k2"]).sum()) + l0
    rows = min(QUERY_ROWS, seq)

    def queries(at):
        start, x_s = at
        q = jnp.einsum("bsd,dhk->bshk", x_s, w["wq"]) + w["bq"]
        # [B, rows, group g, differential head of the group, map, width]:
        # query head 2j + p of j = 2g + i is [g, i, p].
        q = q.reshape(q.shape[:2] + (groups, 2, 2, width))
        scores = jnp.einsum("bqgipk,btgpk->bgipqt", q, k_g) / math.sqrt(width)
        query, key = start + jnp.arange(rows)[:, None], jnp.arange(seq)[None]
        seen = key <= query
        if window is not None:
            seen &= key > query - window
        maps = jax.nn.softmax(jnp.where(seen, scores, -jnp.inf), axis=-1)
        mixed = maps[:, :, :, 0] - lam * maps[:, :, :, 1]   # [B, g, i, q, t]
        o = jnp.einsum("bgiqt,btgk->bqgik", mixed, v_g)
        o = (1.0 - l0) * _rmsnorm(o, w["subln_scale"], SUBLN_EPS)
        o = o.reshape(o.shape[:2] + (2 * groups, 2 * width))
        return jnp.einsum("bqhk,hkd->bqd", o, w["wo"]) + w["bo"]

    out = jax.lax.map(jax.checkpoint(queries),
                      (jnp.arange(0, seq, rows), _segments(x, rows)))
    return _whole(out), kv


def layer(h, w: Dict[str, jax.Array], l0, memory, kv, *, kind, heads,
          kv_heads, window, eps, d_state, dt_rank):
    """One layer of ``kind`` (``mamba``, ``gmu``, ``window``, ``full``,
    ``cross``) on its float32 weights. Returns (h, what it hands on: y of a
    mamba layer, (k, v) of an attention layer, else None)."""
    x = _layernorm(h, w["ln1_scale"], w["ln1_bias"], eps)
    handed = None
    if kind == "mamba":
        out, handed = _mamba(x, w, d_state, dt_rank)
    elif kind == "gmu":
        out = _by_segments(lambda x_s, m_s: (jax.nn.silu(x_s @ w["w_g"])
                                             * m_s) @ w["w_o"], x, memory)
    else:
        out, handed = _attention(
            x, w, l0, kv if kind == "cross" else None, heads, kv_heads,
            window if kind == "window" else None)
    h = h + out

    def mlp(h_s):
        x_s = _layernorm(h_s, w["ln2_scale"], w["ln2_bias"], eps)
        return h_s + (jax.nn.silu(x_s @ w["w_gate"]) * (x_s @ w["w_up"])) \
            @ w["w_down"]

    return _by_segments(mlp, h), handed


def _weights(stack, prefix: str, index, dynamic: bool):
    """One layer of a stack of pairs (``a_``: the first of each pair, ``b_``:
    the second), float32, under the leaves' own names."""
    def pick(a):
        return (jax.lax.dynamic_index_in_dim(a, index, 0, keepdims=False)
                if dynamic else a[index]).astype(F32)

    return {name[2:]: pick(a) for name, a in stack.items()
            if name.startswith(prefix)}


@partial(jax.jit, static_argnames=_STATIC + ("prefix", "hands_on"),
         donate_argnums=(0,))
def _layer_at(h, stack, index, l0, memory, kv, *, prefix, hands_on, **kw):
    """One layer; what it hands on only where a later layer reads it."""
    h, handed = layer(h, _weights(stack, prefix, index, dynamic=True), l0,
                      memory, kv, **kw)
    return h, handed if hands_on else None


@jax.jit
def _embed(wte, tokens):
    return jnp.take(wte, tokens, axis=0).astype(F32)


def _final_norm(h, params, eps):
    return _layernorm(h, params["final_norm_scale"].astype(F32),
                      params["final_norm_bias"].astype(F32), eps)


@partial(jax.jit, static_argnames=("eps",))
def _head_block(h, params, targets, *, eps):
    """Final LayerNorm and the tied head on a block of positions: (sum of
    nll a sequence, sum of logits squared). The block's [rows, vocab]
    logits stay inside."""
    logits = _final_norm(h, params, eps) @ params["wte"].astype(F32).T
    logp = jax.nn.log_softmax(logits, axis=-1)
    nll = -jnp.take_along_axis(logp, targets[..., None], axis=-1)[..., 0]
    return nll.sum(-1), (logits ** 2).sum()


@partial(jax.jit, static_argnames=("eps",))
def _sampled_block(h_at, params, rows, *, eps):
    """The logits of the sampled positions' hidden states ``h_at`` [B, P, d]
    over the rows ``rows`` of the tied table."""
    return _final_norm(h_at, params, eps) @ rows.astype(F32).T


def _walk(layers_run, depth):
    """(published index, kind, the name of its pair's stack, the pair's
    place in it, ``a_`` | ``b_``) of every layer that runs, in order; a
    stack is a stretch of pairs of one kind."""
    middle = depth // 2
    pair_kinds = ["self" if first < middle else
                  "middle" if first == middle else "cross"
                  for first in layers_run[0::2]]
    run, place = -1, 0
    for p, pair_kind in enumerate(pair_kinds):
        if p == 0 or pair_kinds[p - 1] != pair_kind:
            run, place = run + 1, 0
        stack = f"run{run:02d}_{pair_kind}"
        first, second = layers_run[2 * p], layers_run[2 * p + 1]
        assert second == first + 1 and first % 2 == 0, layers_run
        yield first, "gmu" if pair_kind == "cross" else "mamba", stack, \
            place, "a_"
        yield second, {"self": "window", "middle": "full",
                       "cross": "cross"}[pair_kind], stack, place, "b_"
        place += 1


def _through(params, h, layers_run, depth, at, **kw):
    """h through every layer that runs: the memory of layer M and the k, v
    of layer M + 1 kept for the layers behind them. ``at(h, stack, place,
    prefix, l0, memory, kv, kind, hands_on)`` is one layer."""
    middle, memory, kv = depth // 2, None, None
    for index, kind, stack, place, prefix in _walk(layers_run, depth):
        h, handed = at(h, params[stack], place, prefix,
                       jnp.float32(lambda_init(index)), memory, kv, kind,
                       index in (middle, middle + 1))
        if index == middle:
            memory = handed
        if index == middle + 1:
            kv = handed
    return h


def forward(params: Dict[str, Any], tokens, targets, positions, *,
            layers_run, depth, **kw) -> Tuple[jax.Array, ...]:
    """(logits at ``positions`` [B, P, vocab], loss per sequence [B], RMS of
    all logits), float32. ``params`` is the program's tree. The sampled
    logits are a host array, brought over ``SAMPLE_COLUMNS`` of the
    vocabulary at a time: [P, vocab] float32 never sits on the device beside
    the state it is checked on."""
    eps = kw["eps"]
    with jax.default_matmul_precision("highest"):
        h = _through(
            params, _embed(params["wte"], tokens), layers_run, depth,
            lambda h, stack, place, prefix, l0, memory, kv, kind, hands_on:
            _layer_at(h, stack, jnp.int32(place), l0, memory, kv,
                      prefix=prefix, hands_on=hands_on, kind=kind, **kw))
        seq, vocab = tokens.shape[1], params["wte"].shape[0]
        nll, squares = 0.0, 0.0
        for start in range(0, seq, HEAD_ROWS):
            rows = slice(start, min(start + HEAD_ROWS, seq))
            nll_sum, square_sum = _head_block(h[:, rows], params,
                                              targets[:, rows], eps=eps)
            nll, squares = nll + nll_sum, squares + square_sum
        h_at = jnp.take_along_axis(h, positions[..., None], axis=1)
        sampled = np.concatenate([np.asarray(_sampled_block(
            h_at, params, params["wte"][start:start + SAMPLE_COLUMNS],
            eps=eps)) for start in range(0, vocab, SAMPLE_COLUMNS)], axis=-1)
        return (sampled, nll / seq,
                jnp.sqrt(squares / (float(tokens.size) * vocab)))


def nll(params: Dict[str, Any], tokens, targets, *, layers_run, depth,
        **kw) -> jax.Array:
    """``-log softmax(logits_t)[target_t]`` of every position [B, S],
    differentiable in ``params``. One program, the layers walked in Python,
    each rematerialised in the backward pass; for small depths and short
    sequences only."""
    with jax.default_matmul_precision("highest"):
        h = _through(
            params, jnp.take(params["wte"], tokens, axis=0).astype(F32),
            layers_run, depth,
            lambda h, stack, place, prefix, l0, memory, kv, kind, hands_on:
            jax.checkpoint(partial(layer, kind=kind, **kw))(
                h, _weights(stack, prefix, place, dynamic=False), l0,
                memory, kv))
        logits = _final_norm(h, params, kw["eps"]) \
            @ params["wte"].astype(F32).T
        logp = jax.nn.log_softmax(logits, axis=-1)
        return -jnp.take_along_axis(logp, targets[..., None],
                                    axis=-1)[..., 0]


def loss(params: Dict[str, Any], tokens, targets, **kw) -> jax.Array:
    """Mean loss over all positions: what the gradient check takes the
    reference's gradients of."""
    return nll(params, tokens, targets, **kw).mean()
