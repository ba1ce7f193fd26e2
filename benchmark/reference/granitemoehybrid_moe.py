"""Granite 4.0-H's forward pass and loss with routed experts, plainly, as
the yardstick for ``correct`` of the ``granitemoehybrid_moe`` family
(granite-4.0-h-small is one: ``num_local_experts`` 72).
``reference/granitemoehybrid.py`` is the same family without experts
(``num_local_experts`` 0) and is left as it is; this file repeats its mixers
and adds the expert layer beside the shared SwiGLU.

Written from the published implementation (``GraniteMoeHybridForCausalLM``
of ``transformers`` 4.57: ``GraniteMoeHybridMambaLayer.torch_forward``,
``GraniteMoeHybridAttention`` with ``position_embedding_type`` "nope",
``GraniteMoeHybridMLP``, ``GraniteMoeHybridMoE`` with
``GraniteMoeHybridTopKGating`` and ``GraniteMoeHybridParallelExperts``;
``mamba_n_groups`` 1), in ``jax.numpy`` and float32 under
``jax.default_matmul_precision("highest")``, with no kernel, chunked scan,
sort, buffer, layer scan or remat, independent of
``ray_tpu/models/granite.py``, ``ray_tpu/ops/ssd.py`` and
``ray_tpu/ops/moe.py``::

    h        = embedding_multiplier * wte[tokens]
    x        = RMSNorm(h; g1)                                 eps, no bias but the conv's
    mamba:   z | xBC | dt = x W_in
             xBC      = silu(b + sum_k w_k xBC_(t - K + 1 + k))     zeros before the first token
             u | B | C = xBC
             dt       = softplus(dt + dt_bias) ;  A = -exp(A_log)
             S_t      = exp(dt_t A) S_(t-1) + dt_t u_t B_t^T        one step a token, S_0 = 0
             y_t      = S_t C_t + D u_t
             a        = RMSNorm(y * silu(z); g_m) W_out
    attention: a      = softmax(causal(q k^T * attention_multiplier)) v Wo
                        query head i reads KV head i // (heads / kv heads); no positions
    h        = h + residual_multiplier * a
    x        = RMSNorm(h; g2)
    logits_r = x W_r                                          [.., experts], no bias
    top, e   = top_k(logits_r, K) ;  g = softmax(top)         the published order: over the K picked alone
    r        = sum_{i : e_i held} g_i W_down[e_i] (silu(x W_gate[e_i]) * (x W_up[e_i]))
    s        = W_out2(silu(x W_a) * (x W_b))                  the shared SwiGLU, W_a | W_b one matrix
    h        = h + residual_multiplier * (r + s)
    logits   = RMSNorm(h_L; gf) wte^T / logits_scaling ;  loss = mean_t -log softmax(logits_t)[target_t]

**The chip's share.** The parameters may hold a contiguous run of a layer's
experts, ``first_expert`` onwards, as many as ``w_up`` has: the router stays
as wide as ``W_r``, the K picked and their softmax are over all the experts,
and the terms of the picked experts that are not held are left out of r (a
token none of whose picked experts is held gets the shared SwiGLU alone). A
sliced vocabulary is a smaller vocabulary: ``wte`` has the slice's rows, and
ids, logits and loss are over them. The experts are a counted loop, one held
expert after the other over all the tokens of a stretch, each token's term
times its gate for that expert (zero where it did not pick it): no sort, no
grouping and no buffer.

The recurrence is the literal one: a ``lax.scan`` over positions carrying
the state [heads, d_head, d_state], never the chunked algorithm the program
runs (the published ``torch_forward`` is the chunked one; the two are the
same sums in another order, and ``tests/test_granite_moe.py`` holds this
file to ``transformers``). A layer goes over the sequence a stretch at a
time (a state-space layer ``SEGMENT`` positions, handing the conv's last
inputs and the state to the next stretch; an attention layer ``QUERY_ROWS``
query rows against the keys and values of the whole context; the FFN with
the stretch of its mixer) and the head by blocks of positions, so no float32
[S, thousands] intermediate, no S x S scores and no [S, vocab] logits exist
whole.

It takes the program's parameter tree as it sits on the device (bf16, one
stack ``run<NN>_<kind>`` for every run of one kind of layer) and upcasts one
layer at a time, the routed experts' weights one expert at a time.
"""

from __future__ import annotations

from functools import partial
from typing import Any, Dict, Tuple

import jax
import jax.numpy as jnp

F32 = jnp.float32
SEGMENT = 1024     # a state-space layer, keys and values: positions a stretch
QUERY_ROWS = 32    # an attention layer: query rows a block
HEAD_ROWS = 1024   # head: positions a block
_STATIC = ("kind", "heads", "d_state", "attention_multiplier",
           "residual_multiplier", "eps", "top_k", "first_expert")
_EXPERT_LEAVES = ("w_gate", "w_up", "w_down")


def arguments(config: Dict[str, Any]) -> Dict[str, Any]:
    """What ``forward`` and ``loss`` take besides arrays, from the published
    keys of a configuration file (and, for a share, its ``deployment``: the
    first expert held here; how many are held the parameters say)."""
    return {"top_k": config["num_experts_per_tok"],
            "first_expert": config.get("deployment", {}).get(
                "experts_held", {}).get("first", 0),
            "layer_types": tuple(
                config["layer_types"][:config["num_hidden_layers"]]),
            "heads": config["mamba_n_heads"],
            "d_state": config["mamba_d_state"],
            "attention_multiplier": config["attention_multiplier"],
            "embedding_multiplier": config["embedding_multiplier"],
            "residual_multiplier": config["residual_multiplier"],
            "logits_scaling": config["logits_scaling"],
            "eps": config["rms_norm_eps"]}


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


def _recurrence(state, u, dt, A, B, C, D):
    """(state after the last position, y [B, S, H, P]) of the state-space
    recurrence from ``state`` [B, H, P, N], one position a step. u [B, S,
    H, P]; dt [B, S, H]; A, D [H]; B, C [B, S, N]."""

    def step(state, at):
        u_t, dt_t, B_t, C_t = at
        state = jnp.exp(dt_t * A)[..., None, None] * state \
            + (dt_t[..., None] * u_t)[..., None] * B_t[:, None, None, :]
        y_t = (state * C_t[:, None, None, :]).sum(-1) + D[:, None] * u_t
        return state, y_t

    state, y = jax.lax.scan(step, state, tuple(
        a.swapaxes(0, 1) for a in (u, dt, B, C)))
    return state, y.swapaxes(0, 1)


def _gates(x, router, top_k):
    """(picked [.., K], gate of every expert for every token [.., E]): the
    ``top_k`` largest router logits and a softmax over those alone, each
    laid at its expert; zero at the experts a token did not pick."""
    top, picked = jax.lax.top_k(x @ router, top_k)
    gates = jax.nn.softmax(top, axis=-1)
    laid = jax.nn.one_hot(picked, router.shape[-1], dtype=F32) \
        * gates[..., None]
    return picked, laid.sum(-2)


def _ffn(h, w, residual_multiplier, eps, top_k, first_expert):
    """The FFN half of a layer on h [B, rows, d]: the held experts' part of
    the routed sum and the shared SwiGLU, on the same normed input, added to
    the stream once. Returns (h, picked [B, rows, K])."""
    x = _rmsnorm(h, w["ln2_scale"], eps)
    picked, gates = _gates(x, w["router"], top_k)

    def add_expert(e, r):
        """r + g_e Expert_e(x), on held expert e's weights upcast alone."""
        w_gate, w_up, w_down = (
            jax.lax.dynamic_index_in_dim(w[name], e, 0, keepdims=False
                                         ).astype(F32)
            for name in _EXPERT_LEAVES)
        return r + jnp.take(gates, first_expert + e, axis=-1)[..., None] * (
            (jax.nn.silu(x @ w_gate) * (x @ w_up)) @ w_down)

    # A counted loop, one held expert after the other.
    routed = jax.lax.fori_loop(0, w["w_up"].shape[0], add_expert,
                               jnp.zeros_like(x))
    gate, up = jnp.split(x @ w["mlp_in"], 2, axis=-1)
    shared = (jax.nn.silu(gate) * up) @ w["mlp_out"]
    return h + residual_multiplier * (routed + shared), picked


def _mamba_layer(h, w, heads, d_state, residual_multiplier, eps, **ffn):
    """A state-space layer with its FFN on h [B, S, d], a stretch of
    ``SEGMENT`` positions after the other: the conv's last inputs and the
    state pass from stretch to stretch, zero before the first. Returns (h,
    picked [B, S, K])."""
    d_inner, taps = w["w_out"].shape[0], w["conv_w"].shape[0]
    A, batch = -jnp.exp(w["A_log"]), h.shape[0]
    rows = min(SEGMENT, h.shape[1])

    def stretch(carry, h_s):
        tail, state = carry
        x = _rmsnorm(h_s, w["ln1_scale"], eps)
        z, xbc, dt = jnp.split(x @ w["w_in"], [d_inner, w["w_in"].shape[1]
                                               - heads], axis=-1)
        padded = jnp.concatenate([tail, xbc], axis=1)
        xbc = jax.nn.silu(w["conv_b"] + sum(
            w["conv_w"][k] * padded[:, k:k + rows] for k in range(taps)))
        u, B, C = jnp.split(xbc, [d_inner, d_inner + d_state], axis=-1)
        state, y = _recurrence(
            state, u.reshape(u.shape[:2] + (heads, -1)),
            jax.nn.softplus(dt + w["dt_bias"]), A, B, C, w["D"])
        a = _rmsnorm(y.reshape(z.shape) * jax.nn.silu(z), w["norm_scale"],
                     eps) @ w["w_out"]
        return (padded[:, rows:], state), _ffn(
            h_s + residual_multiplier * a, w, residual_multiplier, eps,
            **ffn)

    conv_dim = w["conv_w"].shape[1]
    start = (jnp.zeros((batch, taps - 1, conv_dim), F32),
             jnp.zeros((batch, heads, d_inner // heads, d_state), F32))
    h, picked = jax.lax.scan(stretch, start, _segments(h, rows))[1]
    return _whole(h), _whole(picked)


def _attention_layer(h, w, scale, residual_multiplier, eps, **ffn):
    """An attention layer with its FFN on h [B, S, d]: keys and values of
    the whole context first, then ``QUERY_ROWS`` query rows at a time
    against all of them. Query head i reads KV head i // (heads / kv
    heads): the query heads are taken as [kv heads, heads a kv head].
    Returns (h, picked [B, S, K])."""
    seq = h.shape[1]

    def keys_values(h_s):
        x = _rmsnorm(h_s, w["ln1_scale"], eps)
        return (jnp.einsum("bsd,dgk->bsgk", x, w["wk"]),
                jnp.einsum("bsd,dgk->bsgk", x, w["wv"]))

    k, v = (_whole(a) for a in jax.lax.map(
        keys_values, _segments(h, min(SEGMENT, seq))))
    kv_heads, rows = k.shape[2], min(QUERY_ROWS, seq)

    def queries(at):
        start, h_s = at
        x = _rmsnorm(h_s, w["ln1_scale"], eps)
        q = jnp.einsum("bsd,dhk->bshk", x, w["wq"])
        q = q.reshape(q.shape[:2] + (kv_heads, -1, q.shape[-1]))
        scores = jnp.einsum("bqgjk,btgk->bgjqt", q, k) * scale
        allowed = jnp.arange(seq)[None, :] <= start + jnp.arange(rows)[:, None]
        scores = jnp.where(allowed, scores, -jnp.inf)
        a = jnp.einsum("bgjqt,btgk->bqgjk", jax.nn.softmax(scores, axis=-1),
                       v)
        a = jnp.einsum("bqhk,hkd->bqd", a.reshape(x.shape[:2] + (
            -1, a.shape[-1])), w["wo"])
        return _ffn(h_s + residual_multiplier * a, w, residual_multiplier,
                    eps, **ffn)

    h, picked = jax.lax.map(
        queries, (jnp.arange(0, seq, rows), _segments(h, rows)))
    return _whole(h), _whole(picked)


def block(h, w: Dict[str, jax.Array], *, kind, heads, d_state,
          attention_multiplier, residual_multiplier, eps, top_k,
          first_expert):
    """One layer of ``kind`` on one layer's weights (the program's names;
    float32 but for the routed experts', upcast an expert at a time).
    Returns (h, picked [B, S, K])."""
    ffn = {"top_k": top_k, "first_expert": first_expert}
    if kind == "mamba":
        return _mamba_layer(h, w, heads, d_state, residual_multiplier, eps,
                            **ffn)
    return _attention_layer(h, w, attention_multiplier, residual_multiplier,
                            eps, **ffn)


def _layer(stack, index, dynamic: bool):
    """Layer ``index`` of a stack, float32 but for the routed experts'
    weights, which ``_ffn`` upcasts one expert at a time."""
    def pick(name, a):
        a = jax.lax.dynamic_index_in_dim(a, index, 0, keepdims=False) \
            if dynamic else a[index]
        return a if name in _EXPERT_LEAVES else a.astype(F32)

    return {name: pick(name, a) for name, a in stack.items()}


@partial(jax.jit, static_argnames=_STATIC, donate_argnums=(0,))
def _block_at(h, stack, index, **kw):
    return block(h, _layer(stack, index, dynamic=True), **kw)


@partial(jax.jit, static_argnames=("multiplier",))
def _embed(wte, tokens, *, multiplier):
    return multiplier * jnp.take(wte, tokens, axis=0).astype(F32)


@partial(jax.jit, static_argnames=("eps", "logits_scaling"))
def _head_block(h, params, targets, local, inside, *, eps, logits_scaling):
    """Final RMSNorm and tied head on a block of positions: (the logits at
    the block's own rows ``local`` [B, P] where ``inside``, else 0; sum of
    nll; sum of logits squared). The block's [rows, vocab] logits stay
    inside."""
    logits = _rmsnorm(h, params["lnf_scale"].astype(F32), eps) \
        @ params["wte"].astype(F32).T / logits_scaling
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
            layer_types, embedding_multiplier, logits_scaling,
            with_picked: bool = False, **kw) -> Tuple[jax.Array, ...]:
    """(logits at ``positions`` [B, P, vocab], loss per sequence [B], RMS of
    all logits), float32; with ``with_picked`` also the experts picked [L,
    B, S, K]. ``params`` is the program's tree."""
    eps = kw["eps"]
    with jax.default_matmul_precision("highest"):
        h = _embed(params["wte"], tokens, multiplier=embedding_multiplier)
        picked = []
        for kind, stack, index in _walk(layer_types):
            h, p = _block_at(h, params[stack], jnp.int32(index), kind=kind,
                             **kw)
            picked.append(p)
        seq = tokens.shape[1]
        nll, squares, sampled = 0.0, 0.0, 0.0
        for start in range(0, seq, HEAD_ROWS):
            rows = slice(start, min(start + HEAD_ROWS, seq))
            inside = (positions >= rows.start) & (positions < rows.stop)
            local = jnp.clip(positions - rows.start, 0,
                             rows.stop - rows.start - 1)
            at_rows, nll_sum, square_sum = _head_block(
                h[:, rows], params, targets[:, rows], local, inside, eps=eps,
                logits_scaling=logits_scaling)
            nll, squares = nll + nll_sum, squares + square_sum
            sampled = sampled + at_rows
        vocab = params["wte"].shape[0]
        out = (sampled, nll / seq,
               jnp.sqrt(squares / (float(tokens.size) * vocab)))
        return out + (jnp.stack(picked),) if with_picked else out


def loss(params: Dict[str, Any], tokens, targets, *, layer_types,
         embedding_multiplier, logits_scaling, **kw) -> jax.Array:
    """Mean loss over all positions, differentiable in ``params``: what the
    gradient check takes the reference's gradients of. One program, the
    layers walked in Python, each rematerialised in the backward pass (the
    literal recurrence keeps its state at every position, 2 MB a position
    at the published widths: one layer's at a time); for small depths and
    short sequences only."""
    with jax.default_matmul_precision("highest"):
        h = embedding_multiplier * jnp.take(
            params["wte"], tokens, axis=0).astype(F32)
        for kind, stack, index in _walk(layer_types):
            h = jax.checkpoint(
                lambda h, w, kind=kind: block(h, w, kind=kind, **kw)[0])(
                h, _layer(params[stack], index, dynamic=False))
        logits = _rmsnorm(h, params["lnf_scale"].astype(F32), kw["eps"]) \
            @ params["wte"].astype(F32).T / logits_scaling
        logp = jax.nn.log_softmax(logits, axis=-1)
        return -jnp.take_along_axis(
            logp, targets[..., None], axis=-1)[..., 0].mean()
