"""Nemotron-H's forward pass and loss, plainly, as the yardstick for
``correct`` of the ``nemotron_h`` family
(NVIDIA-Nemotron-3-Nano-30B-A3B-BF16 is one).

Written from the published config's keys (``transformers`` 4.57 has no
``nemotron_h``; its Mamba-2 ``torch_forward`` takes ``n_groups`` > 1 and is
what the recurrence was read against), in ``jax.numpy`` and float32 under
``jax.default_matmul_precision("highest")``, with no kernel, chunked scan,
layer scan over stacks, remat of units, sort or grouped matmul, and
importing nothing of ``ray_tpu``. Every layer is one norm and one mixer::

    h = wte[tokens]
    layer l of kind pattern[l]:  h = h + Mixer(RMSNorm(h; g_l))
    M: z | xBC | dt = x W_in ;  xBC = silu(conv(xBC) + b)        depthwise, causal, conv_kernel taps
       u | B | C = xBC                                            heads x head_dim | groups x state | groups x state
       dt = softplus(dt + dt_bias) ; A = -exp(A_log)
       S_t = exp(dt_t A) S_(t-1) + dt_t u_t B_t^T ; y_t = S_t C_t + D u_t    one position a step; head i reads group i // (heads / groups)
       a = RMSNorm_group(y * silu(z); g_m) W_out                  mean of squares over each group's d_inner / groups channels
    *: q, k, v = x Wq, x Wk, x Wv ;  a = softmax(causal(q k^T / sqrt(head_dim))) v Wo    head i reads KV head i // (heads / kv heads); no positions
    E: s = sigmoid(x W_r) ; pick top_k by s + b ;  w = s[picked] / sum s[picked] * routed_scaling_factor
       a = sum_{i picked and held} w_i W_down_i relu(x W_up_i)^2 + W_down_s relu(x W_up_s)^2
    logits = RMSNorm(h_L; g_f) W_head ;  loss = mean_t -log softmax(logits_t)[target_t]

**Departures from the published model**, each the configuration's to state:
the layers that run are a stretch of the published pattern (``first_layer``,
``num_hidden_layers``); the parameters hold the experts ``first_expert`` to
``first_expert`` + (how many the stacks hold) of the router's width alone, a
chip's share of a layer, and every held expert runs on every token, one after
the other in a counted loop, weighted by a dense ``[tokens, experts]`` matrix
(zero where the token did not pick the expert): what the absent experts
would have added is left out, as the program leaves it out, and the shared
expert is whole; the vocabulary is the slice the tables hold. No rotary
embedding is applied (the family's attention has none), the gated norm's
statistics are a group's, and the correction bias enters the selection
alone.

A state-space layer goes a stretch of ``SEGMENT`` positions after the other
(the conv's last inputs and the state pass on), attention by blocks of
``QUERY_ROWS`` query rows against the keys and values of the whole context,
an expert layer a stretch at a time and the head by blocks of positions, so
neither S x S scores for all heads nor [S, vocab] logits exist whole.

It takes the program's parameter tree as it sits on the device: one stack a
run of the scan's units (``run00_experts_mamba`` with the first layer's
leaves under ``a_`` and the second's under ``b_``, ``run01_attention``,
...; ``_walk`` finds each layer's), upcast one layer, and inside it one
expert, at a time.
"""

from __future__ import annotations

from functools import partial
from typing import Any, Dict, Tuple

import jax
import jax.numpy as jnp
import numpy as np

F32 = jnp.float32
QUERY_ROWS = 128   # attention: query rows a block
SEGMENT = 1024     # everything else along a sequence: positions a block
HEAD_ROWS = 1024   # head: positions a block

KINDS = {"M": "mamba", "E": "experts", "*": "attention"}
_STATIC = ("kind", "heads", "groups", "state", "top_k", "norm_topk_prob",
           "scaling", "eps", "first_expert")


def arguments(config: Dict[str, Any]) -> Dict[str, Any]:
    """What ``forward`` and ``loss`` take besides arrays, from the published
    keys of a configuration file (and, for a share, its ``deployment``: the
    first layer that runs and the first expert held here; how many are held
    the parameters say)."""
    deployment = config.get("deployment", {})
    first = deployment.get("layers_run", {}).get("first", 0)
    pattern = config["hybrid_override_pattern"]
    return {"layer_types": tuple(
                KINDS[letter] for letter in
                pattern[first:first + config["num_hidden_layers"]]),
            "heads": config["mamba_num_heads"],
            "groups": config["n_groups"],
            "state": config["ssm_state_size"],
            "top_k": config["num_experts_per_tok"],
            "norm_topk_prob": config["norm_topk_prob"],
            "scaling": config["routed_scaling_factor"],
            "eps": config["layer_norm_epsilon"],
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


def _recurrence(state, u, dt, A, B, C, D):
    """(state after the last position, y [B, S, H, P]) of the state-space
    recurrence from ``state`` [B, H, P, N], one position a step. u [B, S,
    H, P]; dt [B, S, H]; A, D [H]; B, C [B, S, G, N]: head i reads group
    ``i // (H / G)``, so a position's B and C are laid out a head first."""
    per_group = u.shape[2] // B.shape[2]

    def step(state, at):
        u_t, dt_t, B_t, C_t = at
        B_h, C_h = (jnp.repeat(a, per_group, axis=1) for a in (B_t, C_t))
        state = jnp.exp(dt_t * A)[..., None, None] * state \
            + (dt_t[..., None] * u_t)[..., None] * B_h[:, :, None, :]
        y_t = (state * C_h[:, :, None, :]).sum(-1) + D[:, None] * u_t
        return state, y_t

    state, y = jax.lax.scan(step, state, tuple(
        a.swapaxes(0, 1) for a in (u, dt, B, C)))
    return state, y.swapaxes(0, 1)


def _mamba_layer(h, w, heads, groups, state_size, eps):
    """A state-space layer on h [B, S, d], a stretch of ``SEGMENT``
    positions after the other: the conv's last inputs and the state pass
    from stretch to stretch, zero before the first."""
    d_inner, taps = w["w_out"].shape[0], w["conv_w"].shape[0]
    A, batch = -jnp.exp(w["A_log"]), h.shape[0]
    rows = min(SEGMENT, h.shape[1])
    bias = w.get("conv_b", 0.0)

    def stretch(carry, h_s):
        tail, state = carry
        x = _rmsnorm(h_s, w["ln_scale"], eps)
        z, xbc, dt = jnp.split(x @ w["w_in"], [d_inner, w["w_in"].shape[1]
                                               - heads], axis=-1)
        padded = jnp.concatenate([tail, xbc], axis=1)
        xbc = jax.nn.silu(bias + sum(
            w["conv_w"][k] * padded[:, k:k + rows] for k in range(taps)))
        u, B, C = jnp.split(
            xbc, [d_inner, d_inner + groups * state_size], axis=-1)
        by_group = B.shape[:2] + (groups, state_size)
        state, y = _recurrence(
            state, u.reshape(u.shape[:2] + (heads, -1)),
            jax.nn.softplus(dt + w["dt_bias"]), A, B.reshape(by_group),
            C.reshape(by_group), w["D"])
        # The gate first, then each group of d_inner / groups channels to
        # unit mean square under its own stretch of the scale.
        gated = (y.reshape(z.shape) * jax.nn.silu(z)).reshape(
            z.shape[:2] + (groups, -1))
        normed = gated / jnp.sqrt((gated * gated).mean(-1, keepdims=True)
                                  + eps)
        a = (normed.reshape(z.shape) * w["norm_scale"]) @ w["w_out"]
        return (padded[:, rows:], state), h_s + a

    conv_dim = w["conv_w"].shape[1]
    start = (jnp.zeros((batch, taps - 1, conv_dim), F32),
             jnp.zeros((batch, heads, d_inner // heads, state_size), F32))
    return _whole(jax.lax.scan(stretch, start, _segments(h, rows))[1])


def _attention_layer(h, w, eps):
    """An attention layer on h [B, S, d]: keys and values of the whole
    context first, then ``QUERY_ROWS`` query rows at a time against all of
    them. Query head i reads KV head i // (heads / kv heads): the query
    heads are taken as [kv heads, heads a kv head]. No positions."""
    seq = h.shape[1]

    def keys_values(h_s):
        x = _rmsnorm(h_s, w["ln_scale"], eps)
        return (jnp.einsum("bsd,dgk->bsgk", x, w["wk"]),
                jnp.einsum("bsd,dgk->bsgk", x, w["wv"]))

    k, v = (_whole(a) for a in jax.lax.map(
        keys_values, _segments(h, min(SEGMENT, seq))))
    kv_heads, width, rows = k.shape[2], k.shape[3], min(QUERY_ROWS, seq)

    def queries(at):
        start, h_s = at
        x = _rmsnorm(h_s, w["ln_scale"], eps)
        q = jnp.einsum("bsd,dhk->bshk", x, w["wq"])
        q = q.reshape(q.shape[:2] + (kv_heads, -1, width))
        scores = jnp.einsum("bqgjk,btgk->bgjqt", q, k) / np.sqrt(width)
        allowed = jnp.arange(seq)[None, :] <= start + jnp.arange(rows)[:, None]
        scores = jnp.where(allowed, scores, -jnp.inf)
        a = jnp.einsum("bgjqt,btgk->bqgjk", jax.nn.softmax(scores, axis=-1),
                       v)
        return h_s + jnp.einsum("bqhk,hkd->bqd", a.reshape(
            x.shape[:2] + (-1, width)), w["wo"])

    # Rematerialised a block at a time, so that a backward pass through
    # this holds one block's [rows, S] scores, as the forward pass does.
    return _whole(jax.lax.map(jax.checkpoint(queries), (
        jnp.arange(0, seq, rows), _segments(h, rows))))


def _relu2_mlp(x, w_up, w_down):
    return jnp.square(jax.nn.relu(x @ w_up)) @ w_down


def _routing(x, router, bias, top_k, norm_topk_prob, scaling):
    """(picked [.., K], weight of every expert for every token [.., E]):
    the selection by score plus bias, the weights from the scores alone."""
    scores = jax.nn.sigmoid(x @ router)
    _, picked = jax.lax.top_k(scores + bias, top_k)
    chosen = jax.nn.one_hot(picked, scores.shape[-1], dtype=F32).sum(-2)
    weights = scores * chosen
    if norm_topk_prob:
        weights = weights / weights.sum(-1, keepdims=True)
    return picked, weights * scaling


_EXPERT_LEAVES = ("w_up", "w_down")


def _experts_layer(h, w, top_k, norm_topk_prob, scaling, eps, first_expert):
    """An expert layer on h [B, S, d], a stretch of ``SEGMENT`` positions at
    a time: the held experts' part of the routed sum and the shared expert.
    Returns (h, picked [B, S, K])."""

    def stretch(h_s):
        x = _rmsnorm(h_s, w["ln_scale"], eps)
        picked, weights = _routing(x, w["router"], w["router_bias"], top_k,
                                   norm_topk_prob, scaling)

        def add_expert(e, m):
            """m + w_e Expert_e(x), on held expert e's weights upcast
            alone."""
            w_up, w_down = (
                jax.lax.dynamic_index_in_dim(w[name], e, 0, keepdims=False
                                             ).astype(F32)
                for name in _EXPERT_LEAVES)
            return m + jnp.take(weights, first_expert + e, axis=-1)[
                ..., None] * _relu2_mlp(x, w_up, w_down)

        # A counted loop, one held expert after the other.
        routed = jax.lax.fori_loop(0, w["w_up"].shape[0], add_expert,
                                   jnp.zeros_like(x))
        shared = _relu2_mlp(x, w["shared_w_up"], w["shared_w_down"])
        return h_s + routed + shared, picked

    h, picked = jax.lax.map(jax.checkpoint(stretch),
                            _segments(h, min(SEGMENT, h.shape[1])))
    return _whole(h), _whole(picked)


def block(h, w: Dict[str, jax.Array], *, kind, heads, groups, state, top_k,
          norm_topk_prob, scaling, eps, first_expert):
    """One layer of ``kind`` on one layer's weights (the program's names;
    float32 but for ``w_up`` / ``w_down``, upcast an expert at a time).
    Returns (h, picked [B, S, K]; of a layer without experts [B, S, 0])."""
    if kind == "experts":
        return _experts_layer(h, w, top_k, norm_topk_prob, scaling, eps,
                              first_expert)
    h = _mamba_layer(h, w, heads, groups, state, eps) if kind == "mamba" \
        else _attention_layer(h, w, eps)
    return h, jnp.zeros(h.shape[:2] + (0,), jnp.int32)


def _layer(stack, index, prefix: str, dynamic: bool):
    """Layer ``index`` of a stack (its leaves under ``prefix``), float32 but
    for the routed experts' weights, which ``block`` upcasts one expert at a
    time."""
    def pick(name, a):
        a = jax.lax.dynamic_index_in_dim(a, index, 0, keepdims=False) \
            if dynamic else a[index]
        return a if name in _EXPERT_LEAVES else a.astype(F32)

    return {name[len(prefix):]: pick(name[len(prefix):], a)
            for name, a in stack.items() if name.startswith(prefix)}


@partial(jax.jit, static_argnames=_STATIC + ("prefix",), donate_argnums=(0,))
def _block_at(h, stack, index, *, prefix, **kw):
    return block(h, _layer(stack, index, prefix, dynamic=True), **kw)


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
    """(kind, the name of its run's stack, index within it, the prefix of
    its leaves there) of every layer in order, as the program's tree holds
    them: an ``experts`` layer and the ``mamba`` layer behind it (or, where
    a stretch in which the two alternate starts with ``mamba`` and is of
    even length, the other way round) are one unit whose leaves lie under
    ``a_`` and ``b_``; a stretch's odd layer, first if ``mamba`` and last if
    ``experts``, and every ``attention`` layer are units of one layer,
    without a prefix; a run is a stretch of units of one kind."""
    units, at = [], 0
    while at < len(layer_types):
        end = at + 1
        while end < len(layer_types) and "attention" not in \
                layer_types[end - 1:end + 1] \
                and layer_types[end] != layer_types[end - 1]:
            end += 1
        if (end - at) % 2 and layer_types[at] == "mamba":
            units.append(layer_types[at:at + 1])
            at += 1
        while end - at >= 2:
            units.append(layer_types[at:at + 2])
            at += 2
        if at < end:
            units.append(layer_types[at:end])
        at = end
    run, index = -1, 0
    for i, unit in enumerate(units):
        if i == 0 or units[i - 1] != unit:
            run, index = run + 1, 0
        stack = f"run{run:02d}_" + "_".join(unit)
        for kind, prefix in zip(unit, ("a_", "b_") if len(unit) == 2
                                else ("",)):
            yield kind, stack, index, prefix
        index += 1


def forward(params: Dict[str, Any], tokens, targets, positions, *,
            layer_types, with_picked: bool = False, **kw
            ) -> Tuple[jax.Array, ...]:
    """(logits at ``positions`` [B, P, vocab], loss per sequence [B], RMS of
    all logits), float32; with ``with_picked`` also the experts picked [L
    expert layers, B, S, K]. ``params`` is the program's tree."""
    eps = kw["eps"]
    with jax.default_matmul_precision("highest"):
        h = _embed(params["wte"], tokens)
        picked = []
        for kind, stack, index, prefix in _walk(layer_types):
            h, p = _block_at(h, params[stack], jnp.int32(index), kind=kind,
                             prefix=prefix, **kw)
            if kind == "experts":
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


def loss(params: Dict[str, Any], tokens, targets, *, layer_types, **kw
         ) -> jax.Array:
    """Mean loss over all positions, differentiable in ``params``: what the
    gradient check takes the reference's gradients of. One program, the
    layers walked in Python, each rematerialised in the backward pass (the
    literal recurrence keeps its state at every position, 2 MB a position
    at the published widths: one layer's at a time); for small depths and
    short sequences only."""
    with jax.default_matmul_precision("highest"):
        h = jnp.take(params["wte"], tokens, axis=0).astype(F32)
        for kind, stack, index, prefix in _walk(layer_types):
            h = jax.checkpoint(
                lambda h, w, kind=kind: block(h, w, kind=kind, **kw)[0])(
                h, _layer(params[stack], index, prefix, dynamic=False))
        logits = _rmsnorm(h, params["lnf_scale"].astype(F32), kw["eps"]) \
            @ params["lm_head"].astype(F32)
        logp = jax.nn.log_softmax(logits, axis=-1)
        return -jnp.take_along_axis(
            logp, targets[..., None], axis=-1)[..., 0].mean()
