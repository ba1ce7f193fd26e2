"""dots3-note's forward pass and loss (``dots3_note``), plainly, as the
yardstick for ``correct`` of the family (dots3-note-prev is one).

Written from the published config and from the descriptions its keys point
to (DeepSeek-V3's latent attention with a low-rank query; DeepSeek-V3.2's
indexer, selection and indexer loss; LongCat-Flash's rescale of the two
latents; the head-wise sigmoid gate of arXiv:2505.06708; ``deepseek_v3``'s
expert layer), in ``jax.numpy`` and float32 under
``jax.default_matmul_precision("highest")``, with ``jax.lax.top_k`` on whole
rows, the selection and the window as explicit masks, every held expert
looped and no kernel, layer scan, remat policy, sort or grouped matmul,
independent of ``ray_tpu/models/`` and ``ray_tpu/ops/``::

    h        = wte[tokens]
    published layer l, x = RMSNorm(h; g1); a full layer (layer_types[l] ==
    "full_attention") reads the plain keys, a window layer the swa_ ones:
    c_q      = RMSNorm(x W_qa; g_q) ;  q = (s_q c_q) W_qb              s_q  = sqrt(hidden / q_lora_rank)
    c | k_r  = x W_kva ;  c = s_kv RMSNorm(c; g_kv) ;  k_n | v = c W_kvb   s_kv = sqrt(hidden / kv_lora_rank)
    q_r, k_r = rope(q_r), rope(k_r)            the kind's theta, pairs (2i, 2i+1), k_r one head for all
    full:
      qI     = c_q W_Iq ;  kI = LayerNorm(x W_Ik) ;  rope on the first qk_rope dimensions of both
      w      = x W_Iw / sqrt(index_n_heads * index_head_dim)
      I[t,s] = sum_j w[t,j] ReLU(qI[t,j] . kI[s]) ,  s <= t ;  S_t = top index_topk of I[t, :t+1]
      a_h    = softmax_{s in S_t}(q_h . k_h[s] / sqrt(nope + rope)) v_h[s]
      L_I   += mean_t KL(p[t] / sum p[t] || softmax_{S_t} I[t]) ,  p[t,s] = sum_heads softmax
    window:
      a_h    = softmax_{0 <= t - s < sliding_window_size}(q_h . k_h[s] / sqrt(nope + rope)) v_h[s]
    a_h      = sigmoid(x W_g)_h a_h ;  h = h + concat_h(a_h) W_o
    x        = RMSNorm(h; g2)
    l < first_k_dense_replace: h + W_down(silu(W_gate x) * W_up x)
    experts: s = sigmoid(x W_r) ;  pick top_k of s + b   (b: selection only)
             w = s[picked] / (sum s[picked] + 1e-20) * scaling
             h + Shared(x) + sum_{i picked and held} w_i Expert_i(x)
    logits   = RMSNorm(h_L; g_f) W_head
    loss     = mean_t -log softmax(logits_t)[target_t] + coef * L_I

``p``, ``x`` and ``c_q`` reach the indexer under ``stop_gradient`` and
nothing differentiates the top-k: the indexer's leaves get their gradient
from ``L_I`` alone and every other leaf from the cross-entropy alone. The
gate multiplies the attention's output, not its probabilities: ``p`` is
ungated.

**Departures from the published description.** The vision and audio towers
and the multi-token prediction module are absent (the language model's keys
alone are read). What the config sizes and does not describe is read as the
configuration file's ``assumed`` says: the rescale (LongCat-Flash's), the
indexer's wiring and loss (DeepSeek-V3.2's), the gate's input (the layer's
normed input), the window (a query's own key and the
``sliding_window_size - 1`` before it), plain top-k routing without groups.

**The share.** The parameters hold the experts ``first_expert`` to
``first_expert`` + (how many the stacks hold) of the router's width alone;
every held expert runs on every token, one after the other, weighted by
``w`` (zero where the token did not pick it). What the absent experts would
have added is left out, as the program leaves it out.

Attention and the indexer go by blocks of ``QUERY_ROWS`` query rows against
the keys of the whole context, the head by blocks of positions, so neither
S x S scores for all heads nor [S, vocab] logits exist whole. It takes the
program's parameter tree as it sits on the device (one stack a run of
layers of one kind, ``run00_dense_full``, ...) and upcasts one layer, and
inside an expert layer one expert, at a time.
"""

from __future__ import annotations

from functools import partial
from typing import Any, Dict, Tuple

import jax
import jax.numpy as jnp
import numpy as np

F32 = jnp.float32
QUERY_ROWS = 128   # attention and the indexer: query rows a block
SEGMENT = 1024     # keys, values and the FFN: positions a block
HEAD_ROWS = 1024   # head: positions a block

_STATIC = ("full", "geometry", "topk", "top_k", "scaling", "renormalize",
           "eps", "index_eps", "first_expert")


def _geometry(config: Dict[str, Any], prefix: str, window):
    """(nope, rope, rank, theta, s_q, s_kv, window) of the layers whose keys
    start with ``prefix``."""
    hidden = config["hidden_size"]
    rescale = config["apply_mla_qkv_lora_rescale"]
    q_rank, rank = config[prefix + "q_lora_rank"], \
        config[prefix + "kv_lora_rank"]
    return (config[prefix + "qk_nope_head_dim"],
            config[prefix + "qk_rope_head_dim"], rank,
            float(config[prefix + "rope_theta"]),
            float(np.sqrt(hidden / q_rank)) if rescale else 1.0,
            float(np.sqrt(hidden / rank)) if rescale else 1.0, window)


def arguments(config: Dict[str, Any]) -> Dict[str, Any]:
    """What ``forward`` and ``loss`` take besides arrays, from the published
    keys of a configuration file: which published layers run
    (``layers_run``; all, if absent), and for the share its ``deployment``
    (the first expert held here; how many the parameters say). What the
    config does not publish is under its ``assumed.sizes``."""
    run = config.get("layers_run", range(config["num_hidden_layers"]))
    assumed = config.get("assumed", {}).get("sizes", {})
    held = config.get("deployment", {}).get("experts_held", {})
    for key in ("attention_gate_type", "swa_attention_gate_type"):
        assert config[key] == "headwise", (key, config[key])
    return {"layers": tuple(
                (l < config["first_k_dense_replace"],
                 config["layer_types"][l] == "full_attention") for l in run),
            "geometries": (
                _geometry(config, "swa_", config["sliding_window_size"]),
                _geometry(config, "", None)),
            "topk": config["index_topk"],
            "top_k": config["num_experts_per_tok"],
            "scaling": config["routed_scaling_factor"],
            "renormalize": config["norm_topk_prob"],
            "eps": config["rms_norm_eps"],
            "index_eps": assumed.get("index_norm_eps", 1e-6),
            "coef": assumed.get("indexer_loss_coef", 1.0),
            "first_expert": held.get("first", 0)}


def _rmsnorm(x, scale, eps):
    return x / jnp.sqrt((x * x).mean(-1, keepdims=True) + eps) * scale


def _layernorm(x, scale, bias, eps):
    centred = x - x.mean(-1, keepdims=True)
    return centred / jnp.sqrt((centred ** 2).mean(-1, keepdims=True) + eps) \
        * scale + bias


def _rope(x, positions, theta):
    """x [B, S, ..., R] rotated over its last axis, pairs (2i, 2i+1), the
    result holding all first members, then all second (q and k alike)."""
    half = x.shape[-1] // 2
    freqs = theta ** (-jnp.arange(half, dtype=F32) / half)
    angles = positions.astype(F32)[..., None] * freqs     # [B, S, half]
    angles = angles.reshape(angles.shape[:2] + (1,) * (x.ndim - 3) + (half,))
    first, second = x[..., 0::2], x[..., 1::2]
    return jnp.concatenate([
        first * jnp.cos(angles) - second * jnp.sin(angles),
        second * jnp.cos(angles) + first * jnp.sin(angles)], -1)


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


def _partly_rotated(x, positions, rope, theta):
    return jnp.concatenate(
        [_rope(x[..., :rope], positions, theta), x[..., rope:]], -1)


def block(h, w: Dict[str, jax.Array], positions, *, full, geometry, topk,
          top_k, scaling, renormalize, eps, index_eps, first_expert):
    """One layer on one layer's weights (the program's names; float32 but
    for an expert layer's ``w_gate`` / ``w_up`` / ``w_down``, upcast an
    expert at a time): a full layer (``full``) selects its keys itself, a
    window layer sees ``geometry``'s window. Returns (h, the pairs attended
    over [B, S, S] bool, index loss [B] (0 of a window layer), picked [B, S,
    K] or None, the gate's mean)."""
    nope, rope, rank, theta, s_q, s_kv, window = geometry
    ffn = partial(_ffn, top_k=top_k, scaling=scaling, renormalize=renormalize,
                  eps=eps, first_expert=first_expert)
    batch, seq = h.shape[:2]

    def keys_values(at):
        pos_s, h_s = at
        x = _rmsnorm(h_s, w["ln1_scale"], eps)
        kv_a = x @ w["w_kv_a"]
        c = s_kv * _rmsnorm(kv_a[..., :rank], w["kv_norm_scale"], eps)
        kv = jnp.einsum("bsr,rhk->bshk", c, w["w_kv_b"])
        k_rope = _rope(kv_a[..., None, rank:], pos_s, theta)
        k = jnp.concatenate([kv[..., :nope], jnp.broadcast_to(
            k_rope, kv.shape[:3] + (rope,))], -1)
        if not full:
            return k, kv[..., nope:], jnp.zeros(x.shape[:2] + (0,), F32)
        k_index = _layernorm(jax.lax.stop_gradient(x) @ w["w_ik"],
                             w["ik_norm_scale"],
                             w["ik_norm_bias"], index_eps)
        return k, kv[..., nope:], _partly_rotated(k_index, pos_s, rope, theta)

    rows = min(SEGMENT, seq)
    k, v, k_index = (_whole(a) for a in jax.lax.map(
        keys_values, (_segments(positions, rows), _segments(h, rows))))
    rows = min(QUERY_ROWS, seq)

    def queries(at):
        start, pos_s, h_s = at
        x = _rmsnorm(h_s, w["ln1_scale"], eps)
        c_q = _rmsnorm(x @ w["w_q_a"], w["q_norm_scale"], eps)
        q = jnp.einsum("bsr,rhk->bshk", s_q * c_q, w["w_q_b"])
        q = jnp.concatenate(
            [q[..., :nope], _rope(q[..., nope:], pos_s, theta)], -1)
        behind = (start + jnp.arange(rows)[:, None]) - jnp.arange(seq)[None]
        allowed = behind >= 0
        if full:
            x_i, c_i = jax.lax.stop_gradient((x, c_q))
            q_index = _partly_rotated(
                jnp.einsum("bsr,rje->bsje", c_i, w["w_iq"]), pos_s, rope,
                theta)
            heads, width = q_index.shape[-2:]
            weight = (x_i @ w["w_iw"]) / np.sqrt(heads * width)
            index = jnp.einsum("bqj,bqjt->bqt", weight, jax.nn.relu(
                jnp.einsum("bqje,bte->bqjt", q_index, k_index)))
            index = jnp.where(allowed, index, -jnp.inf)
            _, best = jax.lax.top_k(jax.lax.stop_gradient(index),
                                    min(topk, seq))
            chosen = jax.vmap(jax.vmap(
                lambda row, keys: row.at[keys].set(True)))(
                jnp.zeros(index.shape, jnp.bool_), best) & allowed
        else:
            chosen = jnp.broadcast_to(allowed & (behind < window),
                                      (batch, rows, seq))
        scores = jnp.einsum("bqhk,bthk->bhqt", q, k) / np.sqrt(q.shape[-1])
        probs = jax.nn.softmax(
            jnp.where(chosen[:, None], scores, -jnp.inf), axis=-1)
        a = jnp.einsum("bhqt,bthk->bqhk", probs, v)
        index_loss = jnp.zeros(batch, F32)
        if full:
            target = jax.lax.stop_gradient(probs.sum(1))
            target = target / target.sum(-1, keepdims=True)
            log_q = jax.nn.log_softmax(
                jnp.where(chosen, index, -jnp.inf), axis=-1)
            index_loss = jnp.where(
                chosen & (target > 0),
                target * (jnp.log(jnp.where(target > 0, target, 1.0))
                          - jnp.where(chosen, log_q, 0.0)), 0.0).sum((1, 2))
        gate = jax.nn.sigmoid(x @ w["w_attn_gate"])           # [B, rows, H]
        h_s, picked = ffn(h_s + jnp.einsum(
            "bqhk,hkd->bqd", a * gate[..., None], w["wo"]), w)
        return h_s, chosen, index_loss, picked, gate.sum()

    # Rematerialised a block at a time, so that a backward pass through
    # this holds one block's [rows, S] scores, as the forward pass does.
    h, attended, index_loss, picked, gate = jax.lax.map(
        jax.checkpoint(queries),
        (jnp.arange(0, seq, rows), _segments(positions, rows),
         _segments(h, rows)))
    heads = w["w_attn_gate"].shape[-1]
    return _whole(h), _whole(attended), index_loss.sum(0) / seq, \
        None if picked is None else _whole(picked), \
        gate.sum() / (batch * seq * heads)


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
def _block_at(h, stack, index, positions, **kw):
    return block(h, _layer(stack, index, dynamic=True), positions, **kw)


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


def _walk(layers):
    """(full attention?, the name of its run's stack, index within it) of
    every layer in order; a run is a stretch of layers of one kind, a kind
    the FFN (dense or experts) and the attention (full or window)."""
    kinds = [("dense_" if dense else "moe_") + ("full" if full else "window")
             for dense, full in layers]
    run, index = -1, 0
    for i, kind in enumerate(kinds):
        if i == 0 or kinds[i - 1] != kind:
            run, index = run + 1, 0
        yield layers[i][1], f"run{run:02d}_{kind}", index
        index += 1


def _positions(tokens):
    return jnp.broadcast_to(jnp.arange(tokens.shape[1], dtype=jnp.int32),
                            tokens.shape)


def forward(params: Dict[str, Any], tokens, targets, positions, *, layers,
            geometries, coef, with_picked: bool = False,
            with_selections: bool = False, **kw) -> Tuple[jax.Array, ...]:
    """(logits at ``positions`` [B, P, vocab], loss per sequence [B] (both
    terms), RMS of all logits), float32; with ``with_picked`` also the
    experts picked [L_moe, B, S, K], with ``with_selections`` also (the
    pairs every layer attends over [L, B, S, S], the index loss per sequence
    [B], every layer's mean gate [L]). ``params`` is the program's tree."""
    eps = kw["eps"]
    with jax.default_matmul_precision("highest"):
        h = _embed(params["wte"], tokens)
        where = _positions(tokens)
        picked, attended, gates, index_loss = [], [], [], 0.0
        for full, stack, index in _walk(layers):
            h, pairs, loss_l, p, gate = _block_at(
                h, params[stack], jnp.int32(index), where, full=full,
                geometry=geometries[full], **kw)
            index_loss = index_loss + loss_l
            gates.append(gate)
            if with_selections:
                attended.append(pairs)
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
        out = (sampled, nll / seq + coef * index_loss,
               jnp.sqrt(squares / (float(tokens.size) * vocab)))
        if with_picked:
            out += (jnp.stack(picked),)
        if with_selections:
            out += (jnp.stack(attended), index_loss, jnp.stack(gates))
        return out


def loss(params: Dict[str, Any], tokens, targets, *, layers, geometries,
         coef, parts: bool = False, **kw) -> jax.Array:
    """Mean loss over all positions (both terms), differentiable in
    ``params``: what the gradient check takes the reference's gradients of;
    with ``parts`` the pair (cross-entropy, ``L_I``) instead. One program,
    the layers walked in Python, each rematerialised in the backward pass;
    for small depths and short sequences only."""
    with jax.default_matmul_precision("highest"):
        h = jnp.take(params["wte"], tokens, axis=0).astype(F32)
        where = _positions(tokens)
        index_loss = 0.0
        for full, stack, index in _walk(layers):
            # A layer's float32 copy is made inside what is rematerialised:
            # the backward pass holds one layer's, not every layer's.
            h, _, loss_l, _, _ = jax.checkpoint(
                lambda h, stack, full=full, index=index: block(
                    h, _layer(stack, index, dynamic=False), where, full=full,
                    geometry=geometries[full], **kw))(h, params[stack])
            index_loss = index_loss + loss_l.mean()
        logits = _rmsnorm(h, params["lnf_scale"].astype(F32), kw["eps"]) \
            @ params["lm_head"].astype(F32)
        logp = jax.nn.log_softmax(logits, axis=-1)
        ce = -jnp.take_along_axis(
            logp, targets[..., None], axis=-1)[..., 0].mean()
        return (ce, index_loss) if parts else ce + coef * index_loss
