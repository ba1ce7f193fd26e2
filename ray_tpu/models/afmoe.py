"""AFMoE language models (``model_type: afmoe``, Arcee's Trinity family):
sliding-window and full attention layers mixed, gated attention with a norm
on q and k, a norm before and after every branch, and a routed-expert FFN
with one shared expert after a few leading dense layers.

The config keys carry their published names (``AfmoeConfig``), so a
``config.json`` of the family reads straight into ``AfmoeConfig``. The
published instance behind the preset is Trinity-Large-Preview
(https://huggingface.co/arcee-ai/Trinity-Large-Preview/blob/main/config.json).
As ``AfmoeForCausalLM`` computes it; no bias anywhere, every norm an RMSNorm
with ``rms_norm_eps``::

    h        = wte[tokens] * sqrt(hidden_size)                    mup_enabled
    layer l, kind layer_types[l], dense iff l < num_dense_layers:
    x        = RMSNorm(h; g_in)
    q | k | v = x Wq | x Wk | x Wv     heads | kv heads | kv heads of head_dim
    g        = x Wg                    heads x head_dim, from the same x
    q, k     = RMSNorm(q; g_q), RMSNorm(k; g_k)    over head_dim, one scale vector for all heads
    sliding_attention only: q, k = rope(q), rope(k)    theta rope_theta, pairs (i, i + head_dim / 2)
                            full_attention: no positions
    a        = softmax(mask(q k^T / sqrt(head_dim))) v     query head i reads KV head i // (heads / kv heads)
               mask: key j <= query i, and on sliding_attention also i - j < sliding_window
    a        = (a * sigmoid(g)) Wo                 the gate a channel, before the output projection
    h        = h + RMSNorm(a; g_post_attn)         the norm on the branch's output, then the sum
    x        = RMSNorm(h; g_pre_mlp)
    dense:   m = W_down(silu(W_gate x) * W_up x)
    experts: s = sigmoid(x W_r) in float32 ; picked = top num_experts_per_tok of (s + b)
             w = s[picked] / (sum s[picked] + 1e-20) * route_scale        (route_norm)
             m = Shared(x) + sum_i w_i Expert_i(x)
    h        = h + RMSNorm(m; g_post_mlp)
    logits   = RMSNorm(h_last; g_f) W_head         untied

``b`` (``expert_bias``) steers selection only and is not trained by the
gradient, and no rule moves it here (the published update is training code
the config does not carry). The loss is the cross-entropy alone:
``load_balance_coeff`` sizes a balance term whose form the config does not
give.

This module is the family's config, its table of leaves (``_shapes``) and its
block; the rest is ``models/lm.py``'s ``Decoder``: parameters and specs from
the table, the lookup, the layer scan over kinds of layer with remat, the
head and loss, the expert layers' counters, and the pieces families share
(``rmsnorm``, ``rope``, ``swiglu``, ``expert_ffn``, the attention
dispatch with the layer's window). The expert layer is ``ops/moe.py``. A
layer's kind is its FFN and its attention together
(``dense_sliding_attention``, ``moe_full_attention``, ...); every run of one
kind is one stack of parameters and one scan.

**The chip's share.** ``experts_held = (first, count)`` says which of a
layer's ``num_experts`` live here: the parameters hold those alone, the
router stays ``num_experts`` wide, and the layer returns the shared expert
plus this chip's part of the routed sum (``ops/moe.py``, "Held experts").
None holds them all. A sliced vocabulary is a smaller ``vocab_size``: rows
of ``wte``, columns of the head, ids and loss over the slice. Expert
parallelism (an ``ep`` mesh axis > 1) is not implemented: the share runs
without an exchange, as one chip of the group would between its exchanges.
"""

from __future__ import annotations

import math
from dataclasses import dataclass, replace
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from ray_tpu.models import lm

_PERIOD = ("sliding_attention",) * 3 + ("full_attention",)


@dataclass(frozen=True)
class AfmoeConfig:
    # Published keys, under their published names.
    vocab_size: int = 200192
    hidden_size: int = 3072
    num_hidden_layers: int = 60
    num_dense_layers: int = 6
    #: The kind of attention of every layer of the published depth; a model
    #: cut to ``num_hidden_layers`` runs the first that many.
    layer_types: Tuple[str, ...] = _PERIOD * 15
    sliding_window: int = 4096
    num_attention_heads: int = 48
    num_key_value_heads: int = 8
    head_dim: int = 128
    rope_theta: float = 10000.0
    intermediate_size: int = 12288
    moe_intermediate_size: int = 3072
    num_experts: int = 256
    num_experts_per_tok: int = 4
    num_shared_experts: int = 1
    route_norm: bool = True
    route_scale: float = 2.448
    mup_enabled: bool = True
    rms_norm_eps: float = 1e-5
    max_position_embeddings: int = 262144
    #: (first, count) of the ``num_experts`` whose weights live here; None:
    #: all of them.
    experts_held: Optional[Tuple[int, int]] = None
    # The program's own choices (as GPTConfig has them).
    dtype: Any = jnp.bfloat16
    param_dtype: Any = jnp.float32
    remat: bool = True
    # "full" keeps of a block only what the flash forward kernel returns
    # (output and log-sum-exp, at long sequences: lm.scan_blocks).
    # "selective" adds the values a block names for it, and this model's
    # blocks name none.
    remat_policy: str = "full"
    loss_chunk: int = 0
    attn_impl: str = "dot"  # "dot" | "flash"
    attn_blk_q: int = 512
    attn_blk_k: int = 512

    def __post_init__(self):
        object.__setattr__(self, "layer_types", tuple(self.layer_types))
        object.__setattr__(self, "experts_held", lm.held_experts(
            self.experts_held, self.num_experts))
        if len(self.layer_types) < self.num_hidden_layers:
            raise ValueError("layer_types is shorter than num_hidden_layers")
        unknown = set(self.layer_types) - set(_PERIOD)
        if unknown:
            raise ValueError(f"layer_types of unknown kinds {unknown}")

    @property
    def layers(self) -> Tuple[str, ...]:
        """The kind of each layer that runs: its FFN (``dense`` or ``moe``)
        and its attention, as ``dense_sliding_attention``."""
        return tuple(
            ("dense_" if i < self.num_dense_layers else "moe_") + kind
            for i, kind in enumerate(
                self.layer_types[:self.num_hidden_layers]))

    @property
    def n_moe_layers(self) -> int:
        return max(self.num_hidden_layers - self.num_dense_layers, 0)


PRESETS: Dict[str, AfmoeConfig] = {
    "trinity-large-preview": AfmoeConfig(),
    # Test size: all four kinds of layer (two dense, three expert layers),
    # 8 experts with 2 a token, a window shorter than the test sequences.
    "afmoe-tiny": AfmoeConfig(
        vocab_size=256, hidden_size=64, num_hidden_layers=5,
        num_dense_layers=2,
        layer_types=("sliding_attention", "full_attention",
                     "sliding_attention", "sliding_attention",
                     "full_attention"),
        sliding_window=16, num_attention_heads=4, num_key_value_heads=2,
        head_dim=32, intermediate_size=128, moe_intermediate_size=32,
        num_experts=8, num_experts_per_tok=2, max_position_embeddings=512,
        dtype=jnp.float32, remat=False),
}


def config(name: str, **overrides) -> AfmoeConfig:
    cfg = PRESETS[name]
    return replace(cfg, **overrides) if overrides else cfg


# -- parameters ---------------------------------------------------------

def _shapes(cfg: AfmoeConfig):
    """{"dense" | "moe": {leaf: (shape without the layers axis, logical
    axes, init: a std, or ``lm.ones`` | ``lm.zeros``)}}: one table for
    ``init`` and ``param_specs`` (``lm.Decoder``). Window and full layers
    hold the same leaves."""
    d, h, kv = cfg.hidden_size, cfg.num_attention_heads, \
        cfg.num_key_value_heads
    hd, std = cfg.head_dim, 0.02
    attn = {
        "ln_in_scale": ((d,), ("embed",), lm.ones),
        "wq": ((d, h, hd), ("embed", "heads", "head_dim"), std),
        "wk": ((d, kv, hd), ("embed", "kv_heads", "head_dim"), std),
        "wv": ((d, kv, hd), ("embed", "kv_heads", "head_dim"), std),
        "w_attn_gate": ((d, h, hd), ("embed", "heads", "head_dim"), std),
        "q_norm_scale": ((hd,), (None,), lm.ones),
        "k_norm_scale": ((hd,), (None,), lm.ones),
        "wo": ((h, hd, d), ("heads", "head_dim", "embed"), std),
        "ln_post_attn_scale": ((d,), ("embed",), lm.ones),
        "ln_pre_mlp_scale": ((d,), ("embed",), lm.ones),
        "ln_post_mlp_scale": ((d,), ("embed",), lm.ones),
    }
    f = cfg.moe_intermediate_size
    moe = lm.expert_leaves(d, cfg.num_experts, cfg.experts_held, f,
                           shared_width=cfg.num_shared_experts * f)
    return {"dense": dict(attn, **lm.swiglu_leaves(
                d, cfg.intermediate_size)),
            "moe": dict(attn, **moe)}


def _leaves_of(shapes, kind: str):
    return shapes[kind.split("_", 1)[0]]


# -- forward ------------------------------------------------------------

def _attention(cfg: AfmoeConfig, sliding: bool, x, layer, positions):
    """Gated grouped-query attention on normed x [B, S, d] -> [B, S, d]:
    windowed with rope where ``sliding``, else over the whole context
    without positions."""
    dt = cfg.dtype
    q = jnp.einsum("bsd,dhk->bshk", x, layer["wq"].astype(dt))
    k = jnp.einsum("bsd,dhk->bshk", x, layer["wk"].astype(dt))
    v = jnp.einsum("bsd,dhk->bshk", x, layer["wv"].astype(dt))
    with jax.named_scope("qk_norm"):
        q = lm.rmsnorm(q, layer["q_norm_scale"], cfg.rms_norm_eps)
        k = lm.rmsnorm(k, layer["k_norm_scale"], cfg.rms_norm_eps)
    if sliding:
        q = lm.rope(q, positions, cfg.rope_theta)
        k = lm.rope(k, positions, cfg.rope_theta)
    attn = lm.attention(q, k, v, cfg,
                        window=cfg.sliding_window if sliding else None)
    with jax.named_scope("attn_gate"):
        gate = jnp.einsum("bsd,dhk->bshk", x, layer["w_attn_gate"].astype(dt))
        attn = (attn.astype(jnp.float32)
                * jax.nn.sigmoid(gate.astype(jnp.float32))).astype(dt)
    return jnp.einsum("bshk,hkd->bsd", attn, layer["wo"].astype(dt))


def _block(cfg: AfmoeConfig, kind: str, h, layer, positions):
    """One layer of ``kind`` (``lm.runs``). Returns (h, aux): aux is None
    for a dense layer, else the expert layer's (``lm.expert_aux``)."""
    eps = cfg.rms_norm_eps
    ffn, attention_kind = kind.split("_", 1)
    with jax.named_scope(attention_kind):
        a = _attention(cfg, attention_kind == "sliding_attention",
                       lm.rmsnorm(h, layer["ln_in_scale"], eps), layer,
                       positions)
        h = h + lm.rmsnorm(a, layer["ln_post_attn_scale"], eps)
    x = lm.rmsnorm(h, layer["ln_pre_mlp_scale"], eps)
    if ffn == "dense":
        with jax.named_scope("mlp"):
            m = lm.swiglu(x, layer["w_gate"], layer["w_up"], layer["w_down"])
        return h + lm.rmsnorm(m, layer["ln_post_mlp_scale"], eps), None
    routed, shared, aux = lm.expert_ffn(
        x, layer, top_k=cfg.num_experts_per_tok, scaling=cfg.route_scale,
        normalize=cfg.route_norm, held=cfg.experts_held)
    return h + lm.rmsnorm(routed + shared, layer["ln_post_mlp_scale"],
                          eps), aux


_SHELL = lm.Decoder(
    name="afmoe", shapes=_shapes, leaves_of=_leaves_of,
    block=lambda *args: _block(*args),
    embed_scale=lambda cfg: math.sqrt(cfg.hidden_size)
    if cfg.mup_enabled else None,
    experts=True,
    metrics=lambda cfg, aux, targets: lm.moe_metrics(
        aux, targets.size * cfg.num_experts_per_tok))

#: ``hidden_states``' aux is the expert layers' ``picked`` [L_moe, B, S, K],
#: ``group_sizes`` [L_moe, held experts], ``asked``, ``within_bound`` and
#: ``rows_summed`` [L_moe], in layer order; ``loss_fn``'s metrics are the
#: cross-entropy's and ``lm.moe_metrics``.
init, param_specs = _SHELL.init, _SHELL.param_specs
hidden_states, head = _SHELL.hidden_states, _SHELL.head
forward, forward_with_aux = _SHELL.forward, _SHELL.forward_with_aux
loss_of_hidden, loss_fn = _SHELL.loss_of_hidden, _SHELL.loss_fn
SUMMED_METRICS, RECORDED_METRICS = lm.SUMMED_METRICS, lm.RECORDED_METRICS
