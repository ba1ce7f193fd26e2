"""LFM2 expert language models (``model_type: lfm2_moe``, Liquid AI's LFM2
family): layers whose sequence mixer is a double-gated short convolution,
with a grouped-query attention layer (a norm on q and k, then rope) after
every third, and a routed-expert FFN without a shared expert after a few
leading dense layers.

The config keys carry their published names (``Lfm2Config``), so a
``config.json`` of the family reads straight into ``Lfm2Config``. The
published instance behind the preset is LFM2-24B-A2B
(https://huggingface.co/LiquidAI/LFM2-24B-A2B/blob/main/config.json). The
mixer, the attention and the layer as ``transformers``' ``modeling_lfm2.py``
computes them (``Lfm2ShortConv``, ``Lfm2Attention``, ``Lfm2DecoderLayer``);
no bias anywhere (``conv_bias`` false), every norm an RMSNorm with
``norm_eps``::

    h        = wte[tokens]
    layer l (0-based), attention iff layer_types[l] == "full_attention", dense iff l < num_dense_layers:
    x        = RMSNorm(h; g_op)                                  operator_norm
    conv:    B | C | u = x W_in                                  three chunks of hidden_size, in this order
             z_t    = sum_{k < conv_L_cache} w_k (B * u)_(t - conv_L_cache + 1 + k)      depthwise, causal, zeros before the first token
             m      = (C * z) W_out                              no activation anywhere in the mixer
    attn:    q | k | v = x Wq | x Wk | x Wv                      heads | kv heads | kv heads of head_dim
             q, k   = RMSNorm(q; g_q), RMSNorm(k; g_k)           over head_dim, one scale vector for all heads
             q, k   = rope(q), rope(k)                           whole head, i paired with i + head_dim / 2, theta rope_parameters.rope_theta
             m      = softmax(causal(q k^T / sqrt(head_dim))) v Wo      query head i reads KV head i // (heads / kv heads)
    h        = h + m
    x        = RMSNorm(h; g_ffn)                                 ffn_norm
    dense:   W_down(silu(W_gate x) * W_up x)                     intermediate_size
    experts: s = sigmoid(x W_r) in float32 ; picked = top num_experts_per_tok of (s + b)
             w = s[picked] / (sum s[picked] + 1e-20) * routed_scaling_factor      (norm_topk_prob)
             sum_i w_i Expert_i(x)                               SwiGLUs of moe_intermediate_size ; no shared expert
    h        = h + that
    logits   = RMSNorm(h_last; g_emb) wte^T                      embedding_norm is the final norm; the head is the table

``b`` (``expert_bias``, ``use_expert_bias``) steers selection only and is
not trained by the gradient, and no rule moves it here. The loss is the
cross-entropy alone.

This module is the family's config, its table of leaves (``_shapes``) and its
block; the rest is ``models/lm.py``'s ``Decoder`` (parameters and specs from
the table, the lookup, the layer scan over kinds of layer with remat, the
tied head and the loss, the expert layers' counters) and the pieces families
share: the attention dispatch (``attention``), the gated short convolution's
(``short_conv``: ``ops/short_conv.py``'s fused pass each way where the shapes
tile, else its ``jax.numpy`` form), ``rmsnorm`` / ``rope`` /
``swiglu``. The expert layer is ``ops/moe.py``, called here: without a
shared expert and with a bias that may be off, it is not ``lm.expert_ffn``'s
call. A layer's kind is its FFN and its mixer together (``dense_conv``,
``moe_conv``, ``moe_full_attention``, ...); every run of one kind is one
stack of parameters and one scan.

**The layers that run.** ``num_hidden_layers`` layers from published layer
``first_layer`` on, the first ``num_dense_layers`` of them dense: a cut that
counts the leading dense layers once starts at ``first_layer`` 1, so that
the layer pattern behind them falls as published.

**The chip's share.** ``experts_held = (first, count)`` says which of a
layer's ``num_experts`` live here: the parameters hold those alone, the
router stays ``num_experts`` wide, and the layer returns this chip's part of
the routed sum (``ops/moe.py``, "Held experts"): a token none of whose
experts is held gets a zero from the layer. None holds them all. A sliced
vocabulary is a smaller ``vocab_size``: rows of the one table that is
embedding and head. Expert parallelism (an ``ep`` mesh axis > 1) is not
implemented: the share runs without an exchange.
"""

from __future__ import annotations

from dataclasses import dataclass, replace
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from ray_tpu.models import lm
from ray_tpu.ops.moe import routed_experts

_PUBLISHED_LAYERS = ("conv", "conv") \
    + ("full_attention", "conv", "conv", "conv") * 9 \
    + ("full_attention", "conv")


@dataclass(frozen=True)
class RopeParameters:
    """The published ``rope_parameters`` group."""
    rope_theta: float = 1000000.0
    rope_type: str = "default"


@dataclass(frozen=True)
class Lfm2Config:
    # Published keys, under their published names.
    vocab_size: int = 65536
    hidden_size: int = 2048
    num_hidden_layers: int = 40
    num_dense_layers: int = 2
    #: The mixer of every layer of the published depth; the model runs
    #: ``num_hidden_layers`` of them from ``first_layer`` on.
    layer_types: Tuple[str, ...] = _PUBLISHED_LAYERS
    conv_L_cache: int = 3
    conv_bias: bool = False
    num_attention_heads: int = 32
    num_key_value_heads: int = 8
    #: A dict (as ``config.json`` has it) or a ``RopeParameters``.
    rope_parameters: Any = RopeParameters()
    intermediate_size: int = 11776
    moe_intermediate_size: int = 1536
    num_experts: int = 64
    num_experts_per_tok: int = 4
    norm_topk_prob: bool = True
    routed_scaling_factor: float = 1.0
    use_expert_bias: bool = True
    norm_eps: float = 1e-5
    max_position_embeddings: int = 128000
    tie_word_embeddings: bool = True
    #: The published layer that the model's first layer is (the module
    #: text); the leading ``num_dense_layers`` count from it.
    first_layer: int = 0
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
        if isinstance(self.rope_parameters, dict):
            object.__setattr__(self, "rope_parameters",
                               RopeParameters(**self.rope_parameters))
        object.__setattr__(self, "experts_held", lm.held_experts(
            self.experts_held, self.num_experts))
        if self.first_layer < 0 or len(self.layer_types) \
                < self.first_layer + self.num_hidden_layers:
            raise ValueError(
                f"layer_types has {len(self.layer_types)} layers, fewer than "
                f"first_layer {self.first_layer} + num_hidden_layers "
                f"{self.num_hidden_layers}")
        unknown = set(self.layer_types) - {"conv", "full_attention"}
        if unknown:
            raise ValueError(f"layer_types of unknown kinds {unknown}")
        if self.conv_bias or not self.tie_word_embeddings \
                or self.rope_parameters.rope_type != "default":
            raise NotImplementedError(
                "models/lfm2.py computes conv_bias false, a tied head and "
                "rope_type 'default' only")

    @property
    def head_dim(self) -> int:
        return self.hidden_size // self.num_attention_heads

    @property
    def layers(self) -> Tuple[str, ...]:
        """The kind of each layer that runs: its FFN (``dense`` or ``moe``)
        and its mixer, as ``dense_conv``."""
        run = self.layer_types[self.first_layer:
                               self.first_layer + self.num_hidden_layers]
        return tuple(("dense_" if i < self.num_dense_layers else "moe_") + kind
                     for i, kind in enumerate(run))

    @property
    def n_moe_layers(self) -> int:
        return max(self.num_hidden_layers - self.num_dense_layers, 0)


PRESETS: Dict[str, Lfm2Config] = {
    "lfm2-24b-a2b": Lfm2Config(),
    # Test size: all four kinds of layer (two dense, three expert layers),
    # 8 experts with 2 a token.
    "lfm2-tiny": Lfm2Config(
        vocab_size=256, hidden_size=64, num_hidden_layers=5,
        num_dense_layers=2,
        layer_types=("conv", "full_attention", "conv", "full_attention",
                     "conv"),
        num_attention_heads=4, num_key_value_heads=2, intermediate_size=128,
        moe_intermediate_size=32, num_experts=8, num_experts_per_tok=2,
        max_position_embeddings=512, dtype=jnp.float32, remat=False),
}


def config(name: str, **overrides) -> Lfm2Config:
    cfg = PRESETS[name]
    return replace(cfg, **overrides) if overrides else cfg


# -- parameters ---------------------------------------------------------

def _shapes(cfg: Lfm2Config):
    """{"conv" | "full_attention" | "dense" | "moe": {leaf: (shape without
    the layers axis, logical axes, init)}}: one table for ``init`` and
    ``param_specs`` (``lm.Decoder``); a layer holds its mixer's leaves and
    its FFN's. ``init`` is a std for a normal draw, or ``lm.ones`` |
    ``lm.zeros``: matrices normal(0, 0.02), RMSNorm scales of one, a zero
    ``expert_bias``, the convolution's taps normal with the variance of
    ``nn.Conv1d``'s default. One table ``wte`` is embedding and head."""
    d, h, kv = cfg.hidden_size, cfg.num_attention_heads, \
        cfg.num_key_value_heads
    hd, taps, std = cfg.head_dim, cfg.conv_L_cache, 0.02
    norms = {"operator_norm_scale": ((d,), ("embed",), lm.ones),
             "ffn_norm_scale": ((d,), ("embed",), lm.ones)}
    conv = {
        # The chunks B, C, x side by side, as Lfm2ShortConv's in_proj.
        "w_in": ((d, 3 * d), ("embed", "mlp"), std),
        # nn.Conv1d's default, uniform(+-K^-1/2), has this variance.
        "conv_w": ((taps, d), (None, None), (3 * taps) ** -0.5),
        "w_out": ((d, d), ("mlp", "embed"), std),
    }
    attention = {
        "wq": ((d, h, hd), ("embed", "heads", "head_dim"), std),
        "wk": ((d, kv, hd), ("embed", "kv_heads", "head_dim"), std),
        "wv": ((d, kv, hd), ("embed", "kv_heads", "head_dim"), std),
        "q_norm_scale": ((hd,), (None,), lm.ones),
        "k_norm_scale": ((hd,), (None,), lm.ones),
        "wo": ((h, hd, d), ("heads", "head_dim", "embed"), std),
    }
    return {"conv": dict(norms, **conv),
            "full_attention": dict(norms, **attention),
            "dense": lm.swiglu_leaves(d, cfg.intermediate_size),
            "moe": lm.expert_leaves(d, cfg.num_experts, cfg.experts_held,
                                    cfg.moe_intermediate_size)}


def _leaves_of(shapes, kind: str):
    ffn, mixer = kind.split("_", 1)
    return dict(shapes[mixer], **shapes[ffn])


# -- forward ------------------------------------------------------------

def _short_conv(cfg: Lfm2Config, x, layer):
    """The double-gated short convolution on normed x [B, S, d] -> [B, S,
    d]: one projection to the chunks B | C | x, ``C * conv(B * x)``
    (``lm.short_conv``), one projection back."""
    dt = cfg.dtype
    with jax.named_scope("in_proj"):
        bcx = jnp.einsum("bsd,de->bse", x, layer["w_in"].astype(dt))
    y = lm.short_conv(bcx, layer["conv_w"])
    with jax.named_scope("out_proj"):
        return jnp.einsum("bsd,de->bse", y, layer["w_out"].astype(dt))


def _attention(cfg: Lfm2Config, x, layer, positions):
    """Grouped-query attention on normed x [B, S, d] -> [B, S, d]: a norm on
    q and k over the head, then rope on both."""
    dt, eps = cfg.dtype, cfg.norm_eps
    q = jnp.einsum("bsd,dhk->bshk", x, layer["wq"].astype(dt))
    k = jnp.einsum("bsd,dhk->bshk", x, layer["wk"].astype(dt))
    v = jnp.einsum("bsd,dhk->bshk", x, layer["wv"].astype(dt))
    with jax.named_scope("qk_norm"):
        q = lm.rmsnorm(q, layer["q_norm_scale"], eps)
        k = lm.rmsnorm(k, layer["k_norm_scale"], eps)
    with jax.named_scope("rope"):
        theta = cfg.rope_parameters.rope_theta
        q = lm.rope(q, positions, theta)
        k = lm.rope(k, positions, theta)
    attn = lm.attention(q, k, v, cfg)
    return jnp.einsum("bshk,hkd->bsd", attn, layer["wo"].astype(dt))


def _block(cfg: Lfm2Config, kind: str, h, layer, positions):
    """One layer of ``kind`` (``lm.runs``). Returns (h, aux): aux is None
    for a dense layer, else the expert layer's (``lm.expert_aux``)."""
    ffn, mixer = kind.split("_", 1)
    x = lm.rmsnorm(h, layer["operator_norm_scale"], cfg.norm_eps)
    if mixer == "conv":
        with jax.named_scope("short_conv"):
            h = h + _short_conv(cfg, x, layer)
    else:
        with jax.named_scope("full_attention"):
            h = h + _attention(cfg, x, layer, positions)
    x = lm.rmsnorm(h, layer["ffn_norm_scale"], cfg.norm_eps)
    if ffn == "dense":
        with jax.named_scope("mlp"):
            return h + lm.swiglu(x, layer["w_gate"], layer["w_up"],
                                 layer["w_down"]), None
    B, S, d = x.shape
    bias = layer["router_bias"]
    routed, aux = routed_experts(
        x.reshape(B * S, d), layer["router"],
        bias if cfg.use_expert_bias else jnp.zeros_like(bias),
        layer["w_gate"], layer["w_up"], layer["w_down"],
        top_k=cfg.num_experts_per_tok, scaling=cfg.routed_scaling_factor,
        normalize=cfg.norm_topk_prob, held=cfg.experts_held)
    aux = lm.expert_aux(aux, (B, S))
    return h + routed.reshape(B, S, d), aux


_SHELL = lm.Decoder(
    name="lfm2", shapes=_shapes, leaves_of=_leaves_of,
    block=lambda *args: _block(*args),
    final_norm="embedding_norm_scale", eps="norm_eps", tied=True,
    experts=True,
    metrics=lambda cfg, aux, targets: lm.moe_metrics(
        aux, targets.size * cfg.num_experts_per_tok))

#: ``hidden_states``' aux is the expert layers' ``picked`` [L_moe, B, S, K],
#: ``group_sizes`` [L_moe, held experts], ``asked``, ``within_bound`` and
#: ``rows_summed`` [L_moe], in layer order; ``head`` is the table's rows
#: again.
init, param_specs = _SHELL.init, _SHELL.param_specs
hidden_states, head = _SHELL.hidden_states, _SHELL.head
forward, forward_with_aux = _SHELL.forward, _SHELL.forward_with_aux
loss_of_hidden, loss_fn = _SHELL.loss_of_hidden, _SHELL.loss_fn
SUMMED_METRICS, RECORDED_METRICS = lm.SUMMED_METRICS, lm.RECORDED_METRICS
