"""Mellum language models (``model_type: mellum``, JetBrains' Mellum 2):
sliding-window and full attention layers mixed, each kind with rope
parameters of its own (plain rope in the window layers, YaRN in the full
ones), a norm on q and k, and in every layer a routed-expert FFN whose
router is a softmax over all the experts, with no shared expert, no bias and
no leading dense layer.

The config keys carry their published names (``MellumConfig``), so a
``config.json`` of the family reads straight into ``MellumConfig``. The
published instance behind the preset is Mellum2-12B-A2.5B-Instruct
(https://huggingface.co/JetBrains/Mellum2-12B-A2.5B-Instruct/blob/main/config.json).
No bias anywhere, every norm an RMSNorm with ``rms_norm_eps``; at the
preset's numbers::

    h        = wte[tokens]
    layer l, kind layer_types[l]; every FFN is the expert layer:
    x        = RMSNorm(h; g_in)
    q | k | v = x Wq | x Wk | x Wv          32 | 4 | 4 heads of 128
    q, k     = RMSNorm(q; g_q), RMSNorm(k; g_k)   over head_dim, one scale vector for all heads (assumed, below)
    q, k     = rope_kind(q), rope_kind(k)   pairs (i, i + 64); angle pos * f_kind[i]; cos and sin times m_kind
               sliding_attention: f[i] = 500000^(-2i/128), m = 1
               full_attention (yarn): e[i] = 500000^(-2i/128); d(n) = 128 ln(8192 / (2 pi n)) / (2 ln 500000)
                                      low = floor(d(32)) = 18, high = ceil(d(1)) = 35, both clipped to [0, 127]
                                      r[i] = clip((i - low) / (high - low), 0, 1),  i = 0..63
                                      f[i] = (1 - r[i]) e[i] + r[i] e[i] / 16,  m = 1.2772588722239782
    a        = softmax(mask(q k^T / sqrt(128))) v     query head i reads KV head i // 8
               mask: key j <= query i, and on sliding_attention also i - j < 1024
    h        = h + a Wo
    x        = RMSNorm(h; g_post)
    p        = softmax(x W_r) in float32 over all 64 ; picked = top 8 of p
    w        = p[picked] / sum p[picked]                                  (norm_topk_prob)
    h        = h + sum_i w_i W_down_i (silu(W_gate_i x) * W_up_i x)       experts of 896
    logits   = RMSNorm(h_last; g_f) W_head                                untied

The table ``(f, m)`` of a kind of layer is ``lm.rope_table`` of its
``rope_parameters`` (``transformers``' ``_compute_yarn_parameters``); the
router is ``Qwen3MoeSparseMoeBlock``'s form (``ops/moe.py`` ``route`` with
``score="softmax"``). With ``norm_topk_prob`` the weights are a softmax over
the eight picked logits, so the unpicked columns cancel out of value and
gradient alike.

**Assumed: the norm on q and k.** The config has no key for it. Its key set
(``max_window_layers``, ``use_sliding_window``, ``norm_topk_prob``,
``moe_intermediate_size``, an explicit ``head_dim``, ``attention_bias``) is
that of the ``qwen3_moe`` lineage, whose attention norms q and k a head
without a key for it, so the layer norms them. ``max_window_layers`` and
``use_sliding_window`` are read as ``layer_types`` states them;
``intermediate_size`` is read by no layer (``mlp_layer_types`` is all
``sparse``). The loss is the cross-entropy alone: the config carries no
coefficient of a balance loss, and no key of a multi-token head.

This module is the family's config, its table of leaves (``_shapes``) and its
block; the rest is ``models/lm.py``'s ``Decoder``. The expert layer is
``ops/moe.py``. A layer's kind is its attention's (``sliding_attention``,
``full_attention``); every run of one kind is one stack of parameters and one
scan.

**The chip's share.** ``experts_held = (first, count)`` says which of a
layer's ``num_experts`` live here, as in ``models/afmoe.py``: the parameters
hold those alone, the router stays ``num_experts`` wide, and the layer
returns this chip's part of the routed sum. None holds them all. Expert
parallelism (an ``ep`` mesh axis > 1) is not implemented.
"""

from __future__ import annotations

from dataclasses import dataclass, replace
from typing import Any, Dict, Mapping, Optional, Tuple

import jax
import jax.numpy as jnp

from ray_tpu._private import builtin_metrics
from ray_tpu.models import lm

_PERIOD = ("sliding_attention",) * 3 + ("full_attention",)


class Published(dict):
    """A published mapping as a field of a frozen config: a dict that
    hashes by its items (nested mappings likewise), so that the config is a
    static argument like any other."""

    def __init__(self, mapping: Mapping):
        super().__init__({
            key: Published(value) if isinstance(value, Mapping) else value
            for key, value in mapping.items()})

    def __hash__(self):  # type: ignore[override]
        return hash(tuple(sorted(self.items())))


@dataclass(frozen=True)
class MellumConfig:
    # Published keys, under their published names.
    vocab_size: int = 98304
    hidden_size: int = 2304
    num_hidden_layers: int = 28
    #: The kind of attention of every layer of the published depth; a model
    #: cut to ``num_hidden_layers`` runs the first that many.
    layer_types: Tuple[str, ...] = _PERIOD * 7
    sliding_window: int = 1024
    num_attention_heads: int = 32
    num_key_value_heads: int = 4
    head_dim: int = 128
    #: {kind of layer: its rope's parameters} (``lm.rope_table``).
    rope_parameters: Mapping[str, Mapping[str, Any]] = Published({
        "full_attention": {
            "rope_type": "yarn", "rope_theta": 500000, "factor": 16,
            "original_max_position_embeddings": 8192, "beta_fast": 32,
            "beta_slow": 1, "attention_factor": 1.2772588722239782},
        "sliding_attention": {"rope_type": "default", "rope_theta": 500000},
    })
    moe_intermediate_size: int = 896
    num_experts: int = 64
    num_experts_per_tok: int = 8
    norm_topk_prob: bool = True
    rms_norm_eps: float = 1e-6
    max_position_embeddings: int = 131072
    #: (first, count) of the ``num_experts`` whose weights live here; None:
    #: all of them.
    experts_held: Optional[Tuple[int, int]] = None
    # The program's own choices (as GPTConfig has them).
    dtype: Any = jnp.bfloat16
    param_dtype: Any = jnp.float32
    remat: bool = True
    remat_policy: str = "full"
    loss_chunk: int = 0
    attn_impl: str = "dot"  # "dot" | "flash"
    attn_blk_q: int = 512
    attn_blk_k: int = 512

    def __post_init__(self):
        object.__setattr__(self, "layer_types", tuple(self.layer_types))
        object.__setattr__(self, "rope_parameters",
                           Published(self.rope_parameters))
        object.__setattr__(self, "experts_held", lm.held_experts(
            self.experts_held, self.num_experts))
        if len(self.layer_types) < self.num_hidden_layers:
            raise ValueError("layer_types is shorter than num_hidden_layers")
        unknown = set(self.layer_types) - set(_PERIOD)
        if unknown:
            raise ValueError(f"layer_types of unknown kinds {unknown}")
        for kind in set(self.layers):
            if kind not in self.rope_parameters:
                raise ValueError(f"rope_parameters has no {kind!r}")
            rope_type = self.rope_parameters[kind].get("rope_type", "default")
            if rope_type not in lm.ROPE_TYPES:
                raise NotImplementedError(
                    f"rope_type {rope_type!r} on {kind}: one of "
                    f"{lm.ROPE_TYPES}")

    @property
    def layers(self) -> Tuple[str, ...]:
        """The kind of each layer that runs."""
        return self.layer_types[:self.num_hidden_layers]

    @property
    def n_moe_layers(self) -> int:
        return self.num_hidden_layers


PRESETS: Dict[str, MellumConfig] = {
    "mellum2-12b-a2.5b": MellumConfig(),
    # Test size: both kinds of layer, a window shorter than the test
    # sequences and no multiple of a tile, and a YaRN table whose ramp
    # is neither all 0 nor all 1 at heads of 32: over an original length of
    # 64 with theta 10000, low = 1 and high = 5 of the 16 pairs.
    "mellum-tiny": MellumConfig(
        vocab_size=256, hidden_size=64, num_hidden_layers=4,
        layer_types=("sliding_attention", "full_attention",
                     "sliding_attention", "sliding_attention"),
        sliding_window=20, num_attention_heads=4, num_key_value_heads=2,
        head_dim=32, rope_parameters={
            "full_attention": {
                "rope_type": "yarn", "rope_theta": 10000.0, "factor": 8.0,
                "original_max_position_embeddings": 64, "beta_fast": 4,
                "beta_slow": 1, "attention_factor": 1.2079441541679836},
            "sliding_attention": {"rope_type": "default",
                                  "rope_theta": 10000.0}},
        moe_intermediate_size=32, num_experts=8, num_experts_per_tok=2,
        max_position_embeddings=512, dtype=jnp.float32, remat=False),
}


def config(name: str, **overrides) -> MellumConfig:
    cfg = PRESETS[name]
    return replace(cfg, **overrides) if overrides else cfg


# -- parameters ---------------------------------------------------------

def _shapes(cfg: MellumConfig):
    """{leaf: (shape without the layers axis, logical axes, init: a std, or
    ``lm.ones``)}: one table for ``init`` and ``param_specs``
    (``lm.Decoder``). Window and full layers hold the same leaves."""
    d, h, kv = cfg.hidden_size, cfg.num_attention_heads, \
        cfg.num_key_value_heads
    hd, std = cfg.head_dim, 0.02
    return {
        "ln_in_scale": ((d,), ("embed",), lm.ones),
        "wq": ((d, h, hd), ("embed", "heads", "head_dim"), std),
        "wk": ((d, kv, hd), ("embed", "kv_heads", "head_dim"), std),
        "wv": ((d, kv, hd), ("embed", "kv_heads", "head_dim"), std),
        "q_norm_scale": ((hd,), (None,), lm.ones),
        "k_norm_scale": ((hd,), (None,), lm.ones),
        "wo": ((h, hd, d), ("heads", "head_dim", "embed"), std),
        "ln_post_scale": ((d,), ("embed",), lm.ones),
        **lm.expert_leaves(d, cfg.num_experts, cfg.experts_held,
                           cfg.moe_intermediate_size, bias=False),
    }


# -- forward ------------------------------------------------------------

def _attention(cfg: MellumConfig, kind: str, x, layer, positions):
    """Grouped-query attention on normed x [B, S, d] -> [B, S, d], q and k
    normed and rotated by the table of the layer's ``kind``, under the
    window where the kind is ``sliding_attention``."""
    dt = cfg.dtype
    q = jnp.einsum("bsd,dhk->bshk", x, layer["wq"].astype(dt))
    k = jnp.einsum("bsd,dhk->bshk", x, layer["wk"].astype(dt))
    v = jnp.einsum("bsd,dhk->bshk", x, layer["wv"].astype(dt))
    with jax.named_scope("qk_norm"):
        q = lm.rmsnorm(q, layer["q_norm_scale"], cfg.rms_norm_eps)
        k = lm.rmsnorm(k, layer["k_norm_scale"], cfg.rms_norm_eps)
    with jax.named_scope("rope"):
        q = lm.rope(q, positions, cfg.rope_parameters[kind])
        k = lm.rope(k, positions, cfg.rope_parameters[kind])
    attn = lm.attention(
        q, k, v, cfg,
        window=cfg.sliding_window if kind == "sliding_attention" else None)
    return jnp.einsum("bshk,hkd->bsd", attn, layer["wo"].astype(dt))


def _block(cfg: MellumConfig, kind: str, h, layer, positions):
    """One layer of ``kind`` (``lm.runs``). Returns (h, the expert layer's
    aux: ``lm.expert_aux``)."""
    eps = cfg.rms_norm_eps
    with jax.named_scope(kind):
        h = h + _attention(cfg, kind, lm.rmsnorm(h, layer["ln_in_scale"],
                                                 eps), layer, positions)
    routed, _, aux = lm.expert_ffn(
        lm.rmsnorm(h, layer["ln_post_scale"], eps), layer,
        top_k=cfg.num_experts_per_tok, scaling=1.0,
        normalize=cfg.norm_topk_prob, held=cfg.experts_held,
        score="softmax")
    return h + routed, aux


def window_tile_fill(cfg: MellumConfig, seq_len: int) -> Optional[float]:
    """``lm.window_tile_fill`` of the window layers; None where none runs."""
    if "sliding_attention" not in cfg.layers:
        return None
    return lm.window_tile_fill(cfg, cfg.sliding_window, seq_len)


def _metrics(cfg: MellumConfig, aux, targets):
    """``lm.moe_metrics``, ``moe_picked_mass`` (the layers' mean of the
    probability a token's picked experts hold before renormalising) and
    ``attn_window_tile_fill`` (``window_tile_fill``; not a number where it
    has none, and nothing is recorded then)."""
    fill = window_tile_fill(cfg, targets.shape[1])
    return {**lm.moe_metrics(aux, targets.size * cfg.num_experts_per_tok),
            "moe_picked_mass": aux["picked_mass"].mean(),
            "attn_window_tile_fill": jnp.float32(
                jnp.nan if fill is None else fill)}


_SHELL = lm.Decoder(
    name="mellum", shapes=_shapes, leaves_of=lambda table, kind: table,
    block=lambda *args: _block(*args), experts=True, metrics=_metrics)

#: ``hidden_states``' aux is the expert layers' ``picked`` [L, B, S, K],
#: ``group_sizes`` [L, held experts], ``asked``, ``within_bound``,
#: ``rows_summed`` and ``picked_mass`` [L], in layer order; ``loss_fn``'s
#: metrics are the cross-entropy's and ``_metrics``.
init, param_specs = _SHELL.init, _SHELL.param_specs
hidden_states, head = _SHELL.hidden_states, _SHELL.head
forward, forward_with_aux = _SHELL.forward, _SHELL.forward_with_aux
loss_of_hidden, loss_fn = _SHELL.loss_of_hidden, _SHELL.loss_fn
SUMMED_METRICS = lm.SUMMED_METRICS


RECORDED_METRICS = {
    **lm.RECORDED_METRICS,
    "moe_picked_mass": lambda value:
        builtin_metrics.train_moe_picked_mass().set(value),
    "attn_window_tile_fill": lm.record_window_tile_fill,
}
