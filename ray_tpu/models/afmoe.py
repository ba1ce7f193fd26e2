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

What every language model here shares is ``models/lm.py``'s: the lookup, the
layer scan over kinds of layer with remat (``scan_blocks``), the attention
dispatch with the layer's window (``attention``: dot, or the flash kernels
through one pair table), the chunked head and loss (``next_token_loss``).
The expert layer is ``ops/moe.py``. A layer's kind is its FFN and its
attention together (``dense_sliding_attention``, ``moe_full_attention``,
...); every run of one kind is one stack of parameters and one scan.

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
from functools import partial
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from ray_tpu._private import builtin_metrics
from ray_tpu.models import lm
from ray_tpu.ops.moe import routed_experts
from ray_tpu.parallel.sharding import ShardingRules, constrain

_PERIOD = ("sliding_attention",) * 3 + ("full_attention",)

#: Metrics of ``loss_fn`` that count a batch: summed over accumulation
#: microbatches where the others are averaged (parallel/train_step.py).
SUMMED_METRICS = ("moe_assignments", "moe_tokens", "moe_routed",
                  "moe_calls", "moe_calls_within_bound")

#: Metrics of ``loss_fn`` that feed the registry, each with what records
#: its value there (parallel/train_step.py reads them without a sync).
RECORDED_METRICS = {
    "moe_assignments":
        lambda value: builtin_metrics.train_moe_assignments().inc(value),
    "moe_tokens":
        lambda value: builtin_metrics.train_moe_tokens().inc(value),
    "moe_routed":
        lambda value: builtin_metrics.train_moe_routed().inc(value),
    "moe_calls":
        lambda value: builtin_metrics.train_moe_calls().inc(value),
    "moe_calls_within_bound":
        lambda value: builtin_metrics.train_moe_calls_within_bound().inc(
            value),
    "moe_load_max_over_mean":
        lambda value: builtin_metrics.train_moe_expert_load().set(value),
}


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
        if self.experts_held is not None:
            object.__setattr__(self, "experts_held",
                               tuple(self.experts_held))
            first, count = self.experts_held
            if first < 0 or count < 1 or first + count > self.num_experts:
                raise ValueError(f"experts_held={self.experts_held} of "
                                 f"{self.num_experts} experts")
        if len(self.layer_types) < self.num_hidden_layers:
            raise ValueError("layer_types is shorter than num_hidden_layers")
        unknown = set(self.layer_types) - set(_PERIOD)
        if unknown:
            raise ValueError(f"layer_types of unknown kinds {unknown}")

    @property
    def n_experts_held(self) -> int:
        return self.num_experts if self.experts_held is None \
            else self.experts_held[1]

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

KINDS = tuple(ffn + kind for ffn in ("dense_", "moe_")
              for kind in ("sliding_attention", "full_attention"))


def runs(layers) -> Tuple[Tuple[str, str, int], ...]:
    """(name in the parameter tree, kind, layers) of every run of one kind
    of layer, in order: ``run00_dense_sliding_attention``, ... A run is one
    stack of parameters and one ``lax.scan``."""
    return tuple((f"run{i:02d}_{kind}", kind, n)
                 for i, (kind, n) in enumerate(lm.layer_runs(layers)))


def config(name: str, **overrides) -> AfmoeConfig:
    cfg = PRESETS[name]
    return replace(cfg, **overrides) if overrides else cfg


# -- parameters ---------------------------------------------------------

def _shapes(cfg: AfmoeConfig):
    """{"dense" | "moe": {leaf: (shape without the layers axis, logical
    axes, init std or None for a vector of ones, 0.0 for zeros)}}: one
    table for ``init`` and ``param_specs``. Window and full layers hold the
    same leaves."""
    d, h, kv = cfg.hidden_size, cfg.num_attention_heads, \
        cfg.num_key_value_heads
    hd, std = cfg.head_dim, 0.02
    attn = {
        "ln_in_scale": ((d,), ("embed",), None),
        "wq": ((d, h, hd), ("embed", "heads", "head_dim"), std),
        "wk": ((d, kv, hd), ("embed", "kv_heads", "head_dim"), std),
        "wv": ((d, kv, hd), ("embed", "kv_heads", "head_dim"), std),
        "w_attn_gate": ((d, h, hd), ("embed", "heads", "head_dim"), std),
        "q_norm_scale": ((hd,), (None,), None),
        "k_norm_scale": ((hd,), (None,), None),
        "wo": ((h, hd, d), ("heads", "head_dim", "embed"), std),
        "ln_post_attn_scale": ((d,), ("embed",), None),
        "ln_pre_mlp_scale": ((d,), ("embed",), None),
        "ln_post_mlp_scale": ((d,), ("embed",), None),
    }

    def swiglu(width, prefix=""):
        return {prefix + "w_gate": ((d, width), ("embed", "mlp"), std),
                prefix + "w_up": ((d, width), ("embed", "mlp"), std),
                prefix + "w_down": ((width, d), ("mlp", "embed"), std)}

    e, held, f = cfg.num_experts, cfg.n_experts_held, \
        cfg.moe_intermediate_size
    moe = {
        "router": ((d, e), ("embed", None), std),
        # The published expert_bias: a buffer of zeros that the gradient
        # never moves.
        "router_bias": ((e,), (None,), 0.0),
        "w_gate": ((held, d, f), ("expert", "embed", "mlp"), std),
        "w_up": ((held, d, f), ("expert", "embed", "mlp"), std),
        "w_down": ((held, f, d), ("expert", "mlp", "embed"), std),
        **swiglu(cfg.num_shared_experts * f, "shared_"),
    }
    return {"dense": dict(attn, **swiglu(cfg.intermediate_size)),
            "moe": dict(attn, **moe)}


def _leaves_of(shapes, kind: str):
    return shapes[kind.split("_", 1)[0]]


def init(cfg: AfmoeConfig, key: jax.Array) -> Dict[str, Any]:
    """Parameters: normal(0, 0.02) matrices, RMSNorm scales of one, a zero
    ``expert_bias``. Every run of one kind of layer (``runs``) is a stack of
    its own, over a leading layers axis."""
    pd = cfg.param_dtype
    k_embed, k_head, k_layers = jax.random.split(key, 3)

    def normal(k, shape, std):
        return (jax.random.normal(k, shape, jnp.float32) * std).astype(pd)

    params = {
        "wte": normal(k_embed, (cfg.vocab_size, cfg.hidden_size), 0.02),
        "lnf_scale": jnp.ones((cfg.hidden_size,), pd),
        "lm_head": normal(k_head, (cfg.hidden_size, cfg.vocab_size), 0.02),
    }
    shapes = _shapes(cfg)
    for index, (run, kind, depth) in enumerate(runs(cfg.layers)):
        leaves = _leaves_of(shapes, kind)
        keys = jax.random.split(jax.random.fold_in(k_layers, index),
                                len(leaves))
        params[run] = {
            name: jnp.ones((depth,) + shape, pd) if std is None
            else jnp.zeros((depth,) + shape, pd) if std == 0.0
            else normal(k, (depth,) + shape, std)
            for k, (name, (shape, _, std)) in zip(keys, leaves.items())}
    return params


def param_specs(cfg: AfmoeConfig, rules: ShardingRules) -> Dict[str, Any]:
    """PartitionSpec pytree matching init()'s structure."""
    specs = {"wte": rules.spec("vocab", "embed"),
             "lnf_scale": rules.spec("embed"),
             "lm_head": rules.spec("embed", "vocab")}
    shapes = _shapes(cfg)
    for run, kind, _ in runs(cfg.layers):
        specs[run] = {name: rules.spec("layers", *axes)
                      for name, (_, axes, _) in
                      _leaves_of(shapes, kind).items()}
    return specs


# -- forward ------------------------------------------------------------

# Shared with ``models/lfm2.py``, so they live in ``models/lm.py``; under
# these names the module's own functions call them.
_rmsnorm, _rope = lm.rmsnorm, lm.rope


def _attention(cfg: AfmoeConfig, sliding: bool, x, layer, positions):
    """Gated grouped-query attention on normed x [B, S, d] -> [B, S, d]:
    windowed with rope where ``sliding``, else over the whole context
    without positions."""
    dt = cfg.dtype
    q = jnp.einsum("bsd,dhk->bshk", x, layer["wq"].astype(dt))
    k = jnp.einsum("bsd,dhk->bshk", x, layer["wk"].astype(dt))
    v = jnp.einsum("bsd,dhk->bshk", x, layer["wv"].astype(dt))
    with jax.named_scope("qk_norm"):
        q = _rmsnorm(q, layer["q_norm_scale"], cfg.rms_norm_eps)
        k = _rmsnorm(k, layer["k_norm_scale"], cfg.rms_norm_eps)
    if sliding:
        q = _rope(q, positions, cfg.rope_theta)
        k = _rope(k, positions, cfg.rope_theta)
    attn = lm.attention(q, k, v, cfg,
                        window=cfg.sliding_window if sliding else None)
    with jax.named_scope("attn_gate"):
        gate = jnp.einsum("bsd,dhk->bshk", x, layer["w_attn_gate"].astype(dt))
        attn = (attn.astype(jnp.float32)
                * jax.nn.sigmoid(gate.astype(jnp.float32))).astype(dt)
    return jnp.einsum("bshk,hkd->bsd", attn, layer["wo"].astype(dt))


_swiglu = lm.swiglu


def _block(cfg: AfmoeConfig, kind: str, h, layer, positions):
    """One layer of ``kind`` (``runs``). Returns (h, aux): aux is None for a
    dense layer, else the expert layer's ``picked`` [B, S, K],
    ``group_sizes`` [held experts], ``asked`` (assignments the router gave
    them) and ``within_bound`` (1 where they fit ``ops/moe.py``'s one
    buffer)."""
    eps = cfg.rms_norm_eps
    ffn, attention_kind = kind.split("_", 1)
    with jax.named_scope(attention_kind):
        a = _attention(cfg, attention_kind == "sliding_attention",
                       _rmsnorm(h, layer["ln_in_scale"], eps), layer,
                       positions)
        h = h + _rmsnorm(a, layer["ln_post_attn_scale"], eps)
    x = _rmsnorm(h, layer["ln_pre_mlp_scale"], eps)
    if ffn == "dense":
        with jax.named_scope("mlp"):
            m = _swiglu(x, layer["w_gate"], layer["w_up"], layer["w_down"])
        return h + _rmsnorm(m, layer["ln_post_mlp_scale"], eps), None
    B, S, d = x.shape
    routed, aux = routed_experts(
        x.reshape(B * S, d), layer["router"], layer["router_bias"],
        layer["w_gate"], layer["w_up"], layer["w_down"],
        top_k=cfg.num_experts_per_tok, scaling=cfg.route_scale,
        normalize=cfg.route_norm, held=cfg.experts_held)
    with jax.named_scope("shared_expert"):
        shared = _swiglu(x, layer["shared_w_gate"], layer["shared_w_up"],
                         layer["shared_w_down"])
    aux = {"picked": aux["picked"].reshape(B, S, -1),
           "group_sizes": aux["group_sizes"],
           # With every expert held the router's assignments are all asked,
           # and the one buffer holds them.
           "asked": aux.get("asked", jnp.int32(aux["picked"].size)),
           "within_bound": aux.get("within_bound", jnp.int32(1))}
    m = routed.reshape(B, S, d) + shared
    return h + _rmsnorm(m, layer["ln_post_mlp_scale"], eps), aux


def _no_expert_parallelism():
    from ray_tpu.parallel.mesh import current_mesh
    mesh = current_mesh()
    if mesh is not None and mesh.shape.get("ep", 1) > 1:
        raise NotImplementedError(
            "models/afmoe.py does not implement expert parallelism: the "
            "mesh has ep > 1, and the expert layer (ops/moe.py) computes "
            "the experts held here (experts_held) without an exchange. Use "
            "ep=1 (fsdp and tp shard the expert weights).")


def hidden_states(params: Dict[str, Any], cfg: AfmoeConfig,
                  tokens: jax.Array,
                  positions: Optional[jax.Array] = None):
    """tokens [B, S] int32 -> (final-normed hidden [B, S, d], aux) with aux
    the expert layers' ``picked`` [L_moe, B, S, K], ``group_sizes``
    [L_moe, held experts], ``asked`` and ``within_bound`` [L_moe], in layer
    order."""
    _no_expert_parallelism()
    if positions is None:
        positions = lm.positions_of(tokens)
    x = lm.embed(params["wte"], tokens, cfg.dtype)  # batch-split
    if cfg.mup_enabled:
        x = x * jnp.asarray(math.sqrt(cfg.hidden_size), cfg.dtype)
    x, auxes = lm.scan_blocks(
        cfg, {kind: partial(_block, cfg, kind) for kind in KINDS}, x,
        [params[run] for run, _, _ in runs(cfg.layers)], positions,
        layer_types=cfg.layers)
    x = constrain(x, "batch", "sequence", None)
    auxes = [aux for aux in auxes if aux is not None]
    aux = {name: jnp.concatenate([a[name] for a in auxes])
           for name in auxes[0]} if auxes else {}
    return _rmsnorm(x, params["lnf_scale"], cfg.rms_norm_eps), aux


def head(params: Dict[str, Any], cfg: AfmoeConfig, x: jax.Array):
    """Logits [..., vocab] of final-normed hidden states x [..., d]."""
    return jnp.einsum("...d,dv->...v", x, params["lm_head"].astype(cfg.dtype))


def forward_with_aux(params: Dict[str, Any], cfg: AfmoeConfig,
                     tokens: jax.Array,
                     positions: Optional[jax.Array] = None):
    """tokens [B, S] -> (logits [B, S, vocab], aux of ``hidden_states``)."""
    x, aux = hidden_states(params, cfg, tokens, positions)
    return head(params, cfg, x), aux


def forward(params: Dict[str, Any], cfg: AfmoeConfig, tokens: jax.Array,
            positions: Optional[jax.Array] = None) -> jax.Array:
    return forward_with_aux(params, cfg, tokens, positions)[0]


def loss_of_hidden(params: Dict[str, Any], cfg: AfmoeConfig, x: jax.Array,
                   aux, targets: jax.Array,
                   mask: Optional[jax.Array] = None
                   ) -> Tuple[jax.Array, Dict[str, jax.Array]]:
    """``loss_fn`` from ``hidden_states``' result (x [B, S, d], aux)."""
    loss, metrics = lm.next_token_loss(
        partial(head, lm.head_gathered(params, tied=False), cfg), x,
        targets, mask, cfg.loss_chunk, 0.0)
    if not aux:
        return loss, metrics
    sizes = aux["group_sizes"].astype(jnp.float32)  # [L_moe, held]
    return loss, {
        **metrics,
        "moe_assignments": sizes.sum(),
        "moe_tokens": aux["asked"].astype(jnp.float32).sum(),
        "moe_routed": jnp.float32(
            targets.size * cfg.num_experts_per_tok * cfg.n_moe_layers),
        "moe_calls": jnp.float32(cfg.n_moe_layers),
        "moe_calls_within_bound":
            aux["within_bound"].astype(jnp.float32).sum(),
        "moe_load_max_over_mean": (
            sizes.max(-1) / jnp.maximum(sizes.mean(-1), 1e-9)).max(),
    }


def loss_fn(params: Dict[str, Any], cfg: AfmoeConfig, tokens: jax.Array,
            targets: jax.Array, mask: Optional[jax.Array] = None
            ) -> Tuple[jax.Array, Dict[str, jax.Array]]:
    """Next-token cross-entropy in fp32 (chunked by ``cfg.loss_chunk``), no
    balance term. The metrics carry what the expert layers did:
    ``moe_routed`` (every assignment the router made: tokens x experts per
    token x expert layers), ``moe_tokens`` (those it gave to experts held
    here), ``moe_assignments`` (rows the grouped matmuls computed: equal to
    ``moe_tokens``, or something was dropped) and
    ``moe_load_max_over_mean`` (the busiest held expert's load over the
    held experts' mean, worst layer)."""
    x, aux = hidden_states(params, cfg, tokens)
    return loss_of_hidden(params, cfg, x, aux, targets, mask)
