"""DeepSeek-V3-family language models (``model_type: deepseek_v3``):
multi-head latent attention and a dropless routed-expert FFN.

The config keys carry their published names (``DeepseekV3Config``), so a
``config.json`` of the family reads straight into ``DeepseekConfig``. The
published instance behind the preset is Moonlight-16B-A3B
(https://huggingface.co/moonshotai/Moonlight-16B-A3B/blob/main/config.json).
Per layer, as ``DeepseekV3ForCausalLM`` computes it::

    x        = RMSNorm(h; g1)                          no bias anywhere
    q        = x Wq          -> heads x (qk_nope | qk_rope)        (q_lora_rank null)
    c | k_r  = x W_kv_a      -> kv_lora_rank | qk_rope ;  c = RMSNorm(c; g_kv)
               k_r is one head, shared by all query heads
    k_n | v  = c W_kv_b      -> heads x (qk_nope | v_head)
    q_r, k_r = rope(q_r), rope(k_r)     theta rope_theta, pairs (2i, 2i+1)
    a        = softmax(causal([q_n|q_r] [k_n|k_r]^T / sqrt(qk_nope + qk_rope))) v
    h        = h + a Wo
    x        = RMSNorm(h; g2)
    first first_k_dense_replace layers:  m = W_down(silu(W_gate x) * W_up x)
    the others:  s = sigmoid(x W_r) in float32 ; pick num_experts_per_tok of s + b
                 w = s[picked] / (sum s[picked] + 1e-20) * routed_scaling_factor
                 m = sum_i w_i Expert_i(x) + Shared(x)
    h        = h + m

Training attends with q, k of 192 and v of 128 per head (the latent is
expanded; the absorbed form is a decoding trick). Rope pairs dimension 2i
with 2i+1 as published and, as published, leaves the rotated halves
de-interleaved (all first members, then all second): q and k alike, so
scores are unchanged.

What every language model here shares is ``models/lm.py``'s: the lookup, the
layer scan with remat (``scan_blocks``), the chunked head and loss
(``next_token_loss``), the attention dispatch (``attention``: dot, or the
flash kernels with two head sizes). The expert layer is ``ops/moe.py``.
Every assignment is computed: no capacity, no drop. The correction bias
``b`` steers selection only and is not trained by the gradient (its gradient
is zero; the published update rule's step size is not in the config, so no
rule moves it here either).
Every expert of a layer is held here: the layer tells ``ops/moe.py`` so
(``held=(0, n_routed_experts)``, the whole layer; a chip's share of a layer,
``held=(first, count)`` with the weights of those experts alone, is what
``models/afmoe.py`` runs). Holding a share is not expert parallelism: there
is no exchange of tokens between chips in either model, and a mesh with an
``ep`` axis > 1 still raises ``NotImplementedError`` here.
"""

from __future__ import annotations

from dataclasses import dataclass, replace
from functools import partial
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from ray_tpu._private import builtin_metrics
from ray_tpu.models import lm
from ray_tpu.ops.moe import routed_experts
from ray_tpu.parallel.sharding import ShardingRules, constrain

#: Metrics of ``loss_fn`` that count a batch: summed over accumulation
#: microbatches where the others are averaged (parallel/train_step.py).
SUMMED_METRICS = ("moe_assignments", "moe_tokens")

#: Metrics of ``loss_fn`` that feed the registry, each with what records
#: its value there: a train step reads them off the device without a sync
#: (parallel/train_step.py) and calls these.
RECORDED_METRICS = {
    "moe_assignments":
        lambda value: builtin_metrics.train_moe_assignments().inc(value),
    "moe_tokens":
        lambda value: builtin_metrics.train_moe_tokens().inc(value),
    "moe_load_max_over_mean":
        lambda value: builtin_metrics.train_moe_expert_load().set(value),
}


@dataclass(frozen=True)
class DeepseekConfig:
    # Published keys, under their published names.
    vocab_size: int = 163840
    hidden_size: int = 2048
    num_hidden_layers: int = 27
    first_k_dense_replace: int = 1
    num_attention_heads: int = 16
    kv_lora_rank: int = 512
    qk_nope_head_dim: int = 128
    qk_rope_head_dim: int = 64
    v_head_dim: int = 128
    rope_theta: float = 50000.0
    #: True: the latent layer carries no positions (q_r and k_r are not
    #: rotated); the order then comes from other layers of the model.
    mla_use_nope: bool = False
    intermediate_size: int = 11264
    moe_intermediate_size: int = 1408
    n_routed_experts: int = 64
    num_experts_per_tok: int = 6
    n_shared_experts: int = 2
    routed_scaling_factor: float = 2.446
    norm_topk_prob: bool = True
    rms_norm_eps: float = 1e-5
    max_position_embeddings: int = 8192
    # The program's own choices (as GPTConfig has them).
    dtype: Any = jnp.bfloat16
    param_dtype: Any = jnp.float32
    remat: bool = True
    # "full" keeps of a block only what the flash forward kernel returns
    # (output and log-sum-exp, at long sequences: lm.scan_blocks). "selective" adds the values
    # a block names for it, and this model's blocks name none.
    remat_policy: str = "full"
    loss_chunk: int = 0
    attn_impl: str = "dot"  # "dot" | "flash"
    attn_blk_q: int = 512
    attn_blk_k: int = 512

    @property
    def qk_head_dim(self) -> int:
        return self.qk_nope_head_dim + self.qk_rope_head_dim

    @property
    def n_moe_layers(self) -> int:
        return self.num_hidden_layers - self.first_k_dense_replace


PRESETS: Dict[str, DeepseekConfig] = {
    "moonlight-16b-a3b": DeepseekConfig(),
    # Test size: one dense and two expert layers, 8 experts, 3 per token.
    "deepseek-tiny": DeepseekConfig(
        vocab_size=256, hidden_size=64, num_hidden_layers=3,
        num_attention_heads=4, kv_lora_rank=32, qk_nope_head_dim=16,
        qk_rope_head_dim=8, v_head_dim=16, intermediate_size=128,
        moe_intermediate_size=32, n_routed_experts=8, num_experts_per_tok=3,
        n_shared_experts=2, max_position_embeddings=128, dtype=jnp.float32,
        remat=False),
}


def config(name: str, **overrides) -> DeepseekConfig:
    cfg = PRESETS[name]
    return replace(cfg, **overrides) if overrides else cfg


# -- parameters ---------------------------------------------------------

def _shapes(cfg: DeepseekConfig):
    """{group: {leaf: (shape without the layers axis, logical axes, init
    std or None for a vector of ones/zeros)}}: one table for ``init`` and
    ``param_specs``."""
    d, h = cfg.hidden_size, cfg.num_attention_heads
    std = 0.02
    attn = {
        "ln1_scale": ((d,), ("embed",), None),
        "wq": ((d, h, cfg.qk_head_dim), ("embed", "heads", "head_dim"), std),
        "w_kv_a": ((d, cfg.kv_lora_rank + cfg.qk_rope_head_dim),
                   ("embed", None), std),
        "kv_norm_scale": ((cfg.kv_lora_rank,), (None,), None),
        "w_kv_b": ((cfg.kv_lora_rank, h,
                    cfg.qk_nope_head_dim + cfg.v_head_dim),
                   (None, "heads", "head_dim"), std),
        "wo": ((h, cfg.v_head_dim, d), ("heads", "head_dim", "embed"), std),
        "ln2_scale": ((d,), ("embed",), None),
    }

    def swiglu(width, prefix=""):
        return {prefix + "w_gate": ((d, width), ("embed", "mlp"), std),
                prefix + "w_up": ((d, width), ("embed", "mlp"), std),
                prefix + "w_down": ((width, d), ("mlp", "embed"), std)}

    e, f = cfg.n_routed_experts, cfg.moe_intermediate_size
    moe = {
        "router": ((d, e), ("embed", None), std),
        # The published e_score_correction_bias: a buffer of zeros that the
        # gradient never moves.
        "router_bias": ((e,), (None,), 0.0),
        "w_gate": ((e, d, f), ("expert", "embed", "mlp"), std),
        "w_up": ((e, d, f), ("expert", "embed", "mlp"), std),
        "w_down": ((e, f, d), ("expert", "mlp", "embed"), std),
        **swiglu(cfg.n_shared_experts * f, "shared_"),
    }
    return {"dense_layers": dict(attn, **swiglu(cfg.intermediate_size)),
            "moe_layers": dict(attn, **moe)}


def _depth(cfg: DeepseekConfig, group: str) -> int:
    return cfg.first_k_dense_replace if group == "dense_layers" \
        else cfg.n_moe_layers


def init(cfg: DeepseekConfig, key: jax.Array) -> Dict[str, Any]:
    """Parameters: normal(0, 0.02) matrices, RMSNorm scales of one, a zero
    correction bias. The two stacks (leading dense layers, expert layers)
    each carry a leading layers axis for ``lax.scan``."""
    pd = cfg.param_dtype
    k_embed, k_head, k_layers = jax.random.split(key, 3)

    def normal(k, shape, std):
        return (jax.random.normal(k, shape, jnp.float32) * std).astype(pd)

    params = {
        "wte": normal(k_embed, (cfg.vocab_size, cfg.hidden_size), 0.02),
        "lnf_scale": jnp.ones((cfg.hidden_size,), pd),
        "lm_head": normal(k_head, (cfg.hidden_size, cfg.vocab_size), 0.02),
    }
    for index, (group, leaves) in enumerate(_shapes(cfg).items()):
        depth = _depth(cfg, group)
        keys = jax.random.split(jax.random.fold_in(k_layers, index),
                                len(leaves))
        params[group] = {
            name: jnp.ones((depth,) + shape, pd) if std is None
            else jnp.zeros((depth,) + shape, pd) if std == 0.0
            else normal(k, (depth,) + shape, std)
            for k, (name, (shape, _, std)) in zip(keys, leaves.items())}
    return params


def param_specs(cfg: DeepseekConfig, rules: ShardingRules) -> Dict[str, Any]:
    """PartitionSpec pytree matching init()'s structure."""
    specs = {"wte": rules.spec("vocab", "embed"),
             "lnf_scale": rules.spec("embed"),
             "lm_head": rules.spec("embed", "vocab")}
    for group, leaves in _shapes(cfg).items():
        specs[group] = {name: rules.spec("layers", *axes)
                        for name, (_, axes, _) in leaves.items()}
    return specs


# -- forward ------------------------------------------------------------

def rmsnorm(x, scale, eps):
    x32 = x.astype(jnp.float32)
    y = x32 * jax.lax.rsqrt((x32 ** 2).mean(-1, keepdims=True) + eps)
    return (y * scale.astype(jnp.float32)).astype(x.dtype)


def _rope(x, positions, theta: float):
    """Rotary embedding over the whole last axis of x [B, S, H, R], pairing
    dimension 2i with 2i+1 (angle pos * theta^(-2i/R)) as published; the
    result holds all first members, then all second."""
    half = x.shape[-1] // 2
    freqs = 1.0 / (theta ** (jnp.arange(half, dtype=jnp.float32) / half))
    angles = positions[..., None].astype(jnp.float32) * freqs  # [B, S, half]
    cos, sin = jnp.cos(angles)[:, :, None, :], jnp.sin(angles)[:, :, None, :]
    x32 = x.astype(jnp.float32)
    first, second = x32[..., 0::2], x32[..., 1::2]
    return jnp.concatenate([first * cos - second * sin,
                            second * cos + first * sin], -1).astype(x.dtype)


def mla(cfg, x, layer, positions):
    """Multi-head latent attention on normed x [B, S, d] -> [B, S, d]. cfg
    is this module's config or another model's with the same latent keys
    (``models/kimi_linear.py``); with ``cfg.mla_use_nope`` the 64 "rope"
    dimensions of q and of the shared key go unrotated."""
    dt = cfg.dtype
    nope, rank = cfg.qk_nope_head_dim, cfg.kv_lora_rank
    q = jnp.einsum("bsd,dhk->bshk", x, layer["wq"].astype(dt))
    kv_a = jnp.einsum("bsd,dr->bsr", x, layer["w_kv_a"].astype(dt))
    latent = rmsnorm(kv_a[..., :rank], layer["kv_norm_scale"],
                      cfg.rms_norm_eps)
    kv = jnp.einsum("bsr,rhk->bshk", latent, layer["w_kv_b"].astype(dt))
    rotated = (lambda x: x) if cfg.mla_use_nope else partial(
        _rope, positions=positions, theta=cfg.rope_theta)
    q_rope = rotated(q[..., nope:])
    k_rope = rotated(kv_a[..., None, rank:])
    q = jnp.concatenate([q[..., :nope], q_rope], -1)
    k = jnp.concatenate(
        [kv[..., :nope],
         jnp.broadcast_to(k_rope, q_rope.shape)], -1)
    attn = lm.attention(q, k, kv[..., nope:], cfg)
    return jnp.einsum("bshk,hkd->bsd", attn, layer["wo"].astype(dt))


def swiglu(x, w_gate, w_up, w_down):
    dt = x.dtype
    gate = jnp.einsum("...d,df->...f", x, w_gate.astype(dt))
    up = jnp.einsum("...d,df->...f", x, w_up.astype(dt))
    return jnp.einsum("...f,fd->...d", jax.nn.silu(gate) * up,
                      w_down.astype(dt))


def expert_ffn(x, layer, *, top_k: int, scaling: float, normalize: bool,
               held):
    """The expert layer on normed x [B, S, d] from a layer's leaves
    (``router``, ``router_bias``, the held experts' ``w_gate`` / ``w_up`` /
    ``w_down``, ``shared_*``): (this chip's part of the routed sum, the
    shared experts, aux), the sums [B, S, d], for the caller to add in that
    order. ``held`` is ``ops/moe.py``'s. aux: ``picked`` [B, S, K],
    ``group_sizes`` [held experts] and, on a share, ``asked`` and
    ``within_bound``."""
    B, S, d = x.shape
    routed, aux = routed_experts(
        x.reshape(B * S, d), layer["router"], layer["router_bias"],
        layer["w_gate"], layer["w_up"], layer["w_down"],
        top_k=top_k, scaling=scaling, normalize=normalize, held=held)
    with jax.named_scope("shared_expert"):
        shared = swiglu(x, layer["shared_w_gate"], layer["shared_w_up"],
                        layer["shared_w_down"])
    aux["picked"] = aux["picked"].reshape(B, S, -1)
    return routed.reshape(B, S, d), shared, aux


def _block(cfg: DeepseekConfig, h, layer, positions):
    """One layer; which kind is read off the layer's own leaves. Returns
    (h, aux): aux is None for a dense layer, else the expert layer's
    ``picked`` [B, S, K] and ``group_sizes`` [E]."""
    with jax.named_scope("mla"):
        h = h + mla(cfg, rmsnorm(h, layer["ln1_scale"], cfg.rms_norm_eps),
                    layer, positions)
    x = rmsnorm(h, layer["ln2_scale"], cfg.rms_norm_eps)
    if "router" not in layer:
        with jax.named_scope("mlp"):
            return h + swiglu(x, layer["w_gate"], layer["w_up"],
                              layer["w_down"]), None
    routed, shared, aux = expert_ffn(
        x, layer, top_k=cfg.num_experts_per_tok,
        scaling=cfg.routed_scaling_factor, normalize=cfg.norm_topk_prob,
        held=(0, cfg.n_routed_experts))
    return h + routed + shared, aux


def _no_expert_parallelism():
    from ray_tpu.parallel.mesh import current_mesh
    mesh = current_mesh()
    if mesh is not None and mesh.shape.get("ep", 1) > 1:
        raise NotImplementedError(
            "models/deepseek.py does not implement expert parallelism: the "
            "mesh has ep > 1, and the expert layer (ops/moe.py) sorts and "
            "multiplies every expert's group on one chip. Use ep=1 (fsdp "
            "and tp shard the expert weights).")


def hidden_states(params: Dict[str, Any], cfg: DeepseekConfig,
                  tokens: jax.Array,
                  positions: Optional[jax.Array] = None):
    """tokens [B, S] int32 -> (final-normed hidden [B, S, d], aux) with aux
    the expert layers' ``picked`` [L_moe, B, S, K] and ``group_sizes``
    [L_moe, E]."""
    _no_expert_parallelism()
    if positions is None:
        positions = lm.positions_of(tokens)
    x = lm.embed(params["wte"], tokens, cfg.dtype)  # batch-split
    block = partial(_block, cfg)
    x, _ = lm.scan_blocks(cfg, block, x, params["dense_layers"], positions)
    x, aux = lm.scan_blocks(cfg, block, x, params["moe_layers"], positions)
    x = constrain(x, "batch", "sequence", None)
    return rmsnorm(x, params["lnf_scale"], cfg.rms_norm_eps), aux


def _head(params: Dict[str, Any], cfg: DeepseekConfig, x: jax.Array):
    return jnp.einsum("...d,dv->...v", x, params["lm_head"].astype(cfg.dtype))


def forward_with_aux(params: Dict[str, Any], cfg: DeepseekConfig,
                     tokens: jax.Array,
                     positions: Optional[jax.Array] = None):
    """tokens [B, S] -> (logits [B, S, vocab], aux of ``hidden_states``)."""
    x, aux = hidden_states(params, cfg, tokens, positions)
    return _head(params, cfg, x), aux


def forward(params: Dict[str, Any], cfg: DeepseekConfig, tokens: jax.Array,
            positions: Optional[jax.Array] = None) -> jax.Array:
    return forward_with_aux(params, cfg, tokens, positions)[0]


def loss_fn(params: Dict[str, Any], cfg: DeepseekConfig, tokens: jax.Array,
            targets: jax.Array, mask: Optional[jax.Array] = None
            ) -> Tuple[jax.Array, Dict[str, jax.Array]]:
    """Next-token cross-entropy in fp32 (chunked by ``cfg.loss_chunk``),
    no balance term: the config sizes none. The metrics carry what the
    expert layers did: ``moe_assignments`` (the sum of their group sizes),
    ``moe_tokens`` (tokens x experts per token x expert layers: equal, or
    something was dropped) and ``moe_load_max_over_mean`` (the busiest
    expert's load over the mean, worst layer)."""
    x, aux = hidden_states(params, cfg, tokens)
    head = partial(_head, lm.head_gathered(params, tied=False), cfg)
    loss, metrics = lm.next_token_loss(head, x, targets, mask,
                                       cfg.loss_chunk, 0.0)
    sizes = aux["group_sizes"].astype(jnp.float32)  # [L_moe, E]
    return loss, {
        **metrics,
        "moe_assignments": sizes.sum(),
        "moe_tokens": jnp.float32(
            tokens.size * cfg.num_experts_per_tok * cfg.n_moe_layers),
        "moe_load_max_over_mean": (sizes.max(-1) / sizes.mean(-1)).max(),
    }
