"""DeepSeek-V3-family language models (``model_type: deepseek_v3``):
multi-head latent attention and a dropless routed-expert FFN.

The config keys carry their published names (``DeepseekV3Config``), so a
``config.json`` of the family reads straight into ``DeepseekConfig``. The
published instance behind the preset is Moonlight-16B-A3B
(https://huggingface.co/moonshotai/Moonlight-16B-A3B/blob/main/config.json).
Per layer, as ``DeepseekV3ForCausalLM`` computes it::

    x        = RMSNorm(h; g1)                          no bias anywhere
    q        = x Wq          -> heads x (qk_nope | qk_rope)        (q_lora_rank null;
               with a rank, RMSNorm(x W_qa; g_q) W_qb: lm.mla_leaves)
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

This module is the family's config, its table of leaves (``_shapes``) and its
block; the rest is ``models/lm.py``'s: ``Decoder`` (parameters and specs
from the table, the lookup, the layer scan with remat, the head and loss),
the latent attention and the expert FFN themselves (``mla``, ``expert_ffn``:
``models/kimi_linear.py`` runs them too) and the attention dispatch (dot, or
the flash kernels with two head sizes). The expert layer is ``ops/moe.py``.
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
from typing import Any, Dict, Optional

import jax
import jax.numpy as jnp

from ray_tpu.models import lm


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
    #: Null as published for Moonlight: the query is one matrix. A rank
    #: makes it two and a norm (``lm.mla_leaves``), as DeepSeek-V3 has it.
    q_lora_rank: Optional[int] = None
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
    """{stack: {leaf: (shape without the layers axis, logical axes, init: a
    std, or ``lm.ones`` | ``lm.zeros``)}}: one table for ``init`` and
    ``param_specs`` (``lm.Decoder``): normal(0, 0.02) matrices, RMSNorm
    scales of one, a zero correction bias."""
    d = cfg.hidden_size
    attn = {"ln1_scale": ((d,), ("embed",), lm.ones),
            **lm.mla_leaves(cfg),
            "ln2_scale": ((d,), ("embed",), lm.ones)}
    e, f = cfg.n_routed_experts, cfg.moe_intermediate_size
    moe = lm.expert_leaves(d, e, None, f,
                           shared_width=cfg.n_shared_experts * f)
    return {"dense_layers": dict(attn, **lm.swiglu_leaves(
                d, cfg.intermediate_size)),
            "moe_layers": dict(attn, **moe)}


def _stacks(cfg: DeepseekConfig):
    """The two stacks (leading dense layers, expert layers), each under its
    own name in the parameter tree and a kind of layer of that name."""
    return (("dense_layers", "dense_layers", cfg.first_k_dense_replace),
            ("moe_layers", "moe_layers", cfg.n_moe_layers))


# -- forward ------------------------------------------------------------

def _block(cfg: DeepseekConfig, kind: str, h, layer, positions):
    """One layer of ``kind`` (``_stacks``). Returns (h, aux): aux is None for
    a dense layer, else the expert layer's ``picked`` [B, S, K] and
    ``group_sizes`` [E] (every expert is held: ``lm.expert_aux``' other two
    say nothing here, and this family's metrics do not read them)."""
    with jax.named_scope("mla"):
        h = h + lm.mla(cfg, lm.rmsnorm(h, layer["ln1_scale"],
                                       cfg.rms_norm_eps), layer, positions)
    x = lm.rmsnorm(h, layer["ln2_scale"], cfg.rms_norm_eps)
    if kind == "dense_layers":
        with jax.named_scope("mlp"):
            return h + lm.swiglu(x, layer["w_gate"], layer["w_up"],
                                 layer["w_down"]), None
    routed, shared, aux = lm.expert_ffn(
        x, layer, top_k=cfg.num_experts_per_tok,
        scaling=cfg.routed_scaling_factor, normalize=cfg.norm_topk_prob,
        held=(0, cfg.n_routed_experts))
    return h + routed + shared, {
        "picked": aux["picked"], "group_sizes": aux["group_sizes"]}


def _metrics(cfg: DeepseekConfig, aux, targets):
    """What the expert layers did: ``moe_assignments`` (the sum of their
    group sizes), ``moe_tokens`` (tokens x experts per token x expert
    layers: equal, or something was dropped) and ``moe_load_max_over_mean``
    (the busiest expert's load over the mean, worst layer). The older three
    of ``lm.moe_metrics``' six, under the meaning ``moe_tokens`` had before
    a chip held a share: the Moonlight cell's counters and compiled step."""
    sizes = aux["group_sizes"].astype(jnp.float32)  # [L_moe, E]
    return {
        "moe_assignments": sizes.sum(),
        "moe_tokens": jnp.float32(
            targets.size * cfg.num_experts_per_tok * cfg.n_moe_layers),
        "moe_load_max_over_mean": (sizes.max(-1) / sizes.mean(-1)).max(),
    }


_SHELL = lm.Decoder(
    name="deepseek", shapes=_shapes, block=lambda *args: _block(*args),
    runs_of=_stacks, experts=True, metrics=_metrics)

#: ``hidden_states``' aux is the expert layers' ``picked`` [L_moe, B, S, K]
#: and ``group_sizes`` [L_moe, E].
init, param_specs = _SHELL.init, _SHELL.param_specs
hidden_states, _head = _SHELL.hidden_states, _SHELL.head
forward, forward_with_aux = _SHELL.forward, _SHELL.forward_with_aux
loss_fn = _SHELL.loss_fn
SUMMED_METRICS = ("moe_assignments", "moe_tokens")
RECORDED_METRICS = {name: lm.RECORDED_METRICS[name] for name in (
    "moe_assignments", "moe_tokens", "moe_load_max_over_mean")}
