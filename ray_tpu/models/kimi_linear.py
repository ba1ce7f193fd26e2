"""Kimi Linear language models (``model_type: kimi_linear``): layers of Kimi
Delta Attention (a gated delta rule with a decay a channel, linear in the
sequence) with a latent-attention layer without positions after every
third, and a routed-expert FFN with one shared expert after a leading dense
layer.

The config keys carry their published names (``KimiLinearConfig``;
``linear_attn_config`` as published, its layer lists 1-based), so a
``config.json`` of the family reads straight into ``KimiLinearConfig``. The
published instance behind the preset is Kimi-Linear-48B-A3B-Instruct
(https://huggingface.co/moonshotai/Kimi-Linear-48B-A3B-Instruct/blob/main/config.json).
As ``KimiLinearForCausalLM`` computes it; no bias anywhere, every norm an
RMSNorm with ``rms_norm_eps``::

    h        = wte[tokens]
    layer l (1-based), KDA iff l in kda_layers, dense iff l <= first_k_dense_replace:
    x        = RMSNorm(h; g_in)
    KDA:     q | k | v = silu(conv(x Wq)) | silu(conv(x Wk)) | silu(conv(x Wv))    heads x head_dim each;
                         depthwise causal conv, short_conv_kernel_size taps
             q, k   = q / max(|q|, 1e-6), k / max(|k|, 1e-6)  over a head ;  q = q * head_dim^-0.5
             a      = -exp(A_log[head]) * softplus((x W_fa) W_fb + dt_bias)        log-decay <= 0, a channel, float32
             beta   = sigmoid(x W_beta)                                            one a head and token, float32
             per head, S [keys, values] zero before the first token:
               S_t  = Diag(exp(a_t)) S_(t-1)
               S_t  = S_t + beta_t k_t (v_t - S_t^T k_t)^T        the delta rule
               o_t  = S_t^T q_t
             m      = (RMSNorm(o; g_o) over a head  *  sigmoid((x W_ga) W_gb)) Wo
    MLA:     deepseek_v3's latent attention (lm.mla; q_lora_rank null as published), and with
             mla_use_nope no rotation of q_r, k_r: no positions
    h        = h + m
    x        = RMSNorm(h; g_2)
    dense:   W_down(silu(W_gate x) * W_up x)
    experts: s = sigmoid(x W_r) in float32 ; picked = top num_experts_per_token of (s + b)
             w = s[picked] / (sum s[picked] + 1e-20) * routed_scaling_factor      (moe_renormalize)
             Shared(x) + sum_i w_i Expert_i(x)
    h        = h + that
    logits   = RMSNorm(h_last; g_f) W_head                 untied

``W_fa`` / ``W_ga`` project to ``head_dim`` and ``W_fb`` / ``W_gb`` from it
to heads x head_dim; ``A_log`` is one a head, ``dt_bias`` one a channel.
``b`` (the router's correction bias) steers selection only and is not
trained by the gradient, and no rule moves it here. The loss is the
cross-entropy alone.

The recurrence is ``ops/kda.py``'s chunked kernel pair (through
``lm.delta_rule``), which takes q and k as the convolutions leave them and
``a`` itself: the line ``q, k = ...`` above (the lengths, ``head_dim^-0.5``)
and the running sums of ``a`` are the kernels' own, on a chunk's rows in
VMEM, and nothing of them is this module's. The short convolutions with
their SiLU are ``lm.conv_silu`` (``ops/short_conv.py``'s fused pass each way
over each of q, k, v where the shapes tile, else its ``jax.numpy`` form in
float32), the line ``m = RMSNorm(o; g_o) * sigmoid(...)`` before ``Wo``
``lm.gated_norm`` on o and the second low-rank product as the einsum leaves
it (``ops/gated_norm.py``'s fused pass each way where the shapes tile: the
sigmoid is the kernel's, no float32 gate is written; else its ``jax.numpy``
form), the latent attention and the expert FFN ``lm.mla`` and
``lm.expert_ffn`` (shared with ``models/deepseek.py``), the expert layer
``ops/moe.py``. This module is the
family's config, its table of leaves (``_shapes``, with the decay's two
draws) and its block; parameters and specs from the table, the lookup, the
layer scan, the head and loss and the expert layers' counters are
``lm.Decoder``'s. A layer's kind is its FFN and its mixer together
(``dense_kda``, ``moe_kda``, ``moe_mla``, ...); every run of one kind is one
stack of parameters and one scan.

**The chip's share.** ``experts_held = (first, count)`` says which of a
layer's ``num_experts`` live here: the parameters hold those alone, the
router stays ``num_experts`` wide, and the layer returns the shared expert
plus this chip's part of the routed sum (``ops/moe.py``, "Held experts").
None holds them all. A sliced vocabulary is a smaller ``vocab_size``. Expert
parallelism (an ``ep`` mesh axis > 1) is not implemented: the share runs
without an exchange.
"""

from __future__ import annotations

import math
from dataclasses import dataclass, replace
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from ray_tpu._private import builtin_metrics
from ray_tpu.models import lm
from ray_tpu.ops.kda import decay_floor


@dataclass(frozen=True)
class LinearAttnConfig:
    """The published ``linear_attn_config`` group; layers count from 1."""
    kda_layers: Tuple[int, ...] = tuple(
        l for l in range(1, 28) if l % 4 and l != 27)
    full_attn_layers: Tuple[int, ...] = (4, 8, 12, 16, 20, 24, 27)
    num_heads: int = 32
    head_dim: int = 128
    short_conv_kernel_size: int = 4

    def __post_init__(self):
        for name in ("kda_layers", "full_attn_layers"):
            object.__setattr__(self, name, tuple(getattr(self, name)))


@dataclass(frozen=True)
class KimiLinearConfig:
    # Published keys, under their published names.
    vocab_size: int = 163840
    hidden_size: int = 2304
    num_hidden_layers: int = 27
    first_k_dense_replace: int = 1
    #: Which of the published depth's layers are KDA and which latent
    #: attention; a model cut to ``num_hidden_layers`` runs the first that
    #: many. A dict (as ``config.json`` has it) or a ``LinearAttnConfig``.
    linear_attn_config: Any = LinearAttnConfig()
    num_attention_heads: int = 32
    kv_lora_rank: int = 512
    qk_nope_head_dim: int = 128
    qk_rope_head_dim: int = 64
    v_head_dim: int = 128
    mla_use_nope: bool = True
    rope_theta: float = 10000.0
    intermediate_size: int = 9216
    moe_intermediate_size: int = 1024
    num_experts: int = 256
    num_experts_per_token: int = 8
    num_shared_experts: int = 1
    moe_renormalize: bool = True
    routed_scaling_factor: float = 2.446
    rms_norm_eps: float = 1e-5
    model_max_length: int = 1048576
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
        if isinstance(self.linear_attn_config, dict):
            object.__setattr__(self, "linear_attn_config",
                               LinearAttnConfig(**self.linear_attn_config))
        object.__setattr__(self, "experts_held", lm.held_experts(
            self.experts_held, self.num_experts))
        linear = self.linear_attn_config
        depth = range(1, self.num_hidden_layers + 1)
        if any((l in linear.kda_layers) == (l in linear.full_attn_layers)
               for l in depth):
            raise ValueError(
                "every layer up to num_hidden_layers must be in exactly one "
                "of linear_attn_config's kda_layers and full_attn_layers")

    @property
    def layers(self) -> Tuple[str, ...]:
        """The kind of each layer that runs: its FFN (``dense`` or ``moe``)
        and its mixer (``kda`` or ``mla``), as ``dense_kda``."""
        kda = self.linear_attn_config.kda_layers
        return tuple(
            ("dense_" if l <= self.first_k_dense_replace else "moe_")
            + ("kda" if l in kda else "mla")
            for l in range(1, self.num_hidden_layers + 1))

    @property
    def n_moe_layers(self) -> int:
        return max(self.num_hidden_layers - self.first_k_dense_replace, 0)


PRESETS: Dict[str, KimiLinearConfig] = {
    "kimi-linear-48b-a3b": KimiLinearConfig(),
    # Test size: all four kinds of layer, heads of 128 and sequences of
    # whole chunks so that the delta rule's kernels run (interpreted) on the
    # CPU, 8 experts with 2 a token.
    "kimi-linear-tiny": KimiLinearConfig(
        vocab_size=256, hidden_size=64, num_hidden_layers=5,
        first_k_dense_replace=2,
        linear_attn_config=LinearAttnConfig(
            kda_layers=(1, 3, 4), full_attn_layers=(2, 5), num_heads=2,
            head_dim=128),
        num_attention_heads=4, kv_lora_rank=32, qk_nope_head_dim=16,
        qk_rope_head_dim=8, v_head_dim=16, intermediate_size=128,
        moe_intermediate_size=32, num_experts=8, num_experts_per_token=2,
        model_max_length=512, dtype=jnp.float32, remat=False),
}


def config(name: str, **overrides) -> KimiLinearConfig:
    cfg = PRESETS[name]
    return replace(cfg, **overrides) if overrides else cfg


# -- parameters ---------------------------------------------------------

def _decay_rate(key, shape):
    """``A_log`` as published for the gated delta rule: log U(1, 16) a
    head."""
    return jnp.log(jax.random.uniform(key, shape, jnp.float32, 1.0, 16.0))


def _decay_bias(key, shape):
    """``dt_bias``: the inverse softplus of a log-uniform (0.001, 0.1) a
    channel, so that the log-decays start between -0.001 and -1.6 a step."""
    dt = jnp.exp(jax.random.uniform(
        key, shape, jnp.float32, math.log(0.001), math.log(0.1)))
    return dt + jnp.log(-jnp.expm1(-dt))


def _shapes(cfg: KimiLinearConfig):
    """{"kda" | "mla" | "dense" | "moe": {leaf: (shape without the layers
    axis, logical axes, init)}}: one table for ``init`` and ``param_specs``
    (``lm.Decoder``); a layer holds its mixer's leaves and its FFN's.
    ``init`` is a std for a normal draw, or a callable: matrices normal(0,
    0.02), RMSNorm scales of one, a zero correction bias, the convolutions'
    taps normal with the variance of ``nn.Conv1d``'s default, the decay's
    two vectors as above."""
    d, std = cfg.hidden_size, 0.02
    linear = cfg.linear_attn_config
    kh, hd, taps = linear.num_heads, linear.head_dim, \
        linear.short_conv_kernel_size
    norms = {"ln_in_scale": ((d,), ("embed",), lm.ones),
             "ln2_scale": ((d,), ("embed",), lm.ones)}
    heads = ("embed", "heads", "head_dim")
    kda = {
        "wq": ((d, kh, hd), heads, std),
        "wk": ((d, kh, hd), heads, std),
        "wv": ((d, kh, hd), heads, std),
        # nn.Conv1d's default, uniform(+-K^-1/2), has this variance.
        **{f"conv_{x}": ((taps, kh * hd), (None, None), (3 * taps) ** -0.5)
           for x in "qkv"},
        "w_fa": ((d, hd), ("embed", None), std),
        "w_fb": ((hd, kh, hd), (None, "heads", "head_dim"), std),
        "A_log": ((kh,), (None,), _decay_rate),
        "dt_bias": ((kh, hd), (None, None), _decay_bias),
        "w_beta": ((d, kh), ("embed", None), std),
        "o_norm_scale": ((hd,), (None,), lm.ones),
        "w_ga": ((d, hd), ("embed", None), std),
        "w_gb": ((hd, kh, hd), (None, "heads", "head_dim"), std),
        "wo": ((kh, hd, d), ("heads", "head_dim", "embed"), std),
    }
    f = cfg.moe_intermediate_size
    return {"kda": dict(norms, **kda),
            "mla": dict(norms, **lm.mla_leaves(cfg)),
            "dense": lm.swiglu_leaves(d, cfg.intermediate_size),
            "moe": lm.expert_leaves(
                d, cfg.num_experts, cfg.experts_held, f,
                shared_width=cfg.num_shared_experts * f)}


def _leaves_of(shapes, kind: str):
    ffn, mixer = kind.split("_")
    return dict(shapes[mixer], **shapes[ffn])


# -- forward ------------------------------------------------------------

def _kda(cfg: KimiLinearConfig, x, layer):
    """Kimi Delta Attention on normed x [B, S, d] -> (m [B, S, d], the
    most negative running log-decay a chunk reaches)."""
    dt, f32 = cfg.dtype, jnp.float32
    linear = cfg.linear_attn_config
    heads, hd = linear.num_heads, linear.head_dim
    split = x.shape[:2] + (heads, hd)

    def short_conv(name):
        flat = jnp.einsum("bsd,dhk->bshk", x, layer["w" + name].astype(dt)
                          ).reshape(x.shape[:2] + (heads * hd,))
        return lm.conv_silu(flat, layer["conv_" + name]).reshape(split)

    with jax.named_scope("conv"):
        q, k, v = short_conv("q"), short_conv("k"), short_conv("v")

    with jax.named_scope("kda_gate"):
        low = jnp.einsum("bsd,dr->bsr", x, layer["w_fa"].astype(dt))
        rate = jnp.einsum("bsr,rhk->bshk", low, layer["w_fb"].astype(dt))
        a = -jnp.exp(layer["A_log"].astype(f32))[:, None] * jax.nn.softplus(
            rate.astype(f32) + layer["dt_bias"].astype(f32))
        beta = jax.nn.sigmoid(jnp.einsum(
            "bsd,dh->bsh", x, layer["w_beta"].astype(dt)).astype(f32))
        low = jnp.einsum("bsd,dr->bsr", x, layer["w_ga"].astype(dt))
        gate = jnp.einsum("bsr,rhk->bshk", low, layer["w_gb"].astype(dt))
    out = lm.delta_rule(q, k, v, a, beta)
    flat = x.shape[:2] + (heads * hd,)
    with jax.named_scope("kda_gate"), jax.named_scope("gate_norm"):
        gated = lm.gated_norm(
            out.reshape(flat), gate.reshape(flat), layer["o_norm_scale"],
            cfg.rms_norm_eps, gate_first=False, activation="sigmoid")
    return jnp.einsum("bshk,hkd->bsd", gated.reshape(split),
                      layer["wo"].astype(dt)), \
        decay_floor(a)


def _block(cfg: KimiLinearConfig, kind: str, h, layer, positions):
    """One layer of ``kind`` (``lm.runs``). Returns (h, aux):
    ``decay_floor`` (0 for a latent layer) and, of an expert layer, what
    ``lm.expert_aux`` names."""
    eps = cfg.rms_norm_eps
    ffn, mixer = kind.split("_")
    x = lm.rmsnorm(h, layer["ln_in_scale"], eps)
    with jax.named_scope(mixer):
        if mixer == "kda":
            m, floor = _kda(cfg, x, layer)
        else:
            m, floor = lm.mla(cfg, x, layer, positions), jnp.float32(0.0)
        h = h + m
    aux = {"decay_floor": floor}
    x = lm.rmsnorm(h, layer["ln2_scale"], eps)
    if ffn == "dense":
        with jax.named_scope("mlp"):
            return h + lm.swiglu(x, layer["w_gate"], layer["w_up"],
                                 layer["w_down"]), aux
    routed, shared, moe = lm.expert_ffn(
        x, layer, top_k=cfg.num_experts_per_token,
        scaling=cfg.routed_scaling_factor, normalize=cfg.moe_renormalize,
        held=cfg.experts_held)
    return h + routed + shared, dict(aux, **moe)


def _metrics(cfg: KimiLinearConfig, aux, targets):
    """``kda_decay_floor``: the most negative running sum of log-decays any
    chunk of any KDA layer reached this step; and ``lm.moe_metrics``."""
    return {"kda_decay_floor": aux["decay_floor"].min(),
            **lm.moe_metrics(aux, targets.size * cfg.num_experts_per_token)}


_SHELL = lm.Decoder(
    name="kimi_linear", shapes=_shapes, leaves_of=_leaves_of,
    block=lambda *args: _block(*args), experts=True, metrics=_metrics)

#: ``hidden_states``' aux is ``decay_floor`` [L] and the expert layers'
#: ``picked`` [L_moe, B, S, K], ``group_sizes`` [L_moe, held experts],
#: ``asked``, ``within_bound`` and ``rows_summed`` [L_moe], in layer order.
#: No layer reads ``positions`` under ``mla_use_nope``: the delta rule's
#: layers carry the order.
init, param_specs = _SHELL.init, _SHELL.param_specs
hidden_states, head = _SHELL.hidden_states, _SHELL.head
forward, forward_with_aux = _SHELL.forward, _SHELL.forward_with_aux
loss_of_hidden, loss_fn = _SHELL.loss_of_hidden, _SHELL.loss_fn
SUMMED_METRICS = lm.SUMMED_METRICS
RECORDED_METRICS = dict(
    lm.RECORDED_METRICS, kda_decay_floor=lambda value:
    builtin_metrics.train_kda_decay_floor().set(value))
