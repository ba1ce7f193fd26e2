"""Granite 4.0-H language models (``model_type: granitemoehybrid``): a stack
of Mamba-2 state-space layers with a few grouped-query attention layers
between them, no positions of any kind, a SwiGLU after every mixer (and,
where the model has experts, a routed expert layer beside it), and four
published multipliers.

The config keys carry their published names (``GraniteMoeHybridConfig``),
so a ``config.json`` of the family reads straight into ``GraniteConfig``.
The published instances behind the presets are granite-4.0-h-micro
(https://huggingface.co/ibm-granite/granite-4.0-h-micro/blob/main/config.json:
``num_local_experts`` 0, a SwiGLU of 8192 on a width of 2048) and
granite-4.0-h-small
(https://huggingface.co/ibm-granite/granite-4.0-h-small/blob/main/config.json:
32B-A9B; 72 experts of 768 at 10 a token beside a shared SwiGLU of 1536 in
every one of its 40 layers, 128 state-space heads on a width of 4096).
Per layer, as ``GraniteMoeHybridForCausalLM`` computes it (``transformers``
4.57, ``torch_forward`` for the state-space layer)::

    h        = embedding_multiplier * wte[tokens]
    x        = RMSNorm(h; g1)                          no bias anywhere but the conv
    mamba:   z | xBC | dt = x W_in      d_inner | d_inner + 2 d_state | heads
             xBC      = silu(conv(xBC))  depthwise, mamba_d_conv taps, causal, with bias
             u | B | C = xBC             heads x d_head | groups x d_state | groups x d_state
                                         head i reads B, C of group i // (heads / groups); micro has one group
             dt       = softplus(dt + dt_bias) ;  A = -exp(A_log)      a scalar a head
             S_t      = exp(dt_t A) S_(t-1) + dt_t u_t B_t^T            zero before the first token
             y_t      = S_t C_t + D u_t
             a        = RMSNorm(y * silu(z); g_m) W_out      the gate before the norm, which is over the whole row
                                                              whatever the groups (``GraniteMoeHybridRMSNormGated``)
    attention: q, k, v = x Wq, x Wk, x Wv    heads, kv heads, kv heads of hidden / heads
             a        = softmax(causal(q k^T * attention_multiplier)) v Wo
                        query head i reads KV head i // (heads / kv heads); no positions
    h        = h + residual_multiplier * a
    x        = RMSNorm(h; g2) ;  s = W_out2(silu(x W_a) * (x W_b))    shared_mlp; W_a | W_b one matrix
    experts (num_local_experts > 0; ``GraniteMoeHybridMoE`` beside the shared SwiGLU, on the same x):
             logits   = float32(x W_r)                 W_r [d, num_local_experts], no bias
             top, e   = top_k(logits, num_experts_per_tok) ;  g = softmax(top)     over the picked alone
             r        = sum_i g_i W_out[e_i] (silu(x W_in[e_i][:f]) * x W_in[e_i][f:])    f = intermediate_size
    h        = h + residual_multiplier * (r + s)       r = 0 without experts
    logits   = RMSNorm(h_last; g_f) wte^T / logits_scaling               tied

``num_local_experts`` picks the block's FFN: 0 runs the shared SwiGLU
alone; above 0 the routed sum of ``lm.expert_ffn`` (``ops/moe.py``:
``score="softmax"``, ``normalize=True``, no bias, no scaling) is added to it
before the one multiplication by ``residual_multiplier``. The published gate
is a softmax over the picked logits; a softmax over all the experts
renormalised over the picked is the same function (the picked are the same,
the largest logits being the largest probabilities, and the ratio of two
probabilities is the ratio of their exponentials), which is what
``moe.route`` computes, its weights in float32 where the published code
casts them to the activations' dtype. The experts' leaves are
``lm.expert_leaves``' (``w_gate`` and ``w_up`` apart where the published
``input_linear`` holds both halves in one matrix, gated half first). No
auxiliary loss: the published forward adds a balancing term only when asked
(``output_router_logits``), and the config sizes none.

**The chip's share.** ``experts_held = (first, count)`` says which of a
layer's ``num_local_experts`` live here, as in ``models/nemotron_h.py``: the
parameters hold those alone, the router stays ``num_local_experts`` wide,
and the layer returns this chip's part of the routed sum beside the shared
SwiGLU, which every chip computes alike. Expert parallelism (an ``ep`` mesh
axis > 1) is not implemented, and such a mesh is refused for every member
of the family: one without experts has nothing to spread over it.

The recurrence is ``ops/ssd.py``'s chunked scan (through ``lm.state_space``),
the convolution with its bias and SiLU ``lm.conv_silu`` over xBC's columns of
the in-projection's output where they lie (``ops/short_conv.py``'s fused pass
each way where the shapes tile, else its ``jax.numpy`` form in
float32: no split copy, no float32 padded copy), the line ``a =
RMSNorm(y * silu(z); g_m)`` ``lm.gated_norm`` on y and z's columns of the
same output (``ops/gated_norm.py``'s fused pass each way where the shapes
tile, gate, mean of squares and scale in float32 on whole rows in VMEM, else
its ``jax.numpy`` form),
attention the flash kernels or ``dot`` through ``lm.attention`` with the
model's score scale. This module is the family's config, its table of leaves
(``_shapes``) and its block; parameters and specs from the table, the
lookup, the scan over the two kinds of layer, the tied head and the loss are
``lm.Decoder``'s.
``mamba_n_groups`` is passed on to ``ops/ssd.py`` as the group axis of B and
C. The program computes a tied head only (both published members');
``GraniteConfig`` refuses an untied one.
"""

from __future__ import annotations

from dataclasses import dataclass, replace
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from ray_tpu._private import builtin_metrics
from ray_tpu.models import lm

_PERIOD = ("mamba",) * 5 + ("attention",) + ("mamba",) * 4


@dataclass(frozen=True)
class GraniteConfig:
    # Published keys, under their published names.
    vocab_size: int = 100352
    hidden_size: int = 2048
    num_hidden_layers: int = 40
    #: The kind of every layer of the published depth; a model cut to
    #: ``num_hidden_layers`` runs the first that many.
    layer_types: Tuple[str, ...] = _PERIOD * 4
    num_attention_heads: int = 32
    num_key_value_heads: int = 8
    attention_multiplier: float = 0.015625
    embedding_multiplier: float = 12.0
    residual_multiplier: float = 0.22
    logits_scaling: float = 8.0
    shared_intermediate_size: int = 8192
    #: An expert's width (read only where ``num_local_experts`` > 0).
    intermediate_size: int = 8192
    num_local_experts: int = 0
    num_experts_per_tok: int = 0
    mamba_n_heads: int = 64
    mamba_d_head: int = 64
    mamba_d_state: int = 128
    mamba_n_groups: int = 1
    mamba_d_conv: int = 4
    mamba_expand: int = 2
    mamba_chunk_size: int = 256
    rms_norm_eps: float = 1e-5
    max_position_embeddings: int = 131072
    tie_word_embeddings: bool = True
    #: (first, count) of the ``num_local_experts`` whose weights live here;
    #: None: all of them.
    experts_held: Optional[Tuple[int, int]] = None
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

    def __post_init__(self):
        object.__setattr__(self, "layer_types", tuple(self.layer_types))
        if not self.tie_word_embeddings:
            raise NotImplementedError(
                "models/granite.py computes a tied head only")
        if self.num_local_experts:
            object.__setattr__(self, "experts_held", lm.held_experts(
                self.experts_held, self.num_local_experts))
            if not 0 < self.num_experts_per_tok <= self.num_local_experts:
                raise ValueError(
                    f"num_experts_per_tok {self.num_experts_per_tok} of "
                    f"{self.num_local_experts} experts")
        elif self.experts_held is not None:
            raise ValueError("experts_held of a model without experts")
        if self.mamba_n_heads % self.mamba_n_groups:
            raise ValueError("mamba_n_groups must divide mamba_n_heads")
        if self.mamba_n_heads * self.mamba_d_head != \
                self.mamba_expand * self.hidden_size:
            raise ValueError("mamba_n_heads * mamba_d_head must be "
                             "mamba_expand * hidden_size")
        if len(self.layer_types) < self.num_hidden_layers:
            raise ValueError("layer_types is shorter than num_hidden_layers")

    @property
    def layers(self) -> Tuple[str, ...]:
        """The kind of each layer that runs."""
        return self.layer_types[:self.num_hidden_layers]

    @property
    def n_moe_layers(self) -> int:
        """An expert layer in every layer, or in none."""
        return self.num_hidden_layers if self.num_local_experts else 0

    @property
    def head_dim(self) -> int:
        return self.hidden_size // self.num_attention_heads

    @property
    def mamba_d_inner(self) -> int:
        return self.mamba_n_heads * self.mamba_d_head

    @property
    def conv_dim(self) -> int:
        return self.mamba_d_inner + 2 * self.mamba_n_groups \
            * self.mamba_d_state


PRESETS: Dict[str, GraniteConfig] = {
    "granite-4.0-h-micro": GraniteConfig(),
    # Test size: state-space heads of 64 in one block of 128-lane tiles and
    # a chunk of 128, so that the kernels run (interpreted) on the CPU.
    "granite-tiny": GraniteConfig(
        vocab_size=256, hidden_size=128, num_hidden_layers=4,
        layer_types=("mamba", "attention", "mamba", "mamba"),
        num_attention_heads=4, num_key_value_heads=2,
        attention_multiplier=0.05, shared_intermediate_size=256,
        mamba_n_heads=4, mamba_d_head=64, mamba_d_state=128,
        mamba_chunk_size=128, max_position_embeddings=512,
        dtype=jnp.float32, remat=False),
    "granite-4.0-h-small": GraniteConfig(
        hidden_size=4096, attention_multiplier=0.0078125,
        logits_scaling=16.0, shared_intermediate_size=1536,
        intermediate_size=768, num_local_experts=72, num_experts_per_tok=10,
        mamba_n_heads=128),
}
# granite-tiny's widths with an expert layer in every layer: 16 experts at 4
# a token (at an intermediate_size of 128 or more the grouped product's
# kernels run, interpreted, on the CPU), two runs for the layer scan.
PRESETS["granite-moe-tiny"] = replace(
    PRESETS["granite-tiny"], num_hidden_layers=3,
    layer_types=("mamba", "mamba", "attention"),
    shared_intermediate_size=192, intermediate_size=64,
    num_local_experts=16, num_experts_per_tok=4)


def config(name: str, **overrides) -> GraniteConfig:
    cfg = PRESETS[name]
    return replace(cfg, **overrides) if overrides else cfg


# -- parameters ---------------------------------------------------------

def _shapes(cfg: GraniteConfig):
    """{kind: {leaf: (shape without the layers axis, logical axes, init)}}:
    one table for ``init`` and ``param_specs`` (``lm.Decoder``). ``init`` is
    a std for a normal draw, or a callable. As the published
    ``_init_weights`` leaves them: normal(0, 0.02) matrices, RMSNorm scales,
    ``dt_bias`` and ``D`` of one, a zero conv bias, ``A_log`` =
    log(1..heads); the conv's taps normal with the variance of
    ``nn.Conv1d``'s default, as Mamba-2's own code leaves them (at 0.02 the
    conv passes nothing on). With experts every layer also holds
    ``lm.expert_leaves``' router (no bias) and the held experts' SwiGLUs,
    drawn after its other leaves."""
    d, f = cfg.hidden_size, cfg.shared_intermediate_size
    h, kv, hd = cfg.num_attention_heads, cfg.num_key_value_heads, cfg.head_dim
    di, mh = cfg.mamba_d_inner, cfg.mamba_n_heads
    std = 0.02
    shared = {
        "ln1_scale": ((d,), ("embed",), lm.ones),
        "ln2_scale": ((d,), ("embed",), lm.ones),
        # input_linear: the gated half, then the other.
        "mlp_in": ((d, 2 * f), ("embed", "mlp"), std),
        "mlp_out": ((f, d), ("mlp", "embed"), std),
    }
    mamba = {
        # in_proj: z | xBC | dt.
        "w_in": ((d, di + cfg.conv_dim + mh), ("embed", None), std),
        # nn.Conv1d's default, uniform(+-K^-1/2), has this variance.
        "conv_w": ((cfg.mamba_d_conv, cfg.conv_dim), (None, None),
                   (3 * cfg.mamba_d_conv) ** -0.5),
        "conv_b": ((cfg.conv_dim,), (None,), lm.zeros),
        "dt_bias": ((mh,), (None,), lm.ones),
        "A_log": ((mh,), (None,), lm.log_arange),
        "D": ((mh,), (None,), lm.ones),
        "norm_scale": ((di,), (None,), lm.ones),
        "w_out": ((di, d), (None, "embed"), std),
    }
    attention = {
        "wq": ((d, h, hd), ("embed", "heads", "head_dim"), std),
        "wk": ((d, kv, hd), ("embed", "kv_heads", "head_dim"), std),
        "wv": ((d, kv, hd), ("embed", "kv_heads", "head_dim"), std),
        "wo": ((h, hd, d), ("heads", "head_dim", "embed"), std),
    }
    experts = lm.expert_leaves(
        d, cfg.num_local_experts, cfg.experts_held, cfg.intermediate_size,
        bias=False) if cfg.num_local_experts else {}
    return {"mamba": dict(shared, **mamba, **experts),
            "attention": dict(shared, **attention, **experts)}


# -- forward ------------------------------------------------------------

def _mamba(cfg: GraniteConfig, x, layer):
    """The Mamba-2 mixer on normed x [B, S, d] -> [B, S, d]."""
    dt_, f32 = cfg.dtype, jnp.float32
    di, groups, n = cfg.mamba_d_inner, cfg.mamba_n_groups, cfg.mamba_d_state
    proj = jnp.einsum("bsd,de->bse", x, layer["w_in"].astype(dt_))
    dt = proj[..., di + cfg.conv_dim:]
    with jax.named_scope("conv"):
        xbc = lm.conv_silu(proj, layer["conv_w"], layer["conv_b"],
                           start=di, width=cfg.conv_dim)
    u, B, C = jnp.split(xbc, [di, di + groups * n], axis=-1)
    dt = jax.nn.softplus(dt.astype(f32) + layer["dt_bias"].astype(f32))
    B, C = (a.reshape(a.shape[:2] + (groups, n)) for a in (B, C))
    y = lm.state_space(
        u.reshape(u.shape[:2] + (cfg.mamba_n_heads, cfg.mamba_d_head)), dt,
        -jnp.exp(layer["A_log"].astype(f32)), B, C, layer["D"].astype(f32),
        cfg.mamba_chunk_size)
    with jax.named_scope("gate_norm"):
        normed = lm.gated_norm(
            y.reshape(y.shape[:2] + (di,)), proj, layer["norm_scale"],
            cfg.rms_norm_eps, gate_first=True, activation="silu")
    return jnp.einsum("bse,ed->bsd", normed, layer["w_out"].astype(dt_))


def _attention(cfg: GraniteConfig, x, layer):
    """Grouped-query attention without positions on normed x."""
    dt_ = cfg.dtype
    q = jnp.einsum("bsd,dhk->bshk", x, layer["wq"].astype(dt_))
    k = jnp.einsum("bsd,dhk->bshk", x, layer["wk"].astype(dt_))
    v = jnp.einsum("bsd,dhk->bshk", x, layer["wv"].astype(dt_))
    attn = lm.attention(q, k, v, cfg, scale=cfg.attention_multiplier)
    return jnp.einsum("bshk,hkd->bsd", attn, layer["wo"].astype(dt_))


def _mlp(cfg: GraniteConfig, x, layer):
    dt_ = cfg.dtype
    gate, up = jnp.split(
        jnp.einsum("bsd,df->bsf", x, layer["mlp_in"].astype(dt_)), 2, axis=-1)
    return jnp.einsum("bsf,fd->bsd", jax.nn.silu(gate) * up,
                      layer["mlp_out"].astype(dt_))


_MIXERS = {"mamba": _mamba, "attention": _attention}


def _block(cfg: GraniteConfig, kind: str, h, layer, positions):
    """One layer of ``kind``: the mixer, then the FFN ``num_local_experts``
    picks (the shared SwiGLU alone, or with the routed experts' sum on the
    same normed input added to it), each added to the residual stream times
    ``residual_multiplier``. Returns (h, the expert layer's aux:
    ``lm.expert_aux``, or None without experts)."""
    scale = cfg.residual_multiplier
    with jax.named_scope(kind):
        h = h + scale * _MIXERS[kind](
            cfg, lm.rmsnorm(h, layer["ln1_scale"], cfg.rms_norm_eps), layer)
    with jax.named_scope("mlp"):
        x = lm.rmsnorm(h, layer["ln2_scale"], cfg.rms_norm_eps)
        m = _mlp(cfg, x, layer)
    aux = None
    if cfg.num_local_experts:
        routed, _, aux = lm.expert_ffn(
            x, layer, top_k=cfg.num_experts_per_tok, scaling=1.0,
            normalize=True, held=cfg.experts_held, score="softmax")
        m = routed + m
    return h + scale * m, aux


def _metrics(cfg: GraniteConfig, aux, targets):
    """``lm.moe_metrics`` and ``moe_picked_mass`` (the layers' mean of the
    probability a token's picked experts hold before renormalising); {} of a
    model without experts, whose blocks return no aux."""
    if "picked_mass" not in aux:
        return {}
    return {**lm.moe_metrics(aux, targets.size * cfg.num_experts_per_tok),
            "moe_picked_mass": aux["picked_mass"].mean()}


_SHELL = lm.Decoder(
    name="granite", shapes=_shapes, block=lambda *args: _block(*args),
    tied=True, embed_scale=lambda cfg: cfg.embedding_multiplier,
    logits_divisor=lambda cfg: cfg.logits_scaling, experts=True,
    metrics=_metrics)

#: ``head`` is the tied head's, over ``logits_scaling``. ``forward_with_aux``
#: returns the expert layers' aux beside the logits: ``picked`` [L, B, S,
#: K], ``group_sizes`` [L, held experts], ``asked``, ``within_bound``,
#: ``rows_summed`` and ``picked_mass`` [L], in layer order ({} without
#: experts); ``loss_fn``'s metrics are the cross-entropy's and ``_metrics``.
init, param_specs = _SHELL.init, _SHELL.param_specs
head, forward, loss_fn = _SHELL.head, _SHELL.forward, _SHELL.loss_fn
forward_with_aux = _SHELL.forward_with_aux
SUMMED_METRICS = lm.SUMMED_METRICS
RECORDED_METRICS = {
    **lm.RECORDED_METRICS,
    "moe_picked_mass": lambda value:
        builtin_metrics.train_moe_picked_mass().set(value),
}


def hidden_states(params: Dict[str, Any], cfg: GraniteConfig,
                  tokens: jax.Array,
                  positions: Optional[jax.Array] = None) -> jax.Array:
    """tokens [B, S] int32 -> final-normed hidden [B, S, d]. No layer reads
    ``positions``: the state-space layers carry the order."""
    return _SHELL.hidden_states(params, cfg, tokens, positions)[0]


def loss_of_hidden(params: Dict[str, Any], cfg: GraniteConfig, x: jax.Array,
                   targets: jax.Array, mask: Optional[jax.Array] = None
                   ) -> Tuple[jax.Array, Dict[str, jax.Array]]:
    """``loss_fn`` from ``hidden_states``' result x [B, S, d], without the
    expert layers' metrics (``hidden_states`` hands their aux on to no
    one)."""
    return _SHELL.loss_of_hidden(params, cfg, x, {}, targets, mask)
