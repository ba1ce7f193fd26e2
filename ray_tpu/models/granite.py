"""Granite 4.0-H language models (``model_type: granitemoehybrid``): a stack
of Mamba-2 state-space layers with a few grouped-query attention layers
between them, no positions of any kind, a SwiGLU after every mixer, and
four published multipliers.

The config keys carry their published names (``GraniteMoeHybridConfig``),
so a ``config.json`` of the family reads straight into ``GraniteConfig``.
The published instance behind the preset is granite-4.0-h-micro
(https://huggingface.co/ibm-granite/granite-4.0-h-micro/blob/main/config.json).
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
    x        = RMSNorm(h; g2) ;  m = W_out2(silu(x W_a) * (x W_b))    W_a | W_b one matrix
    h        = h + residual_multiplier * m
    logits   = RMSNorm(h_last; g_f) wte^T / logits_scaling               tied

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
C. The program computes ``num_local_experts`` 0 and a tied head only
(granite-4.0-h-micro's); ``GraniteConfig`` refuses others.
"""

from __future__ import annotations

from dataclasses import dataclass, replace
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

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
    num_local_experts: int = 0
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
        if self.num_local_experts or not self.tie_word_embeddings:
            raise NotImplementedError(
                "models/granite.py computes num_local_experts 0 and a tied "
                "head only")
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
}


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
    conv passes nothing on)."""
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
    return {"mamba": dict(shared, **mamba),
            "attention": dict(shared, **attention)}


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
    """One layer of ``kind``: the mixer, then the SwiGLU, each added to the
    residual stream times ``residual_multiplier``. Returns (h, None)."""
    scale = cfg.residual_multiplier
    with jax.named_scope(kind):
        h = h + scale * _MIXERS[kind](
            cfg, lm.rmsnorm(h, layer["ln1_scale"], cfg.rms_norm_eps), layer)
    with jax.named_scope("mlp"):
        h = h + scale * _mlp(
            cfg, lm.rmsnorm(h, layer["ln2_scale"], cfg.rms_norm_eps), layer)
    return h, None


_SHELL = lm.Decoder(
    name="granite", shapes=_shapes, block=lambda *args: _block(*args),
    tied=True, embed_scale=lambda cfg: cfg.embedding_multiplier,
    logits_divisor=lambda cfg: cfg.logits_scaling)

#: ``head`` is the tied head's, over ``logits_scaling``; no block returns
#: aux, so ``hidden_states`` and ``loss_of_hidden`` carry none.
init, param_specs = _SHELL.init, _SHELL.param_specs
head, forward, loss_fn = _SHELL.head, _SHELL.forward, _SHELL.loss_fn


def hidden_states(params: Dict[str, Any], cfg: GraniteConfig,
                  tokens: jax.Array,
                  positions: Optional[jax.Array] = None) -> jax.Array:
    """tokens [B, S] int32 -> final-normed hidden [B, S, d]. No layer reads
    ``positions``: the state-space layers carry the order."""
    return _SHELL.hidden_states(params, cfg, tokens, positions)[0]


def loss_of_hidden(params: Dict[str, Any], cfg: GraniteConfig, x: jax.Array,
                   targets: jax.Array, mask: Optional[jax.Array] = None
                   ) -> Tuple[jax.Array, Dict[str, jax.Array]]:
    """``loss_fn`` from ``hidden_states``' result x [B, S, d]."""
    return _SHELL.loss_of_hidden(params, cfg, x, {}, targets, mask)
