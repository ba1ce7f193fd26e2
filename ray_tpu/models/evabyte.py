"""EvaByte byte-level language models (``model_type: evabyte``,
``attention_class: eva``): a tokenizer-free decoder over 320 byte and special
ids whose attention is EVA (Zheng, Yuan, Wang, Kong: "Efficient Attention
via Control Variates", ICLR 2023, in the deterministic form the release
trains) and whose one hidden state feeds ``num_pred_heads`` heads, head i
predicting byte t + 1 + i.

The config keys carry their published names (``EvaByteConfig``), so a
``config.json`` of the family reads straight into the config here. The
published instance behind the preset is EvaByte 6.5B
(https://huggingface.co/EvaByte/EvaByte/blob/main/config.json). The release
is remote code and no ``transformers`` has the model: the layer is written
from the paper and the release's description. With ``N(h; g) = h *
rsqrt(mean(h^2) + eps) * (1 + g)`` (``norm_add_unit_offset``), chunks ``C_j``
of ``chunk_size`` keys and block windows of ``window_size``, per layer and
head (``phi``, ``mu`` in R^head_dim learned, a pair a head and layer)::

    x        = N(h; g1)
    q, k, v  = x W_q, x W_k, x W_v               no bias
    q, k     = rope(q), rope(k)                  theta rope_theta, the whole head, i paired with i + head_dim / 2
    a[m]     = softmax_{m in C_j}(k[m] . phi)
    kc[j]    = sum_{m in C_j} a[m] k[m] + mu     ;  vc[j] = sum_{m in C_j} a[m] v[m]
    o[t]     = softmax over {m in t's window, m <= t} and {j : C_j in a window before t's}
               of (q[t] . k[m] | q[t] . kc[j]) / sqrt(head_dim), applied to (v[m] | vc[j])
    h        = h + o W_o                         the sum formed in float32 (fp32_skip_add)
    h        = h + W_down(silu(W_gate N(h; g2)) * W_up N(h; g2))      likewise
    logits   = N(h_last; g_f) W_head -> [num_pred_heads, vocab] in float32 (fp32_logits)
    loss     = mean over heads i of CE(logits[:, i], byte[t + 1 + i]), a (t, i) past the sequence masked out

Rope comes before the pooling: a summary pools rotated keys. What the
published config does not say and this module assumes: ``mu`` is a vector a
head added to the pooled key, ``k . phi`` has no further scale, the rope's
pairing, equal weights on the heads' losses, and ``fp32_skip_add`` as the
stream plus the branch summed in float32 and stored in the stream's dtype.

This module is the family's config, its table of leaves and its block; the
pooling, the attention over two sources under one softmax and its kernels
are ``ops/eva.py`` (through ``lm.eva_attention``), the lookup, the layer
scan, the head of several predictions and its loss ``models/lm.py``'s
(``Decoder`` with ``pred_heads``).
"""

from __future__ import annotations

from dataclasses import dataclass, replace
from typing import Any, Dict, Tuple

import jax
import jax.numpy as jnp

from ray_tpu._private import builtin_metrics
from ray_tpu.models import lm

#: The most prediction heads ``RECORDED_METRICS`` has a series for (the
#: published count).
MAX_PRED_HEADS = 8


@dataclass(frozen=True)
class EvaByteConfig:
    # Published keys, under their published names.
    vocab_size: int = 320
    hidden_size: int = 4096
    num_hidden_layers: int = 32
    num_attention_heads: int = 32
    num_key_value_heads: int = 32
    intermediate_size: int = 11008
    window_size: int = 2048
    chunk_size: int = 16
    num_pred_heads: int = 8
    rope_theta: float = 100000.0
    rms_norm_eps: float = 1e-5
    init_std: float = 0.01275
    max_position_embeddings: int = 32768
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
        if self.num_key_value_heads != self.num_attention_heads:
            raise ValueError(
                "EVA attention pools one key and value head a query head: "
                f"{self.num_key_value_heads} KV heads under "
                f"{self.num_attention_heads}")
        if self.hidden_size % self.num_attention_heads \
                or self.window_size % self.chunk_size:
            raise ValueError(
                f"heads of {self.hidden_size} / {self.num_attention_heads}, "
                f"windows of {self.window_size} in chunks of "
                f"{self.chunk_size}: neither may leave a remainder")
        if not 1 <= self.num_pred_heads <= MAX_PRED_HEADS:
            raise ValueError(f"1 to {MAX_PRED_HEADS} prediction heads, got "
                             f"{self.num_pred_heads}")

    @property
    def head_dim(self) -> int:
        return self.hidden_size // self.num_attention_heads

    @property
    def layers(self) -> Tuple[str, ...]:
        return ("eva",) * self.num_hidden_layers


PRESETS: Dict[str, EvaByteConfig] = {
    "evabyte-6.5b": EvaByteConfig(),
    # Test size: sequences of 256 see four windows and 24 summaries.
    "evabyte-tiny": EvaByteConfig(
        hidden_size=128, num_hidden_layers=2, num_attention_heads=4,
        num_key_value_heads=4, intermediate_size=256, window_size=64,
        chunk_size=8, num_pred_heads=4, max_position_embeddings=256,
        dtype=jnp.float32, remat=False),
}


def config(name: str, **overrides) -> EvaByteConfig:
    cfg = PRESETS[name]
    return replace(cfg, **overrides) if overrides else cfg


# -- parameters ---------------------------------------------------------

def _shapes(cfg: EvaByteConfig):
    """{"eva": {leaf: (shape without the layers axis, logical axes,
    init)}}. Matrices normal(0, ``init_std``); the norms' offsets g zero
    (the scale is 1 + g); ``phi`` and ``mu`` normal(0, head_dim^-1/2)."""
    d, h, hd = cfg.hidden_size, cfg.num_attention_heads, cfg.head_dim
    f, std = cfg.intermediate_size, cfg.init_std
    qkv = ((d, h, hd), ("embed", "heads", "head_dim"), std)
    vector = ((h, hd), ("heads", "head_dim"), hd ** -0.5)
    return {"eva": {
        "ln1_scale": ((d,), ("embed",), lm.zeros),
        "wq": qkv, "wk": qkv, "wv": qkv,
        "eva_phi": vector, "eva_mu": vector,
        "wo": ((h, hd, d), ("heads", "head_dim", "embed"), std),
        "ln2_scale": ((d,), ("embed",), lm.zeros),
        "w_gate": ((d, f), ("embed", "mlp"), std),
        "w_up": ((d, f), ("embed", "mlp"), std),
        "w_down": ((f, d), ("mlp", "embed"), std)}}


# -- forward ------------------------------------------------------------

def _norm(cfg: EvaByteConfig, h, g):
    """``N(h; g)``: the scale is ``1 + g``."""
    return lm.rmsnorm(h, 1.0 + g.astype(jnp.float32), cfg.rms_norm_eps)


def _skip_add(h, branch):
    """``fp32_skip_add``: the stream plus a branch, summed in float32, in
    the stream's dtype."""
    return (h.astype(jnp.float32) + branch.astype(jnp.float32)
            ).astype(h.dtype)


def _block(cfg: EvaByteConfig, kind: str, h, layer, positions):
    """One layer. Returns (h, {"summary_mass": the mean share of a query's
    softmax sum on summaries})."""
    dt = cfg.dtype
    x = _norm(cfg, h, layer["ln1_scale"])
    with jax.named_scope("attention"):
        q, k, v = (jnp.einsum("bsd,dhk->bshk", x, layer[w].astype(dt))
                   for w in ("wq", "wk", "wv"))
        q = lm.rope(q, positions, cfg.rope_theta)
        k = lm.rope(k, positions, cfg.rope_theta)
        out, mass = lm.eva_attention(
            q, k, v, layer["eva_phi"], layer["eva_mu"], cfg,
            cfg.window_size, cfg.chunk_size)
        h = _skip_add(h, jnp.einsum("bshk,hkd->bsd", out,
                                    layer["wo"].astype(dt)))
    x = _norm(cfg, h, layer["ln2_scale"])
    with jax.named_scope("mlp"):
        h = _skip_add(h, lm.swiglu(x, layer["w_gate"], layer["w_up"],
                                   layer["w_down"]))
    return h, {"summary_mass": mass.mean()}


def _metrics(cfg: EvaByteConfig, aux, targets):
    """``eva_pairs_share`` (pairs the attention covers over the causal
    pairs, counted from the table the kernels were traced with),
    ``eva_summary_mass`` (the layers' mean) and, for the heads the config
    lacks of ``MAX_PRED_HEADS``, ``mbp_loss_<i>`` not a number (nothing is
    recorded of them)."""
    from ray_tpu.ops import eva
    S = targets.shape[1]
    tiles = (cfg.attn_blk_q, cfg.attn_blk_k) if cfg.attn_impl == "flash" \
        else ()
    return {"eva_pairs_share": jnp.float32(eva.pairs_share(
                S, cfg.window_size, cfg.chunk_size, *tiles)),
            "eva_summary_mass": aux["summary_mass"].mean(),
            **{f"mbp_loss_{i}": jnp.float32(jnp.nan)
               for i in range(cfg.num_pred_heads, MAX_PRED_HEADS)}}


_SHELL = lm.Decoder(
    name="evabyte", shapes=_shapes, block=lambda *args: _block(*args),
    unit_offset=True, pred_heads=lambda cfg: cfg.num_pred_heads,
    fp32_logits=True, top_std=lambda cfg: cfg.init_std, metrics=_metrics)

#: ``hidden_states``' aux is ``summary_mass`` [layers]; ``head`` returns
#: [..., num_pred_heads, vocab] in float32.
init, param_specs = _SHELL.init, _SHELL.param_specs
hidden_states, head = _SHELL.hidden_states, _SHELL.head
forward, forward_with_aux = _SHELL.forward, _SHELL.forward_with_aux
loss_of_hidden, loss_fn = _SHELL.loss_of_hidden, _SHELL.loss_fn


def _record_head_loss(head: int):
    def record(value: float) -> None:
        if value == value:  # a head the config lacks comes as not a number
            builtin_metrics.train_mbp_loss().set(value,
                                                 {"head": str(head)})
    return record


RECORDED_METRICS = {
    "eva_pairs_share": lambda value:
        builtin_metrics.train_eva_pairs_share().set(value),
    "eva_summary_mass": lambda value:
        builtin_metrics.train_eva_summary_mass().set(value),
    **{f"mbp_loss_{i}": _record_head_loss(i)
       for i in range(MAX_PRED_HEADS)}}
