"""MiniCPM-SALA language models (``model_type: minicpm_sala``): a decoder
whose layers mix tokens one of two ways (``mixer_types``), block-sparse
softmax attention chosen by the model's own scores (``minicpm4``: InfLLM-V2)
in one layer of four and Lightning linear attention with a fixed decay a
head (``lightning-attn``) in the other three, under muP's three scalars.

The config keys carry their published names, so a ``config.json`` of the
family reads straight into the config here. The published instance behind
the preset is MiniCPM-SALA 9B
(https://huggingface.co/openbmb/MiniCPM-SALA/blob/main/config.json). The
release is remote code: the layers are written from the config's keys, the
MiniCPM4 report (arXiv:2506.07900) and InfLLM-V2 (arXiv:2509.24663) for the
``minicpm4`` mixer, Lightning Attention-2 (arXiv:2401.04658) and
MiniMax-01's use of it for the other. With ``N`` an RMSNorm, ``N_head`` one
over a head's ``head_dim`` with a learned scale, ``r = scale_depth /
sqrt(published layers)``, L the published depth (``len(mixer_types)``) and l
a layer's published index::

    h_0      = scale_emb * wte[token]
    x        = N(h; g1) ;  h = h + r * Mixer(x)
    x        = N(h; g2) ;  h = h + r * W_down(silu(W_gate x) * W_up x)
    logits   = (N(h_L; g_f) / (hidden_size / dim_model_base)) W_head

    minicpm4:        q = N_head(x W_q; g_q)   32 heads ;  k = N_head(x W_k; g_k), v = x W_v   2 KV heads, no positions
                     over dense_len: a = softmax over the keys s <= t of the blocks ``ops/infllm.py`` selects
                     (top ``topk`` blocks of ``block_size`` a query and KV group, the first block and the
                     local window forced in); at or under it: causal attention
                     Mixer(x) = (a * sigmoid(x W_g)) W_o
    lightning-attn:  q, k = rope(N_head(x W_q; g_q)), rope(N_head(x W_k; g_k)) ;  v = x W_v      32 heads each
                     lambda_h = exp(-2^(-8 (h + 1) / H) * (1 - l / (L - 1) + 1e-5))
                     S_t = lambda_h S_(t-1) + k_t v_t^T ;  o_t = q_t S_t / sqrt(head_dim)
                     Mixer(x) = (N_head(o; g_o) * sigmoid(x W_g)) W_o

No bias anywhere. What the published config does not say and this module
assumes (the benchmark's configuration lists each under
``assumed.readings``): the ``sparse_config`` sizes (the ``minicpm4``
release's), the window forced by whole blocks and counted among ``topk``,
kernels lying partly beyond the query invisible, the q/k norm's learned
scale and its place before rope, the decay's layer factor at the published
index, no activation on the linear mixer's q, k, v, the output norm a group
a head; ``mup_denominator`` and ``rand_init`` are initialisation only.

This module is the family's config, its table of leaves and its two blocks;
the selection and the attention over it are ``ops/infllm.py``'s (through
``lm.block_sparse_attention``), the recurrence ``ops/lightning.py``'s
(``lm.linear_attention``), the gate and norm behind it
``ops/gated_norm.py``'s (``lm.gated_norm``), the lookup, the layer scan, the
head and the three scalars' places ``models/lm.py``'s (``Decoder``).
"""

from __future__ import annotations

import math
from dataclasses import dataclass, replace
from typing import Any, Dict, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from ray_tpu._private import builtin_metrics
from ray_tpu.models import lm

#: The published ``mixer_types`` of MiniCPM-SALA 9B: ``minicpm4`` at layers
#: 0, 9, 16, 17, 22, 29, 30, 31.
_SPARSE_AT = (0, 9, 16, 17, 22, 29, 30, 31)
MIXER_TYPES = tuple("minicpm4" if i in _SPARSE_AT else "lightning-attn"
                    for i in range(32))
KINDS = {"minicpm4": "sparse", "lightning-attn": "lightning"}


@dataclass(frozen=True)
class MiniCPMSALAConfig:
    # Published keys, under their published names.
    vocab_size: int = 73448
    hidden_size: int = 4096
    intermediate_size: int = 16384
    num_hidden_layers: int = 32
    num_attention_heads: int = 32
    num_key_value_heads: int = 2
    head_dim: int = 128
    lightning_nh: int = 32
    lightning_nkv: int = 32
    lightning_head_dim: int = 128
    mixer_types: Tuple[str, ...] = MIXER_TYPES
    rope_theta: float = 10000.0
    rms_norm_eps: float = 1e-6
    scale_emb: float = 12.0
    scale_depth: float = 1.4
    dim_model_base: int = 256
    max_position_embeddings: int = 524288
    # The ``minicpm4`` mixer's ``sparse_config`` (its own release's sizes).
    sparse_kernel_size: int = 32
    sparse_kernel_stride: int = 16
    sparse_block_size: int = 64
    sparse_topk: int = 64
    sparse_init_blocks: int = 1
    sparse_window_size: int = 2048
    sparse_dense_len: int = 8192
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
        object.__setattr__(self, "mixer_types", tuple(self.mixer_types))
        unknown = set(self.mixer_types) - set(KINDS)
        if unknown:
            raise ValueError(f"mixer_types {sorted(unknown)}: the program "
                             f"computes {sorted(KINDS)}")
        if self.num_hidden_layers > len(self.mixer_types):
            raise ValueError(
                f"{self.num_hidden_layers} layers of "
                f"{len(self.mixer_types)} published mixer_types")
        if self.num_attention_heads % self.num_key_value_heads \
                or self.lightning_nkv != self.lightning_nh:
            raise ValueError(
                f"{self.num_attention_heads} heads over "
                f"{self.num_key_value_heads} KV heads must divide; the "
                f"linear mixer has a key head a query head "
                f"({self.lightning_nkv} under {self.lightning_nh})")

    @property
    def layers(self) -> Tuple[str, ...]:
        """The kind of every layer that runs: the first
        ``num_hidden_layers`` of the published ``mixer_types``."""
        return tuple(KINDS[m]
                     for m in self.mixer_types[:self.num_hidden_layers])

    @property
    def residual_scale(self) -> float:
        """``r``: ``scale_depth`` over the root of the **published** depth,
        whatever part of it runs."""
        return self.scale_depth / math.sqrt(len(self.mixer_types))

    @property
    def sparse_sizes(self):
        from ray_tpu.ops.infllm import Sizes
        return Sizes(self.sparse_kernel_size, self.sparse_kernel_stride,
                     self.sparse_block_size, self.sparse_topk,
                     self.sparse_init_blocks, self.sparse_window_size)


PRESETS: Dict[str, MiniCPMSALAConfig] = {
    "minicpm-sala-9b": MiniCPMSALAConfig(),
    # Test size: a period of the published pattern, heads of the published
    # 128 (the kernels tile by it); sequences of 512 are over dense_len (8
    # blocks of 64, of which a late query keeps 4: the first, the two of its
    # window and one by score).
    "minicpm-sala-tiny": MiniCPMSALAConfig(
        vocab_size=512, hidden_size=128, intermediate_size=256,
        num_hidden_layers=4, num_attention_heads=4, num_key_value_heads=2,
        head_dim=128, lightning_nh=2, lightning_nkv=2, lightning_head_dim=128,
        dim_model_base=32, max_position_embeddings=1024, sparse_topk=4,
        sparse_window_size=128, sparse_dense_len=256, dtype=jnp.float32,
        remat=False),
}


def config(name: str, **overrides) -> MiniCPMSALAConfig:
    cfg = PRESETS[name]
    return replace(cfg, **overrides) if overrides else cfg


def decay_slopes(cfg: MiniCPMSALAConfig, layer: int) -> np.ndarray:
    """``-log lambda_h`` [lightning_nh] float32 of the layer at published
    index ``layer``: ``2^(-8 (h + 1) / H) * (1 - l / (L - 1) + 1e-5)``."""
    H, L = cfg.lightning_nh, len(cfg.mixer_types)
    base = 2.0 ** (-8.0 * np.arange(1, H + 1, dtype=np.float64) / H)
    return (base * (1.0 - layer / max(L - 1, 1) + 1e-5)).astype(np.float32)


def _constants(cfg: MiniCPMSALAConfig, run: str):
    """``decay_slope`` [layers, heads] of a run of linear-attention layers,
    by their published indices; nothing of a sparse run."""
    at = 0
    for name, kind, depth in lm.runs(cfg.layers):
        if name == run:
            if kind != "lightning":
                return {}
            return {"decay_slope": jnp.asarray(np.stack(
                [decay_slopes(cfg, l) for l in range(at, at + depth)]))}
        at += depth
    raise KeyError(run)


# -- parameters ---------------------------------------------------------

def _shapes(cfg: MiniCPMSALAConfig):
    """{kind: {leaf: (shape without the layers axis, logical axes, init)}}.
    Matrices normal(0, 0.02), every norm's scale one."""
    d, f = cfg.hidden_size, cfg.intermediate_size

    def mixer(heads, kv_heads, hd, out_norm):
        leaves = {
            "ln1_scale": ((d,), ("embed",), lm.ones),
            "wq": ((d, heads, hd), ("embed", "heads", "head_dim"), 0.02),
            "wk": ((d, kv_heads, hd), ("embed", "heads", "head_dim"), 0.02),
            "wv": ((d, kv_heads, hd), ("embed", "heads", "head_dim"), 0.02),
            "q_norm_scale": ((hd,), (None,), lm.ones),
            "k_norm_scale": ((hd,), (None,), lm.ones),
            "w_g": ((d, heads * hd), ("embed", "mlp"), 0.02),
        }
        if out_norm:
            leaves["o_norm_scale"] = ((hd,), (None,), lm.ones)
        leaves["wo"] = ((heads, hd, d), ("heads", "head_dim", "embed"), 0.02)
        leaves["ln2_scale"] = ((d,), ("embed",), lm.ones)
        leaves.update(lm.swiglu_leaves(d, f))
        return leaves

    return {"sparse": mixer(cfg.num_attention_heads, cfg.num_key_value_heads,
                            cfg.head_dim, False),
            "lightning": mixer(cfg.lightning_nh, cfg.lightning_nkv,
                               cfg.lightning_head_dim, True)}


# -- forward ------------------------------------------------------------

def _qkv(cfg, x, layer):
    """q, k (each head normed with its learned scale) and v of normed x."""
    dt, eps = cfg.dtype, cfg.rms_norm_eps
    q, k, v = (jnp.einsum("bsd,dhk->bshk", x, layer[w].astype(dt))
               for w in ("wq", "wk", "wv"))
    return (lm.rmsnorm(q, layer["q_norm_scale"], eps),
            lm.rmsnorm(k, layer["k_norm_scale"], eps), v)


def _sparse_mixer(cfg: MiniCPMSALAConfig, x, layer, positions):
    """The ``minicpm4`` mixer on normed x: (out [B, S, d], aux)."""
    dt = cfg.dtype
    B, S, _ = x.shape
    q, k, v = _qkv(cfg, x, layer)
    if S > cfg.sparse_dense_len:
        a, aux = lm.block_sparse_attention(q, k, v, cfg, cfg.sparse_sizes)
    else:
        a = lm.attention(q, k, v, cfg)
        nan = jnp.float32(jnp.nan)  # no selection: nothing is recorded
        aux = {"selected_share": nan, "live_tile_share": nan,
               "free_mass": nan}
    gate = jax.nn.sigmoid(jnp.einsum(
        "bsd,df->bsf", x, layer["w_g"].astype(dt)).astype(jnp.float32))
    gated = (a.reshape(B, S, -1).astype(jnp.float32) * gate).astype(dt)
    return jnp.einsum("bshk,hkd->bsd", gated.reshape(a.shape),
                      layer["wo"].astype(dt)), aux


def _lightning_mixer(cfg: MiniCPMSALAConfig, x, layer, positions):
    """The ``lightning-attn`` mixer on normed x: (out [B, S, d], None)."""
    dt = cfg.dtype
    B, S, _ = x.shape
    q, k, v = _qkv(cfg, x, layer)
    q = lm.rope(q, positions, cfg.rope_theta)
    k = lm.rope(k, positions, cfg.rope_theta)
    o = lm.linear_attention(q, k, v, layer["decay_slope"])
    with jax.named_scope("lightning_gate"):
        z = jnp.einsum("bsd,df->bsf", x, layer["w_g"].astype(dt))
        gated = lm.gated_norm(
            o.reshape(B, S, -1), z, layer["o_norm_scale"], cfg.rms_norm_eps,
            gate_first=False, activation="sigmoid")
    return jnp.einsum("bshk,hkd->bsd", gated.reshape(o.shape),
                      layer["wo"].astype(dt)), None


_MIXERS = {"sparse": _sparse_mixer, "lightning": _lightning_mixer}


def _block(cfg: MiniCPMSALAConfig, kind: str, h, layer, positions):
    """One layer of ``kind``; both branches enter the stream times ``r``.
    Returns (h, the sparse mixer's gauges or None)."""
    r = jnp.asarray(cfg.residual_scale, cfg.dtype)
    with jax.named_scope(kind):
        branch, aux = _MIXERS[kind](
            cfg, lm.rmsnorm(h, layer["ln1_scale"], cfg.rms_norm_eps), layer,
            positions)
        h = h + r * branch
    with jax.named_scope("mlp"):
        x = lm.rmsnorm(h, layer["ln2_scale"], cfg.rms_norm_eps)
        h = h + r * lm.swiglu(x, layer["w_gate"], layer["w_up"],
                              layer["w_down"])
    return h, aux


def decay_floor(cfg: MiniCPMSALAConfig) -> float:
    """The least ``lambda^chunk`` of the linear-attention layers that run:
    what the steepest head keeps of a state over one chunk of the kernels
    (``ops/lightning.py`` ``CHUNK``); 1.0 of a model without such a layer."""
    from ray_tpu.ops.lightning import CHUNK
    steepest = max((float(decay_slopes(cfg, l)[0])
                    for l, kind in enumerate(cfg.layers)
                    if kind == "lightning"), default=0.0)
    return math.exp(-steepest * CHUNK)


def _metrics(cfg: MiniCPMSALAConfig, aux, targets):
    """The sparse layers' gauges, each the mean over those layers (not a
    number at or under ``dense_len``, where nothing selects: nothing is
    recorded then), and ``lightning_decay_floor``."""
    out = {"lightning_decay_floor": jnp.float32(decay_floor(cfg))}
    for name in ("selected_share", "live_tile_share", "free_mass"):
        if name in aux:
            out["sala_" + name] = aux[name].mean()
    return out


_SHELL = lm.Decoder(
    name="minicpm_sala", shapes=_shapes, block=lambda *args: _block(*args),
    constants=_constants, embed_scale=lambda cfg: cfg.scale_emb,
    logits_divisor=lambda cfg: cfg.hidden_size / cfg.dim_model_base,
    fp32_logits=True, metrics=_metrics)

#: ``hidden_states``' aux holds the sparse layers' three gauges, each
#: [sparse layers]; ``head`` returns float32 logits.
init, param_specs = _SHELL.init, _SHELL.param_specs
hidden_states, head = _SHELL.hidden_states, _SHELL.head
forward, forward_with_aux = _SHELL.forward, _SHELL.forward_with_aux
loss_of_hidden, loss_fn = _SHELL.loss_of_hidden, _SHELL.loss_fn


def _recorded(gauge):
    def record(value: float) -> None:
        if value == value:  # not a number: no selection ran
            gauge().set(value)
    return record


RECORDED_METRICS = {
    "sala_selected_share": _recorded(
        builtin_metrics.train_sala_selected_share),
    "sala_live_tile_share": _recorded(
        builtin_metrics.train_sala_live_tile_share),
    "sala_free_mass": _recorded(builtin_metrics.train_sala_free_mass),
    "lightning_decay_floor": _recorded(
        builtin_metrics.train_lightning_decay_floor),
}
