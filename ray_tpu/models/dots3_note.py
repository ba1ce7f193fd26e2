"""dots3-note-family language models (``model_type: dots3_note``): one
decoder with two latent-attention geometries, full layers that attend over
the keys a learned indexer selects (DeepSeek Sparse Attention) beside
window layers with ranks, head count and head sizes of their own, every
layer's attention gated a head, every latent rescaled, and ``deepseek_v3``'s
routed-expert FFN after a leading dense layer.

The config keys carry their published names, so a ``config.json`` of the
family reads straight into the config here; the published instance behind
the preset is dots3-note-prev
(https://huggingface.co/dots-studio/dots3-note-prev/blob/main/config.json).
Per layer, no bias anywhere, ``x = RMSNorm(h; g1)``; a window layer reads
the ``swa_*`` keys where a full layer reads the plain ones::

    c_q      = RMSNorm(x W_qa; g_q)                      [q_lora_rank]
    q        = (s_q c_q) W_qb      -> heads x (qk_nope | qk_rope)      s_q  = sqrt(hidden / q_lora_rank)
    c | k_r  = x W_kva             -> kv_lora_rank | qk_rope ; c = s_kv RMSNorm(c; g_kv) ; s_kv = sqrt(hidden / kv_lora_rank)
    k_n | v  = c W_kvb             -> heads x (qk_nope | v_head) ; k_r one head for all, not rescaled
    q_r, k_r = rope(q_r), rope(k_r)     the kind's theta, pairs (2i, 2i+1)
    full layers (``layer_types`` "full_attention"), each with an indexer of its own:
      qI, kI, w, I[t,s], S_t       as ``models/glm_moe_dsa.py`` has them, qI from the unscaled c_q
      a_h    = softmax_{s in S_t}(q_h . k_h[s] / sqrt(qk_nope + qk_rope)) v_h[s]
    window layers ("sliding_attention"):
      a_h    = softmax_{0 <= t - s < sliding_window_size}(...) v_h[s]
    a_h      = sigmoid(x W_g)_h a_h     W_g [hidden, heads]: one gate a head
    h        = h + concat_h(a_h) W_o
    x        = RMSNorm(h; g2)
    layers before first_k_dense_replace: m = W_down(silu(W_gate x) * W_up x)
    the others: ``deepseek_v3``'s routed experts and shared expert (``lm.expert_ffn``)
    h        = h + m

The rescale is ``apply_mla_qkv_lora_rescale`` (read as LongCat-Flash's
``mla_scale_q_lora`` / ``mla_scale_kv_lora``), the gate
``attention_gate_type: "headwise"`` (Qiu et al., arXiv:2505.06708). The loss
is ``CE + indexer_loss_coef * L_I`` summed over the full layers, as
``glm_moe_dsa``'s; the gate does not enter the indexers' target. The vision
and audio towers and the multi-token prediction module are not implemented.

This module is the family's config, its two tables of attention leaves and
its block; everything else is ``models/lm.py``'s: the latent projections
(``lm.mla_qkv`` on a view of the config a kind of layer, ``Latent``), the
indexer, the attention over its selection and the indexers' loss (what this
family shares with ``models/glm_moe_dsa.py``, on ``ops/dsa.py``), the
window (``lm.attention``), the expert FFN, the layer scan, the head and the
loss. A layer's kind is its FFN
and its attention together (``dense_full``, ``moe_full``, ``moe_window``);
every run of one kind is one stack of parameters and one scan.

**The cut and the share** are ``models/glm_moe_dsa.py``'s: ``first_layer``
and ``num_hidden_layers`` say which published layers run (``layer_types``
and ``first_k_dense_replace`` stay the published ones, read at the published
index), ``experts_held = (first, count)`` which experts live here, a sliced
vocabulary is a smaller ``vocab_size``. Expert parallelism (an ``ep`` mesh
axis > 1) is not implemented.
"""

from __future__ import annotations

import math
from dataclasses import dataclass, replace
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
from jax.ad_checkpoint import checkpoint_name

from ray_tpu._private import builtin_metrics
from ray_tpu.models import lm
from ray_tpu.ops import dsa

#: ``layer_types`` entry -> the attention's kind in a layer's kind.
ATTENTION = {"full_attention": "full", "sliding_attention": "window"}


def published_layer_types(depth: int = 46, period: int = 4
                          ) -> Tuple[str, ...]:
    """dots3-note-prev's ``layer_types``: full attention in layer 0 and in
    every ``period``-th layer from layer 1 on, a window elsewhere."""
    return tuple("full_attention" if l == 0 or (l - 1) % period == 0
                 else "sliding_attention" for l in range(depth))


@dataclass(frozen=True)
class Latent:
    """One kind of layer's latent attention as ``lm.mla_leaves`` and
    ``lm.mla_qkv`` read it: the geometry under the published plain names,
    the scalars on the two latents (None: none) and the window (None:
    none)."""
    hidden_size: int
    num_attention_heads: int
    q_lora_rank: int
    kv_lora_rank: int
    qk_nope_head_dim: int
    qk_rope_head_dim: int
    v_head_dim: int
    rope_theta: float
    rms_norm_eps: float
    dtype: Any
    q_lora_scale: Optional[float]
    kv_lora_scale: Optional[float]
    window: Optional[int]
    mla_use_nope = False


@dataclass(frozen=True)
class Dots3NoteConfig:
    # Published keys, under their published names.
    vocab_size: int = 152064
    hidden_size: int = 5120
    #: Layers that run; the published depth is ``len(layer_types)``.
    num_hidden_layers: int = 46
    first_k_dense_replace: int = 1
    #: "full_attention" | "sliding_attention" for every published layer.
    layer_types: Tuple[str, ...] = published_layer_types()
    num_attention_heads: int = 128
    q_lora_rank: int = 1024
    kv_lora_rank: int = 512
    qk_nope_head_dim: int = 128
    qk_rope_head_dim: int = 64
    v_head_dim: int = 128
    rope_theta: float = 80000000.0
    swa_num_attention_heads: int = 64
    swa_q_lora_rank: int = 1024
    swa_kv_lora_rank: int = 1024
    swa_qk_nope_head_dim: int = 192
    swa_qk_rope_head_dim: int = 64
    swa_v_head_dim: int = 128
    swa_rope_theta: float = 50000.0
    sliding_window_size: int = 513
    apply_mla_qkv_lora_rescale: bool = True
    attention_gate_type: str = "headwise"
    swa_attention_gate_type: str = "headwise"
    index_n_heads: int = 64
    index_head_dim: int = 128
    index_topk: int = 2048
    intermediate_size: int = 13824
    moe_intermediate_size: int = 1536
    n_routed_experts: int = 256
    num_experts_per_tok: int = 8
    n_shared_experts: int = 1
    routed_scaling_factor: float = 1.0
    norm_topk_prob: bool = True
    rms_norm_eps: float = 1e-5
    max_position_embeddings: int = 524288
    #: The published index of the first layer that runs.
    first_layer: int = 0
    #: (first, count) of the ``n_routed_experts`` whose weights live here;
    #: None: all of them.
    experts_held: Optional[Tuple[int, int]] = None
    #: Not published keys: DeepSeek-V3.2's (``models/glm_moe_dsa.py``).
    indexer_loss_coef: float = 1.0
    index_norm_eps: float = 1e-6
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
        object.__setattr__(self, "experts_held", lm.held_experts(
            self.experts_held, self.n_routed_experts))
        run = self.layer_types[
            self.first_layer:self.first_layer + self.num_hidden_layers]
        if len(run) != self.num_hidden_layers or set(run) - set(ATTENTION):
            raise ValueError(
                f"layers {self.first_layer} to {self.first_layer} + "
                f"{self.num_hidden_layers} of layer_types "
                f"({len(self.layer_types)} entries of {sorted(ATTENTION)})")
        for key in ("attention_gate_type", "swa_attention_gate_type"):
            if getattr(self, key) != "headwise":
                raise ValueError(
                    f"{key}={getattr(self, key)!r}: the gate is 'headwise', "
                    "one sigmoid a head")

    @property
    def layers(self) -> Tuple[str, ...]:
        """The kind of each layer that runs: its FFN (``dense`` or ``moe``)
        and its attention (``full`` or ``window``), as ``moe_window``."""
        return tuple(
            ("dense_" if l < self.first_k_dense_replace else "moe_")
            + ATTENTION[self.layer_types[l]]
            for l in range(self.first_layer,
                           self.first_layer + self.num_hidden_layers))

    @property
    def n_moe_layers(self) -> int:
        return sum(kind.startswith("moe_") for kind in self.layers)

    def latent(self, attention: str) -> Latent:
        """The latent attention of the ``full`` or the ``window`` layers."""
        of = (lambda key: getattr(self, key)) if attention == "full" \
            else (lambda key: getattr(self, "swa_" + key))
        d, q_rank, rank = self.hidden_size, of("q_lora_rank"), \
            of("kv_lora_rank")
        rescale = self.apply_mla_qkv_lora_rescale
        return Latent(
            hidden_size=d, num_attention_heads=of("num_attention_heads"),
            q_lora_rank=q_rank, kv_lora_rank=rank,
            qk_nope_head_dim=of("qk_nope_head_dim"),
            qk_rope_head_dim=of("qk_rope_head_dim"),
            v_head_dim=of("v_head_dim"), rope_theta=of("rope_theta"),
            rms_norm_eps=self.rms_norm_eps, dtype=self.dtype,
            q_lora_scale=math.sqrt(d / q_rank) if rescale else None,
            kv_lora_scale=math.sqrt(d / rank) if rescale else None,
            window=None if attention == "full" else self.sliding_window_size)


PRESETS: Dict[str, Dots3NoteConfig] = {
    "dots3-note-prev": Dots3NoteConfig(),
    # Test size: published layers 0 to 3 of the published pattern (the
    # leading dense layer, then full, window, window: every kind of layer
    # and a run of two), two geometries with unlike head counts, ranks and
    # head sizes, a window shorter than the test sequences, 32 indexer heads
    # (with few, rows of scores tie at 0), the top 24 of up to 64 keys, 8
    # experts with 2 a token.
    "dots3-tiny": Dots3NoteConfig(
        vocab_size=256, hidden_size=64, num_hidden_layers=4,
        layer_types=published_layer_types(8),
        num_attention_heads=4, q_lora_rank=48, kv_lora_rank=32,
        qk_nope_head_dim=16, qk_rope_head_dim=8, v_head_dim=16,
        rope_theta=80000.0,
        swa_num_attention_heads=2, swa_q_lora_rank=40, swa_kv_lora_rank=48,
        swa_qk_nope_head_dim=24, swa_qk_rope_head_dim=8, swa_v_head_dim=16,
        swa_rope_theta=500.0, sliding_window_size=20,
        index_n_heads=32, index_head_dim=16, index_topk=24,
        intermediate_size=128, moe_intermediate_size=32, n_routed_experts=8,
        num_experts_per_tok=2, max_position_embeddings=512,
        dtype=jnp.float32, remat=False),
}


def config(name: str, **overrides) -> Dots3NoteConfig:
    cfg = PRESETS[name]
    return replace(cfg, **overrides) if overrides else cfg


# -- parameters ---------------------------------------------------------

def _attention_leaves(cfg: Dots3NoteConfig, attention: str):
    """One kind of layer's attention: the norms, the latent's leaves at the
    kind's geometry, the gate and, of a full layer, its indexer."""
    d, std = cfg.hidden_size, 0.02
    latent = cfg.latent(attention)
    leaves = {"ln1_scale": ((d,), ("embed",), lm.ones),
              **lm.mla_leaves(latent),
              "w_attn_gate": ((d, latent.num_attention_heads),
                              ("embed", "heads"), std),
              "ln2_scale": ((d,), ("embed",), lm.ones)}
    if attention == "full":
        leaves.update(lm.indexer_leaves(cfg))
    return leaves


def _shapes(cfg: Dots3NoteConfig):
    """{"full" | "window" | "dense" | "moe": {leaf: (shape without the
    layers axis, logical axes, init)}}: one table for ``init`` and
    ``param_specs`` (``lm.Decoder``). Matrices normal(0, 0.02), norm scales
    of one, the LayerNorm's bias and the correction bias zero."""
    d, f = cfg.hidden_size, cfg.moe_intermediate_size
    return {"full": _attention_leaves(cfg, "full"),
            "window": _attention_leaves(cfg, "window"),
            "dense": lm.swiglu_leaves(d, cfg.intermediate_size),
            "moe": lm.expert_leaves(
                d, cfg.n_routed_experts, cfg.experts_held, f,
                shared_width=cfg.n_shared_experts * f)}


def _leaves_of(shapes, kind: str):
    ffn, attention = kind.split("_")
    return {**shapes[attention], **shapes[ffn]}


# -- forward ------------------------------------------------------------

def _selected(cfg: Dots3NoteConfig, x, c_q, q, k, v, layer, positions):
    """A full layer's attention over its own indexer's selection: (out, its
    aux: ``index_loss`` [B] and ``selected``)."""
    with jax.named_scope("dsa_index"):
        scores = lm.index_scores(cfg, x, c_q, layer, positions)
    with jax.named_scope("dsa_select"):
        selection = checkpoint_name(
            dsa.select(jax.lax.stop_gradient(scores), cfg.index_topk),
            dsa.SELECTION_NAME)
    with jax.named_scope("mla_full"):
        attn, lse = lm.selected_attention(cfg, q, k, v, selection)
    with jax.named_scope("dsa_probs"):
        probs = lm.selection_probs(cfg, q, k, lse, selection)
        return attn, {
            "index_loss": dsa.index_loss(scores, probs, selection),
            "selected": selection.astype(jnp.float32).sum()}


def _gated(x, attn, w_gate, scope: str):
    """(attn [B, S, H, Dv] times a sigmoid a head of normed x [B, S, d],
    float32, the gate's mean)."""
    with jax.named_scope(scope):
        gate = jax.nn.sigmoid(jnp.einsum(
            "bsd,dh->bsh", x, w_gate.astype(x.dtype)).astype(jnp.float32))
        return (attn.astype(jnp.float32) * gate[..., None]).astype(
            attn.dtype), gate.mean()


def _block(cfg: Dots3NoteConfig, kind: str, h, layer, positions):
    """One layer of ``kind`` (``lm.runs``). Returns (h, aux): ``gate_full``
    or ``gate_window`` (the gate's mean), of a full layer ``index_loss`` [B]
    and ``selected`` (the pairs its selection keeps), of an expert layer
    what ``lm.expert_aux`` names."""
    eps = cfg.rms_norm_eps
    ffn, attention = kind.split("_")
    latent = cfg.latent(attention)
    scope = "mla_" + attention
    x = lm.rmsnorm(h, layer["ln1_scale"], eps)
    with jax.named_scope(scope):
        q, k, v, c_q = lm.mla_qkv(latent, x, layer, positions)
    if attention == "full":
        attn, aux = _selected(cfg, x, c_q, q, k, v, layer, positions)
    else:
        aux = {}
        with jax.named_scope(scope):
            attn = lm.attention(q, k, v, cfg, window=latent.window)
    attn, aux["gate_" + attention] = _gated(
        x, attn, layer["w_attn_gate"], "attn_gate_" + attention)
    with jax.named_scope(scope):
        h = h + jnp.einsum("bshk,hkd->bsd", attn,
                           layer["wo"].astype(cfg.dtype))
    x = lm.rmsnorm(h, layer["ln2_scale"], eps)
    if ffn == "dense":
        with jax.named_scope("mlp"):
            return h + lm.swiglu(x, layer["w_gate"], layer["w_up"],
                                 layer["w_down"]), aux
    routed, shared_expert, moe = lm.expert_ffn(
        x, layer, top_k=cfg.num_experts_per_tok,
        scaling=cfg.routed_scaling_factor, normalize=cfg.norm_topk_prob,
        held=cfg.experts_held)
    return h + routed + shared_expert, dict(aux, **moe)


def window_tile_fill(cfg: Dots3NoteConfig, seq_len: int) -> Optional[float]:
    """``lm.window_tile_fill`` of the window layers; None where none runs."""
    if not any(kind.endswith("_window") for kind in cfg.layers):
        return None
    return lm.window_tile_fill(cfg, cfg.sliding_window_size, seq_len)


def _metrics(cfg: Dots3NoteConfig, aux, targets):
    """``lm.selection_metrics`` of the full layers, ``attn_gate_mean_full``
    and ``attn_gate_mean_window`` (the gates' mean over heads, tokens and
    the kind's layers), ``attn_window_tile_fill`` and ``lm.moe_metrics``. A
    kind of layer that does not run reads not a number, and nothing is
    recorded then."""
    nan = jnp.float32(jnp.nan)
    fill = window_tile_fill(cfg, targets.shape[1])
    return {**lm.selection_metrics(aux, targets),
            **{"attn_gate_mean_" + kind: aux["gate_" + kind].mean()
               if "gate_" + kind in aux else nan
               for kind in ("full", "window")},
            "attn_window_tile_fill": nan if fill is None
            else jnp.float32(fill),
            **lm.moe_metrics(aux, targets.size * cfg.num_experts_per_tok)}


_SHELL = lm.Decoder(
    name="dots3_note", shapes=_shapes, leaves_of=_leaves_of,
    block=lambda *args: _block(*args), experts=True, metrics=_metrics,
    extra_loss=lm.index_loss)

#: ``hidden_states``' aux is ``gate_full`` [full layers], ``gate_window``
#: [window layers], ``index_loss`` [full layers, B], ``selected`` [full
#: layers] and the expert layers' ``picked`` [L_moe, B, S, K],
#: ``group_sizes`` [L_moe, held experts], ``asked``, ``within_bound`` and
#: ``rows_summed`` [L_moe], in layer order.
init, param_specs = _SHELL.init, _SHELL.param_specs
hidden_states, head = _SHELL.hidden_states, _SHELL.head
forward, forward_with_aux = _SHELL.forward, _SHELL.forward_with_aux
loss_of_hidden, loss_fn = _SHELL.loss_of_hidden, _SHELL.loss_fn
SUMMED_METRICS = lm.SUMMED_METRICS


def _record_gate_mean(kind: str):
    return lm.unless_nan(lambda value: builtin_metrics.train_attn_gate_mean(
        ).set(value, {"kind": kind}))


RECORDED_METRICS = {
    **lm.RECORDED_METRICS, **lm.SELECTION_RECORDED,
    "attn_window_tile_fill": lm.record_window_tile_fill,
    "attn_gate_mean_full": _record_gate_mean("full"),
    "attn_gate_mean_window": _record_gate_mean("window"),
}
