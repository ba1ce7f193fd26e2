"""GLM-5-family language models (``model_type: glm_moe_dsa``): multi-head
latent attention with a low-rank query, over keys that a learned indexer
selects (DeepSeek Sparse Attention, the selection shared by the layers
behind the one that made it), and ``deepseek_v3``'s routed-expert FFN after
leading dense layers.

The config keys carry their published names (``GlmMoeDsaConfig``), so a
``config.json`` of the family reads straight into the config here. The
published instance behind the preset is GLM-5.2
(https://huggingface.co/zai-org/GLM-5.2/blob/main/config.json). Per layer,
no bias anywhere, ``x = RMSNorm(h; g1)``::

    c_q      = RMSNorm(x W_qa; g_q)                      [q_lora_rank]
    q        = c_q W_qb            -> heads x (qk_nope | qk_rope)
    c | k_r  = x W_kva             -> kv_lora_rank | qk_rope ; c = RMSNorm(c; g_kv) ; k_r one head for all
    k_n | v  = c W_kvb             -> heads x (qk_nope | v_head)
    q_r, k_r = rope(q_r), rope(k_r)     theta rope_theta, pairs (2i, 2i+1)
    layers whose indexer_types entry is "full":
      qI     = c_q W_Iq            -> index_n_heads x index_head_dim, rope on the first qk_rope of them
      kI     = LayerNorm(x W_Ik)   -> index_head_dim, one head, rope on the same
      w      = x W_Iw * index_n_heads^-1/2 * index_head_dim^-1/2        float32
      I[t,s] = sum_j w[t,j] ReLU(qI[t,j] . kI[s])        s <= t, float32
      S_t    = the index_topk largest of I[t, :t+1]      (all of them while t < index_topk)
    layers whose entry is "shared": S_t of the nearest earlier "full" layer
    a        = softmax_{s in S_t}([q_n|q_r] . [k_n|k_r][s] / sqrt(qk_nope + qk_rope)) v[s]
    h        = h + a W_o
    x        = RMSNorm(h; g2)
    leading dense layers: m = W_down(silu(W_gate x) * W_up x)
    the others: s = sigmoid(x W_r) in float32 ; pick num_experts_per_tok of s + b
                w = s[picked] / (sum s[picked] + 1e-20) * routed_scaling_factor
                m = sum_i w_i Expert_i(x) + Shared(x)
    h        = h + m

The loss is ``CE + indexer_loss_coef * L_I``: ``L_I = mean_t KL(p[t, S_t] ||
softmax_{s in S_t} I[t, s])`` with ``p[t, s]`` the main attention's
probabilities summed over the heads and normalised over ``S_t``, summed over
the layers that own an indexer. ``p``, ``x`` and ``c_q`` enter the indexer
under ``stop_gradient``: the indexer learns from ``L_I`` alone and
everything else from the cross-entropy alone, through the selected keys
(DeepSeek-V3.2's sparse training stage; nothing differentiates the top-k).
The multi-token prediction module (``num_nextn_predict_layers``) is not
implemented: ``lm.Decoder`` has one head.

This module is the family's config, its table of leaves and its block; the
latent attention's projections (``lm.mla_qkv``), the indexer, the attention
over its selection and the indexers' loss (``lm.index_scores`` and what
stands beside it, shared with ``models/dots3_note.py``), the expert FFN
(``lm.expert_ffn``), the lookup, the layer scan, the head and the loss are
``models/lm.py``'s, the scores' kernel, the selection, the attention over it
and the head-summed probabilities ``ops/dsa.py``'s. A layer's kind is its
FFN and its indexer's type together (``dense_full``, ``moe_shared``,
``moe_full``); every run of one kind is one stack of parameters and one
scan, and a "full" run hands its last layer's selection on to the runs
behind it (``lm.scan_blocks``, ``shares``): integers, which carry no
cotangent back.

**The cut and the share.** ``first_layer`` and ``num_hidden_layers`` say
which published layers run (``first_layer`` to ``first_layer +
num_hidden_layers - 1``; ``first_k_dense_replace`` and ``indexer_types`` stay
the published ones, read at the published index). ``experts_held = (first,
count)`` says which of a layer's ``n_routed_experts`` live here, as in
``models/kimi_linear.py``; a sliced vocabulary is a smaller ``vocab_size``.
Expert parallelism (an ``ep`` mesh axis > 1) is not implemented.
"""

from __future__ import annotations

from dataclasses import dataclass, replace
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
from jax.ad_checkpoint import checkpoint_name

from ray_tpu.models import lm
from ray_tpu.ops import dsa

#: What a "full" layer hands on to the "shared" layers behind it.
SELECTION = "selection"


def published_indexer_types(depth: int = 78, dense: int = 3, freq: int = 4
                            ) -> Tuple[str, ...]:
    """GLM-5.2's ``indexer_types``: an indexer in every leading dense layer
    and in every ``freq``-th layer from the last of them on."""
    return tuple("full" if l < dense or (l - dense + 1) % freq == 0
                 else "shared" for l in range(depth))


@dataclass(frozen=True)
class GlmMoeDsaConfig:
    # Published keys, under their published names.
    vocab_size: int = 154880
    hidden_size: int = 6144
    #: Layers that run; the published depth is ``len(indexer_types)``.
    num_hidden_layers: int = 78
    first_k_dense_replace: int = 3
    num_attention_heads: int = 64
    q_lora_rank: int = 2048
    kv_lora_rank: int = 512
    qk_nope_head_dim: int = 192
    qk_rope_head_dim: int = 64
    v_head_dim: int = 256
    rope_theta: float = 8000000.0
    index_n_heads: int = 32
    index_head_dim: int = 128
    index_topk: int = 2048
    #: "full" | "shared" for every published layer.
    indexer_types: Tuple[str, ...] = published_indexer_types()
    intermediate_size: int = 12288
    moe_intermediate_size: int = 2048
    n_routed_experts: int = 256
    num_experts_per_tok: int = 8
    n_shared_experts: int = 1
    routed_scaling_factor: float = 2.5
    norm_topk_prob: bool = True
    rms_norm_eps: float = 1e-5
    max_position_embeddings: int = 1048576
    #: The published index of the first layer that runs.
    first_layer: int = 0
    #: (first, count) of the ``n_routed_experts`` whose weights live here;
    #: None: all of them.
    experts_held: Optional[Tuple[int, int]] = None
    #: The weight of the indexers' loss beside the cross-entropy, and the
    #: epsilon of the LayerNorm on the indexer's key (neither is a
    #: published key: DeepSeek-V3.2's).
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

    #: ``lm.mla_qkv`` reads it: the latent layer's rope dimensions rotate.
    mla_use_nope = False

    def __post_init__(self):
        object.__setattr__(self, "indexer_types", tuple(self.indexer_types))
        object.__setattr__(self, "experts_held", lm.held_experts(
            self.experts_held, self.n_routed_experts))
        run = self.indexer_types[
            self.first_layer:self.first_layer + self.num_hidden_layers]
        if len(run) != self.num_hidden_layers or set(run) - {"full",
                                                             "shared"}:
            raise ValueError(
                f"layers {self.first_layer} to {self.first_layer} + "
                f"{self.num_hidden_layers} of indexer_types "
                f"({len(self.indexer_types)} entries of 'full' | 'shared')")
        if run and run[0] != "full":
            raise ValueError(
                f"the first layer that runs (published layer "
                f"{self.first_layer}) shares a selection no layer here makes")

    @property
    def layers(self) -> Tuple[str, ...]:
        """The kind of each layer that runs: its FFN (``dense`` or ``moe``)
        and its indexer's type, as ``dense_full``."""
        return tuple(
            ("dense_" if l < self.first_k_dense_replace else "moe_")
            + self.indexer_types[l]
            for l in range(self.first_layer,
                           self.first_layer + self.num_hidden_layers))

    @property
    def n_moe_layers(self) -> int:
        return sum(kind.startswith("moe_") for kind in self.layers)


PRESETS: Dict[str, GlmMoeDsaConfig] = {
    "glm-5.2": GlmMoeDsaConfig(),
    # Test size: published layers 2 to 6 of a model with three leading dense
    # layers (one dense layer and a whole period: full, shared x 3, full),
    # 32 indexer heads (with few, every head's ReLU is zero for some pairs
    # and their scores tie at 0), the top 24 of up to 64 keys, 8 experts
    # with 2 a token.
    "glm-tiny": GlmMoeDsaConfig(
        vocab_size=256, hidden_size=64, num_hidden_layers=5, first_layer=2,
        indexer_types=published_indexer_types(8),
        num_attention_heads=4, q_lora_rank=48, kv_lora_rank=32,
        qk_nope_head_dim=16, qk_rope_head_dim=8, v_head_dim=24,
        index_n_heads=32, index_head_dim=16, index_topk=24,
        intermediate_size=128, moe_intermediate_size=32, n_routed_experts=8,
        num_experts_per_tok=2, max_position_embeddings=512,
        dtype=jnp.float32, remat=False),
}


def config(name: str, **overrides) -> GlmMoeDsaConfig:
    cfg = PRESETS[name]
    return replace(cfg, **overrides) if overrides else cfg


# -- parameters ---------------------------------------------------------

def _shapes(cfg: GlmMoeDsaConfig):
    """{"attn" | "full" | "dense" | "moe": {leaf: (shape without the layers
    axis, logical axes, init)}}: one table for ``init`` and ``param_specs``
    (``lm.Decoder``); a layer holds the attention's leaves, its indexer's
    where it owns one, and its FFN's. Matrices normal(0, 0.02), norm scales
    of one, the LayerNorm's bias and the correction bias zero."""
    d = cfg.hidden_size
    attn = {"ln1_scale": ((d,), ("embed",), lm.ones),
            **lm.mla_leaves(cfg),
            "ln2_scale": ((d,), ("embed",), lm.ones)}
    f = cfg.moe_intermediate_size
    return {"attn": attn, "full": lm.indexer_leaves(cfg), "shared": {},
            "dense": lm.swiglu_leaves(d, cfg.intermediate_size),
            "moe": lm.expert_leaves(
                d, cfg.n_routed_experts, cfg.experts_held, f,
                shared_width=cfg.n_shared_experts * f)}


def _leaves_of(shapes, kind: str):
    ffn, indexer = kind.split("_")
    return {**shapes["attn"], **shapes[indexer], **shapes[ffn]}


# -- forward ------------------------------------------------------------

#: What rotates the indexer's q and k: a name of this module, so that a test
#: or a planted fault can put another in its place.
_partly_rotated = lm.partly_rotated


def _block(cfg: GlmMoeDsaConfig, kind: str, h, layer, positions, shared):
    """One layer of ``kind`` (``lm.runs``). Returns (h, aux): of a layer
    that owns an indexer ``index_loss`` [B], ``selected`` (the pairs its
    selection keeps) and the selection it hands on; of an expert layer what
    ``lm.expert_aux`` names."""
    eps = cfg.rms_norm_eps
    ffn, indexer = kind.split("_")
    aux = {}
    x = lm.rmsnorm(h, layer["ln1_scale"], eps)
    with jax.named_scope("mla"):
        q, k, v, c_q = lm.mla_qkv(cfg, x, layer, positions)
    if indexer == "full":
        with jax.named_scope("dsa_index"):
            scores = lm.index_scores(cfg, x, c_q, layer, positions,
                                    _partly_rotated)
        with jax.named_scope("dsa_select"):
            selection = checkpoint_name(
                dsa.select(jax.lax.stop_gradient(scores), cfg.index_topk),
                dsa.SELECTION_NAME)
    else:
        selection = shared[SELECTION]
    with jax.named_scope("mla"):
        attn, lse = lm.selected_attention(cfg, q, k, v, selection)
        h = h + jnp.einsum("bshk,hkd->bsd", attn,
                           layer["wo"].astype(cfg.dtype))
    if indexer == "full":
        with jax.named_scope("dsa_probs"):
            probs = lm.selection_probs(cfg, q, k, lse, selection)
            aux = {"index_loss": dsa.index_loss(scores, probs, selection),
                   "selected": selection.astype(jnp.float32).sum(),
                   lm.HANDED_ON: {SELECTION: selection}}
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


def _metrics(cfg: GlmMoeDsaConfig, aux, targets):
    """``lm.selection_metrics`` and ``lm.moe_metrics``."""
    return {**lm.selection_metrics(aux, targets),
            **lm.moe_metrics(aux, targets.size * cfg.num_experts_per_tok)}


_SHELL = lm.Decoder(
    name="glm_moe_dsa", shapes=_shapes, leaves_of=_leaves_of,
    block=lambda *args, **kwargs: _block(*args, **kwargs), shares=True,
    experts=True, metrics=_metrics, extra_loss=lm.index_loss)

#: ``hidden_states``' aux is ``index_loss`` [indexers, B] and ``selected``
#: [indexers] and the expert layers' ``picked`` [L_moe, B, S, K],
#: ``group_sizes`` [L_moe, held experts], ``asked``, ``within_bound`` and
#: ``rows_summed`` [L_moe], in layer order.
init, param_specs = _SHELL.init, _SHELL.param_specs
hidden_states, head = _SHELL.hidden_states, _SHELL.head
forward, forward_with_aux = _SHELL.forward, _SHELL.forward_with_aux
loss_of_hidden, loss_fn = _SHELL.loss_of_hidden, _SHELL.loss_fn
SUMMED_METRICS = lm.SUMMED_METRICS
RECORDED_METRICS = {**lm.RECORDED_METRICS, **lm.SELECTION_RECORDED}
