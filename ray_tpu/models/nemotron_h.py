"""Nemotron-H language models (``model_type: nemotron_h``, NVIDIA's
Nemotron 3 Nano): a stack in which **every layer is one mixer**, a Mamba-2
state-space layer with several B/C groups, a routed-expert layer of
squared-ReLU experts, or grouped-query attention without positions, in the
order ``hybrid_override_pattern`` spells (``M``, ``E``, ``*``).

The config keys carry their published names, so a ``config.json`` of the
family reads straight into ``NemotronHConfig``. The published instance behind
the preset is NVIDIA-Nemotron-3-Nano-30B-A3B-BF16
(https://huggingface.co/nvidia/NVIDIA-Nemotron-3-Nano-30B-A3B-BF16/blob/main/config.json:
52 layers, 23 ``M``, 23 ``E``, 6 ``*``; 31.6B parameters, 3.2B a token). No
bias anywhere but the conv, every norm an RMSNorm with
``layer_norm_epsilon``; at the preset's numbers::

    h = wte[tokens]                                     no multiplier; untied lm_head; final RMSNorm
    layer l:  h = h + Mixer_l(RMSNorm(h; g_l))          one norm and one branch a layer
    M (Mamba-2): z | xBC | dt = x W_in                  4096 | 4096 + 2 x 8 x 128 = 6144 | 64
                 xBC = silu(conv(xBC) + b)              depthwise, 4 taps, causal, over all 6144 columns
                 u | B | C = xBC                        64 heads x 64 | 8 groups x 128 | 8 groups x 128
                 dt = softplus(dt + dt_bias) ; A = -exp(A_log)       a scalar a head
                 S_t = exp(dt_t A) S_(t-1) + dt_t u_t B_t^T ; y_t = S_t C_t + D u_t     head i reads B, C of group i // 8
                 a = RMSNorm_grouped(y * silu(z); g_m) W_out         gate first; mean of squares over each group's 512
                                                                     channels (d_inner / n_groups); g_m is 4096 wide
    * (attention): q, k, v = x Wq, x Wk, x Wv           32 heads, 2 KV heads, head 128
                 a = softmax(causal(q k^T / sqrt(128))) v Wo         head i reads KV head i // 16; no positions
    E (experts): s = sigmoid(x W_r) in float32 ; pick top 6 of 128 by s + b (n_group 1, topk_group 1: plain top-6)
                 w = s[picked] / sum(s[picked]) * 2.5
                 a = sum_i w_i W_down_i relu(x W_up_i)^2  +  W_down_s relu(x W_up_s)^2
                                                        routed width 1856, shared width 3712, no gate matrix
    logits = RMSNorm(h_last; g_f) lm_head

**Readings the keys do not settle.** (1) No rotary embedding in the
attention layers although the config carries ``rope_theta`` and
``partial_rotary_factor``: the family's attention applies none, the
state-space layers carry the order (as in ``models/granite.py``). (2) The
gated norm's statistics are a B/C group's (Mamba-2's own
``RMSNormGated(group_size = d_inner / n_groups)``). (3) The correction bias
``b`` steers the selection only and no rule here moves it (``noaux_tc``'s
update is training code the config does not carry). (4) ``time_step_min`` /
``time_step_max`` / ``time_step_floor`` (``dt_bias`` starts as the inverse
softplus of a step log-uniform between them), ``rescale_prenorm_residual``
and ``A_log`` = log(1..heads) are initialisation and change no forward
equation. (5) ``chunk_size`` is the chunked scan's, not a width: any value
computes the same function. ``expand`` and ``intermediate_size`` are read by
nothing (``mamba_num_heads * mamba_head_dim`` is the inner width; the
pattern has no dense MLP layer, ``-``, and one is refused).

This module is the family's config, its table of leaves (``_shapes``) and its
blocks; the rest is ``models/lm.py``'s: ``Decoder``, ``state_space``
(``ops/ssd.py``, B and C with their group axis), ``conv_silu``,
``gated_norm`` (a scale as wide as the row, statistics a group),
``attention`` and ``expert_ffn`` (``ops/moe.py`` with ``activation="relu2"``
and no gate). **The scan's unit**: neighbours always differ, so a run of one
kind would be one layer. A stretch of alternating ``E`` and ``M`` is a run of
units of two layers (``experts_mamba``; its leaves under ``a_`` and ``b_``),
with a lone layer where a stretch has an odd one and a run of its own for an
attention layer: the published 52 layers are 15 runs (``units``). Each layer
of a unit is rematerialised on its own (``lm.rematerialised``), so a backward
pass holds one layer's intermediates at a time.

**The layers that run.** ``first_layer`` and ``num_hidden_layers`` name a
stretch of the published pattern, which is kept whole and read at the
published index (None of the published model is cut by default).

**The chip's share.** ``experts_held = (first, count)`` says which of a
layer's ``n_routed_experts`` live here, as in ``models/afmoe.py``: the
parameters hold those alone, the router stays ``n_routed_experts`` wide, and
the layer returns this chip's part of the routed sum beside the shared
expert, which every chip computes alike. Expert parallelism (an ``ep`` mesh
axis > 1) is not implemented.
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

#: ``hybrid_override_pattern``'s letters -> the kind of layer.
KINDS = {"M": "mamba", "E": "experts", "*": "attention"}
_PATTERN = "MEMEM*EMEMEM*EMEMEM*EMEMEM*EMEMEM*EMEMEMEM*EMEMEMEME"


@dataclass(frozen=True)
class NemotronHConfig:
    # Published keys, under their published names.
    vocab_size: int = 131072
    hidden_size: int = 2688
    #: How many layers run: ``hybrid_override_pattern`` from ``first_layer``.
    num_hidden_layers: int = 52
    hybrid_override_pattern: str = _PATTERN
    num_attention_heads: int = 32
    num_key_value_heads: int = 2
    head_dim: int = 128
    mamba_num_heads: int = 64
    mamba_head_dim: int = 64
    ssm_state_size: int = 128
    n_groups: int = 8
    conv_kernel: int = 4
    use_conv_bias: bool = True
    chunk_size: int = 128
    time_step_min: float = 0.001
    time_step_max: float = 0.1
    time_step_floor: float = 0.0001
    mlp_hidden_act: str = "relu2"
    mamba_hidden_act: str = "silu"
    moe_intermediate_size: int = 1856
    moe_shared_expert_intermediate_size: int = 3712
    n_routed_experts: int = 128
    n_shared_experts: int = 1
    num_experts_per_tok: int = 6
    norm_topk_prob: bool = True
    routed_scaling_factor: float = 2.5
    n_group: int = 1
    topk_group: int = 1
    layer_norm_epsilon: float = 1e-5
    max_position_embeddings: int = 262144
    tie_word_embeddings: bool = False
    #: The published index of the first layer that runs.
    first_layer: int = 0
    #: (first, count) of the ``n_routed_experts`` whose weights live here;
    #: None: all of them.
    experts_held: Optional[Tuple[int, int]] = None
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
        object.__setattr__(self, "experts_held", lm.held_experts(
            self.experts_held, self.n_routed_experts))
        unknown = set(self.hybrid_override_pattern) - set(KINDS)
        if unknown:
            raise NotImplementedError(
                f"hybrid_override_pattern has {sorted(unknown)}: "
                f"models/nemotron_h.py computes {sorted(KINDS)} only")
        if self.first_layer < 0 or self.first_layer + self.num_hidden_layers \
                > len(self.hybrid_override_pattern):
            raise ValueError(
                f"layers {self.first_layer} to {self.first_layer} + "
                f"{self.num_hidden_layers} of a pattern of "
                f"{len(self.hybrid_override_pattern)}")
        if self.mamba_num_heads % self.n_groups \
                or self.num_attention_heads % self.num_key_value_heads:
            raise ValueError("n_groups must divide mamba_num_heads, and "
                             "num_key_value_heads num_attention_heads")
        if (self.mlp_hidden_act, self.mamba_hidden_act, self.n_group,
                self.topk_group, self.tie_word_embeddings) \
                != ("relu2", "silu", 1, 1, False):
            raise NotImplementedError(
                "models/nemotron_h.py computes mlp_hidden_act 'relu2', "
                "mamba_hidden_act 'silu', n_group 1, topk_group 1 and an "
                "untied head only")

    @property
    def layers(self) -> Tuple[str, ...]:
        """The kind of each layer that runs."""
        first = self.first_layer
        return tuple(KINDS[letter] for letter in self.hybrid_override_pattern[
            first:first + self.num_hidden_layers])

    @property
    def n_moe_layers(self) -> int:
        return self.layers.count("experts")

    @property
    def mamba_d_inner(self) -> int:
        return self.mamba_num_heads * self.mamba_head_dim

    @property
    def conv_dim(self) -> int:
        return self.mamba_d_inner + 2 * self.n_groups * self.ssm_state_size


PRESETS: Dict[str, NemotronHConfig] = {
    "nemotron-3-nano-30b-a3b": NemotronHConfig(),
    # Test size: all three kinds of layer, a lone layer, a run of two units
    # and a lone expert layer at the end; two B/C groups of two heads of 64
    # (a head block of 128 lanes a group) with a state of 128 and chunks of
    # 128, a gated norm over groups of 128, so that those kernels run
    # (interpreted) on the CPU; experts of 192, a width no multiple of 128
    # that the grouped product's kernels take at rows of whole tiles; four
    # query heads over two KV heads.
    "nemotron-h-tiny": NemotronHConfig(
        vocab_size=256, hidden_size=128, num_hidden_layers=7,
        hybrid_override_pattern="MEMEM*E", num_attention_heads=4,
        num_key_value_heads=2, head_dim=32, mamba_num_heads=4,
        mamba_head_dim=64, ssm_state_size=128, n_groups=2, chunk_size=128,
        moe_intermediate_size=192, moe_shared_expert_intermediate_size=320,
        n_routed_experts=8, num_experts_per_tok=2,
        max_position_embeddings=512, dtype=jnp.float32, remat=False),
}


def config(name: str, **overrides) -> NemotronHConfig:
    cfg = PRESETS[name]
    return replace(cfg, **overrides) if overrides else cfg


# -- the scan's units ---------------------------------------------------

def units(kinds) -> Tuple[str, ...]:
    """The scan's unit of every layer of ``kinds`` in turn: a stretch in
    which ``mamba`` and ``experts`` alternate is units of two layers
    (``experts_mamba``; ``mamba_experts`` where it starts with a state-space
    layer and is of even length) with its odd layer alone, first if it is a
    state-space layer and last if an expert layer; any other layer is a unit
    by itself."""
    out, kinds = [], list(kinds)
    while kinds:
        n = 1
        while n < len(kinds) and {kinds[n - 1], kinds[n]} == {"mamba",
                                                               "experts"}:
            n += 1
        stretch, kinds = kinds[:n], kinds[n:]
        if n % 2 and stretch[0] == "mamba":
            out.append(stretch.pop(0))
        out += ["_".join(stretch[:2])] * (len(stretch) // 2)
        if len(stretch) % 2:
            out.append(stretch[-1])
    return tuple(out)


def _runs(cfg: NemotronHConfig):
    """``lm.runs`` of the units that run: 15 of the published 52 layers."""
    return lm.runs(units(cfg.layers))


# -- parameters ---------------------------------------------------------

def _step_bias(cfg: NemotronHConfig):
    """``dt_bias``: the inverse softplus of a step log-uniform between
    ``time_step_min`` and ``time_step_max`` a head, no smaller than
    ``time_step_floor`` (Mamba-2's published initialisation)."""
    def draw(key, shape):
        dt = jnp.maximum(jnp.exp(jax.random.uniform(
            key, shape, jnp.float32, math.log(cfg.time_step_min),
            math.log(cfg.time_step_max))), cfg.time_step_floor)
        return dt + jnp.log(-jnp.expm1(-dt))
    return draw


def _shapes(cfg: NemotronHConfig):
    """{kind: {leaf: (shape without the layers axis, logical axes, init)}}:
    one table for ``init`` and ``param_specs`` (``lm.Decoder``); a unit of
    two layers holds its first layer's leaves under ``a_`` and its second's
    under ``b_`` (``_leaves_of``). Matrices normal(0, 0.02), RMSNorm scales
    and ``D`` of one, a zero conv bias and correction bias, ``A_log`` =
    log(1..heads), ``dt_bias`` by ``_step_bias``; the conv's taps normal
    with the variance of ``nn.Conv1d``'s default, as Mamba-2's own code
    leaves them."""
    d, std = cfg.hidden_size, 0.02
    h, kv, hd = cfg.num_attention_heads, cfg.num_key_value_heads, cfg.head_dim
    di, mh = cfg.mamba_d_inner, cfg.mamba_num_heads
    norm = {"ln_scale": ((d,), ("embed",), lm.ones)}
    conv_bias = {"conv_b": ((cfg.conv_dim,), (None,), lm.zeros)} \
        if cfg.use_conv_bias else {}
    mamba = {
        # in_proj: z | xBC | dt.
        "w_in": ((d, di + cfg.conv_dim + mh), ("embed", None), std),
        # nn.Conv1d's default, uniform(+-K^-1/2), has this variance.
        "conv_w": ((cfg.conv_kernel, cfg.conv_dim), (None, None),
                   (3 * cfg.conv_kernel) ** -0.5),
        **conv_bias,
        "dt_bias": ((mh,), (None,), _step_bias(cfg)),
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
        d, cfg.n_routed_experts, cfg.experts_held, cfg.moe_intermediate_size,
        cfg.n_shared_experts * cfg.moe_shared_expert_intermediate_size,
        gated=False)
    return {"mamba": dict(norm, **mamba),
            "attention": dict(norm, **attention),
            "experts": dict(norm, **experts)}


def _leaves_of(shapes, unit: str):
    kinds = unit.split("_")
    if len(kinds) == 1:
        return shapes[unit]
    return {prefix + name: leaf
            for prefix, kind in zip(("a_", "b_"), kinds)
            for name, leaf in shapes[kind].items()}


# -- forward ------------------------------------------------------------

def _scanned(cfg: NemotronHConfig, x, layer):
    """The Mamba-2 mixer up to its norm on normed x [B, S, d]: (the
    recurrence's output y [B, S, d_inner], the in-projection's output z |
    xBC | dt, whose first columns gate y)."""
    f32 = jnp.float32
    di, groups, n = cfg.mamba_d_inner, cfg.n_groups, cfg.ssm_state_size
    proj = jnp.einsum("bsd,de->bse", x, layer["w_in"].astype(cfg.dtype))
    dt = proj[..., di + cfg.conv_dim:]
    with jax.named_scope("conv"):
        xbc = lm.conv_silu(proj, layer["conv_w"], layer.get("conv_b"),
                           start=di, width=cfg.conv_dim)
    u, B, C = jnp.split(xbc, [di, di + groups * n], axis=-1)
    dt = jax.nn.softplus(dt.astype(f32) + layer["dt_bias"].astype(f32))
    by_group = B.shape[:2] + (groups, n)
    y = lm.state_space(
        u.reshape(u.shape[:2] + (cfg.mamba_num_heads, cfg.mamba_head_dim)),
        dt, -jnp.exp(layer["A_log"].astype(f32)), B.reshape(by_group),
        C.reshape(by_group), layer["D"].astype(f32), cfg.chunk_size)
    return y.reshape(y.shape[:2] + (di,)), proj


def _mamba(cfg: NemotronHConfig, x, layer):
    """The Mamba-2 mixer on normed x [B, S, d] -> [B, S, d]."""
    y, proj = _scanned(cfg, x, layer)
    with jax.named_scope("gate_norm"):
        normed = lm.gated_norm(
            y, proj, layer["norm_scale"], cfg.layer_norm_epsilon,
            gate_first=True, activation="silu",
            group=cfg.mamba_d_inner // cfg.n_groups)
    return jnp.einsum("bse,ed->bsd", normed, layer["w_out"].astype(cfg.dtype))


def _attention(cfg: NemotronHConfig, x, layer):
    """Grouped-query attention without positions on normed x."""
    dt_ = cfg.dtype
    q = jnp.einsum("bsd,dhk->bshk", x, layer["wq"].astype(dt_))
    k = jnp.einsum("bsd,dhk->bshk", x, layer["wk"].astype(dt_))
    v = jnp.einsum("bsd,dhk->bshk", x, layer["wv"].astype(dt_))
    attn = lm.attention(q, k, v, cfg, scale=cfg.head_dim ** -0.5)
    return jnp.einsum("bshk,hkd->bsd", attn, layer["wo"].astype(dt_))


def _experts(cfg: NemotronHConfig, x, layer):
    """The expert layer on normed x: (this chip's part of the routed sum
    plus the shared expert, ``lm.expert_aux``)."""
    routed, shared, aux = lm.expert_ffn(
        x, layer, top_k=cfg.num_experts_per_tok,
        scaling=cfg.routed_scaling_factor, normalize=cfg.norm_topk_prob,
        held=cfg.experts_held, activation=cfg.mlp_hidden_act)
    return routed + shared, aux


def _layer(cfg: NemotronHConfig, kind: str, h, layer):
    """One layer of ``kind``: its norm, its one mixer, added to the residual
    stream. Returns (h, an expert layer's aux or None)."""
    x = lm.rmsnorm(h, layer["ln_scale"], cfg.layer_norm_epsilon)
    aux = None
    with jax.named_scope(kind):
        if kind == "experts":
            out, aux = _experts(cfg, x, layer)
        else:
            out = {"mamba": _mamba, "attention": _attention}[kind](
                cfg, x, layer)
    return h + out, aux


def _block(cfg: NemotronHConfig, unit: str, h, leaves, positions):
    """One unit (``units``) from its leaves: its layers in turn, each
    rematerialised on its own. Returns (h, the unit's expert layer's aux, or
    None of a unit without one). No layer reads ``positions``: the
    state-space layers carry the order."""
    kinds = unit.split("_")
    layers = [leaves] if len(kinds) == 1 else [
        {name[2:]: leaf for name, leaf in leaves.items()
         if name.startswith(prefix)} for prefix in ("a_", "b_")]
    aux = None
    for kind, layer in zip(kinds, layers):
        h, of_layer = lm.rematerialised(cfg, partial(_layer, cfg, kind))(
            h, layer)
        aux = aux or of_layer
    return h, aux


def _metrics(cfg: NemotronHConfig, aux, targets):
    """``lm.moe_metrics`` and ``moe_relu2_zero_share``: of the hidden
    activations the held experts computed, the share their squared ReLU
    zeroed (the layers' mean)."""
    return {**lm.moe_metrics(aux, targets.size * cfg.num_experts_per_tok),
            "moe_relu2_zero_share": aux["relu2_zero_share"].mean()}


_SHELL = lm.Decoder(
    name="nemotron_h", shapes=_shapes, leaves_of=_leaves_of, runs_of=_runs,
    block=lambda *args: _block(*args), eps="layer_norm_epsilon",
    experts=True, metrics=_metrics, remat_in_block=True)

#: ``hidden_states``' aux is the expert layers' ``picked`` [L, B, S, K],
#: ``group_sizes`` [L, held experts], ``asked``, ``within_bound``,
#: ``rows_summed`` and ``relu2_zero_share`` [L], in layer order; ``loss_fn``'s
#: metrics are the cross-entropy's and ``_metrics``.
init, param_specs = _SHELL.init, _SHELL.param_specs
hidden_states, head = _SHELL.hidden_states, _SHELL.head
forward, forward_with_aux = _SHELL.forward, _SHELL.forward_with_aux
loss_of_hidden, loss_fn = _SHELL.loss_of_hidden, _SHELL.loss_fn
SUMMED_METRICS = lm.SUMMED_METRICS
RECORDED_METRICS = {
    **lm.RECORDED_METRICS,
    "moe_relu2_zero_share": lambda value:
        builtin_metrics.train_moe_relu2_zero_share().set(value),
}
