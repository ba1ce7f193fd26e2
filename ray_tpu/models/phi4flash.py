"""Phi-4-mini-flash language models (``model_type: phi4flash``, Microsoft's
SambaY decoder-hybrid-decoder): Mamba-1 layers alternating with differential
attention, and a second half whose layers read one memory and one K/V that
the middle of the stack made.

The config keys carry their published names (``Phi4FlashConfig``), so a
``config.json`` of the family reads straight into it. The published instance
behind the preset is Phi-4-mini-flash-reasoning
(https://huggingface.co/microsoft/Phi-4-mini-flash-reasoning/blob/main/config.json).
Its Mamba-1 sizes are not in that config and are the family's defaults
(``mamba_d_state`` 16, ``mamba_d_conv`` 4, ``mamba_expand`` 2, ``mamba_dt_rank``
ceil(hidden_size / 16)). Every norm but ``subln`` is an ``nn.LayerNorm``
(scale and bias, ``layer_norm_eps``); no positions of any kind::

    h = wte[tokens];  M = num_hidden_layers // 2 (16)
    layer i of the published num_hidden_layers:
      x = LN(h; g1, b1);  h = h + mixer_i(x)
      x = LN(h; g2, b2);  h = h + (silu(x W_gate) * x W_up) W_down
    mixer_i:  i even, i <= M   mamba          (i = M also hands on its memory m)
              i odd,  i <  M   differential attention, a query sees itself and the sliding_window - 1 keys before it
              i = M + 1        differential attention, causal over all keys; hands on its k, v
              i even, i >  M   gated memory unit on m
              i odd,  i >  M + 1   differential attention of this layer's q onto layer M + 1's k, v, causal
    mamba:    xs | z = x W_in;   xs = silu(conv(xs) + b_c)              depthwise, causal, mamba_d_conv taps
              dtr | B | C = xs W_x;   delta = softplus(dtr W_dt + b_dt)
              A = -exp(A_log)
              H_t = exp(delta_t (x) A) * H_(t-1) + (delta_t * xs_t) (x) B_t     zero before the first token
              y_t = H_t C_t + D * xs_t ;   m = y (layer M, before the gate) ;   out = (y * silu(z)) W_out
    gmu:      out = (silu(x W_g) * m) W_o
    attention: q = x Wq + bq [heads x hd];  k, v = x Wk + bk, x Wv + bv [kv heads x hd]   (a cross layer has Wq, bq, Wo, bo only)
              differential head j of heads / 2: q heads 2j, 2j+1;  g = j // 2: k heads 2g, 2g+1;  V_g = [v_2g | v_2g+1]
              P1 = softmax(mask(q_2j k_2g^T / sqrt(hd)));   P2 = softmax(mask(q_2j+1 k_2g+1^T / sqrt(hd)))
              lambda = exp(lq1 . lk1) - exp(lq2 . lk2) + l0(i);   l0(i) = 0.8 - 0.6 exp(-0.3 i)
              o_j = (1 - l0(i)) RMSNorm((P1 - lambda P2) V_g; g_sub, eps 1e-5);   out = concat_j(o_j) Wo + bo
    logits = LN(h_last; gf, bf) wte^T

This module is the family's config, its table of leaves (``_shapes``) and its
blocks; the rest is ``models/lm.py``'s: ``Decoder`` (parameters and specs
from the table, the lookup, the scan with remat, the LayerNorm before the
tied head, the loss) and the dispatches (``attention``: both softmax maps of
a differential head are two query heads of the flash kernels, against k and
``V_g`` laid out to the query heads here; ``conv_silu`` on the projection's
columns where they lie; ``selective_scan``). **The scan's unit is a pair of
layers**, since neighbours always differ: ``self`` (mamba, window attention),
``middle`` (mamba that hands on ``m``, full attention that hands on k and v)
and ``cross`` (gated memory unit, cross attention): three kinds, three runs.
What the middle pair hands on is held once and every cross pair reads it
(``lm.scan_blocks``, ``shares``).

**The layers that run.** ``layers_run`` names the published indices that run,
whole pairs in order (None: all ``num_hidden_layers``); ``l0(i)`` is a
function of the published index, so a cut layer keeps its own.
"""

from __future__ import annotations

import math
from dataclasses import dataclass, replace
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from ray_tpu._private import builtin_metrics
from ray_tpu.models import lm
from ray_tpu.ops.selective_scan import decay_floor

_SUBLN_EPS = 1e-5


@dataclass(frozen=True)
class Phi4FlashConfig:
    # Published keys, under their published names.
    vocab_size: int = 200064
    hidden_size: int = 2560
    intermediate_size: int = 10240
    #: The published depth: the middle pair is layers ``num_hidden_layers
    #: // 2`` and the one behind it. ``layers_run`` says which of them run.
    num_hidden_layers: int = 32
    num_attention_heads: int = 40
    num_key_value_heads: int = 20
    sliding_window: int = 512
    mb_per_layer: int = 2
    layer_norm_eps: float = 1e-5
    max_position_embeddings: int = 262144
    tie_word_embeddings: bool = True
    mlp_bias: bool = False
    lm_head_bias: bool = False
    hidden_act: str = "silu"
    # Mamba-1's sizes: the family's defaults, not in the published config.
    mamba_d_state: int = 16
    mamba_d_conv: int = 4
    mamba_expand: int = 2
    #: None: ceil(hidden_size / 16).
    mamba_dt_rank: Optional[int] = None
    #: The published indices that run, whole pairs in order; None: all.
    layers_run: Optional[Tuple[int, ...]] = None
    # The program's own choices (as GPTConfig has them).
    dtype: Any = jnp.bfloat16
    param_dtype: Any = jnp.float32
    remat: bool = True
    # "full" keeps of a block only what the flash forward kernel returns
    # (output and log-sum-exp, where the keys a query sees are many:
    # lm.scan_blocks). "selective" adds the values a block names for it, and
    # this model's blocks name none.
    remat_policy: str = "full"
    loss_chunk: int = 0
    attn_impl: str = "dot"  # "dot" | "flash"
    attn_blk_q: int = 512
    attn_blk_k: int = 512

    def __post_init__(self):
        if self.layers_run is not None:
            object.__setattr__(self, "layers_run", tuple(self.layers_run))
        run, depth = self.layers, self.num_hidden_layers
        pairs = list(zip(run[0::2], run[1::2]))
        if depth % 4 or len(run) % 2 or list(run) != sorted(set(run)) \
                or any(a % 2 or b != a + 1 or b >= depth for a, b in pairs):
            raise ValueError(
                f"layers_run {run} must be whole pairs (2p, 2p + 1) of the "
                f"{depth} published layers, in order")
        middle = depth // 2
        if run and run[-1] > middle + 1 and middle not in run:
            raise ValueError(
                f"layers_run {run} has layers behind the middle pair "
                f"({middle}, {middle + 1}) and not the pair whose memory and "
                "K/V they read")
        if self.num_attention_heads % 4 \
                or self.num_key_value_heads * 2 != self.num_attention_heads:
            raise ValueError(
                "differential attention pairs query heads (2j, 2j + 1) with "
                "the key heads (2g, 2g + 1) of g = j // 2: num_attention_heads"
                " must be a multiple of 4 and twice num_key_value_heads")
        if self.mb_per_layer != 2 or not self.tie_word_embeddings \
                or self.mlp_bias or self.lm_head_bias \
                or self.hidden_act != "silu":
            raise NotImplementedError(
                "models/phi4flash.py computes mb_per_layer 2, a tied head "
                "without bias, an MLP without bias and hidden_act 'silu' "
                "only")

    @property
    def layers(self) -> Tuple[int, ...]:
        """The published index of every layer that runs."""
        return tuple(range(self.num_hidden_layers)) \
            if self.layers_run is None else self.layers_run

    @property
    def head_dim(self) -> int:
        return self.hidden_size // self.num_attention_heads

    @property
    def d_inner(self) -> int:
        return self.mamba_expand * self.hidden_size

    @property
    def dt_rank(self) -> int:
        return self.mamba_dt_rank or math.ceil(self.hidden_size / 16)


PRESETS: Dict[str, Phi4FlashConfig] = {
    "phi-4-mini-flash-reasoning": Phi4FlashConfig(),
    # Test size: eight layers (two self pairs, the middle pair, one cross
    # pair), an inner width of 256 and sequences of whole chunks so that the
    # selective scan's and the convolution's kernels run (interpreted) on the
    # CPU, four heads of 32 (two differential heads of one group).
    "phi4flash-tiny": Phi4FlashConfig(
        vocab_size=256, hidden_size=128, intermediate_size=256,
        num_hidden_layers=8, num_attention_heads=4, num_key_value_heads=2,
        sliding_window=64, max_position_embeddings=512, dtype=jnp.float32,
        remat=False),
}


def config(name: str, **overrides) -> Phi4FlashConfig:
    cfg = PRESETS[name]
    return replace(cfg, **overrides) if overrides else cfg


def lambda_init(layer: int) -> float:
    """``l0`` of published layer ``layer``."""
    return 0.8 - 0.6 * math.exp(-0.3 * layer)


def pair_kind(cfg: Phi4FlashConfig, first: int) -> str:
    """The kind of the pair whose first layer is published layer ``first``."""
    middle = cfg.num_hidden_layers // 2
    return "self" if first < middle else \
        "middle" if first == middle else "cross"


def _runs(cfg: Phi4FlashConfig):
    """``lm.runs`` of the pairs that run."""
    return lm.runs(pair_kind(cfg, first) for first in cfg.layers[0::2])


def _constants(cfg: Phi4FlashConfig, run: str):
    """``lambda_init`` [pairs]: ``l0`` of each of the run's attention
    layers, by its published index."""
    firsts, at = cfg.layers[0::2], 0
    for name, _, depth in _runs(cfg):
        if name == run:
            return {"lambda_init": jnp.asarray(
                [lambda_init(first + 1) for first in firsts[at:at + depth]],
                jnp.float32)}
        at += depth
    raise KeyError(run)


# -- parameters ---------------------------------------------------------

def _step_bias(key, shape):
    """``b_dt``: the inverse softplus of a step log-uniform in (0.001,
    0.1) a channel, Mamba's published initialisation."""
    dt = jnp.exp(jax.random.uniform(
        key, shape, jnp.float32, math.log(0.001), math.log(0.1)))
    return dt + jnp.log(-jnp.expm1(-dt))


def _shapes(cfg: Phi4FlashConfig):
    """{"layer" | "mamba" | "attention" | "cross" | "gmu": {leaf: (shape
    without the layers axis, logical axes, init)}}: one table for ``init``
    and ``param_specs`` (``lm.Decoder``). ``layer`` is what every layer has
    (its two LayerNorms and its SwiGLU); a pair holds its first layer's
    leaves under ``a_`` and its second's under ``b_`` (``_leaves_of``).
    Matrices normal(0, 0.02), LayerNorm and ``subln`` scales of one, biases
    of zero, the lambda vectors normal(0, 0.1), the convolution's taps normal
    with the variance of ``nn.Conv1d``'s default, ``W_dt`` normal(0,
    dt_rank^-1/2), ``A_log``, ``b_dt`` and ``D`` (ones) as Mamba-1
    publishes them."""
    d, f, std = cfg.hidden_size, cfg.intermediate_size, 0.02
    h, kv, hd = cfg.num_attention_heads, cfg.num_key_value_heads, \
        cfg.head_dim
    di, n, rank, taps = cfg.d_inner, cfg.mamba_d_state, cfg.dt_rank, \
        cfg.mamba_d_conv
    layer = {"ln1_scale": ((d,), ("embed",), lm.ones),
             "ln1_bias": ((d,), ("embed",), lm.zeros),
             "ln2_scale": ((d,), ("embed",), lm.ones),
             "ln2_bias": ((d,), ("embed",), lm.zeros),
             **lm.swiglu_leaves(d, f)}
    mamba = {
        # xs | z side by side.
        "w_in": ((d, 2 * di), ("embed", "mlp"), std),
        # nn.Conv1d's default, uniform(+-K^-1/2), has this variance.
        "conv_w": ((taps, di), (None, None), (3 * taps) ** -0.5),
        "conv_b": ((di,), (None,), lm.zeros),
        # dtr | B | C side by side.
        "w_x": ((di, rank + 2 * n), ("mlp", None), std),
        "w_dt": ((rank, di), (None, "mlp"), rank ** -0.5),
        "b_dt": ((di,), (None,), _step_bias),
        "A_log": ((di, n), (None, None), lm.log_arange),
        "D": ((di,), (None,), lm.ones),
        "w_out": ((di, d), ("mlp", "embed"), std),
    }
    heads = ("heads", "head_dim")
    cross = {
        "wq": ((d, h, hd), ("embed",) + heads, std),
        "bq": ((h, hd), heads, lm.zeros),
        **{f"lambda_{x}": ((hd,), (None,), 0.1)
           for x in ("q1", "k1", "q2", "k2")},
        "subln_scale": ((2 * hd,), (None,), lm.ones),
        # A differential head is two query heads' width: V_g's.
        "wo": ((h // 2, 2 * hd, d), heads + ("embed",), std),
        "bo": ((d,), ("embed",), lm.zeros),
    }
    kv_heads = ("kv_heads", "head_dim")
    attention = dict(cross, **{
        "wk": ((d, kv, hd), ("embed",) + kv_heads, std),
        "bk": ((kv, hd), kv_heads, lm.zeros),
        "wv": ((d, kv, hd), ("embed",) + kv_heads, std),
        "bv": ((kv, hd), kv_heads, lm.zeros)})
    gmu = {"w_g": ((d, di), ("embed", "mlp"), std),
           "w_o": ((di, d), ("mlp", "embed"), std)}
    return {"layer": layer, "mamba": mamba, "attention": attention,
            "cross": cross, "gmu": gmu}


#: The mixers of a pair of each kind, first layer then second.
_MIXERS = {"self": ("mamba", "attention"), "middle": ("mamba", "attention"),
           "cross": ("gmu", "cross")}


def _leaves_of(shapes, kind: str):
    return {prefix + name: leaf
            for prefix, mixer in zip(("a_", "b_"), _MIXERS[kind])
            for name, leaf in dict(shapes["layer"], **shapes[mixer]).items()}


# -- forward ------------------------------------------------------------

def _mamba(cfg: Phi4FlashConfig, x, layer):
    """The Mamba-1 mixer on normed x [B, S, d] -> (out [B, S, d], y [B, S,
    d_inner] before the gate, the most negative ``delta_t A``)."""
    dt, f32 = cfg.dtype, jnp.float32
    di, n, rank = cfg.d_inner, cfg.mamba_d_state, cfg.dt_rank
    with jax.named_scope("in_proj"):
        proj = jnp.einsum("bsd,de->bse", x, layer["w_in"].astype(dt))
    with jax.named_scope("conv"):
        xs = lm.conv_silu(proj, layer["conv_w"], layer["conv_b"], start=0,
                          width=di)
    with jax.named_scope("steps"):
        low = jnp.einsum("bse,er->bsr", xs, layer["w_x"].astype(dt))
        delta = jax.nn.softplus(
            jnp.einsum("bsr,re->bse", low[..., :rank],
                       layer["w_dt"].astype(dt)).astype(f32)
            + layer["b_dt"].astype(f32)).astype(dt)
        A = -jnp.exp(layer["A_log"].astype(f32))
    with jax.named_scope("scan"):
        y = lm.selective_scan(xs, delta, A, low[..., rank:rank + n],
                              low[..., rank + n:], layer["D"])
    with jax.named_scope("out_proj"):
        gated = y * jax.nn.silu(proj[..., di:])
        return jnp.einsum("bse,ed->bsd", gated, layer["w_out"].astype(dt)), \
            y, decay_floor(delta, A)


def _gmu(cfg: Phi4FlashConfig, x, layer, m):
    """The gated memory unit on normed x [B, S, d] and the handed-on memory
    m [B, S, d_inner] -> [B, S, d]."""
    dt = cfg.dtype
    gate = jnp.einsum("bsd,de->bse", x, layer["w_g"].astype(dt))
    return jnp.einsum("bse,ed->bsd", jax.nn.silu(gate) * m,
                      layer["w_o"].astype(dt))


def _to_query_heads(cfg: Phi4FlashConfig, k, v):
    """k, v [B, S, kv heads, hd] as the attention dispatch takes them, one
    a query head: query head h of differential head j = h // 2 and group g =
    j // 2 scores against k head 2g + h % 2 and weighs ``V_g`` = [v_2g |
    v_2g+1] (neighbouring heads side by side: a reshape)."""
    heads = cfg.num_attention_heads
    of_query = jnp.asarray([2 * (h // 4) + h % 2 for h in range(heads)])
    v_g = v.reshape(v.shape[:2] + (v.shape[2] // 2, 2 * v.shape[3]))
    return jnp.take(k, of_query, axis=2), jnp.repeat(v_g, 4, axis=2)


def _lambda(layer, l0):
    """``exp(lq1 . lk1) - exp(lq2 . lk2) + l0``, float32."""
    f32 = jnp.float32
    return jnp.exp((layer["lambda_q1"].astype(f32)
                    * layer["lambda_k1"].astype(f32)).sum()) \
        - jnp.exp((layer["lambda_q2"].astype(f32)
                   * layer["lambda_k2"].astype(f32)).sum()) + l0


def _subln(o, scale, l0):
    """``(1 - l0) RMSNorm(o; scale)`` over a differential head's width."""
    return lm.rmsnorm(o, scale, _SUBLN_EPS) * (1.0 - l0)


def _differential(cfg: Phi4FlashConfig, x, layer, l0, kv=None, window=None):
    """Differential attention on normed x [B, S, d] -> (out [B, S, d], (k,
    v) [B, S, kv heads, hd], lambda). ``kv``: another layer's (k, v) in
    place of this layer's own (a cross layer: it has no Wk, Wv)."""
    dt, f32 = cfg.dtype, jnp.float32
    q = jnp.einsum("bsd,dhk->bshk", x, layer["wq"].astype(dt)) \
        + layer["bq"].astype(dt)
    if kv is None:
        kv = tuple(jnp.einsum("bsd,dhk->bshk", x, layer["w" + n].astype(dt))
                   + layer["b" + n].astype(dt) for n in "kv")
    both = lm.attention(q, *_to_query_heads(cfg, *kv), cfg, window=window)
    with jax.named_scope("subln"):
        lam = _lambda(layer, l0)
        maps = both.reshape(both.shape[:2] + (both.shape[2] // 2, 2, -1)
                            ).astype(f32)
        o = _subln(maps[..., 0, :] - lam * maps[..., 1, :],
                   layer["subln_scale"], l0)
    out = jnp.einsum("bshk,hkd->bsd", o.astype(dt), layer["wo"].astype(dt)) \
        + layer["bo"].astype(dt)
    return out, kv, lam


def _feed_forward(cfg: Phi4FlashConfig, h, layer):
    x = lm.layernorm(h, layer["ln2_scale"], layer["ln2_bias"],
                     cfg.layer_norm_eps)
    with jax.named_scope("mlp"):
        return h + lm.swiglu(x, layer["w_gate"], layer["w_up"],
                             layer["w_down"])


def _block(cfg: Phi4FlashConfig, kind: str, h, pair, positions, shared):
    """One pair of layers of ``kind`` (``_runs``) from the pair's leaves and
    its ``lambda_init``. Returns (h, aux): ``decay_floor`` (0 for a cross
    pair), ``lambda`` and, of the middle pair, what it hands on
    (``lm.HANDED_ON``: ``m``, ``k``, ``v``)."""
    eps = cfg.layer_norm_eps
    first, second = ({name[2:]: leaf for name, leaf in pair.items()
                      if name.startswith(prefix)} for prefix in ("a_", "b_"))
    l0 = pair["lambda_init"]
    x = lm.layernorm(h, first["ln1_scale"], first["ln1_bias"], eps)
    if kind == "cross":
        with jax.named_scope("gmu"):
            h = h + _gmu(cfg, x, first, shared["m"])
        floor = jnp.float32(0.0)
    else:
        with jax.named_scope("mamba"):
            out, m, floor = _mamba(cfg, x, first)
            h = h + out
    h = _feed_forward(cfg, h, first)
    x = lm.layernorm(h, second["ln1_scale"], second["ln1_bias"], eps)
    if kind == "self":
        with jax.named_scope("window"):
            out, kv, lam = _differential(cfg, x, second, l0,
                                         window=cfg.sliding_window)
    elif kind == "middle":
        with jax.named_scope("full"):
            out, kv, lam = _differential(cfg, x, second, l0)
    else:
        with jax.named_scope("cross"):
            out, kv, lam = _differential(cfg, x, second, l0,
                                         kv=(shared["k"], shared["v"]))
    h = _feed_forward(cfg, h + out, second)
    aux = {"decay_floor": floor, "lambda": lam}
    if kind == "middle":
        aux[lm.HANDED_ON] = {"m": m, "k": kv[0], "v": kv[1]}
    return h, aux


def _metrics(cfg: Phi4FlashConfig, aux, targets):
    """``selective_scan_decay_floor``: the most negative ``delta_t A`` any
    element of any Mamba layer saw this step (where a state forgets within a
    token); ``diff_attention_lambda_max``: the largest lambda of any layer
    (above 1 the second map outweighs the first)."""
    return {"selective_scan_decay_floor": aux["decay_floor"].min(),
            "diff_attention_lambda_max": aux["lambda"].max()}


_SHELL = lm.Decoder(
    name="phi4flash", shapes=_shapes, leaves_of=_leaves_of, runs_of=_runs,
    block=lambda *args, **kwargs: _block(*args, **kwargs),
    final_norm="final_norm_scale", final_norm_bias="final_norm_bias",
    eps="layer_norm_eps", tied=True, shares=True, constants=_constants,
    metrics=_metrics)

#: ``hidden_states``' aux is ``decay_floor`` and ``lambda`` [pairs], in
#: layer order. No layer reads ``positions``: the Mamba layers carry the
#: order.
init, param_specs = _SHELL.init, _SHELL.param_specs
hidden_states, head = _SHELL.hidden_states, _SHELL.head
forward, forward_with_aux = _SHELL.forward, _SHELL.forward_with_aux
loss_of_hidden, loss_fn = _SHELL.loss_of_hidden, _SHELL.loss_fn
RECORDED_METRICS = {
    "selective_scan_decay_floor": lambda value:
        builtin_metrics.train_selective_scan_decay_floor().set(value),
    "diff_attention_lambda_max": lambda value:
        builtin_metrics.train_diff_attention_lambda_max().set(value),
}
