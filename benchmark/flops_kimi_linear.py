"""The benchmark's arithmetic for the ``kimi_linear`` family: model FLOPs a
token by ``flops.py``'s convention, and the operations and bytes that the
Pallas kernels of its step (the delta rule's chunked pair, flash attention,
grouped matmul) execute.

The convention is ``flops.py``'s: 6 per parameter that sits in a matrix
multiplication a token goes through (2 forward, 4 backward; the short
convolutions' taps among them), the input embedding left out (a lookup),
recompute not counted, the latent layers' scores and weighted sum over the
full S x S (causal skipping not credited), and the delta rule **as the
literal recurrence**, as ``flops_granite.py`` counts its scan: per head and
element of the [keys, values] state a token decays it (1), reads it with k
(2), writes the difference (2) and reads it with q (2): 7 forward, 21 in
training. The chunked kernels execute more than that (below); what they add
is the program's choice, not the model's need. A configuration that is one
chip's share of a deployment (``deployment.experts_held``) counts the routed
experts at what this chip computes, ``num_experts_per_token * count / of`` a
token under even routing, as ``flops_afmoe.py`` does.

The ``executed`` functions count what a kernel really runs, for a roofline
share, and every call of a step as the step runs them since PR 30: the
flash forward kernel once a layer (its outputs are kept for the backward
pass at these lengths), ``kda_fwd`` and the grouped matmuls' forward twice
where the block is rematerialised.
"""

from __future__ import annotations

import itertools
import math
from typing import Any, Dict, List, Optional

import flops_deepseek
from flops_afmoe import (expert_params, held_share, least_seconds,  # noqa: F401
                         router_width)


def layer_kinds(config: Dict[str, Any]) -> List[tuple]:
    """[(dense?, KDA?)] of the layers that run (the published lists count
    from 1)."""
    kda = set(config["linear_attn_config"]["kda_layers"])
    return [(l <= config["first_k_dense_replace"], l in kda)
            for l in range(1, config["num_hidden_layers"] + 1)]


def layer_counts(config: Dict[str, Any]) -> Dict[str, int]:
    kinds = layer_kinds(config)
    return {"dense": sum(d for d, _ in kinds),
            "moe": sum(not d for d, _ in kinds),
            "kda": sum(k for _, k in kinds),
            "mla": sum(not k for _, k in kinds)}


def longest_kda_run(config: Dict[str, Any]) -> int:
    """Layers in the longest run of one kind of KDA layer: one scan, so one
    instruction a kernel call site."""
    return max((len(list(run)) for (_, kda), run in itertools.groupby(
        layer_kinds(config)) if kda), default=0)


def kda_params(config: Dict[str, Any]) -> int:
    """One KDA layer's mixer: Wq, Wk, Wv and their taps, the two low-rank
    pairs (decay, output gate), W_beta and Wo."""
    d, linear = config["hidden_size"], config["linear_attn_config"]
    heads, hd = linear["num_heads"], linear["head_dim"]
    wide = heads * hd
    return (3 * d * wide + 3 * linear["short_conv_kernel_size"] * wide
            + 2 * (d * hd + hd * wide) + d * heads + wide * d)


def _ffn_params(config: Dict[str, Any], routed: float):
    """(a dense layer's FFN, an expert layer's with ``routed`` routed
    experts counted)."""
    d = config["hidden_size"]
    return 3 * d * config["intermediate_size"], \
        d * router_width(config) + expert_params(config) * (
            config["num_shared_experts"] + routed)


def active_matmul_params(config: Dict[str, Any]) -> float:
    """Matmul parameters one token goes through on this chip in a forward
    pass: the mixer of every layer, the dense SwiGLU in the leading layers,
    in the others the router, the shared expert and
    ``num_experts_per_token`` x ``held_share`` routed experts, and the
    head."""
    n = layer_counts(config)
    dense, moe = _ffn_params(
        config, config["num_experts_per_token"] * held_share(config))
    return (n["kda"] * kda_params(config)
            + n["mla"] * flops_deepseek.attention_params(config)
            + n["dense"] * dense + n["moe"] * moe
            + config["hidden_size"] * config["vocab_size"])


def held_params(config: Dict[str, Any]) -> int:
    """Every parameter the chip holds in a matrix: the layers with
    ``num_experts`` (held) experts each, ``wte`` and the head."""
    n = layer_counts(config)
    dense, moe = _ffn_params(config, config["num_experts"])
    return (n["kda"] * kda_params(config)
            + n["mla"] * flops_deepseek.attention_params(config)
            + n["dense"] * dense + n["moe"] * moe
            + 2 * config["hidden_size"] * config["vocab_size"])


def recurrence_flops_per_token(config: Dict[str, Any]) -> float:
    """The literal delta rule of one KDA layer in training: 21 per head and
    element of the state."""
    linear = config["linear_attn_config"]
    return 21.0 * linear["num_heads"] * linear["head_dim"] ** 2


def model_flops_per_token(config: Dict[str, Any], seq_len: int) -> float:
    """Model FLOPs one token costs in training on this chip: 6 per active
    matmul parameter, the latent layers' attention ``6 heads (qk + v) S``,
    the KDA layers' recurrences."""
    n = layer_counts(config)
    qk = config["qk_nope_head_dim"] + config["qk_rope_head_dim"]
    return (6.0 * active_matmul_params(config)
            + 6.0 * n["mla"] * config["num_attention_heads"]
            * (qk + config["v_head_dim"]) * seq_len
            + n["kda"] * recurrence_flops_per_token(config))


# -- what the kernels execute ----------------------------------------------

def kda_call(kernel: str, config: Dict[str, Any], batch: int, seq_len: int,
             chunk: int, itemsize: int = 2) -> Dict[str, float]:
    """FLOPs and least HBM bytes of one call of ``kda_fwd`` or ``kda_bwd``
    (``ray_tpu/ops/kda.py``) on [batch, seq_len] tokens, heads of K keys and
    V = K values, chunks of L.

    Per chunk and head the forward makes, at each of the log2 L levels, the
    decayed q.k and k.k products of that level's pairs as two whole [L, K]
    x [K, L] products (2 x 2 L L K) and the two [L, L] x [L, L] products
    that double the triangular inverse (2 x 2 L L L, float32, counted once
    each whatever passes the unit makes), then k and q on the entry state
    and the exit state's update (3 x 2 L K V) and the inverse on the
    right-hand side and the q.k matrix on the pseudo-values (2 x 2 L L V).
    The backward differentiates the same function: the forward's products
    again and two transposed products for each, 3 x the forward.

    Bytes: each operand read and each result written once. Forward: q, k,
    v and o, the float32 running log-decay (K a token and head) and beta,
    and the chunks' entry states (float32 [V, K]). Backward: those inputs
    and the states, dO, and the cotangents of q, k, v, the running
    log-decay and beta."""
    linear = config["linear_attn_config"]
    heads, width = linear["num_heads"], linear["head_dim"]
    chunks = batch * seq_len // chunk * heads
    tokens = batch * seq_len * heads
    levels = int(math.log2(chunk))
    forward = (levels * (4.0 * chunk * chunk * width + 4.0 * chunk ** 3)
               + 6.0 * chunk * width * width + 4.0 * chunk * chunk * width)
    narrow, decay = tokens * width * itemsize, tokens * (width + 1) * 4
    states = chunks * width * width * 4
    if kernel == "kda_fwd":
        return {"flops": chunks * forward,
                "bytes": float(4 * narrow + decay + states)}
    if kernel == "kda_bwd":
        return {"flops": chunks * 3.0 * forward,
                "bytes": float(7 * narrow + 2 * decay + states)}
    raise ValueError(f"no such kernel: {kernel!r}")


def grouped_matmul_call(config: Dict[str, Any], tokens: int,
                        share: Optional[float] = None) -> Dict[str, float]:
    """FLOPs and least HBM bytes of one grouped product of an expert layer
    (``gmm``, or ``tgmm`` for the weights' cotangent): ``2 rows d f`` with
    rows = tokens x ``num_experts_per_token`` x ``share`` (the share of the
    assignments that fall on held experts: ``held_share`` under even
    routing, or what the program's counters measured); bytes: the rows'
    operand and result and the held experts' weights once."""
    d, f = config["hidden_size"], config["moe_intermediate_size"]
    share = held_share(config) if share is None else share
    rows = tokens * config["num_experts_per_token"] * share
    return {"flops": 2.0 * rows * d * f,
            "bytes": float(rows * (d + f) * 2
                           + config["num_experts"] * d * f * 2)}


def step_kernel_calls(config: Dict[str, Any], batch: int, seq_len: int,
                      chunk: int, blk_q: int, blk_k: int, remat: bool,
                      share: Optional[float] = None
                      ) -> Dict[str, Dict[str, float]]:
    """{kernel: {"calls", "flops", "bytes"}} of one training step: calls a
    step and one call's FLOPs and least bytes. ``kda_fwd`` twice a KDA
    layer with remat, ``kda_bwd`` once; the three flash kernels once a
    latent layer; ``gmm``: three products forward (twice with remat) and
    the three rows' cotangents; ``tgmm``: the three weights' cotangents."""
    n = layer_counts(config)
    again = 2 if remat else 1
    heads = config["num_attention_heads"]
    d_qk = config["qk_nope_head_dim"] + config["qk_rope_head_dim"]
    out = {}
    if n["kda"]:
        out["kda_fwd"] = dict(kda_call("kda_fwd", config, batch, seq_len,
                                       chunk), calls=n["kda"] * again)
        out["kda_bwd"] = dict(kda_call("kda_bwd", config, batch, seq_len,
                                       chunk), calls=n["kda"])
    if n["mla"]:
        for kernel in flops_deepseek.FLASH_PRODUCTS:
            out[kernel] = dict(flops_deepseek.flash_call(
                kernel, batch * heads, seq_len, d_qk, config["v_head_dim"],
                blk_q, blk_k), calls=n["mla"])
    if n["moe"]:
        one = grouped_matmul_call(config, batch * seq_len, share)
        out["gmm"] = dict(one, calls=n["moe"] * (3 * again + 3))
        out["tgmm"] = dict(one, calls=n["moe"] * 3)
    return out
