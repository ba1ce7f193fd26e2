"""The benchmark's arithmetic for the ``glm_moe_dsa`` family: model FLOPs a
token by ``flops.py``'s convention, the parameters a chip holds and the
whole published model's, and the operations and bytes that the Pallas
kernels of its step (attention over the selection: three kernels and the
head-summed probabilities; grouped matmul) execute at the least.

The convention is ``flops.py``'s: 6 per parameter that sits in a matrix
multiplication a token goes through (2 forward, 4 backward), the input
embedding left out (a lookup), recompute not counted. Two things depart from
the other families' count, both so that the figure reads the same work
whichever way the program computes it:

* the main attention is counted over the **selected** pairs, ``sum_t min(t
  + 1, index_topk)``, not over S x S: the model attends over no others. A
  program that masks whole causal tiles executes more than that, one that
  gathers the selected keys executes that; both are held to this count.
* the indexer's scores are counted over the **causal** pairs (``S (S + 1) /
  2`` times ``2 index_n_heads index_head_dim``): every causal pair has to be
  scored before any is chosen. Its ReLU, weights and sum over heads, the
  top-k and the loss are not matrix products and count nothing.

A configuration that is one chip's share of a deployment
(``deployment.experts_held``) counts the routed experts at what this chip
computes, ``num_experts_per_tok * count / of`` a token under even routing,
as ``flops_afmoe.py`` does.

The kernels' ``least`` FLOPs and bytes are for a roofline share: a call's
selected pairs times its products, its operands read and results written
once (the selection's S x S bytes among them, once a batch row).
"""

from __future__ import annotations

import itertools
from typing import Any, Dict, List, Optional

from flops_afmoe import (expert_params, held_share,  # noqa: F401
                         least_seconds)


def layers_run(config: Dict[str, Any]) -> List[int]:
    return list(config.get("layers_run",
                           range(config["num_hidden_layers"])))


def layer_kinds(config: Dict[str, Any]) -> List[tuple]:
    """[(dense?, owns an indexer?)] of the layers that run."""
    return [(l < config["first_k_dense_replace"],
             config["indexer_types"][l] == "full")
            for l in layers_run(config)]


def layer_counts(config: Dict[str, Any]) -> Dict[str, int]:
    kinds = layer_kinds(config)
    return {"layers": len(kinds), "dense": sum(d for d, _ in kinds),
            "moe": sum(not d for d, _ in kinds),
            "full": sum(f for _, f in kinds)}


def longest_run(config: Dict[str, Any]) -> int:
    """Layers in the longest run of one kind: one scan, so one instruction
    a kernel call site, and the busiest of a kernel's name."""
    return max(len(list(run)) for _, run in itertools.groupby(
        layer_kinds(config)))


def attention_params(config: Dict[str, Any]) -> int:
    """W_qa, W_qb, W_kva, W_kvb and W_o of one layer."""
    d, h = config["hidden_size"], config["num_attention_heads"]
    nope, rope = config["qk_nope_head_dim"], config["qk_rope_head_dim"]
    rank, v = config["kv_lora_rank"], config["v_head_dim"]
    q_rank = config["q_lora_rank"]
    return (d * q_rank + q_rank * h * (nope + rope) + d * (rank + rope)
            + rank * h * (nope + v) + h * v * d)


def indexer_params(config: Dict[str, Any]) -> int:
    """W_Iq, W_Ik and W_Iw of one layer that owns an indexer."""
    heads, width = config["index_n_heads"], config["index_head_dim"]
    return (config["q_lora_rank"] * heads * width
            + config["hidden_size"] * (width + heads))


def router_width(config: Dict[str, Any]) -> int:
    held = config.get("deployment", {}).get("experts_held")
    return held["of"] if held else config["n_routed_experts"]


def _ffn_params(config: Dict[str, Any], routed: float):
    """(a dense layer's FFN, an expert layer's with ``routed`` routed
    experts counted)."""
    d = config["hidden_size"]
    return 3 * d * config["intermediate_size"], \
        d * router_width(config) + expert_params(config) * (
            config["n_shared_experts"] + routed)


def active_matmul_params(config: Dict[str, Any]) -> float:
    """Matmul parameters one token goes through on this chip in a forward
    pass: the attention of every layer, the indexer of those that own one,
    the dense SwiGLU in the leading layers, in the others the router, the
    shared expert and ``num_experts_per_tok`` x ``held_share`` routed
    experts, and the head."""
    n = layer_counts(config)
    dense, moe = _ffn_params(
        config, config["num_experts_per_tok"] * held_share(config))
    return (n["layers"] * attention_params(config)
            + n["full"] * indexer_params(config)
            + n["dense"] * dense + n["moe"] * moe
            + config["hidden_size"] * config["vocab_size"])


def held_params(config: Dict[str, Any]) -> int:
    """Every parameter the chip holds in a matrix: the layers with
    ``n_routed_experts`` (held) experts each, ``wte`` and the head."""
    n = layer_counts(config)
    dense, moe = _ffn_params(config, config["n_routed_experts"])
    return (n["layers"] * attention_params(config)
            + n["full"] * indexer_params(config)
            + n["dense"] * dense + n["moe"] * moe
            + 2 * config["hidden_size"] * config["vocab_size"])


def published_params(config: Dict[str, Any]) -> int:
    """The whole published model by the same count: every key a file cut
    (``reduced``) at its published value, every published layer, and the
    multi-token prediction modules (each a projection of two hidden states
    onto one and a whole expert layer sharing a selection)."""
    whole = dict(config, **{key: cut["published"] for key, cut in
                            config.get("reduced", {}).items()})
    whole.pop("layers_run", None)
    whole.pop("deployment", None)
    d = whole["hidden_size"]
    _, moe = _ffn_params(whole, whole["n_routed_experts"])
    return held_params(whole) + whole["num_nextn_predict_layers"] * (
        2 * d * d + attention_params(whole) + moe)


def selected_pairs(seq_len: int, topk: int) -> int:
    """``sum_t min(t + 1, topk)`` over a sequence's rows."""
    full = min(topk, seq_len)
    return full * (full + 1) // 2 + (seq_len - full) * full


def causal_pairs(seq_len: int) -> int:
    return seq_len * (seq_len + 1) // 2


def selected_share(seq_len: int, topk: int) -> float:
    """What ``dsa.selected_share`` has to read."""
    return selected_pairs(seq_len, topk) / causal_pairs(seq_len)


def flops_by_part(config: Dict[str, Any], seq_len: int) -> Dict[str, float]:
    """Model FLOPs a token in training, by part."""
    n = layer_counts(config)
    d, heads = config["hidden_size"], config["num_attention_heads"]
    qk = config["qk_nope_head_dim"] + config["qk_rope_head_dim"]
    dense, moe = _ffn_params(
        config, config["num_experts_per_tok"] * held_share(config))
    selected = selected_pairs(seq_len, config["index_topk"]) / seq_len
    causal = causal_pairs(seq_len) / seq_len
    return {
        "attention_projections": 6.0 * n["layers"] * attention_params(config),
        "attention_over_selection": 6.0 * n["layers"] * heads
        * (qk + config["v_head_dim"]) * selected,
        "indexer_projections": 6.0 * n["full"] * indexer_params(config),
        "indexer_scores": 6.0 * n["full"] * config["index_n_heads"]
        * config["index_head_dim"] * causal,
        "dense_ffn": 6.0 * n["dense"] * dense,
        "expert_ffn": 6.0 * n["moe"] * moe,
        "head": 6.0 * d * config["vocab_size"],
    }


def model_flops_per_token(config: Dict[str, Any], seq_len: int) -> float:
    return sum(flops_by_part(config, seq_len).values())


# -- what the kernels execute at the least ----------------------------------

#: Per selected pair, in units of 2: how many products over the q/k head
#: size (D) and over the v head size (Dv) each kernel makes.
PRODUCTS = {"dsa_fwd": (1, 1),       # q k^T | p v
            "dsa_bwd_dq": (2, 1),    # q k^T, ds k | dO v^T
            "dsa_bwd_dkv": (2, 2),   # q k^T, ds^T q | p^T dO, dO v^T
            "dsa_probs": (1, 0)}     # q k^T


def attention_call(kernel: str, config: Dict[str, Any], batch: int,
                   seq_len: int, itemsize: int = 2) -> Dict[str, float]:
    """FLOPs and least HBM bytes of one call of a kernel of
    ``ray_tpu/ops/dsa.py``: its products over the selected pairs of every
    head; each operand read and each result written once (q, k, v and for
    the backward kernels dO, the outputs, the selection's S x S bytes a
    batch row, and ``dsa_probs``' float32 S x S result; the float32 row
    vectors are left out)."""
    on_d, on_dv = PRODUCTS[kernel]
    heads = config["num_attention_heads"]
    d_qk = config["qk_nope_head_dim"] + config["qk_rope_head_dim"]
    d_v = config["v_head_dim"]
    pairs = selected_pairs(seq_len, config["index_topk"])
    rows = batch * heads * seq_len * itemsize
    arrays = {"dsa_fwd": 2 * d_qk + 2 * d_v,             # q k | v o
              "dsa_bwd_dq": 3 * d_qk + 2 * d_v,          # q k dq | v dO
              "dsa_bwd_dkv": 3 * d_qk + 3 * d_v,         # q k dk | v dO dv
              "dsa_probs": 2 * d_qk}[kernel]             # q k
    square = batch * seq_len * seq_len
    return {"flops": batch * heads * pairs * 2.0 * (on_d * d_qk
                                                    + on_dv * d_v),
            "bytes": float(rows * arrays + square
                           + (4 * square if kernel == "dsa_probs" else 0))}


def keeps_forward(config: Dict[str, Any], seq_len: int) -> bool:
    """``ops/flash_attention.worth_keeping``'s rule: the forward kernel's
    outputs are kept for the backward pass from S = 32 Dv on."""
    return seq_len >= 32 * config["v_head_dim"]


def grouped_matmul_call(config: Dict[str, Any], tokens: int,
                        share: Optional[float] = None) -> Dict[str, float]:
    """FLOPs and least HBM bytes of one grouped product of an expert layer
    (``gmm``, or ``tgmm`` for the weights' cotangent): ``2 rows d f`` with
    rows = tokens x ``num_experts_per_tok`` x ``share`` (the share of the
    assignments that fall on held experts: ``held_share`` under even
    routing, or what the program's counters measured); bytes: the rows'
    operand and result and the held experts' weights once."""
    d, f = config["hidden_size"], config["moe_intermediate_size"]
    share = held_share(config) if share is None else share
    rows = tokens * config["num_experts_per_tok"] * share
    return {"flops": 2.0 * rows * d * f,
            "bytes": float(rows * (d + f) * 2
                           + config["n_routed_experts"] * d * f * 2)}


def step_kernel_calls(config: Dict[str, Any], batch: int, seq_len: int,
                      remat: bool, share: Optional[float] = None
                      ) -> Dict[str, Dict[str, float]]:
    """{kernel: {"calls", "flops", "bytes"}} of one training step: calls a
    step and one call's FLOPs and least bytes. ``dsa_fwd`` once a layer,
    twice where the block is rematerialised and its outputs are not kept
    (``keeps_forward``); the two backward kernels once a layer;
    ``dsa_probs`` once a layer that owns an indexer, twice with remat (the
    indexer's loss is part of the block, and its gradient reads p again);
    ``gmm``: three products forward (twice with remat) and the three rows'
    cotangents; ``tgmm``: the three weights' cotangents."""
    n = layer_counts(config)
    again = 2 if remat else 1
    forward = again if not keeps_forward(config, seq_len) else 1
    calls = {"dsa_fwd": n["layers"] * forward, "dsa_bwd_dq": n["layers"],
             "dsa_bwd_dkv": n["layers"], "dsa_probs": n["full"] * again}
    out = {kernel: dict(attention_call(kernel, config, batch, seq_len),
                        calls=count) for kernel, count in calls.items()}
    if n["moe"]:
        one = grouped_matmul_call(config, batch * seq_len, share)
        out["gmm"] = dict(one, calls=n["moe"] * (3 * again + 3))
        out["tgmm"] = dict(one, calls=n["moe"] * 3)
    return out
