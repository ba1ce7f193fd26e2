"""The benchmark's arithmetic for the ``afmoe`` family: model FLOPs a token by
``flops.py``'s convention, and the operations and bytes that the Pallas
kernels of its step (flash attention with and without a window, grouped
matmul) execute.

The convention is ``flops.py``'s: 6 per parameter that sits in a matrix
multiplication a token goes through (2 forward, 4 backward), the input
embedding left out (a lookup), recompute not counted, attention's scores and
weighted sum over the full rectangle of keys a query may see by the model's
definition, causal skipping not credited: S keys in a ``full_attention``
layer and ``min(S, sliding_window)`` in a ``sliding_attention`` layer, whose
definition has no more. A configuration that is one chip's share of a
deployment (``deployment.experts_held``: ``count`` of ``of`` experts) counts
the routed experts **at what this chip computes**: a token makes
``num_experts_per_tok`` assignments over all the experts, of which under
even routing ``count / of`` fall on the experts held here, so
``num_experts_per_tok * count / of`` routed experts a token (0.125 for 8 of
256 with 4 a token), beside the shared expert, the router at its whole width
and the slice of the head. Counting all ``num_experts_per_tok`` would credit
work that other chips do.

The ``executed`` functions count what a kernel really runs, for a roofline
share: the tiles of the pair table once (``executed_tiles``: those above the
diagonal and those wholly behind the window are skipped), every call of a
step (the flash forward kernel once a layer, its outputs being kept for the
backward pass at these lengths; the grouped matmuls' forward twice where the
block is rematerialised), and the grouped matmuls' rows at this chip's share
of the assignments.
"""

from __future__ import annotations

from typing import Any, Dict, Optional

import flops_deepseek

#: Kernels of a window layer carry this suffix in the trace.
WINDOW_SUFFIX = "_win"


def layer_kinds(config: Dict[str, Any]):
    """[(dense?, sliding?)] of the layers that run."""
    kinds = config["layer_types"][:config["num_hidden_layers"]]
    return [(i < config["num_dense_layers"], kind == "sliding_attention")
            for i, kind in enumerate(kinds)]


def layer_counts(config: Dict[str, Any]) -> Dict[str, int]:
    kinds = layer_kinds(config)
    return {"dense": sum(d for d, _ in kinds),
            "moe": sum(not d for d, _ in kinds),
            "sliding": sum(s for _, s in kinds),
            "full": sum(not s for _, s in kinds)}


def held_share(config: Dict[str, Any]) -> float:
    """The share of a layer's experts that live here: ``count / of`` of
    ``deployment.experts_held``, 1 where the configuration holds them all."""
    held = config.get("deployment", {}).get("experts_held")
    return held["count"] / held["of"] if held else 1.0


def attention_params(config: Dict[str, Any]) -> int:
    """Wq, Wk, Wv, the gate's projection and Wo of one layer."""
    d, hd = config["hidden_size"], config["head_dim"]
    heads, kv = config["num_attention_heads"], config["num_key_value_heads"]
    return 3 * d * heads * hd + 2 * d * kv * hd


def expert_params(config: Dict[str, Any]) -> int:
    """One expert's SwiGLU (routed or shared)."""
    return 3 * config["hidden_size"] * config["moe_intermediate_size"]


def router_width(config: Dict[str, Any]) -> int:
    held = config.get("deployment", {}).get("experts_held")
    return held["of"] if held else config["num_experts"]


def active_matmul_params(config: Dict[str, Any]) -> float:
    """Matmul parameters one token goes through on this chip in a forward
    pass (the module text): attention in every layer, the dense SwiGLU in
    the leading layers, in the others the router, the shared experts and
    ``num_experts_per_tok`` x ``held_share`` routed experts, and the head."""
    d, n = config["hidden_size"], layer_counts(config)
    moe = d * router_width(config) + expert_params(config) * (
        config["num_shared_experts"]
        + config["num_experts_per_tok"] * held_share(config))
    return ((n["dense"] + n["moe"]) * attention_params(config)
            + n["dense"] * 3 * d * config["intermediate_size"]
            + n["moe"] * moe + d * config["vocab_size"])


def held_params(config: Dict[str, Any]) -> int:
    """Every parameter the chip holds, vectors left out: the matrices of
    the layers with ``num_experts`` (held) experts each, ``wte`` and the
    head."""
    d, n = config["hidden_size"], layer_counts(config)
    moe = d * router_width(config) + expert_params(config) * (
        config["num_shared_experts"] + config["num_experts"])
    return ((n["dense"] + n["moe"]) * attention_params(config)
            + n["dense"] * 3 * d * config["intermediate_size"]
            + n["moe"] * moe + 2 * d * config["vocab_size"])


def model_flops_per_token(config: Dict[str, Any], seq_len: int) -> float:
    """Model FLOPs one token costs in training on this chip: 6 per active
    matmul parameter plus attention, ``12 heads head_dim keys`` a layer,
    keys = S in a full layer and min(S, sliding_window) in a window layer."""
    n = layer_counts(config)
    width = config["num_attention_heads"] * config["head_dim"]
    keys = n["full"] * seq_len \
        + n["sliding"] * min(seq_len, config["sliding_window"])
    return 6.0 * active_matmul_params(config) + 12.0 * width * keys


# -- what the kernels execute ----------------------------------------------

def executed_tiles(seq_len: int, window: Optional[int], blk_q: int,
                   blk_k: int) -> int:
    """Tiles of a causal S x S grid, under a ``window`` if one is given, that
    hold at least one allowed pair (key j <= query i, and i - j < window):
    counted pair of tiles by pair of tiles from their corners."""
    count = 0
    for qi in range(seq_len // blk_q):
        first_row, last_row = qi * blk_q, (qi + 1) * blk_q - 1
        for ki in range(seq_len // blk_k):
            first_col, last_col = ki * blk_k, (ki + 1) * blk_k - 1
            if first_col > last_row:
                continue  # above the diagonal
            if window is not None and first_row - last_col >= window:
                continue  # wholly behind the window
            count += 1
    return count


def flash_call(kernel: str, batch_heads: int, seq_len: int,
               window: Optional[int], head_dim: int, blk_q: int, blk_k: int,
               itemsize: int = 2) -> Dict[str, float]:
    """FLOPs and least HBM bytes of one call of a flash kernel (``kernel``
    without the window's suffix): the executed tiles' products
    (``flops_deepseek.FLASH_PRODUCTS``); bytes as
    ``flops_deepseek.flash_call`` counts them, each operand read and each
    result written once."""
    on_d, on_dv = flops_deepseek.FLASH_PRODUCTS[kernel]
    tiles = executed_tiles(seq_len, window, blk_q, blk_k)
    bytes_ = flops_deepseek.flash_call(
        kernel, batch_heads, seq_len, head_dim, head_dim, blk_q, blk_k,
        itemsize)["bytes"]
    return {"flops": batch_heads * tiles * 2.0 * blk_q * blk_k
            * (on_d + on_dv) * head_dim, "bytes": bytes_}


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
                           + config["num_experts"] * d * f * 2)}


def step_kernel_calls(config: Dict[str, Any], batch: int, seq_len: int,
                      blk_q: int, blk_k: int, remat: bool,
                      share: Optional[float] = None
                      ) -> Dict[str, Dict[str, float]]:
    """{kernel: {"calls", "flops", "bytes"}} of one training step: calls a
    step and one call's FLOPs and least bytes. A window layer's flash
    kernels under their own names where the window cuts something (a
    window the sequence does not reach is causal attention, by the causal
    kernels); the flash kernels run every query head against its own copy
    of K and V, so their batch is the query heads'. ``gmm``: three products
    forward (twice with remat) and the three rows' cotangents; ``tgmm``: the
    three weights' cotangents."""
    n = layer_counts(config)
    heads, hd = config["num_attention_heads"], config["head_dim"]
    window = config["sliding_window"]
    cuts = window < seq_len
    out = {}
    for kernel in flops_deepseek.FLASH_PRODUCTS:
        for suffix, layers, w in (
                (WINDOW_SUFFIX, n["sliding"] if cuts else 0, window),
                ("", n["full"] + (0 if cuts else n["sliding"]), None)):
            if layers:
                out[kernel + suffix] = dict(flash_call(
                    kernel, batch * heads, seq_len, w, hd, blk_q, blk_k),
                    calls=layers)
    if n["moe"]:
        one = grouped_matmul_call(config, batch * seq_len, share)
        out["gmm"] = dict(one, calls=n["moe"] * (3 * (2 if remat else 1) + 3))
        out["tgmm"] = dict(one, calls=n["moe"] * 3)
    return out


def least_seconds(call: Dict[str, float], peak_flops: float,
                  peak_bytes: float) -> float:
    """The least time the chip could take for one call: the larger of its
    FLOPs over the peak and its bytes over the bandwidth."""
    return max(call["flops"] / peak_flops, call["bytes"] / peak_bytes)
