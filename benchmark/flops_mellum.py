"""The benchmark's arithmetic for the ``mellum`` family: model FLOPs a token
by ``flops.py``'s convention, and the operations and bytes that the Pallas
kernels of its step (flash attention with and without a window, grouped
matmul) execute.

The convention is ``flops.py``'s: 6 per parameter that sits in a matrix
multiplication a token goes through (2 forward, 4 backward), the input
embedding left out (a lookup), recompute not counted, attention's scores and
weighted sum over the full rectangle of keys a query may see by the model's
definition, causal skipping not credited: S keys in a ``full_attention``
layer and ``min(S, sliding_window)`` in a ``sliding_attention`` layer. A
token goes through its ``num_experts_per_tok`` experts, not all
``num_experts``: there is no shared expert and no dense layer, so the expert
layer is every layer's whole FFN. A configuration that is one chip's share
of a deployment (``deployment.experts_held``) counts the routed experts at
what this chip computes, as ``flops_afmoe.py`` does.

The ``executed`` counts are ``flops_afmoe.py``'s (the tiles of the pair
table once: ``executed_tiles``) at this family's calls a step: the flash
forward kernel once a layer where its outputs are kept for the backward pass
(``keeps_forward``: the full layer at these lengths) and twice where the
block is rematerialised and they are not (a window of 1024 over heads of
128); the grouped matmuls' forward twice where the block is rematerialised,
at the rows the products are given (tokens x experts per token x the share
held).
"""

from __future__ import annotations

from typing import Any, Dict, Optional

import flops_deepseek
from flops_afmoe import (WINDOW_SUFFIX, executed_tiles, flash_call,  # noqa: F401
                         grouped_matmul_call, held_share, least_seconds,
                         router_width)


def layer_counts(config: Dict[str, Any]) -> Dict[str, int]:
    kinds = config["layer_types"][:config["num_hidden_layers"]]
    sliding = sum(kind == "sliding_attention" for kind in kinds)
    return {"layers": len(kinds), "sliding": sliding,
            "full": len(kinds) - sliding}


def attention_params(config: Dict[str, Any]) -> int:
    """Wq, Wk, Wv and Wo of one layer."""
    d, hd = config["hidden_size"], config["head_dim"]
    heads, kv = config["num_attention_heads"], config["num_key_value_heads"]
    return 2 * d * heads * hd + 2 * d * kv * hd


def expert_params(config: Dict[str, Any]) -> int:
    """One expert's SwiGLU."""
    return 3 * config["hidden_size"] * config["moe_intermediate_size"]


def layer_params(config: Dict[str, Any]) -> int:
    """Every parameter of one layer as this chip holds it: attention, the
    router at its whole width, the ``num_experts`` experts the file counts
    (held), and the vectors (two norms of the stream, two of a head)."""
    d = config["hidden_size"]
    return (attention_params(config) + d * router_width(config)
            + config["num_experts"] * expert_params(config)
            + 2 * d + 2 * config["head_dim"])


def held_params(config: Dict[str, Any]) -> int:
    """Every parameter the chip holds: the layers, ``wte``, the head and
    the final norm."""
    d = config["hidden_size"]
    return (layer_counts(config)["layers"] * layer_params(config)
            + 2 * d * config["vocab_size"] + d)


def active_matmul_params(config: Dict[str, Any]) -> float:
    """Matmul parameters one token goes through on this chip in a forward
    pass: in every layer attention, the router and ``num_experts_per_tok`` x
    ``held_share`` experts; and the head."""
    d = config["hidden_size"]
    layer = attention_params(config) + d * router_width(config) \
        + expert_params(config) * config["num_experts_per_tok"] \
        * held_share(config)
    return layer_counts(config)["layers"] * layer + d * config["vocab_size"]


def attention_flops_per_token(config: Dict[str, Any], seq_len: int) -> float:
    """``12 heads head_dim keys`` a layer, keys = S in a full layer and
    min(S, sliding_window) in a window layer."""
    n = layer_counts(config)
    width = config["num_attention_heads"] * config["head_dim"]
    keys = n["full"] * seq_len \
        + n["sliding"] * min(seq_len, config["sliding_window"])
    return 12.0 * width * keys


def model_flops_per_token(config: Dict[str, Any], seq_len: int) -> float:
    """Model FLOPs one token costs in training on this chip."""
    return 6.0 * active_matmul_params(config) \
        + attention_flops_per_token(config, seq_len)


# -- what the kernels execute ----------------------------------------------

def keeps_forward(keys: int, head_dim: int) -> bool:
    """Whether a rematerialised block keeps the flash forward kernel's
    outputs (``ops/flash_attention.py`` ``worth_keeping``: from 32 keys a
    query sees for each of a head's dimensions), so that the backward pass
    does not call it again."""
    return keys >= 32 * head_dim


def step_kernel_calls(config: Dict[str, Any], batch: int, seq_len: int,
                      blk_q: int, blk_k: int, remat: bool,
                      share: Optional[float] = None
                      ) -> Dict[str, Dict[str, float]]:
    """{kernel: {"calls", "flops", "bytes"}} of one training step: calls a
    step and one call's FLOPs and least bytes. A window layer's flash
    kernels under their own names where the window cuts something; the flash
    kernels run every query head against its own copy of K and V, so their
    batch is the query heads'; the forward kernel twice a layer where the
    block is rematerialised and its outputs are not kept
    (``keeps_forward``). ``gmm``: three products forward (twice with remat)
    and the three rows' cotangents a layer; ``tgmm``: the three weights'
    cotangents."""
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
                again = kernel == "flash_fwd" and remat and not \
                    keeps_forward(min(seq_len, w or seq_len), hd)
                out[kernel + suffix] = dict(flash_call(
                    kernel, batch * heads, seq_len, w, hd, blk_q, blk_k),
                    calls=layers * (2 if again else 1))
    one = grouped_matmul_call(config, batch * seq_len, share)
    out["gmm"] = dict(one, calls=n["layers"] * (3 * (2 if remat else 1) + 3))
    out["tgmm"] = dict(one, calls=n["layers"] * 3)
    return out
