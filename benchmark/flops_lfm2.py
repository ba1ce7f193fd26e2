"""The benchmark's arithmetic for the ``lfm2_moe`` family: model FLOPs a
token by ``flops.py``'s convention, and the operations and bytes that the
Pallas kernels of its step (the gated short convolution's pair, flash
attention, grouped matmul) execute.

The convention is ``flops.py``'s: 6 per parameter that sits in a matrix
multiplication a token goes through (2 forward, 4 backward; the short
convolution's taps among them, as ``flops_kimi_linear.py`` counts Kimi's),
the input embedding left out (a lookup; the tied table counts once, as the
head), recompute not counted, attention's scores and weighted sum over the
full S x S (causal skipping not credited). The convolution's two gates are
elementwise and count nothing. A configuration that is one chip's share of a
deployment (``deployment.experts_held``) counts the routed experts at what
this chip computes, ``num_experts_per_tok * count / of`` a token under even
routing, as ``flops_afmoe.py`` does; there is no shared expert.

The layers that run are ``num_hidden_layers`` of the published
``layer_types`` from ``deployment.layers_run.first`` on (0 where the file
does not say), the first ``num_dense_layers`` of them dense.

The ``executed`` functions count what a kernel really runs, for a roofline
share, and every call of a step as the step runs them: the flash forward
kernel once a layer where its outputs are kept for the backward pass
(``flash_attention.worth_keeping``), ``short_conv_fwd`` and the grouped
matmuls' forward twice where the block is rematerialised.
"""

from __future__ import annotations

from typing import Any, Dict, List, Optional

import flops_deepseek
from flops_afmoe import (expert_params, grouped_matmul_call,  # noqa: F401
                         held_share, least_seconds, router_width)


def layer_kinds(config: Dict[str, Any]) -> List[tuple]:
    """[(dense?, convolution?)] of the layers that run."""
    first = config.get("deployment", {}).get("layers_run", {}).get("first", 0)
    kinds = config["layer_types"][first:first + config["num_hidden_layers"]]
    return [(i < config["num_dense_layers"], kind == "conv")
            for i, kind in enumerate(kinds)]


def layer_counts(config: Dict[str, Any]) -> Dict[str, int]:
    kinds = layer_kinds(config)
    return {"dense": sum(d for d, _ in kinds),
            "moe": sum(not d for d, _ in kinds),
            "conv": sum(c for _, c in kinds),
            "attention": sum(not c for _, c in kinds)}


def conv_params(config: Dict[str, Any]) -> int:
    """One convolution layer's mixer: the projection to the three chunks,
    the taps, and the projection back."""
    d = config["hidden_size"]
    return 3 * d * d + config["conv_L_cache"] * d + d * d


def attention_params(config: Dict[str, Any]) -> int:
    """Wq, Wk, Wv and Wo of one layer; a head is hidden_size over the
    query heads."""
    d = config["hidden_size"]
    kv = d // config["num_attention_heads"] * config["num_key_value_heads"]
    return 2 * d * d + 2 * d * kv


def _ffn_params(config: Dict[str, Any], routed: float):
    """(a dense layer's FFN, an expert layer's with ``routed`` routed
    experts counted)."""
    d = config["hidden_size"]
    return 3 * d * config["intermediate_size"], \
        d * router_width(config) + expert_params(config) * routed


def _params(config: Dict[str, Any], routed: float) -> float:
    n = layer_counts(config)
    dense, moe = _ffn_params(config, routed)
    return (n["conv"] * conv_params(config)
            + n["attention"] * attention_params(config)
            + n["dense"] * dense + n["moe"] * moe
            + config["hidden_size"] * config["vocab_size"])


def active_matmul_params(config: Dict[str, Any]) -> float:
    """Matmul parameters one token goes through on this chip in a forward
    pass: the mixer of every layer, the dense SwiGLU in the leading layers,
    in the others the router and ``num_experts_per_tok`` x ``held_share``
    routed experts, and the head (the tied table, once)."""
    return _params(config,
                   config["num_experts_per_tok"] * held_share(config))


def held_params(config: Dict[str, Any]) -> int:
    """Every parameter the chip holds in a matrix: the layers with
    ``num_experts`` (held) experts each, and the one table."""
    return _params(config, config["num_experts"])


def model_flops_per_token(config: Dict[str, Any], seq_len: int) -> float:
    """Model FLOPs one token costs in training on this chip: 6 per active
    matmul parameter plus attention, ``12 hidden_size S`` a layer (32 heads
    of 64 are the hidden size)."""
    return 6.0 * active_matmul_params(config) + 12.0 * \
        layer_counts(config)["attention"] * config["hidden_size"] * seq_len


# -- what the kernels execute ----------------------------------------------

def short_conv_call(kernel: str, config: Dict[str, Any], batch: int,
                    seq_len: int, itemsize: int = 2) -> Dict[str, float]:
    """FLOPs and least HBM bytes of one call of ``short_conv_fwd`` or
    ``short_conv_bwd`` (``ray_tpu/ops/short_conv.py``) on [batch, seq_len]
    tokens of d channels with K taps.

    Forward, a token and channel: the gate before (1), K products and
    K - 1 sums, the gate after (1). Backward: the same again for ``z``,
    ``dy * C`` and ``dy * z`` (2), the K taps of the way back (2 K - 1), the
    two products by it (2), and the taps' cotangent (2 K).

    Bytes: each operand read and each result written once. Forward: the
    three chunks and y. Backward: the three chunks and dy, the three
    chunks' cotangent. The taps, their cotangent and the few rows a tile
    reads of its neighbours are left out: under 2 % of it."""
    d, taps = config["hidden_size"], config["conv_L_cache"]
    cells = float(batch * seq_len * d)
    if kernel == "short_conv_fwd":
        return {"flops": cells * (2 * taps + 1),
                "bytes": cells * 4 * itemsize}
    if kernel == "short_conv_bwd":
        return {"flops": cells * (6 * taps + 4),
                "bytes": cells * 7 * itemsize}
    raise ValueError(f"no such kernel: {kernel!r}")


def step_kernel_calls(config: Dict[str, Any], batch: int, seq_len: int,
                      blk_q: int, blk_k: int, remat: bool,
                      flash_kept: bool = True,
                      share: Optional[float] = None
                      ) -> Dict[str, Dict[str, float]]:
    """{kernel: {"calls", "flops", "bytes"}} of one training step: calls a
    step and one call's FLOPs and least bytes. ``short_conv_fwd`` twice a
    convolution layer with remat, ``short_conv_bwd`` once; the flash
    kernels once an attention layer, the forward twice where remat runs it
    again (``flash_kept`` false); every query head against its own copy of K
    and V, so their batch is the query heads'. ``gmm``: three products
    forward (twice with remat) and the three rows' cotangents; ``tgmm``: the
    three weights' cotangents."""
    n = layer_counts(config)
    again = 2 if remat else 1
    heads = config["num_attention_heads"]
    width = config["hidden_size"] // heads
    out = {}
    if n["conv"]:
        out["short_conv_fwd"] = dict(short_conv_call(
            "short_conv_fwd", config, batch, seq_len), calls=n["conv"] * again)
        out["short_conv_bwd"] = dict(short_conv_call(
            "short_conv_bwd", config, batch, seq_len), calls=n["conv"])
    if n["attention"]:
        for kernel in flops_deepseek.FLASH_PRODUCTS:
            forward_again = kernel == "flash_fwd" and remat and not flash_kept
            out[kernel] = dict(flops_deepseek.flash_call(
                kernel, batch * heads, seq_len, width, width, blk_q, blk_k),
                calls=n["attention"] * (2 if forward_again else 1))
    if n["moe"]:
        one = grouped_matmul_call(config, batch * seq_len, share)
        out["gmm"] = dict(one, calls=n["moe"] * (3 * again + 3))
        out["tgmm"] = dict(one, calls=n["moe"] * 3)
    return out
