"""The benchmark's arithmetic for the ``granitemoehybrid_moe`` family
(``granitemoehybrid`` with ``num_local_experts`` > 0: granite-4.0-h-small):
parameters held and published, model FLOPs a token by ``flops.py``'s
convention, and the operations and bytes that every Pallas kernel of its
step executes (the chunked state-space scan, its conv and its gate-norm,
flash attention, the grouped products of the SwiGLU experts, the share's way
back to tokens).

The convention is ``flops.py``'s: 6 per parameter that sits in a matrix
multiplication a token goes through (2 forward, 4 backward), the input
embedding left out (a lookup; the tied table counts once, as the head),
recompute not counted, attention's scores and weighted sum over the full S x
S (causal skipping not credited), the state-space recurrence as the literal
one (``flops_granite.py``: 15 per head channel and state element). In every
layer a token goes through its mixer (``flops_granite.py``'s count), the
shared SwiGLU, the router at its whole width and ``num_experts_per_tok`` x
``held_share`` routed experts of three matrices each: a configuration that
is one chip's share of a deployment (``deployment.experts_held``) counts the
routed experts at what this chip computes, its 10 experts' share held here
and not all 10, as ``flops_afmoe.py`` does. The conv, norms and gates are
left out, as GPT-J's biases and norms are.

The ``executed`` counts are of what a kernel really runs, for a roofline
share: every product of every grid step, causal flash tiles once, the
grouped products at the rows this chip computed, the conv's and the
gate-norm's passes by the arrays they move, and every call of a step: a
forward kernel twice where its layer is rematerialised and its outputs are
not kept.
"""

from __future__ import annotations

from typing import Any, Dict, Optional

import flops_deepseek
import flops_granite
from flops_afmoe import held_share, least_seconds  # noqa: F401
from flops_granite import (attention_params, layer_counts, mamba_params,
                           mlp_params, scan_flops_per_token)
from flops_nemotron_h import keeps_forward, rows_to_tokens_call


def d_inner(config: Dict[str, Any]) -> int:
    return config["mamba_n_heads"] * config["mamba_d_head"]


def conv_dim(config: Dict[str, Any]) -> int:
    return d_inner(config) + 2 * config["mamba_n_groups"] \
        * config["mamba_d_state"]


def mamba_vectors(config: Dict[str, Any]) -> int:
    """The conv's taps and bias, dt_bias, A_log, D and the gated norm."""
    return (config["mamba_d_conv"] + 1) * conv_dim(config) \
        + 3 * config["mamba_n_heads"] + d_inner(config)


def expert_params(config: Dict[str, Any]) -> int:
    """One routed expert's SwiGLU: ``intermediate_size`` wide."""
    return 3 * config["hidden_size"] * config["intermediate_size"]


def router_width(config: Dict[str, Any]) -> int:
    held = config.get("deployment", {}).get("experts_held")
    return held["of"] if held else config["num_local_experts"]


def published_vocab(config: Dict[str, Any]) -> int:
    """Rows of the whole vocabulary: ``deployment.vocab_slice.of``, or the
    file's own where it holds it whole."""
    return config.get("deployment", {}).get("vocab_slice", {}).get(
        "of", config["vocab_size"])


def _published_counts(config: Dict[str, Any]) -> Dict[str, int]:
    kinds = config["layer_types"]
    return {kind: kinds.count(kind) for kind in ("mamba", "attention")}


def _params(config: Dict[str, Any], n: Dict[str, int], experts: int,
            vocab: int) -> int:
    """Every parameter of ``n`` layers a kind with ``experts`` routed
    experts a layer and ``vocab`` rows in the tied table: matrices and
    vectors (two norms a layer, the final norm)."""
    d, layers = config["hidden_size"], n["mamba"] + n["attention"]
    ffn = mlp_params(config) + d * router_width(config) \
        + experts * expert_params(config)
    return (n["mamba"] * (mamba_params(config) + mamba_vectors(config))
            + n["attention"] * attention_params(config)
            + layers * (ffn + 2 * d) + d * vocab + d)


def held_params(config: Dict[str, Any]) -> int:
    """Every parameter the chip holds: 2.055 B for the cell's cut."""
    return _params(config, layer_counts(config), config["num_local_experts"],
                   config["vocab_size"])


def published_params(config: Dict[str, Any]) -> int:
    """Every parameter of the published model: all of ``layer_types``, the
    router's width of experts and the whole vocabulary: 32.2 B."""
    return _params(config, _published_counts(config), router_width(config),
                   published_vocab(config))


def active_matmul_params(config: Dict[str, Any],
                         published: bool = False) -> float:
    """Matmul parameters one token goes through in a forward pass: on this
    chip (its share of the token's routed experts, its slice's head), or,
    with ``published``, in the whole model: 8.80 B, the row's A9B."""
    d = config["hidden_size"]
    n = _published_counts(config) if published else layer_counts(config)
    share = 1.0 if published else held_share(config)
    vocab = published_vocab(config) if published else config["vocab_size"]
    ffn = mlp_params(config) + d * router_width(config) \
        + expert_params(config) * config["num_experts_per_tok"] * share
    return (n["mamba"] * mamba_params(config)
            + n["attention"] * attention_params(config)
            + (n["mamba"] + n["attention"]) * ffn + d * vocab)


def model_flops_per_token(config: Dict[str, Any], seq_len: int) -> float:
    """Model FLOPs one token costs in training on this chip: 6 per active
    matmul parameter, attention ``12 L_attention hidden S``, the scans."""
    n = layer_counts(config)
    return (6.0 * active_matmul_params(config)
            + 12.0 * n["attention"] * config["hidden_size"] * seq_len
            + n["mamba"] * scan_flops_per_token(config))


# -- what the kernels execute ----------------------------------------------

#: [tokens, width] arrays a row pass moves (its products are no bound):
#: ``ops/short_conv.py`` ``conv_silu`` over the conv's columns (read and
#: written; the backward reads the input and the cotangent and writes one),
#: ``ops/gated_norm.py`` over d_inner (y, z and the result; the backward
#: reads y, z and the cotangent and writes two).
ROW_PASSES = {"conv_silu_fwd": (conv_dim, 2), "conv_silu_bwd": (conv_dim, 3),
              "gated_norm_fwd": (d_inner, 3), "gated_norm_bwd": (d_inner, 5)}


def row_pass_call(kernel: str, config: Dict[str, Any], batch: int,
                  seq_len: int, itemsize: int = 2) -> Dict[str, float]:
    """Least HBM bytes of one call of a conv or gate-norm kernel: each
    operand read and each result written once; elementwise work and a
    4-tap sum, so no FLOPs bound."""
    width, arrays = ROW_PASSES[kernel]
    return {"flops": 0.0, "bytes": float(
        arrays * batch * seq_len * width(config) * itemsize)}


def grouped_matmul_call(config: Dict[str, Any], tokens: int,
                        share: Optional[float] = None) -> Dict[str, float]:
    """FLOPs and least HBM bytes of one grouped product of an expert layer
    (``gmm``, or ``tgmm`` for a weight's cotangent): ``2 rows d f`` with
    rows = tokens x ``num_experts_per_tok`` x ``share`` (the share of the
    assignments that fall on held experts: ``held_share`` under even
    routing, or what the program's counters measured) and f the experts'
    ``intermediate_size``; bytes: the rows' operand and result and the held
    experts' weights once."""
    d, f = config["hidden_size"], config["intermediate_size"]
    share = held_share(config) if share is None else share
    rows = tokens * config["num_experts_per_tok"] * share
    return {"flops": 2.0 * rows * d * f,
            "bytes": float(rows * (d + f) * 2
                           + config["num_local_experts"] * d * f * 2)}


def step_kernel_calls(config: Dict[str, Any], batch: int, seq_len: int,
                      blk_q: int, blk_k: int, remat: bool,
                      share: Optional[float] = None
                      ) -> Dict[str, Dict[str, float]]:
    """{kernel: {"calls", "flops", "bytes"}} of one training step on a
    routing within ``ops/moe.py``'s one buffer: calls a step and one call's
    FLOPs and least bytes. A state-space layer's three forward kernels twice
    with remat, its backward kernels once; the flash kernels once an
    attention layer (every query head against its own copy of K and V, so
    their batch is the query heads'), the forward twice where remat runs it
    again (``keeps_forward``); in **every** layer the expert SwiGLU's
    ``gmm`` three products forward, the same three again in the share's
    backward (which multiplies a buffer's rows again: the second forward a
    rematerialised layer would run anyway, and it runs them with or without
    remat) and the three rows' cotangents, ``tgmm`` the three weights'
    cotangents, ``moe_rows_to_tokens`` the forward's weighted sum and the
    backward's ``d x``."""
    n = layer_counts(config)
    layers = n["mamba"] + n["attention"]
    again = 2 if remat else 1
    heads = config["num_attention_heads"]
    hd = config["hidden_size"] // heads
    out = {}
    for name in ("ssd", "conv_silu", "gated_norm"):
        for way, calls in (("_fwd", n["mamba"] * again),
                           ("_bwd", n["mamba"])):
            one = flops_granite.ssd_call(name + way, config, batch, seq_len) \
                if name == "ssd" else row_pass_call(
                    name + way, config, batch, seq_len)
            out[name + way] = dict(one, calls=calls)
    for kernel in flops_deepseek.FLASH_PRODUCTS:
        forward_again = kernel == "flash_fwd" and remat \
            and not keeps_forward(seq_len, hd)
        out[kernel] = dict(flops_deepseek.flash_call(
            kernel, batch * heads, seq_len, hd, hd, blk_q, blk_k),
            calls=n["attention"] * (2 if forward_again else 1))
    one = grouped_matmul_call(config, batch * seq_len, share)
    out["gmm"] = dict(one, calls=layers * 9)
    out["tgmm"] = dict(one, calls=layers * 3)
    out["moe_rows_to_tokens"] = dict(rows_to_tokens_call(
        config, batch * seq_len, share), calls=layers * 2)
    return {name: one for name, one in out.items() if one["calls"]}
