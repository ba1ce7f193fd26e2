"""The benchmark's arithmetic for the ``dots3_note`` family: model FLOPs a
token by ``flops.py``'s convention, the parameters a chip holds and the
whole published model's, and the operations and bytes that the Pallas
kernels of its step (attention over the selection: three kernels and the
head-summed probabilities; attention in a window: three kernels; grouped
matmul) execute at the least.

The convention is ``flops.py``'s: 6 per parameter that sits in a matrix
multiplication a token goes through (2 forward, 4 backward), the input
embedding left out (a lookup), recompute not counted; and, as
``flops_glm_moe_dsa.py`` has it, attention over the pairs the model attends
over and no others:

* a full layer's main attention over the **selected** pairs, ``sum_t min(t
  + 1, index_topk)``, its indexer's scores over the **causal** pairs;
* a window layer's attention over the **window's** pairs, ``sum_t min(t +
  1, sliding_window_size)``. A program that runs whole tiles executes more
  than either (at tiles of 512 twice the window's pairs, and every causal
  pair of a full layer); it is held to this count.

The two kinds of layer have geometries of their own (``geometry``): a full
layer reads the plain keys, a window layer the ``swa_`` ones. The gate's
matrix (hidden x heads) is a matmul parameter like any other. A
configuration that is one chip's share of a deployment
(``deployment.experts_held``) counts the routed experts at what this chip
computes, ``num_experts_per_tok * count / of`` a token under even routing.
"""

from __future__ import annotations

import itertools
from typing import Any, Dict, List, Optional

from flops_afmoe import WINDOW_SUFFIX, held_share, least_seconds  # noqa: F401
from flops_deepseek import FLASH_PRODUCTS
from flops_glm_moe_dsa import (PRODUCTS, causal_pairs, expert_params,
                               indexer_params, router_width, selected_pairs,
                               selected_share)  # noqa: F401

KINDS = ("full", "window")


def layers_run(config: Dict[str, Any]) -> List[int]:
    return list(config.get("layers_run",
                           range(config["num_hidden_layers"])))


def layer_kinds(config: Dict[str, Any]) -> List[tuple]:
    """[(dense?, "full" | "window")] of the layers that run."""
    return [(l < config["first_k_dense_replace"],
             "full" if config["layer_types"][l] == "full_attention"
             else "window") for l in layers_run(config)]


def layer_counts(config: Dict[str, Any]) -> Dict[str, int]:
    kinds = layer_kinds(config)
    return {"layers": len(kinds), "dense": sum(d for d, _ in kinds),
            "moe": sum(not d for d, _ in kinds),
            **{kind: sum(k == kind for _, k in kinds) for kind in KINDS}}


def longest_run(config: Dict[str, Any], kind: str) -> int:
    """Layers in the longest run of one kind of layer (FFN and attention)
    whose attention is ``kind``: one scan, so one instruction a kernel call
    site, and the busiest of a kernel's name."""
    return max((len(list(run)) for (_, k), run in itertools.groupby(
        layer_kinds(config)) if k == kind), default=0)


def geometry(config: Dict[str, Any], kind: str) -> Dict[str, int]:
    """A kind of layer's latent attention: ``heads``, ``q_rank``, ``rank``,
    ``nope``, ``rope``, ``v`` and ``qk`` = nope + rope."""
    prefix = "" if kind == "full" else "swa_"
    found = {name: config[prefix + key] for name, key in (
        ("heads", "num_attention_heads"), ("q_rank", "q_lora_rank"),
        ("rank", "kv_lora_rank"), ("nope", "qk_nope_head_dim"),
        ("rope", "qk_rope_head_dim"), ("v", "v_head_dim"))}
    return dict(found, qk=found["nope"] + found["rope"])


def attention_params(config: Dict[str, Any], kind: str) -> int:
    """W_qa, W_qb, W_kva, W_kvb, W_o and the gate's W_g of one layer."""
    d, g = config["hidden_size"], geometry(config, kind)
    return (d * g["q_rank"] + g["q_rank"] * g["heads"] * g["qk"]
            + d * (g["rank"] + g["rope"])
            + g["rank"] * g["heads"] * (g["nope"] + g["v"])
            + g["heads"] * g["v"] * d + d * g["heads"])


def _ffn_params(config: Dict[str, Any], routed: float):
    """(a dense layer's FFN, an expert layer's with ``routed`` routed
    experts counted)."""
    d = config["hidden_size"]
    return 3 * d * config["intermediate_size"], \
        d * router_width(config) + expert_params(config) * (
            config["n_shared_experts"] + routed)


def _layers_params(config: Dict[str, Any], routed: float) -> float:
    n = layer_counts(config)
    dense, moe = _ffn_params(config, routed)
    return (sum(n[kind] * attention_params(config, kind) for kind in KINDS)
            + n["full"] * indexer_params(config)
            + n["dense"] * dense + n["moe"] * moe)


def active_matmul_params(config: Dict[str, Any]) -> float:
    """Matmul parameters one token goes through on this chip in a forward
    pass: every layer's attention with its gate, the full layers' indexers,
    the dense SwiGLU in the leading layer, in the others the router, the
    shared expert and ``num_experts_per_tok`` x ``held_share`` routed
    experts, and the head."""
    return _layers_params(
        config, config["num_experts_per_tok"] * held_share(config)) \
        + config["hidden_size"] * config["vocab_size"]


def held_params(config: Dict[str, Any]) -> int:
    """Every parameter the chip holds in a matrix: the layers with
    ``n_routed_experts`` (held) experts each, ``wte`` and the head."""
    return _layers_params(config, config["n_routed_experts"]) \
        + 2 * config["hidden_size"] * config["vocab_size"]


def _published(config: Dict[str, Any]) -> Dict[str, Any]:
    whole = dict(config, **{key: cut["published"] for key, cut in
                            config.get("reduced", {}).items()})
    whole.pop("layers_run", None)
    whole.pop("deployment", None)
    return whole


def published_params(config: Dict[str, Any]) -> int:
    """The whole published language model by the same count: every key a
    file cut (``reduced``) at its published value, every published layer.
    The vision and audio towers and the multi-token prediction module are
    not in the row's config and not counted."""
    return held_params(_published(config))


def published_active_params(config: Dict[str, Any]) -> float:
    """What a token goes through in the whole published language model,
    the lookup's row counted as the catalog's "A" counts it."""
    whole = _published(config)
    return active_matmul_params(whole) \
        + whole["hidden_size"] * whole["vocab_size"]


def window_pairs(seq_len: int, window: int) -> int:
    """``sum_t min(t + 1, window)``: the same sum as a selection's."""
    return selected_pairs(seq_len, window)


def flops_by_part(config: Dict[str, Any], seq_len: int) -> Dict[str, float]:
    """Model FLOPs a token in training, by part."""
    n = layer_counts(config)
    full, window = geometry(config, "full"), geometry(config, "window")
    dense, moe = _ffn_params(
        config, config["num_experts_per_tok"] * held_share(config))
    selected = selected_pairs(seq_len, config["index_topk"]) / seq_len
    kept = window_pairs(seq_len, config["sliding_window_size"]) / seq_len
    return {
        "attention_projections": 6.0 * sum(
            n[kind] * attention_params(config, kind) for kind in KINDS),
        "attention_over_selection": 6.0 * n["full"] * full["heads"]
        * (full["qk"] + full["v"]) * selected,
        "attention_in_window": 6.0 * n["window"] * window["heads"]
        * (window["qk"] + window["v"]) * kept,
        "indexer_projections": 6.0 * n["full"] * indexer_params(config),
        "indexer_scores": 6.0 * n["full"] * config["index_n_heads"]
        * config["index_head_dim"] * causal_pairs(seq_len) / seq_len,
        "dense_ffn": 6.0 * n["dense"] * dense,
        "expert_ffn": 6.0 * n["moe"] * moe,
        "head": 6.0 * config["hidden_size"] * config["vocab_size"],
    }


def model_flops_per_token(config: Dict[str, Any], seq_len: int) -> float:
    return sum(flops_by_part(config, seq_len).values())


# -- what the kernels execute at the least ----------------------------------

def attention_call(kernel: str, config: Dict[str, Any], batch: int,
                   seq_len: int, itemsize: int = 2) -> Dict[str, float]:
    """FLOPs and least HBM bytes of one call of an attention kernel. A
    kernel of ``ray_tpu/ops/dsa.py`` (``dsa_*``): its products over the
    selected pairs of a full layer's heads, each operand read and each
    result written once (the selection's S x S bytes a batch row, and
    ``dsa_probs``' float32 S x S result, among them). A window layer's
    flash kernel (``flash_*_win``): its products over the window's pairs."""
    windowed = kernel.endswith(WINDOW_SUFFIX)
    g = geometry(config, "window" if windowed else "full")
    if windowed:
        kernel = kernel[:-len(WINDOW_SUFFIX)]
        on_d, on_dv = FLASH_PRODUCTS[kernel]
        pairs = window_pairs(seq_len, config["sliding_window_size"])
        kernel = kernel.replace("flash", "dsa")
    else:
        on_d, on_dv = PRODUCTS[kernel]
        pairs = selected_pairs(seq_len, config["index_topk"])
    rows = batch * g["heads"] * seq_len * itemsize
    arrays = {"dsa_fwd": 2 * g["qk"] + 2 * g["v"],       # q k | v o
              "dsa_bwd_dq": 3 * g["qk"] + 2 * g["v"],    # q k dq | v dO
              "dsa_bwd_dkv": 3 * g["qk"] + 3 * g["v"],   # q k dk | v dO dv
              "dsa_probs": 2 * g["qk"]}[kernel]          # q k
    square = 0 if windowed else batch * seq_len * seq_len
    return {"flops": batch * g["heads"] * pairs * 2.0
            * (on_d * g["qk"] + on_dv * g["v"]),
            "bytes": float(rows * arrays + square
                           + (4 * square if kernel == "dsa_probs" else 0))}


def keeps_forward(keys: int, v_head: int) -> bool:
    """``ops/flash_attention.worth_keeping``'s rule: a rematerialised block
    keeps the forward kernel's outputs from 32 keys a query sees for each of
    a value head's dimensions."""
    return keys >= 32 * v_head


def grouped_matmul_call(config: Dict[str, Any], tokens: int,
                        share: Optional[float] = None) -> Dict[str, float]:
    """FLOPs and least HBM bytes of one grouped product of an expert layer
    (``gmm``, or ``tgmm`` for the weights' cotangent), as
    ``flops_glm_moe_dsa.grouped_matmul_call`` counts them."""
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
    step and one call's FLOPs and least bytes. A kind's forward kernel once
    a layer, twice where the block is rematerialised and its outputs are
    not kept (``keeps_forward``: a full layer's keys are the sequence's, a
    window layer's the window's); the two backward kernels once a layer;
    ``dsa_probs`` once a full layer, twice with remat (the indexer's loss
    is part of the block, and its gradient reads p again); ``gmm``: three
    products forward (twice with remat) and the three rows' cotangents;
    ``tgmm``: the three weights' cotangents. A window the sequence does not
    reach would run the causal kernels, which no cell of this family does."""
    n = layer_counts(config)
    again = 2 if remat else 1
    window = config["sliding_window_size"]
    if n["window"] and window >= seq_len:
        raise ValueError(f"a window of {window} does not cut {seq_len} keys")
    calls = {}
    for kind, fwd, keys in (("full", "dsa_fwd", seq_len),
                            ("window", "flash_fwd" + WINDOW_SUFFIX, window)):
        if not n[kind]:
            continue
        kept = keeps_forward(keys, geometry(config, kind)["v"])
        calls[fwd] = n[kind] * (1 if kept else again)
        for name in ("bwd_dq", "bwd_dkv"):
            calls[fwd.replace("fwd", name)] = n[kind]
    if n["full"]:
        calls["dsa_probs"] = n["full"] * again
    out = {kernel: dict(attention_call(kernel, config, batch, seq_len),
                        calls=count) for kernel, count in calls.items()}
    if n["moe"]:
        one = grouped_matmul_call(config, batch * seq_len, share)
        out["gmm"] = dict(one, calls=n["moe"] * (3 * again + 3))
        out["tgmm"] = dict(one, calls=n["moe"] * 3)
    return out
