"""The benchmark's arithmetic for the ``nemotron_h`` family: parameters held
and published, model FLOPs a token by ``flops.py``'s convention, and the
operations and bytes that the Pallas kernels of its step execute (the grouped
chunked state-space scan, its conv and its grouped gate-norm, flash
attention, the grouped products of squared-ReLU experts, the share's way
back to tokens).

The convention is ``flops.py``'s: 6 per parameter that sits in a matrix
multiplication a token goes through (2 forward, 4 backward), the input
embedding left out (a lookup), recompute not counted, attention's scores and
weighted sum over the full S x S of the one kind of attention layer there is
(causal skipping not credited). Every layer is one mixer, so a token goes
through: in a state-space layer the in- and out-projection and the literal
recurrence (``flops_granite.py``'s count: 15 per head channel and state
element, whatever the groups: B and C are shared, not the state); in an
attention layer Wq, Wk, Wv, Wo; in an expert layer the router at its whole
width, the shared expert (two matrices of
``moe_shared_expert_intermediate_size``) and ``num_experts_per_tok`` x
``held_share`` routed experts of two matrices each: a configuration that is
one chip's share of a deployment (``deployment.experts_held``) counts the
routed experts at what this chip computes, its 6 experts' share held here
and not all 32, as ``flops_afmoe.py`` does. The conv, norms and gates are
left out, as GPT-J's biases and norms are.

The ``executed`` counts are of what a kernel really runs, for a roofline
share: every product of every grid step (``C B^T`` once a chunk and
**group**), causal flash tiles once, the grouped products at the rows this
chip computed, and every call of a step: a forward kernel twice where its
layer is rematerialised and its outputs are not kept.
"""

from __future__ import annotations

from typing import Any, Dict, Optional

import flops_deepseek
from flops_afmoe import held_share, least_seconds  # noqa: F401

KINDS = {"M": "mamba", "E": "experts", "*": "attention"}


def layer_kinds(config: Dict[str, Any], published: bool = False):
    """The kind of every layer that runs (``deployment.layers_run`` of the
    pattern), or of the whole pattern with ``published``."""
    pattern = config["hybrid_override_pattern"]
    if not published:
        first = config.get("deployment", {}).get("layers_run", {}).get(
            "first", 0)
        pattern = pattern[first:first + config["num_hidden_layers"]]
    return [KINDS[letter] for letter in pattern]


def layer_counts(config: Dict[str, Any], published: bool = False
                 ) -> Dict[str, int]:
    kinds = layer_kinds(config, published)
    return {kind: kinds.count(kind) for kind in KINDS.values()}


def d_inner(config: Dict[str, Any]) -> int:
    return config["mamba_num_heads"] * config["mamba_head_dim"]


def conv_dim(config: Dict[str, Any]) -> int:
    return d_inner(config) + 2 * config["n_groups"] * config["ssm_state_size"]


def mamba_params(config: Dict[str, Any]) -> int:
    """in_proj (z | xBC | dt) and out_proj of one state-space layer."""
    d = config["hidden_size"]
    return d * (d_inner(config) + conv_dim(config)
                + config["mamba_num_heads"]) + d_inner(config) * d


def mamba_vectors(config: Dict[str, Any]) -> int:
    """The conv's taps and bias, dt_bias, A_log, D and the gated norm."""
    return (config["conv_kernel"] + 1) * conv_dim(config) \
        + 3 * config["mamba_num_heads"] + d_inner(config)


def attention_params(config: Dict[str, Any]) -> int:
    """Wq, Wk, Wv and Wo of one attention layer."""
    d, hd = config["hidden_size"], config["head_dim"]
    return 2 * d * hd * (config["num_attention_heads"]
                         + config["num_key_value_heads"])


def expert_params(config: Dict[str, Any]) -> int:
    """One routed expert: two matrices, no gate."""
    return 2 * config["hidden_size"] * config["moe_intermediate_size"]


def shared_params(config: Dict[str, Any]) -> int:
    return 2 * config["hidden_size"] * config["n_shared_experts"] \
        * config["moe_shared_expert_intermediate_size"]


def router_width(config: Dict[str, Any]) -> int:
    held = config.get("deployment", {}).get("experts_held")
    return held["of"] if held else config["n_routed_experts"]


def published_vocab(config: Dict[str, Any]) -> int:
    """Rows of the whole vocabulary: ``deployment.vocab_slice.of``, or the
    file's own where it holds it whole."""
    return config.get("deployment", {}).get("vocab_slice", {}).get(
        "of", config["vocab_size"])


def _params(config: Dict[str, Any], n: Dict[str, int], experts: int,
            vocab: int) -> int:
    """Every parameter of ``n`` layers a kind with ``experts`` routed
    experts a layer and ``vocab`` rows in each of the two tables: matrices,
    the correction bias and the vectors (a norm a layer, the final norm)."""
    d, width = config["hidden_size"], router_width(config)
    experts_layer = d * width + width + experts * expert_params(config) \
        + shared_params(config)
    return (n["mamba"] * (mamba_params(config) + mamba_vectors(config))
            + n["attention"] * attention_params(config)
            + n["experts"] * experts_layer
            + sum(n.values()) * d + 2 * d * vocab + d)


def held_params(config: Dict[str, Any]) -> int:
    """Every parameter the chip holds: 1.713 B for the cell's cut."""
    return _params(config, layer_counts(config), config["n_routed_experts"],
                   config["vocab_size"])


def published_params(config: Dict[str, Any]) -> int:
    """Every parameter of the published model: the whole pattern, the
    router's width of experts and the whole vocabulary
    (``deployment.vocab_slice.of``): 31.58 B."""
    return _params(config, layer_counts(config, published=True),
                   router_width(config), published_vocab(config))


def active_matmul_params(config: Dict[str, Any],
                         published: bool = False) -> float:
    """Matmul parameters one token goes through in a forward pass: on this
    chip (its share of the token's routed experts, its slice's head), or,
    with ``published``, in the whole model: 3.228 B, the row's A3.2B."""
    d, n = config["hidden_size"], layer_counts(config, published)
    share = 1.0 if published else held_share(config)
    vocab = published_vocab(config) if published else config["vocab_size"]
    experts_layer = d * router_width(config) + shared_params(config) \
        + expert_params(config) * config["num_experts_per_tok"] * share
    return (n["mamba"] * mamba_params(config)
            + n["attention"] * attention_params(config)
            + n["experts"] * experts_layer + d * vocab)


def scan_flops_per_token(config: Dict[str, Any]) -> float:
    """The literal recurrence of one state-space layer in training: 15 per
    head channel and state element."""
    return 15.0 * d_inner(config) * config["ssm_state_size"]


def model_flops_per_token(config: Dict[str, Any], seq_len: int) -> float:
    """Model FLOPs one token costs in training on this chip: 6 per active
    matmul parameter, attention ``12 heads head_dim S`` in an attention
    layer, the scans."""
    n = layer_counts(config)
    return (6.0 * active_matmul_params(config)
            + 12.0 * n["attention"] * config["num_attention_heads"]
            * config["head_dim"] * seq_len
            + n["mamba"] * scan_flops_per_token(config))


# -- what the kernels execute ----------------------------------------------

def ssd_call(kernel: str, config: Dict[str, Any], batch: int, seq_len: int,
             chunk: int, itemsize: int = 2) -> Dict[str, float]:
    """FLOPs and least HBM bytes of one call of ``ssd_fwd`` or ``ssd_bwd``
    (``ray_tpu/ops/ssd.py``) on [batch, seq_len] tokens at chunks of
    ``chunk``: ``flops_granite.ssd_call``'s count with ``C B^T`` (and, in
    the backward, the two products of its cotangent) once a chunk and
    group, and B, C and their cotangents ``n_groups`` x ``ssm_state_size``
    wide."""
    heads, width = config["mamba_num_heads"], config["mamba_head_dim"]
    state, groups = config["ssm_state_size"], config["n_groups"]
    chunks, tokens = batch * seq_len // chunk, batch * seq_len
    square, with_state = 2.0 * chunk * chunk, 2.0 * chunk * state * width
    wide = tokens * heads * width * itemsize
    states = chunks * heads * state * width * 4
    vectors = tokens * heads * 4
    shared = tokens * groups * state
    if kernel == "ssd_fwd":
        flops = chunks * (groups * square * state
                          + heads * (square * width + 2 * with_state))
        moved = 2 * wide + 2 * shared * itemsize + states + 5 * vectors
    elif kernel == "ssd_bwd":
        flops = chunks * (3 * groups * square * state
                          + heads * (2 * square * width + 5 * with_state))
        moved = 3 * wide + 2 * shared * (itemsize + 4) + states \
            + 10 * vectors
    else:
        raise ValueError(f"no such kernel: {kernel!r}")
    return {"flops": flops, "bytes": float(moved)}


#: [tokens, width] arrays a row pass moves (its products are no bound):
#: ``ops/short_conv.py`` ``conv_silu`` over the conv's columns,
#: ``ops/gated_norm.py`` over d_inner.
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
    (``gmm``, or ``tgmm`` for the weights' cotangent): ``2 rows d f`` with
    rows = tokens x ``num_experts_per_tok`` x ``share`` (the share of the
    assignments that fall on held experts: ``held_share`` under even
    routing, or what the program's counters measured) and f the published
    1856 (the last tile's 64 columns past the edge are no work the model
    asks for); bytes: the rows' operand and result and the held experts'
    weights once."""
    d, f = config["hidden_size"], config["moe_intermediate_size"]
    share = held_share(config) if share is None else share
    rows = tokens * config["num_experts_per_tok"] * share
    return {"flops": 2.0 * rows * d * f,
            "bytes": float(rows * (d + f) * 2
                           + config["n_routed_experts"] * d * f * 2)}


def rows_to_tokens_call(config: Dict[str, Any], tokens: int,
                        share: Optional[float] = None, itemsize: int = 2
                        ) -> Dict[str, float]:
    """Least HBM bytes of one call of ``moe_rows_to_tokens``: the held rows
    read once, the tokens' float32 sums written once."""
    d = config["hidden_size"]
    share = held_share(config) if share is None else share
    rows = tokens * config["num_experts_per_tok"] * share
    return {"flops": 0.0, "bytes": float(rows * d * itemsize
                                         + tokens * d * 4)}


def keeps_forward(keys: int, head_dim: int) -> bool:
    """``ops/flash_attention.py`` ``worth_keeping``: from 32 keys a query
    sees for each of a head's dimensions a rematerialised layer keeps the
    flash forward kernel's outputs."""
    return keys >= 32 * head_dim


def step_kernel_calls(config: Dict[str, Any], batch: int, seq_len: int,
                      blk_q: int, blk_k: int, remat: bool, chunk: int,
                      share: Optional[float] = None
                      ) -> Dict[str, Dict[str, float]]:
    """{kernel: {"calls", "flops", "bytes"}} of one training step on a
    routing within ``ops/moe.py``'s one buffer: calls a step and one call's
    FLOPs and least bytes. A state-space layer's three forward kernels twice
    with remat, its backward kernels once; the flash kernels once an
    attention layer (every query head against its own copy of K and V, so
    their batch is the query heads'), the forward twice where remat runs it
    again (``keeps_forward``); an expert layer's ``gmm`` two products
    forward, the same two again in the share's backward (which multiplies a
    buffer's rows again: the second forward a rematerialised layer would
    run anyway, and it runs them with or without remat) and the two rows'
    cotangents, ``tgmm`` the two weights' cotangents, ``moe_rows_to_tokens``
    the forward's weighted sum and the backward's ``d x``."""
    n = layer_counts(config)
    again = 2 if remat else 1
    heads, hd = config["num_attention_heads"], config["head_dim"]
    out = {}
    if n["mamba"]:
        for name in ("ssd", "conv_silu", "gated_norm"):
            for way, calls in (("_fwd", n["mamba"] * again),
                               ("_bwd", n["mamba"])):
                one = ssd_call(name + way, config, batch, seq_len, chunk) \
                    if name == "ssd" else row_pass_call(
                        name + way, config, batch, seq_len)
                out[name + way] = dict(one, calls=calls)
    if n["attention"]:
        for kernel in flops_deepseek.FLASH_PRODUCTS:
            forward_again = kernel == "flash_fwd" and remat \
                and not keeps_forward(seq_len, hd)
            out[kernel] = dict(flops_deepseek.flash_call(
                kernel, batch * heads, seq_len, hd, hd, blk_q, blk_k),
                calls=n["attention"] * (2 if forward_again else 1))
    if n["experts"]:
        one = grouped_matmul_call(config, batch * seq_len, share)
        out["gmm"] = dict(one, calls=n["experts"] * 6)
        out["tgmm"] = dict(one, calls=n["experts"] * 2)
        out["moe_rows_to_tokens"] = dict(rows_to_tokens_call(
            config, batch * seq_len, share), calls=n["experts"] * 2)
    return out
