"""The benchmark's arithmetic for the ``granitemoehybrid`` family: model
FLOPs a token by ``flops.py``'s convention, and the operations and bytes
that the Pallas kernels of its step (the chunked state-space scan, flash
attention) execute.

The convention is ``flops.py``'s: 6 per parameter that sits in a matrix
multiplication a token goes through (2 forward, 4 backward), the input
embedding left out (a lookup; the tied table counts once, as the head),
attention's scores and weighted sum over the full S x S (causal skipping not
credited), recompute not counted. The state-space recurrence is counted as
the literal one, as attention is counted by its definition and not by its
kernel: per token, head and state element 3 operations to update the state
(decay it, form ``dt u B^T``, add) and 2 to read it (``S C``), times 3 for
the backward pass. What the chunked form adds (``C B^T``, the [L, L] decay
products) is the program's choice, not the model's need. The depthwise conv
(``6 * mamba_d_conv`` a channel and token), norms and gates are left out,
as GPT-J's biases and norms are.

The ``executed`` functions count what a kernel really runs, for a roofline
share: every product of every grid step, causal flash tiles once, and every
call of a step, the forward kernels twice where the block is rematerialised.
"""

from __future__ import annotations

from typing import Any, Dict

import flops_deepseek


def layer_counts(config: Dict[str, Any]) -> Dict[str, int]:
    """How many layers of each kind run: ``layer_types`` cut to
    ``num_hidden_layers``."""
    kinds = config["layer_types"][:config["num_hidden_layers"]]
    return {kind: kinds.count(kind) for kind in ("mamba", "attention")}


def mamba_params(config: Dict[str, Any]) -> int:
    """in_proj (z | xBC | dt) and out_proj of one state-space layer."""
    d = config["hidden_size"]
    d_inner = config["mamba_n_heads"] * config["mamba_d_head"]
    conv_dim = d_inner + 2 * config["mamba_n_groups"] * config["mamba_d_state"]
    return d * (d_inner + conv_dim + config["mamba_n_heads"]) + d_inner * d


def attention_params(config: Dict[str, Any]) -> int:
    """Wq, Wk, Wv and Wo of one attention layer; heads of hidden / heads."""
    d, heads = config["hidden_size"], config["num_attention_heads"]
    head_dim = d // heads
    return 2 * d * heads * head_dim \
        + 2 * d * config["num_key_value_heads"] * head_dim


def mlp_params(config: Dict[str, Any]) -> int:
    """input_linear (two halves) and output_linear of one layer's SwiGLU."""
    return 3 * config["hidden_size"] * config["shared_intermediate_size"]


def matmul_params(config: Dict[str, Any]) -> int:
    """Matmul parameters one token goes through in a forward pass: every
    layer's mixer and SwiGLU, and the tied table once, as the head."""
    n = layer_counts(config)
    return (n["mamba"] * mamba_params(config)
            + n["attention"] * attention_params(config)
            + (n["mamba"] + n["attention"]) * mlp_params(config)
            + config["hidden_size"] * config["vocab_size"])


def scan_flops_per_token(config: Dict[str, Any]) -> float:
    """The literal recurrence of one state-space layer in training: 15 per
    head and state element."""
    return 15.0 * config["mamba_n_heads"] * config["mamba_d_head"] \
        * config["mamba_d_state"]


def model_flops_per_token(config: Dict[str, Any], seq_len: int) -> float:
    """Model FLOPs one token costs in training: 6 per matmul parameter,
    attention ``12 L_attention hidden S``, the scans."""
    n = layer_counts(config)
    return (6.0 * matmul_params(config)
            + 12.0 * n["attention"] * config["hidden_size"] * seq_len
            + n["mamba"] * scan_flops_per_token(config))


# -- what the kernels execute ----------------------------------------------

def ssd_call(kernel: str, config: Dict[str, Any], batch: int, seq_len: int,
             itemsize: int = 2) -> Dict[str, float]:
    """FLOPs and least HBM bytes of one call of ``ssd_fwd`` or ``ssd_bwd``
    (``ray_tpu/ops/ssd.py``) on [batch, seq_len] tokens.

    Per chunk of L positions the forward makes ``C B^T`` (2 L L N) once and,
    per head, the decayed scores on u (2 L L P), ``C`` on the entry state
    and ``B^T`` on the weighted u (2 L N P each). The backward makes
    ``C B^T`` and the two products of its cotangent (3 x 2 L L N) once and,
    per head, ``dY u^T`` and the scores' transpose on dY (2 x 2 L L P), and
    five products with a state (``C`` on the state, ``B`` on its cotangent,
    the cotangents of C, B and the entry state: 5 x 2 L N P).

    Bytes: each operand read and each result written once. Forward: u and
    y, B and C, the chunks' entry states (float32), and five float32 values
    a position and head (dt and the running sum as columns and as rows, the
    sum's distance to the chunk's end). Backward: u, dY and du, the entry
    states, B, C and their float32 cotangents, those five values and the
    five it returns."""
    chunk, heads = config["mamba_chunk_size"], config["mamba_n_heads"]
    width, state = config["mamba_d_head"], config["mamba_d_state"]
    chunks = batch * seq_len // chunk
    tokens = batch * seq_len
    square, with_state = 2.0 * chunk * chunk, 2.0 * chunk * state * width
    wide = tokens * heads * width * itemsize
    states = chunks * heads * state * width * 4
    vectors = tokens * heads * 4
    shared = tokens * state
    if kernel == "ssd_fwd":
        flops = chunks * (square * state
                          + heads * (square * width + 2 * with_state))
        moved = 2 * wide + 2 * shared * itemsize + states + 5 * vectors
    elif kernel == "ssd_bwd":
        flops = chunks * (3 * square * state
                          + heads * (2 * square * width + 5 * with_state))
        moved = 3 * wide + 2 * shared * (itemsize + 4) + states \
            + 10 * vectors
    else:
        raise ValueError(f"no such kernel: {kernel!r}")
    return {"flops": flops, "bytes": float(moved)}


def step_kernel_calls(config: Dict[str, Any], batch: int, seq_len: int,
                      blk_q: int, blk_k: int, remat: bool
                      ) -> Dict[str, Dict[str, float]]:
    """{kernel: {"calls", "flops", "bytes"}} of one training step: calls a
    step (the forward kernels twice with remat) and one call's FLOPs and
    least bytes. The flash kernels run every query head against its own
    copy of K and V (``_repeat_heads``), so their batch is the query
    heads'."""
    n = layer_counts(config)
    again = 2 if remat else 1
    heads = config["num_attention_heads"]
    head_dim = config["hidden_size"] // heads
    calls = {"ssd_fwd": n["mamba"] * again, "ssd_bwd": n["mamba"],
             "flash_fwd": n["attention"] * again,
             "flash_bwd_dq": n["attention"],
             "flash_bwd_dkv": n["attention"]}
    out = {}
    for kernel, count in calls.items():
        one = ssd_call(kernel, config, batch, seq_len) \
            if kernel.startswith("ssd") else flops_deepseek.flash_call(
                kernel, batch * heads, seq_len, head_dim, head_dim, blk_q,
                blk_k)
        out[kernel] = dict(one, calls=count)
    return out
