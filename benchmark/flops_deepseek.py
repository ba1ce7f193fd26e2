"""The benchmark's arithmetic for the ``deepseek_v3`` family: model FLOPs a
token by ``flops.py``'s convention, and the operations and bytes that the
Pallas kernels of its step (flash attention, grouped matmul) execute.

``flops.py`` reads GPT-J's keys; this reads ``DeepseekV3Config``'s. The
convention is the same: 6 per parameter that sits in a matrix multiplication
a token goes through (2 forward, 4 backward), the input embedding left out
(a lookup), attention's scores and weighted sum over the full S x S (causal
skipping not credited), recompute not counted. A token goes through its
``num_experts_per_tok`` routed experts and the shared one, not all
``n_routed_experts``: inactive experts would inflate a utilization.

The ``executed_*`` functions count what a kernel really runs, for a roofline
share: causal tiles once (tiles above the diagonal are skipped), and every
call of a step, the forward kernels twice where the block is rematerialised.
"""

from __future__ import annotations

from typing import Any, Dict


def attention_params(config: Dict[str, Any]) -> int:
    """Wq, W_kv_a, W_kv_b and Wo of one layer (``q_lora_rank`` null)."""
    d, h = config["hidden_size"], config["num_attention_heads"]
    nope, rope = config["qk_nope_head_dim"], config["qk_rope_head_dim"]
    rank, v = config["kv_lora_rank"], config["v_head_dim"]
    return (d * h * (nope + rope) + d * (rank + rope)
            + rank * h * (nope + v) + h * v * d)


def active_matmul_params(config: Dict[str, Any]) -> int:
    """Matmul parameters one token touches in a forward pass: attention
    projections in every layer, the dense SwiGLU in the leading layers, the
    router, ``num_experts_per_tok`` routed experts and the shared experts in
    the others, and the untied head."""
    d = config["hidden_size"]
    layers, dense = config["num_hidden_layers"], config["first_k_dense_replace"]
    expert = 3 * d * config["moe_intermediate_size"]
    moe = d * config["n_routed_experts"] + expert * (
        config["num_experts_per_tok"] + config["n_shared_experts"])
    return (layers * attention_params(config)
            + dense * 3 * d * config["intermediate_size"]
            + (layers - dense) * moe + d * config["vocab_size"])


def model_flops_per_token(config: Dict[str, Any], seq_len: int) -> float:
    """Model FLOPs one token costs in training: 6 per active matmul
    parameter plus attention, ``6 L H (qk_head + v_head) S``."""
    heads = config["num_attention_heads"]
    qk = config["qk_nope_head_dim"] + config["qk_rope_head_dim"]
    return 6.0 * active_matmul_params(config) + 6.0 * (
        config["num_hidden_layers"] * heads
        * (qk + config["v_head_dim"]) * seq_len)


# -- what the kernels execute ----------------------------------------------

#: Per [blk_q, blk_k] tile, in units of 2 * blk_q * blk_k: how many
#: products over the q/k head size (D) and over the v head size (Dv) each
#: flash kernel makes.
FLASH_PRODUCTS = {"flash_fwd": (1, 1),       # q k^T | p v
                  "flash_bwd_dq": (2, 1),    # q k^T, ds k | dO v^T
                  "flash_bwd_dkv": (2, 2)}   # q k^T, ds^T q | p^T dO, dO v^T


def causal_tiles(seq_len: int, blk_q: int, blk_k: int) -> int:
    """Tiles of a causal S x S grid that hold at least one allowed pair:
    the ones the kernels do not skip."""
    return sum(min(-(-((qi + 1) * blk_q) // blk_k), seq_len // blk_k)
               for qi in range(seq_len // blk_q))


def flash_call(kernel: str, batch_heads: int, seq_len: int, d_qk: int,
               d_v: int, blk_q: int, blk_k: int, itemsize: int = 2
               ) -> Dict[str, float]:
    """FLOPs and least HBM bytes of one call of a causal flash kernel:
    executed tiles only; bytes are each operand read and each result
    written once (q, k, v, and for the backward kernels dO, plus the
    outputs; the float32 row vectors are left out)."""
    on_d, on_dv = FLASH_PRODUCTS[kernel]
    tiles = causal_tiles(seq_len, blk_q, blk_k)
    flops = batch_heads * tiles * 2.0 * blk_q * blk_k * (
        on_d * d_qk + on_dv * d_v)
    rows = batch_heads * seq_len * itemsize
    arrays = {"flash_fwd": 2 * d_qk + 2 * d_v,          # q k | v o
              "flash_bwd_dq": 3 * d_qk + 2 * d_v,       # q k dq | v dO
              "flash_bwd_dkv": 3 * d_qk + 3 * d_v}[kernel]  # q k dk | v dO dv
    return {"flops": flops, "bytes": float(rows * arrays)}


def grouped_matmul_layer(config: Dict[str, Any], tokens: int,
                         remat: bool) -> Dict[str, float]:
    """FLOPs and least HBM bytes of the grouped matmuls of one expert layer
    in one training step: 3 forward products of 2 * rows * d * f (again
    where the block is rematerialised) and 6 backward (each product's two
    cotangents: the rows' by ``gmm``, the weights' by ``tgmm``), rows =
    tokens * num_experts_per_tok. Bytes: per product its row operands and
    result once, and every expert's weights once. ``products`` counts them,
    ``tgmm`` those of the weights' cotangents."""
    d, f = config["hidden_size"], config["moe_intermediate_size"]
    rows = tokens * config["num_experts_per_tok"]
    products = 3 * (2 if remat else 1) + 6
    weights = config["n_routed_experts"] * d * f * 2
    return {"flops": products * 2.0 * rows * d * f,
            "bytes": float(products * (rows * (d + f) * 2 + weights)),
            "products": products, "tgmm": 3}


def step_kernel_flops(config: Dict[str, Any], batch: int, seq_len: int,
                      blk_q: int, blk_k: int, remat: bool
                      ) -> Dict[str, float]:
    """Executed FLOPs of one training step by kernel: the three flash
    kernels over every layer (the forward twice with remat) and the
    grouped matmuls over the expert layers."""
    layers = config["num_hidden_layers"]
    moe_layers = layers - config["first_k_dense_replace"]
    heads = config["num_attention_heads"]
    d_qk = config["qk_nope_head_dim"] + config["qk_rope_head_dim"]
    calls = {"flash_fwd": layers * (2 if remat else 1),
             "flash_bwd_dq": layers, "flash_bwd_dkv": layers}
    out = {k: n * flash_call(k, batch * heads, seq_len, d_qk,
                             config["v_head_dim"], blk_q, blk_k)["flops"]
           for k, n in calls.items()}
    out["grouped_matmul"] = moe_layers * grouped_matmul_layer(
        config, batch * seq_len, remat)["flops"]
    return out
