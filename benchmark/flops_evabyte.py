"""The benchmark's arithmetic for the ``evabyte`` family: model FLOPs a token
by ``flops.py``'s convention, the parameters a chip holds and the whole
published model's, the pairs EVA attention defines, and the operations and
bytes that the step's Pallas kernels (``eva_fwd``, ``eva_bwd_dq``,
``eva_bwd_dkv``) and its pooling execute at the least.

The convention is ``flops.py``'s: 6 per parameter that sits in a matrix
multiplication a token goes through (2 forward, 4 backward), the input
embedding left out (a lookup), recompute not counted. The attention is
counted over the **pairs EVA defines**, whatever tables compute them: query
t against the ``t - s + 1`` keys of its own window (``s = window * (t //
window)``) and the ``s / chunk`` summaries of the windows before it, two
products a pair (``q k^T`` over ``head_dim``, ``p v`` over ``head_dim``). A
program that computes and masks more (the diagonal tiles' upper halves, the
summary tiles' columns past a window's count) is held to this count and
reads low by what it masks. The head is ``num_pred_heads x vocab_size``
wide. The pooling's three contractions over a chunk (``k . phi``, ``a k``,
``a v``) are counted as the products they are; its softmax is not.

The kernels' ``least`` FLOPs and bytes are for a roofline share: a call's
pairs times its products, each operand read and each result written once
(the stacked keys and values are ``S / chunk`` rows, up to a tile, longer
than S).
"""

from __future__ import annotations

from typing import Any, Dict

from flops_afmoe import least_seconds  # noqa: F401


def head_dim(config: Dict[str, Any]) -> int:
    return config["hidden_size"] // config["num_attention_heads"]


def layer_matmul_params(config: Dict[str, Any]) -> int:
    """W_q, W_k, W_v, W_o and the SwiGLU's three of one layer."""
    d = config["hidden_size"]
    return 4 * d * d + 3 * d * config["intermediate_size"]


def layer_params(config: Dict[str, Any]) -> int:
    """Every parameter of one layer: its matrices, two norms' offsets and
    EVA's two vectors a head."""
    return layer_matmul_params(config) + 2 * config["hidden_size"] \
        + 2 * config["num_attention_heads"] * head_dim(config)


def head_params(config: Dict[str, Any]) -> int:
    return config["hidden_size"] * config["num_pred_heads"] \
        * config["vocab_size"]


def held_params(config: Dict[str, Any]) -> int:
    """Everything the chip holds: the layers that run, the table, the final
    norm and the head of all the prediction heads."""
    d = config["hidden_size"]
    return config["num_hidden_layers"] * layer_params(config) \
        + config["vocab_size"] * d + d + head_params(config)


def published_params(config: Dict[str, Any]) -> int:
    """The whole published model by the same count: every key a file cut
    (``reduced``) at its published value."""
    return held_params(dict(config, **{
        key: cut["published"] for key, cut in
        config.get("reduced", {}).items()}))


def local_pairs(seq_len: int, window: int) -> int:
    """``sum_t (t - s + 1)``: every query against its own window's keys up
    to itself."""
    whole, rest = divmod(seq_len, window)
    return whole * window * (window + 1) // 2 + rest * (rest + 1) // 2


def summary_pairs(seq_len: int, window: int, chunk: int) -> int:
    """``sum_t s / chunk``: every query against the summaries of the
    windows before its own."""
    whole, rest = divmod(seq_len, window)
    a_window = window // chunk
    return a_window * window * (whole * (whole - 1) // 2) \
        + rest * whole * a_window


def pairs(config: Dict[str, Any], seq_len: int) -> int:
    """(query, key or summary) pairs a head of one sequence: 65,028,096 at
    32768 with a window of 2048 and chunks of 16."""
    window, chunk = config["window_size"], config["chunk_size"]
    return local_pairs(seq_len, window) + summary_pairs(seq_len, window,
                                                        chunk)


def causal_pairs(seq_len: int) -> int:
    return seq_len * (seq_len + 1) // 2


def pairs_share(config: Dict[str, Any], seq_len: int) -> float:
    """What ``eva.pairs_share`` has to read."""
    return pairs(config, seq_len) / causal_pairs(seq_len)


def flops_by_part(config: Dict[str, Any], seq_len: int) -> Dict[str, float]:
    """Model FLOPs a token in training, by part."""
    layers, d = config["num_hidden_layers"], config["hidden_size"]
    heads, hd = config["num_attention_heads"], head_dim(config)
    return {
        "attention_projections": 6.0 * layers * 4 * d * d,
        "attention_over_pairs": 6.0 * layers * heads * 2 * hd
        * pairs(config, seq_len) / seq_len,
        "pooling": 6.0 * layers * heads * 3 * hd,
        "ffn": 6.0 * layers * 3 * d * config["intermediate_size"],
        "head": 6.0 * head_params(config),
    }


def model_flops_per_token(config: Dict[str, Any], seq_len: int) -> float:
    return sum(flops_by_part(config, seq_len).values())


# -- what the kernels execute at the least ----------------------------------

#: Per pair, in units of 2 x head_dim: the products each kernel makes over
#: the q/k head size and over the v head size.
PRODUCTS = {"eva_fwd": (1, 1),       # q k^T | p v
            "eva_bwd_dq": (2, 1),    # q k^T, ds k | dO v^T
            "eva_bwd_dkv": (2, 2)}   # q k^T, ds^T q | p^T dO, dO v^T


def stacked_rows(config: Dict[str, Any], seq_len: int, blk_k: int) -> int:
    """Rows of the stacked keys: the summaries up to a whole tile, then the
    keys."""
    summaries = seq_len // config["chunk_size"]
    return -(-summaries // blk_k) * blk_k + seq_len


def attention_call(kernel: str, config: Dict[str, Any], batch: int,
                   seq_len: int, blk_k: int = 512, itemsize: int = 2
                   ) -> Dict[str, float]:
    """FLOPs and least HBM bytes of one call of a kernel of
    ``ray_tpu/ops/eva.py``: its products over EVA's pairs of every head;
    q, the stacked keys and values and (for the backward kernels) dO read
    once, the results written once; the float32 row vectors left out."""
    on_d, on_dv = PRODUCTS[kernel]
    heads, hd = config["num_attention_heads"], head_dim(config)
    rows = stacked_rows(config, seq_len, blk_k)
    long_arrays = {"eva_fwd": 2, "eva_bwd_dq": 2, "eva_bwd_dkv": 4}[kernel]
    short_arrays = {"eva_fwd": 2, "eva_bwd_dq": 3, "eva_bwd_dkv": 2}[kernel]
    return {"flops": batch * heads * pairs(config, seq_len) * 2.0 * hd
            * (on_d + on_dv),
            "bytes": float(batch * heads * hd * itemsize
                           * (long_arrays * rows + short_arrays * seq_len))}


def pool_call(config: Dict[str, Any], batch: int, seq_len: int,
              itemsize: int = 2) -> Dict[str, float]:
    """FLOPs and least HBM bytes of one layer's pooling forward (XLA's, no
    kernel): k and v read once, the summaries written."""
    heads, hd = config["num_attention_heads"], head_dim(config)
    keys = batch * heads * seq_len
    return {"flops": keys * 3 * 2.0 * hd,
            "bytes": float(keys * hd * itemsize * 2
                           * (1 + 1 / config["chunk_size"]))}


def keeps_forward(config: Dict[str, Any], seq_len: int) -> bool:
    """``ops/flash_attention.worth_keeping``'s rule, asked as ``ops/eva.py``
    asks it: the forward kernel's outputs are kept for the backward pass
    where the most keys and summaries a query sees are 32 head_dim or
    more."""
    window = config["window_size"]
    seen = min(seq_len, window) + max(seq_len - window, 0) \
        // config["chunk_size"]
    return seen >= 32 * head_dim(config)


def step_kernel_calls(config: Dict[str, Any], batch: int, seq_len: int,
                      remat: bool, blk_k: int = 512
                      ) -> Dict[str, Dict[str, float]]:
    """{kernel: {"calls", "flops", "bytes"}} of one training step: calls a
    step and one call's FLOPs and least bytes. ``eva_fwd`` once a layer,
    twice where the block is rematerialised and its outputs are not kept
    (``keeps_forward``); the two backward kernels once a layer."""
    layers = config["num_hidden_layers"]
    forward = 2 if remat and not keeps_forward(config, seq_len) else 1
    calls = {"eva_fwd": layers * forward, "eva_bwd_dq": layers,
             "eva_bwd_dkv": layers}
    return {kernel: dict(attention_call(kernel, config, batch, seq_len,
                                        blk_k), calls=count)
            for kernel, count in calls.items()}
