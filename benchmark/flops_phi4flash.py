"""The benchmark's arithmetic for the ``phi4flash`` family: model FLOPs a
token by ``flops.py``'s convention, and the operations and bytes that the
Pallas kernels of its step (the selective scan's pair, the short
convolution's pair, flash attention with and without a window) execute.

The convention is ``flops.py``'s: 6 per parameter that sits in a matrix
multiplication a token goes through (2 forward, 4 backward; the short
convolution's taps among them, as ``flops_kimi_linear.py`` counts Kimi's),
the input embedding left out (a lookup; the tied table counts once, as the
head), recompute not counted. Attention's products are over the keys a
query sees (``sliding_window`` in a window layer, S in a full or a cross
layer: causal skipping not credited), ``q k^T`` at the head's width and ``p
V_g`` at twice it, for both softmax maps of every differential head: the
query heads. The recurrence is counted literally, 6 operations a state
element forward (``delta A``, the decay's product, ``(delta xs) B``, their
sum, the product with ``C`` and its sum over the states; the exponential is
no FLOP) and twice that backward. The gates, the norms and the subtraction
of the maps are elementwise and count nothing.

The layers that run are ``layers_run`` (published indices; all
``num_hidden_layers`` where the file does not say), the published depth
``reduced.num_hidden_layers.published`` where the file cuts it.

The ``executed`` functions count what a kernel really runs, for a roofline
share, and every call of a step as the step runs them: the forward kernels
twice where the block is rematerialised, but the flash forward once where
its outputs are kept for the backward pass
(``flash_attention.worth_keeping``: the full and the cross layers'; a
window layer's 63 tiles a head are run again).
"""

from __future__ import annotations

import math
from typing import Any, Dict, List, Optional

import flops_afmoe
import flops_deepseek
from flops_afmoe import least_seconds  # noqa: F401

#: Kernels of a window layer carry this suffix in the trace.
WINDOW_SUFFIX = "_win"
#: Literal operations of the recurrence a state element, forward.
RECURRENCE_OPS = 6


def published_depth(config: Dict[str, Any]) -> int:
    return config.get("reduced", {}).get("num_hidden_layers", {}).get(
        "published", config["num_hidden_layers"])


def layers_run(config: Dict[str, Any]) -> List[int]:
    return list(config.get("layers_run", range(published_depth(config))))


def layer_kind(config: Dict[str, Any], index: int) -> str:
    """``mamba``, ``window``, ``full``, ``gmu`` or ``cross``: the mixer of
    published layer ``index``."""
    middle = published_depth(config) // 2
    if index % 2 == 0:
        return "mamba" if index <= middle else "gmu"
    return "window" if index < middle else \
        "full" if index == middle + 1 else "cross"


def layer_counts(config: Dict[str, Any]) -> Dict[str, int]:
    kinds = [layer_kind(config, i) for i in layers_run(config)]
    return {kind: kinds.count(kind)
            for kind in ("mamba", "window", "full", "gmu", "cross")}


def longest_mamba_run(config: Dict[str, Any]) -> int:
    """Mamba layers of the longest run of pairs: the self pairs' (each pair's
    first layer; the middle pair's is a run of one)."""
    middle = published_depth(config) // 2
    run = layers_run(config)
    return sum(i % 2 == 0 and i < middle for i in run) \
        or int(middle in run)


def mamba_sizes(config: Dict[str, Any]) -> Dict[str, int]:
    """(d_inner, d_state, d_conv, dt_rank): the family's defaults unless the
    file's ``assumed.mamba_sizes`` says otherwise."""
    given = config.get("assumed", {}).get("mamba_sizes", {})
    d = config["hidden_size"]
    return {"d_inner": given.get("mamba_expand", 2) * d,
            "d_state": given.get("mamba_d_state", 16),
            "d_conv": given.get("mamba_d_conv", 4),
            "dt_rank": given.get("mamba_dt_rank") or math.ceil(d / 16)}


def mixer_params(config: Dict[str, Any], kind: str) -> int:
    """Matmul parameters of one layer's mixer (the convolution's taps among
    a Mamba layer's)."""
    d = config["hidden_size"]
    m = mamba_sizes(config)
    di = m["d_inner"]
    kv = d // config["num_attention_heads"] * config["num_key_value_heads"]
    return {
        "mamba": d * 2 * di + m["d_conv"] * di
        + di * (m["dt_rank"] + 2 * m["d_state"]) + m["dt_rank"] * di + di * d,
        "gmu": 2 * d * di,
        "window": 2 * d * d + 2 * d * kv,
        "full": 2 * d * d + 2 * d * kv,
        "cross": 2 * d * d,
    }[kind]


def matmul_params(config: Dict[str, Any]) -> int:
    """Matmul parameters one token goes through in a forward pass: every
    layer's mixer and SwiGLU, and the head (the tied table, once)."""
    d = config["hidden_size"]
    ffn = 3 * d * config["intermediate_size"]
    return sum(n * (mixer_params(config, kind) + ffn)
               for kind, n in layer_counts(config).items()) \
        + d * config["vocab_size"]


def all_params(config: Dict[str, Any]) -> int:
    """Every parameter held: ``matmul_params`` and the vectors (LayerNorm
    scales and biases, the attention's biases, lambdas and ``subln``, a
    Mamba layer's ``conv_b``, ``b_dt``, ``A_log`` and ``D``)."""
    d = config["hidden_size"]
    hd = d // config["num_attention_heads"]
    kv = hd * config["num_key_value_heads"]
    m = mamba_sizes(config)
    attention = 2 * d + 4 * hd + 2 * hd
    vectors = {"mamba": m["d_inner"] * (3 + m["d_state"]), "gmu": 0,
               "window": attention + 2 * kv, "full": attention + 2 * kv,
               "cross": attention}
    return matmul_params(config) + 2 * d + sum(
        n * (vectors[kind] + 4 * d)
        for kind, n in layer_counts(config).items())


def attention_flops_per_token(config: Dict[str, Any], seq_len: int) -> float:
    """Training FLOPs a token of every attention layer's two products: 6
    (2 forward, 4 backward) a query head, key seen and element of the head's
    width (``q k^T``) and of twice it (``p V_g``)."""
    n = layer_counts(config)
    heads = config["num_attention_heads"]
    hd = config["hidden_size"] // heads
    keys = n["window"] * min(seq_len, config["sliding_window"]) \
        + (n["full"] + n["cross"]) * seq_len
    return 6.0 * heads * keys * 3 * hd


def recurrence_flops_per_token(config: Dict[str, Any]) -> float:
    m = mamba_sizes(config)
    return 3.0 * RECURRENCE_OPS * m["d_inner"] * m["d_state"] \
        * layer_counts(config)["mamba"]


def model_flops_per_token(config: Dict[str, Any], seq_len: int) -> float:
    """Model FLOPs one token costs in training: 6 per matmul parameter, the
    attention's products over the keys a query sees, the literal
    recurrence."""
    return 6.0 * matmul_params(config) \
        + attention_flops_per_token(config, seq_len) \
        + recurrence_flops_per_token(config)


# -- what the kernels execute ----------------------------------------------

def selective_scan_call(kernel: str, config: Dict[str, Any], batch: int,
                        seq_len: int, itemsize: int = 2) -> Dict[str, float]:
    """Operations and least HBM bytes of one call of ``selective_scan_fwd``
    or ``selective_scan_bwd`` (``ray_tpu/ops/selective_scan.py``) on [batch,
    seq_len] tokens of d_inner channels and d_state states.

    Forward, a state element: the literal 6. Backward: the chunk's states
    again (4), the state's cotangent (2), the four sums that give ``C``'s,
    ``B``'s, ``delta xs``'s and ``delta``'s cotangents (2 each) and ``A``'s
    (2), and the decay's product (2): 20. These run on the vector unit, of
    which ``peaks.json`` has no peak: against the matrix unit's they are
    far under the bytes' time, so the least time is the bytes'.

    Bytes: each wide operand read and each result written once. Forward:
    ``xs``, ``delta`` and ``y``. Backward: ``xs``, ``delta``, ``dy`` and the
    two cotangents. B, C, the chunks' entry states (float32, N x d_inner a
    chunk of 256 tokens: an eighth of a wide array's bytes) and A's partial
    sums are left out."""
    m = mamba_sizes(config)
    cells = float(batch * seq_len * m["d_inner"])
    if kernel == "selective_scan_fwd":
        return {"flops": cells * m["d_state"] * RECURRENCE_OPS,
                "bytes": cells * 3 * itemsize}
    if kernel == "selective_scan_bwd":
        return {"flops": cells * m["d_state"] * 20,
                "bytes": cells * 5 * itemsize}
    raise ValueError(f"no such kernel: {kernel!r}")


def conv_silu_call(kernel: str, config: Dict[str, Any], batch: int,
                   seq_len: int, itemsize: int = 2) -> Dict[str, float]:
    """Operations and least HBM bytes of one call of ``conv_silu_fwd`` or
    ``conv_silu_bwd`` (``ray_tpu/ops/short_conv.py``) over d_inner columns
    with K taps and a bias. Forward: K products and K sums, the SiLU (4).
    Backward: the same again, the SiLU's derivative (4), the K taps of the
    way back (2 K - 1) and the taps' and the bias's cotangent (2 K + 1).
    Bytes: the columns read and the result written once (forward), the
    columns, ``dy`` and the columns' cotangent (backward)."""
    m = mamba_sizes(config)
    cells, taps = float(batch * seq_len * m["d_inner"]), m["d_conv"]
    if kernel == "conv_silu_fwd":
        return {"flops": cells * (2 * taps + 4),
                "bytes": cells * 2 * itemsize}
    if kernel == "conv_silu_bwd":
        return {"flops": cells * (6 * taps + 8),
                "bytes": cells * 3 * itemsize}
    raise ValueError(f"no such kernel: {kernel!r}")


def flash_call(kernel: str, config: Dict[str, Any], batch: int,
               seq_len: int, window: Optional[int], blk_q: int, blk_k: int
               ) -> Dict[str, float]:
    """FLOPs and least HBM bytes of one call of a flash kernel (``kernel``
    without the window's suffix) of a differential-attention layer: every
    query head against its own copy of a key head (the head's width) and of
    ``V_g`` (twice it), the executed tiles' products
    (``flops_deepseek.FLASH_PRODUCTS``); bytes as
    ``flops_deepseek.flash_call`` counts them."""
    heads = config["num_attention_heads"]
    hd = config["hidden_size"] // heads
    on_d, on_dv = flops_deepseek.FLASH_PRODUCTS[kernel]
    tiles = flops_afmoe.executed_tiles(seq_len, window, blk_q, blk_k)
    return {"flops": batch * heads * tiles * 2.0 * blk_q * blk_k
            * (on_d * hd + on_dv * 2 * hd),
            "bytes": flops_deepseek.flash_call(
                kernel, batch * heads, seq_len, hd, 2 * hd, blk_q, blk_k
            )["bytes"]}


def step_kernel_calls(config: Dict[str, Any], batch: int, seq_len: int,
                      blk_q: int, blk_k: int, remat: bool,
                      kept: Optional[Dict[str, bool]] = None
                      ) -> Dict[str, Dict[str, float]]:
    """{kernel: {"calls", "flops", "bytes"}} of one training step: calls a
    step and one call's FLOPs and least bytes. The scan's and the
    convolution's forward twice a Mamba layer with remat, their backward
    once; the flash kernels once an attention layer, the forward twice
    where remat runs it again: ``kept`` says of the window layers
    (``"window"``) and of the others (``"causal"``) whether the forward's
    outputs are kept (both, if not given). A window the sequence does not
    reach is causal attention, by the causal kernels."""
    n = layer_counts(config)
    again = 2 if remat else 1
    kept = kept or {}
    out = {}
    if n["mamba"]:
        for name, one in (("selective_scan", selective_scan_call),
                          ("conv_silu", conv_silu_call)):
            out[name + "_fwd"] = dict(one(name + "_fwd", config, batch,
                                          seq_len), calls=n["mamba"] * again)
            out[name + "_bwd"] = dict(one(name + "_bwd", config, batch,
                                          seq_len), calls=n["mamba"])
    window = config["sliding_window"]
    cuts = window < seq_len
    for suffix, layers, w, is_kept in (
            (WINDOW_SUFFIX, n["window"] if cuts else 0, window,
             kept.get("window", True)),
            ("", n["full"] + n["cross"] + (0 if cuts else n["window"]), None,
             kept.get("causal", True))):
        for kernel in flops_deepseek.FLASH_PRODUCTS if layers else ():
            forward_again = kernel == "flash_fwd" and remat and not is_kept
            out[kernel + suffix] = dict(
                flash_call(kernel, config, batch, seq_len, w, blk_q, blk_k),
                calls=layers * (2 if forward_again else 1))
    return out
