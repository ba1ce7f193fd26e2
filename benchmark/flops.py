"""The benchmark's own arithmetic: model FLOPs per token and chip peaks.

Kept here, under ``paths``, so that no PR that claims a gain can move the
yardstick. ``ray_tpu.models.gpt.flops_per_token`` is the program's figure
and is not used: it counts the input embedding table in N, which is a
lookup and multiplies nothing.
"""

from __future__ import annotations

import json
import os
from typing import Any, Dict

_PEAKS = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                      "peaks.json")


def matmul_params(n_layer: int, n_embd: int, n_inner: int, vocab_size: int,
                  n_head: int, head_dim: int) -> int:
    """Parameters that sit in a matrix multiplication of a GPT-J forward
    pass: per layer Wq, Wk, Wv, Wo and the two FFN matrices, then the
    untied output head. Biases, LayerNorm vectors and the input embedding
    (a lookup) multiply nothing and are left out."""
    attn = 4 * n_embd * n_head * head_dim
    ffn = 2 * n_embd * n_inner
    return n_layer * (attn + ffn) + n_embd * vocab_size


def train_flops_per_token(n_layer: int, n_embd: int, n_inner: int,
                          vocab_size: int, n_head: int, head_dim: int,
                          seq_len: int) -> float:
    """Model FLOPs one token costs in training: 6 per matmul parameter
    (2 forward, 4 backward) plus attention's scores and weighted sum,
    12 * L * d * S with d = n_head * head_dim: the full S x S product,
    causal skipping not credited, as the stated convention. Recomputed
    operations (remat, the chunked loss's second head matmul) do not
    count: they are the program's choice, not the model's need."""
    n = matmul_params(n_layer, n_embd, n_inner, vocab_size, n_head, head_dim)
    return 6.0 * n + 12.0 * n_layer * n_head * head_dim * seq_len


def model_flops_per_token(config: Dict[str, Any], seq_len: int) -> float:
    """``train_flops_per_token`` from a configuration file's published keys
    (``n_layer``, ``n_embd``, ``n_head``, ``n_inner`` or 4 * n_embd,
    ``vocab_size``)."""
    n_embd, n_head = config["n_embd"], config["n_head"]
    return train_flops_per_token(
        n_layer=config["n_layer"], n_embd=n_embd,
        n_inner=config.get("n_inner") or 4 * n_embd,
        vocab_size=config["vocab_size"], n_head=n_head,
        head_dim=n_embd // n_head, seq_len=seq_len)


def peak(device_kind: str, what: str = "bf16_flops_per_s") -> float:
    """A chip's published peak, by jax's ``device_kind``. Unknown: error."""
    with open(_PEAKS) as f:
        table = json.load(f)
    try:
        return float(table[device_kind][what])
    except (KeyError, TypeError):
        known = sorted(k for k in table if k != "source")
        raise ValueError(f"no published {what} for device_kind "
                         f"{device_kind!r}; known: {known}") from None
