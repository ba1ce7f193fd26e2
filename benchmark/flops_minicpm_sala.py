"""The benchmark's arithmetic for the ``minicpm_sala`` family: model FLOPs a
token by ``flops.py``'s convention, the parameters a chip holds and the whole
published model's, the pairs block-sparse attention attends, and the
operations and bytes that the step's Mosaic kernels execute at the least:
``sala_fwd`` / ``sala_bwd_dq`` / ``sala_bwd_dkv`` (``ray_tpu/ops/
infllm.py``), ``lightning_fwd`` / ``lightning_bwd`` (``ops/lightning.py``)
and ``gated_norm_fwd`` / ``gated_norm_bwd`` (``ops/gated_norm.py``, behind
every linear-attention layer).

The convention is ``flops.py``'s: 6 per parameter that sits in a matrix
multiplication a token goes through (2 forward, 4 backward), the input
embedding left out (a lookup), recompute not counted. Beside the matrices:

* the sparse layers' attention over the **pairs the selection defines** at
  the configuration's sizes (every query keeps ``topk`` blocks, its own up
  to itself; every causal pair at or under ``dense_len``), two products a
  pair, times 3 for training. What the tiles compute and mask beyond them is
  work done and not credited.
* the compressed scores, ``q . Kc`` over the kernels visible to a query,
  forward only (no gradient passes the selection): 2 a product.
* the recurrence's four products a chunk of ``LIGHTNING_CHUNK`` positions
  (``Q K^T``, ``P V``, ``Q S``, ``K^T V``), times 3 for training. The chunk
  is the program's (``ops/lightning.py`` ``CHUNK``;
  ``tests/test_flops_minicpm_sala.py`` holds the two equal): a literal
  recurrence would count ``2 K V`` a token and head twice, 0.4 of this.

The kernels' ``least`` FLOPs and bytes are for a roofline share: a call's
products, each operand read and each result written once (the selection as
the block-level bytes it is kept as, not the token-level mask the kernels
are handed).
"""

from __future__ import annotations

from typing import Any, Dict, List

from flops_afmoe import least_seconds  # noqa: F401

#: Positions a chunk of the recurrence's kernels.
LIGHTNING_CHUNK = 256
KINDS = {"minicpm4": "sparse", "lightning-attn": "lightning"}


def layers(config: Dict[str, Any]) -> List[str]:
    """The kind of every layer that runs."""
    return [KINDS[m] for m in
            config["mixer_types"][:config["num_hidden_layers"]]]


def count(config: Dict[str, Any], kind: str) -> int:
    return layers(config).count(kind)


def layer_matmul_params(config: Dict[str, Any], kind: str) -> int:
    """The matrices of one layer: q, k, v, the output gate, o and the
    SwiGLU's three."""
    d, f = config["hidden_size"], config["intermediate_size"]
    if kind == "sparse":
        q = config["num_attention_heads"] * config["head_dim"]
        kv = config["num_key_value_heads"] * config["head_dim"]
    else:
        q = config["lightning_nh"] * config["lightning_head_dim"]
        kv = config["lightning_nkv"] * config["lightning_head_dim"]
    return d * (3 * q + 2 * kv) + 3 * d * f


def layer_params(config: Dict[str, Any], kind: str) -> int:
    """Every parameter of one layer: its matrices, two norms' scales, the
    q/k norms' and (linear attention) the output norm's."""
    hd = config["head_dim"] if kind == "sparse" \
        else config["lightning_head_dim"]
    return layer_matmul_params(config, kind) + 2 * config["hidden_size"] \
        + (2 if kind == "sparse" else 3) * hd


def head_params(config: Dict[str, Any]) -> int:
    return config["hidden_size"] * config["vocab_size"]


def held_params(config: Dict[str, Any]) -> int:
    """Everything the chip holds: the layers that run, the table, the final
    norm and the untied head."""
    return sum(layer_params(config, kind) for kind in layers(config)) \
        + 2 * head_params(config) + config["hidden_size"]


def published_params(config: Dict[str, Any]) -> int:
    """The whole published model by the same count: every key a file cut
    (``reduced``) at its published value."""
    return held_params(dict(config, **{
        key: cut["published"] for key, cut in
        config.get("reduced", {}).items()}))


def causal_pairs(seq_len: int) -> int:
    return seq_len * (seq_len + 1) // 2


def selected_pairs(config: Dict[str, Any], seq_len: int) -> int:
    """(query, key) pairs a head of one sequence attends: 58,335,232 at
    16384 with the top 64 blocks of 64."""
    sparse = config["assumed"]["sparse_config"]
    block, topk = sparse["block_size"], sparse["topk"]
    if seq_len <= sparse["dense_len"]:
        return causal_pairs(seq_len)
    return sum((min(t // block + 1, topk) - 1) * block + t % block + 1
               for t in range(seq_len))


def selected_share(config: Dict[str, Any], seq_len: int) -> float:
    """What ``sala.selected_share`` has to read."""
    return selected_pairs(config, seq_len) / causal_pairs(seq_len)


def visible_kernels(config: Dict[str, Any], seq_len: int) -> int:
    """(query, compressed key) pairs a head scores: the kernels that lie
    wholly at or before the query; none at or under ``dense_len``."""
    sparse = config["assumed"]["sparse_config"]
    kernel, stride = sparse["kernel_size"], sparse["kernel_stride"]
    if seq_len <= sparse["dense_len"]:
        return 0
    n = (seq_len - kernel) // stride + 1
    return sum(min(max((t - kernel + 1) // stride + 1, 0), n)
               for t in range(seq_len))


def recurrence_flops(config: Dict[str, Any]) -> float:
    """Forward FLOPs a token of one linear-attention layer's chunked
    recurrence: the four products a chunk, over its positions."""
    hd, L = config["lightning_head_dim"], LIGHTNING_CHUNK
    return config["lightning_nh"] * 2.0 * (2 * L * hd + 2 * hd * hd)


def flops_by_part(config: Dict[str, Any], seq_len: int) -> Dict[str, float]:
    """Model FLOPs a token in training, by part."""
    sparse, linear = count(config, "sparse"), count(config, "lightning")
    heads, hd = config["num_attention_heads"], config["head_dim"]
    d, f = config["hidden_size"], config["intermediate_size"]
    return {
        "sparse_projections": 6.0 * sparse * (
            layer_matmul_params(config, "sparse") - 3 * d * f),
        "sparse_attention_over_pairs": 6.0 * sparse * heads * 2 * hd
        * selected_pairs(config, seq_len) / seq_len,
        "compressed_scores": 2.0 * sparse * heads * hd
        * visible_kernels(config, seq_len) / seq_len,
        "lightning_projections": 6.0 * linear * (
            layer_matmul_params(config, "lightning") - 3 * d * f),
        "recurrence": 3.0 * linear * recurrence_flops(config),
        "ffn": 6.0 * (sparse + linear) * 3 * d * f,
        "head": 6.0 * head_params(config),
    }


def model_flops_per_token(config: Dict[str, Any], seq_len: int) -> float:
    return sum(flops_by_part(config, seq_len).values())


# -- what the kernels execute at the least ----------------------------------

#: ``ops/infllm.py``'s kernels, per pair, in units of 2 x head_dim.
SALA_PRODUCTS = {"sala_fwd": 2,       # q k^T, p v
                 "sala_bwd_dq": 3,    # q k^T, ds k, dO v^T
                 "sala_bwd_dkv": 4}   # q k^T, ds^T q, p^T dO, dO v^T
#: [S, heads, head_dim] arrays each reads or writes a query head (q, out,
#: dO, dq; dk and dv leave a query head) and a KV head (k, v).
SALA_ARRAYS = {"sala_fwd": (2, 2), "sala_bwd_dq": (3, 2),
               "sala_bwd_dkv": (4, 2)}
#: ``ops/lightning.py``'s kernels: products a chunk over [L, L] and over
#: [head_dim, head_dim], and [S, heads, head_dim] arrays moved.
LIGHTNING_PRODUCTS = {"lightning_fwd": (2, 2, 4), "lightning_bwd": (5, 4, 7)}
#: ``ops/gated_norm.py``'s: [S, width] arrays moved (no product).
NORM_ARRAYS = {"gated_norm_fwd": 3, "gated_norm_bwd": 5}


def sala_call(kernel: str, config: Dict[str, Any], batch: int, seq_len: int,
              itemsize: int = 2) -> Dict[str, float]:
    heads, hd = config["num_attention_heads"], config["head_dim"]
    groups = config["num_key_value_heads"]
    per_head, per_group = SALA_ARRAYS[kernel]
    blocks = seq_len // config["assumed"]["sparse_config"]["block_size"]
    return {"flops": batch * heads * selected_pairs(config, seq_len)
            * 2.0 * hd * SALA_PRODUCTS[kernel],
            "bytes": float(batch * seq_len * (
                hd * itemsize * (per_head * heads + per_group * groups)
                + groups * blocks))}


def lightning_call(kernel: str, config: Dict[str, Any], batch: int,
                   seq_len: int, itemsize: int = 2) -> Dict[str, float]:
    heads, hd = config["lightning_nh"], config["lightning_head_dim"]
    L = LIGHTNING_CHUNK
    square, state, arrays = LIGHTNING_PRODUCTS[kernel]
    chunks = seq_len // L
    return {"flops": batch * heads * chunks * 2.0
            * (square * L * L * hd + state * L * hd * hd),
            "bytes": float(batch * heads * (
                arrays * seq_len * hd * itemsize + chunks * hd * hd * 4))}


def norm_call(kernel: str, config: Dict[str, Any], batch: int, seq_len: int,
              itemsize: int = 2) -> Dict[str, float]:
    width = config["lightning_nh"] * config["lightning_head_dim"]
    return {"flops": 0.0, "bytes": float(
        NORM_ARRAYS[kernel] * batch * seq_len * width * itemsize)}


def keeps_forward(config: Dict[str, Any], seq_len: int) -> bool:
    """``ops/flash_attention.worth_keeping``'s rule as ``ops/infllm.py``
    asks it: ``sala_fwd``'s outputs survive remat where the most keys a
    query sees (``topk`` blocks) are 32 head_dim or more."""
    sparse = config["assumed"]["sparse_config"]
    return min(seq_len, sparse["topk"] * sparse["block_size"]) \
        >= 32 * config["head_dim"]


def step_kernel_calls(config: Dict[str, Any], batch: int, seq_len: int,
                      remat: bool) -> Dict[str, Dict[str, float]]:
    """{kernel: {"calls", "flops", "bytes"}} of one training step over
    ``dense_len``: calls a step and one call's FLOPs and least bytes. A
    forward kernel runs twice a layer where the block is rematerialised and
    its outputs are not kept (``keeps_forward`` for ``sala_fwd``; never for
    the recurrence and the gated norm); a backward kernel once."""
    sparse, linear = count(config, "sparse"), count(config, "lightning")
    twice = 2 if remat else 1
    sala_fwd = 1 if keeps_forward(config, seq_len) else twice
    calls = {"sala_fwd": (sparse * sala_fwd, sala_call),
             "sala_bwd_dq": (sparse, sala_call),
             "sala_bwd_dkv": (sparse, sala_call),
             "lightning_fwd": (linear * twice, lightning_call),
             "lightning_bwd": (linear, lightning_call),
             "gated_norm_fwd": (linear * twice, norm_call),
             "gated_norm_bwd": (linear, norm_call)}
    return {kernel: dict(call(kernel, config, batch, seq_len), calls=n)
            for kernel, (n, call) in calls.items() if n}
