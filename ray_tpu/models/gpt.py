"""GPT family — the flagship model (GPT-J-6B architecture), TPU-first.

This is the model behind the north-star benchmark (BASELINE.json: GPT-J-6B
fine-tune at ≥40% MFU): rotary position embeddings and the GPT-J *parallel*
residual block (one LayerNorm feeding attention and MLP simultaneously —
one fewer sequential matmul chain, friendlier to MXU pipelining). Design
choices for TPU:

* **Pure-pytree params + functional apply** — no module framework between
  the arrays and GSPMD; every parameter carries a logical-axis name so
  sharding is a `ShardingRules` table (parallel/sharding.py).
* **`lax.scan` over stacked layer params** — one compiled block body
  regardless of depth: O(1) XLA compile time, and GSPMD shards the stacked
  weights with a leading `layers` axis.
* **bf16 activations/matmuls, fp32 softmax & layernorm accumulation** —
  MXU-native without numerics drift.
* **Static shapes everywhere**; causal masking via iota comparison, no
  dynamic slicing in the hot path.

The lookup, the layer scan, the attention dispatch and the chunked head and
loss are ``models/lm.py``'s, shared with every other language model here;
the sliced block's exchanges over tp are ``models/exchange.py``'s.

Capability parity note: the reference has no model zoo of its own (models
come from torch); this module is the JAX equivalent of what
`transformers.GPTJForCausalLM` provides to the reference's Train examples
(reference: release/air_tests/air_benchmarks/workloads/torch_benchmark.py
trains torchvision models; the GPT-J fine-tune config is driver-supplied).
"""

from __future__ import annotations

import math
from dataclasses import dataclass, replace
from functools import partial
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
from jax.ad_checkpoint import checkpoint_name

from ray_tpu.models import exchange, lm
from ray_tpu.parallel.mesh import current_rules
from ray_tpu.parallel.sharding import ShardingRules, constrain


@dataclass(frozen=True)
class GPTConfig:
    vocab_size: int = 50400
    n_layers: int = 28
    d_model: int = 4096
    n_heads: int = 16
    n_kv_heads: Optional[int] = None  # != n_heads → GQA/MQA
    d_ff: int = 16384
    max_seq_len: int = 2048
    rotary_dim: int = 64  # GPT-J applies rotary to a prefix of head_dim
    parallel_block: bool = True  # GPT-J parallel attn+MLP residual
    tie_embeddings: bool = False
    dtype: Any = jnp.bfloat16  # activation/compute dtype
    param_dtype: Any = jnp.float32
    remat: bool = True  # checkpoint each block (HBM ⇄ FLOPs trade)
    # "full": save block boundaries and, at long sequences, the flash
    # forward kernel's output and log-sum-exp (O(S) bytes that cost an
    # O(S^2) kernel to get back: lm.scan_blocks), recompute everything
    # else in backward (lowest memory). "selective": additionally save the named tensors tagged in
    # _block (rotary q/k/v, attention output, pre-activation FFN) — the
    # expensive-to-recompute matmul outputs — cutting backward recompute
    # to layernorms and the elementwise rest for ~2.5x less activation
    # memory than no remat at all.
    remat_policy: str = "full"  # "full" | "selective"
    # Tokens per cross-entropy chunk (0 = unchunked). The [tokens, vocab]
    # fp32 logits and their cotangent are the single largest activation in
    # training; chunking streams them through a lax.scan so peak HBM holds
    # one chunk instead of the full batch. Nothing is recomputed for it: the
    # one walk forms each chunk's d x and d W while its logits are there
    # (lm.chunked_ce).
    loss_chunk: int = 0
    attn_impl: str = "dot"  # "dot" | "flash" | "ring" | "ulysses"
    # Flash-attention tile sizes. 512x512 keeps both the Q tile and the
    # streamed KV tile comfortably in VMEM on v5e (measured ~4% faster
    # than 1024x1024 on the 410M single-chip recipe); _pick_block clamps
    # them for short sequences.
    attn_blk_q: int = 512
    attn_blk_k: int = 512
    layernorm_eps: float = 1e-5

    @property
    def head_dim(self) -> int:
        return self.d_model // self.n_heads

    @property
    def kv_heads(self) -> int:
        return self.n_kv_heads or self.n_heads

    def num_params(self) -> int:
        d, f, v, L = self.d_model, self.d_ff, self.vocab_size, self.n_layers
        kvh = self.kv_heads * self.head_dim
        per_layer = d * d + 2 * d * kvh + d * d + (2 * d * f + f) + d + 2 * d
        head = 0 if self.tie_embeddings else v * d + v
        return v * d + L * per_layer + 2 * d + head


# -- presets ------------------------------------------------------------

PRESETS: Dict[str, GPTConfig] = {
    # The north-star model (matches EleutherAI/gpt-j-6b hyperparameters).
    "gptj-6b": GPTConfig(),
    # Single-v5e-chip benchmark model.
    "gpt-410m": GPTConfig(
        vocab_size=50304, n_layers=24, d_model=1024, n_heads=16,
        d_ff=4096, rotary_dim=32, max_seq_len=1024),
    "gpt2-124m": GPTConfig(
        vocab_size=50304, n_layers=12, d_model=768, n_heads=12, d_ff=3072,
        rotary_dim=32, max_seq_len=1024),
    # HBM-pressure benchmark model (GPT-neo-1.3B dims): adam state for
    # 1.3B params (~10GB fp32 moments) cannot fit a 16GB chip next to
    # params+grads — pairs with train_step.memory_efficient_optimizer
    # (factored second moments) for the single-chip bench.
    "gpt-1.3b": GPTConfig(
        vocab_size=50304, n_layers=24, d_model=2048, n_heads=16,
        d_ff=8192, rotary_dim=64, max_seq_len=1024),
    # Largest single-16GB-chip trainable point on the way to gptj-6b
    # (GPT-neo-2.7B dims): bf16 params (5.3GB) + grads (5.3GB) +
    # factored moments fit; the 6b config's params+grads alone are
    # 24.2GB (see bench.py gptj6b feasibility probe).
    "gpt-2.7b": GPTConfig(
        vocab_size=50304, n_layers=32, d_model=2560, n_heads=32,
        d_ff=10240, rotary_dim=64, max_seq_len=1024),
    # Test-size configs.
    "gpt-tiny": GPTConfig(
        vocab_size=256, n_layers=2, d_model=64, n_heads=4, d_ff=128,
        rotary_dim=8, max_seq_len=128, dtype=jnp.float32, remat=False),
    "gpt-micro": GPTConfig(
        vocab_size=512, n_layers=4, d_model=128, n_heads=8, d_ff=512,
        rotary_dim=16, max_seq_len=256, dtype=jnp.float32, remat=False),
}


def config(name: str, **overrides) -> GPTConfig:
    cfg = PRESETS[name]
    return replace(cfg, **overrides) if overrides else cfg


# -- parameter init + sharding specs -----------------------------------

def init(cfg: GPTConfig, key: jax.Array) -> Dict[str, Any]:
    """Initialize parameters (GPT-2-style scaled normal init)."""
    k_embed, k_layers, k_head = jax.random.split(key, 3)
    d, f, v, L = cfg.d_model, cfg.d_ff, cfg.vocab_size, cfg.n_layers
    h, kvh, hd = cfg.n_heads, cfg.kv_heads, cfg.head_dim
    pd = cfg.param_dtype
    std = 0.02
    out_std = std / math.sqrt(2 * L)

    def norm(k, shape, s=std):
        return (jax.random.normal(k, shape, jnp.float32) * s).astype(pd)

    ks = jax.random.split(k_layers, 6)

    def stack(k, shape, s=std):
        # One leading layers axis for lax.scan.
        return norm(k, (L,) + shape, s)

    layers = {
        "ln1_scale": jnp.ones((L, d), pd),
        "ln1_bias": jnp.zeros((L, d), pd),
        "wq": stack(ks[0], (d, h, hd)),
        "wk": stack(ks[1], (d, kvh, hd)),
        "wv": stack(ks[2], (d, kvh, hd)),
        "wo": stack(ks[3], (h, hd, d), out_std),
        "b_out": jnp.zeros((L, d), pd),
        "w_in": stack(ks[4], (d, f)),
        "b_in": jnp.zeros((L, f), pd),
        "w_out": stack(ks[5], (f, d), out_std),
    }
    if not cfg.parallel_block:
        layers["ln2_scale"] = jnp.ones((L, d), pd)
        layers["ln2_bias"] = jnp.zeros((L, d), pd)
    params = {
        "wte": norm(k_embed, (v, d)),
        "layers": layers,
        "lnf_scale": jnp.ones((d,), pd),
        "lnf_bias": jnp.zeros((d,), pd),
    }
    if not cfg.tie_embeddings:
        params["lm_head"] = norm(k_head, (d, v))
        params["lm_head_bias"] = jnp.zeros((v,), pd)
    return params


def param_specs(cfg: GPTConfig, rules: ShardingRules) -> Dict[str, Any]:
    """PartitionSpec pytree matching init()'s structure."""
    r = rules
    layers = {
        "ln1_scale": r.spec("layers", "embed"),
        "ln1_bias": r.spec("layers", "embed"),
        "wq": r.spec("layers", "embed", "heads", "head_dim"),
        "wk": r.spec("layers", "embed", "kv_heads", "head_dim"),
        "wv": r.spec("layers", "embed", "kv_heads", "head_dim"),
        "wo": r.spec("layers", "heads", "head_dim", "embed"),
        "b_out": r.spec("layers", "embed"),
        "w_in": r.spec("layers", "embed", "mlp"),
        "b_in": r.spec("layers", "mlp"),
        "w_out": r.spec("layers", "mlp", "embed"),
    }
    if not cfg.parallel_block:
        layers["ln2_scale"] = r.spec("layers", "embed")
        layers["ln2_bias"] = r.spec("layers", "embed")
    specs = {
        "wte": r.spec("vocab", "embed"),
        "layers": layers,
        "lnf_scale": r.spec("embed"),
        "lnf_bias": r.spec("embed"),
    }
    if not cfg.tie_embeddings:
        specs["lm_head"] = r.spec("embed", "vocab")
        specs["lm_head_bias"] = r.spec("vocab")
    return specs


# -- forward ------------------------------------------------------------

def _layernorm(x, scale, bias, eps):
    x32 = x.astype(jnp.float32)
    mu = x32.mean(-1, keepdims=True)
    var = ((x32 - mu) ** 2).mean(-1, keepdims=True)
    y = (x32 - mu) * jax.lax.rsqrt(var + eps)
    return (y * scale.astype(jnp.float32)
            + bias.astype(jnp.float32)).astype(x.dtype)


def _rotary(x, positions, rotary_dim):
    """Rotary embedding on the first rotary_dim dims of each head, pairing
    dimension i with i + rotary_dim/2 (the GPT-NeoX "rotate half" pairing).
    The published GPT-J pairs 2i with 2i+1: same frequencies, and the same
    result up to a fixed permutation of Wq's and Wk's columns inside each
    head (the GPT-J configuration files list it as a departure).
    x: [B, S, H, D], positions: [B, S]."""
    if rotary_dim == 0:
        return x
    rot, rest = x[..., :rotary_dim], x[..., rotary_dim:]
    half = rotary_dim // 2
    freqs = 1.0 / (10000.0 ** (jnp.arange(half, dtype=jnp.float32) / half))
    angles = positions[..., None].astype(jnp.float32) * freqs  # [B, S, half]
    cos = jnp.cos(angles)[:, :, None, :].astype(x.dtype)
    sin = jnp.sin(angles)[:, :, None, :].astype(x.dtype)
    x1, x2 = rot[..., :half], rot[..., half:]
    rot_out = jnp.concatenate(
        [x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)
    return jnp.concatenate([rot_out, rest], axis=-1)


def _attend(cfg: GPTConfig, q, k, v, positions):
    q = checkpoint_name(_rotary(q, positions, cfg.rotary_dim), "attn_q")
    k = checkpoint_name(_rotary(k, positions, cfg.rotary_dim), "attn_k")
    v = checkpoint_name(v, "attn_v")
    return checkpoint_name(lm.attention(q, k, v, cfg), "attn_raw")


def _mlp_in(cfg: GPTConfig, h, layer):
    return checkpoint_name(
        jnp.einsum("bsd,df->bsf", h, layer["w_in"].astype(cfg.dtype)),
        "ffn_in")


def _mlp_out(cfg: GPTConfig, ff, layer):
    dt = cfg.dtype
    ff = jax.nn.gelu(ff + layer["b_in"].astype(dt))
    return jnp.einsum("bsf,fd->bsd", ff, layer["w_out"].astype(dt))


def _block(cfg: GPTConfig, x, layer, positions):
    """One transformer block. x: [B, S, D]. Returns (x, None):
    ``lm.scan_blocks``' contract, and this block has nothing to stack.
    Its sums over tp are the partitioner's; on a mesh whose tp divides S
    the flash path runs ``_block_on_slices`` instead."""
    dt = cfg.dtype
    h = _layernorm(x, layer["ln1_scale"], layer["ln1_bias"],
                   cfg.layernorm_eps)
    # Scope names are metadata: they name the operations in a profile.
    with jax.named_scope("attention"):
        q = jnp.einsum("bsd,dhk->bshk", h, layer["wq"].astype(dt))
        k = jnp.einsum("bsd,dhk->bshk", h, layer["wk"].astype(dt))
        v = jnp.einsum("bsd,dhk->bshk", h, layer["wv"].astype(dt))
        attn = _attend(cfg, q, k, v, positions)
        attn_out = jnp.einsum("bshk,hkd->bsd", attn, layer["wo"].astype(dt))

    if cfg.parallel_block:
        mlp_in = h  # GPT-J: shared LN feeds both branches
    else:
        x = x + attn_out
        mlp_in = _layernorm(x, layer["ln2_scale"], layer["ln2_bias"],
                            cfg.layernorm_eps)
    with jax.named_scope("mlp"):
        mlp_out = _mlp_out(cfg, _mlp_in(cfg, mlp_in, layer), layer)

    b_out = layer["b_out"].astype(dt)
    if cfg.parallel_block:
        # Under tp both products are partial sums. Added to each other
        # before anything else they are reduced over tp together: one
        # all-reduce of [B, S, d] a layer, not one each.
        return x + ((attn_out + mlp_out) + b_out), None
    return x + (mlp_out + b_out), None


def _block_on_slices(cfg: GPTConfig, x, layer, positions):
    """``_block`` per shard of tp (``exchange.exchanged_over_tp``): x is this
    chip's slice of S, [B, S / tp, D], in and out; ``layer`` holds this
    chip's heads and columns of the MLP; positions are whole. The same
    products in the same dtypes on the same rows, in an order that lets
    the block's tp traffic travel beside them (a slice takes 1.6 ms on
    the wire, and every collective issued behind it waits for it):

    * the norm runs on the chip's own rows (a row of d is whole here);
    * its rows go round the ring (``exchange.gathered_product``): q, k and v of
      the slice in hand first, 0.7 ms each, during which the partitioner
      gathers the next weights; then the slice is sent on beside the MLP's
      first product on it, 2.9 ms, which needs nothing else from the wire.
      The MLP is per token: its hidden activations stay in slices;
    * q, k and v are placed once (``exchange.ring_place``) for the kernels,
      which take whole sequences and this chip's heads, as [b, h, s, k]:
      the order the products leave them in and the kernels take them in;
    * ``attn @ wo + ff @ w_out`` is one ``exchange.scattered_product``: the
      other chips' slices first, each partial sum sent on while this chip's
      own slice is multiplied (0.8 + 3.2 ms), then added to what arrives. In
      the backward pass its cotangent goes back once the recomputed
      forward's exchange has landed (``landed``), and its own slice's
      products run after their recomputed inputs.

    A parallel block is one gather and one sum; a sequential one needs
    x + attention before its second norm: two of each, through the same
    helpers. Autodiff's transposes are exchanges too: two a layer forward,
    three backward beside the two recomputed, all asynchronous (on four
    v5e chips 2,561 ms a GPT-J step against 2,683 with the two sums as
    all-reduces; my chip runs, PR 32)."""
    dt = cfg.dtype
    h = _layernorm(x, layer["ln1_scale"], layer["ln1_bias"],
                   cfg.layernorm_eps)
    # [b, h, s, k]: the order the products leave their heads in, and the
    # kernels take them in; S is axis 2 there.
    qkv = [lambda rows, w=w: jnp.einsum("bsd,dhk->bhsk", rows,
                                        layer[w].astype(dt))
           for w in ("wq", "wk", "wv")]

    def attend(parts):
        q, k, v = (a.transpose(0, 2, 1, 3) for a in exchange.ring_place(
            [tuple(part[:3]) for part in parts], 2))
        attn = _attend(cfg, q, k, v, positions)
        return exchange.ring_split(attn.transpose(0, 2, 1, 3), 2)

    def attn_out(attn, layer):
        return jnp.einsum("bhsk,hkd->bsd", attn, layer["wo"].astype(dt))

    with jax.named_scope("attention"):
        parts = exchange.gathered_product(
            h, qkv + [lambda rows: _mlp_in(cfg, rows, layer)]
            if cfg.parallel_block else qkv)
        attn = attend(parts)
    # What the row-split products take of the layer; the first product on
    # the slice that arrives last says the forward's exchange has landed.
    shared = {name: layer[name] for name in ("wo", "b_in", "w_out")}
    landed = parts[-1][0]
    b_out = layer["b_out"].astype(dt)
    if cfg.parallel_block:
        with jax.named_scope("mlp"):
            out = exchange.scattered_product(
                lambda inputs, layer: attn_out(inputs[0], layer)
                + _mlp_out(cfg, inputs[1], layer),
                [(attn_t, part[3]) for attn_t, part in zip(attn, parts)],
                shared, after=landed)
        return x + (out + b_out), None
    with jax.named_scope("attention"):
        x = x + exchange.scattered_product(attn_out, attn, shared,
                                           after=landed)
    h = _layernorm(x, layer["ln2_scale"], layer["ln2_bias"],
                   cfg.layernorm_eps)
    with jax.named_scope("mlp"):
        parts = exchange.gathered_product(
            h, [lambda rows: _mlp_in(cfg, rows, layer)])
        out = exchange.scattered_product(
            lambda ff, layer: _mlp_out(cfg, ff, layer),
            [part[0] for part in parts], shared)
    return x + (out + b_out), None


def _exchange_mesh(cfg: GPTConfig, seq_len: int):
    """``exchange.tp_exchange_mesh`` if this config's heads and MLP split
    over its tp evenly, else None."""
    mesh = exchange.tp_exchange_mesh(cfg, seq_len)
    if mesh is None:
        return None
    tp = mesh.shape["tp"]
    fits = not (cfg.n_heads % tp or cfg.kv_heads % tp or cfg.d_ff % tp)
    return mesh if fits else None


def hidden_states(params: Dict[str, Any], cfg: GPTConfig,
                  tokens: jax.Array,
                  positions: Optional[jax.Array] = None) -> jax.Array:
    """tokens [B, S] int32 → final-layernormed hidden [B, S, d].

    Where the mesh and S allow (``exchange.tp_exchange_mesh``) the residual
    stream is split over tp along S from the lookup to the scan's exit and
    the blocks run on slices (``_block_on_slices``); it is gathered once,
    before the final norm and the vocabulary-parallel head."""
    if positions is None:
        positions = lm.positions_of(tokens)
    mesh, layers = _exchange_mesh(cfg, tokens.shape[1]), params["layers"]
    if mesh is None:
        block, stream = partial(_block, cfg), "sequence"
    else:
        (block, layers), stream = exchange.exchanged_over_tp(
            partial(_block_on_slices, cfg), mesh, layers, param_specs(
                cfg, current_rules() or ShardingRules())["layers"]), "stream"
    x = lm.embed(params["wte"], tokens, cfg.dtype, stream)
    x, _ = lm.scan_blocks(cfg, block, x, layers, positions)
    x = constrain(x, "batch", "sequence", None)
    return _layernorm(x, params["lnf_scale"], params["lnf_bias"],
                      cfg.layernorm_eps)


def _head(params: Dict[str, Any], cfg: GPTConfig, x: jax.Array) -> jax.Array:
    """Hidden [..., d] → logits [..., vocab] (compute dtype)."""
    if cfg.tie_embeddings:
        return jnp.einsum("...d,vd->...v", x, params["wte"].astype(cfg.dtype))
    logits = jnp.einsum("...d,dv->...v", x, params["lm_head"].astype(cfg.dtype))
    return logits + params["lm_head_bias"].astype(cfg.dtype)


def forward(params: Dict[str, Any], cfg: GPTConfig, tokens: jax.Array,
            positions: Optional[jax.Array] = None) -> jax.Array:
    """tokens [B, S] int32 → logits [B, S, vocab] (compute dtype)."""
    return _head(params, cfg, hidden_states(params, cfg, tokens, positions))


def loss_fn(params: Dict[str, Any], cfg: GPTConfig, tokens: jax.Array,
            targets: jax.Array, mask: Optional[jax.Array] = None,
            z_loss: float = 0.0) -> Tuple[jax.Array, Dict[str, jax.Array]]:
    """Next-token cross-entropy in fp32 (+ optional z-loss regularizer).

    With ``cfg.loss_chunk > 0`` the head matmul + fp32 softmax run chunked
    (see ``lm.chunked_ce`` and GPTConfig.loss_chunk); ``lm.chunked_ce``
    hoists the head's parameters out of the closure it is given here."""
    x = hidden_states(params, cfg, tokens)
    head = partial(_head, lm.head_gathered(params, cfg.tie_embeddings), cfg)
    return lm.next_token_loss(head, x, targets, mask, cfg.loss_chunk, z_loss)


def flops_per_token(cfg: GPTConfig) -> float:
    """Approximate training FLOPs/token (6N + attention quadratic term)."""
    attn = 12 * cfg.n_layers * cfg.d_model * cfg.max_seq_len
    return 6.0 * cfg.num_params() + attn
