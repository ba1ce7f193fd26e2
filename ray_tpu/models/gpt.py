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
from jax.sharding import PartitionSpec

from ray_tpu.parallel.sharding import ShardingRules, ambient_spec, constrain


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
    # "full": save only block boundaries, recompute everything in backward
    # (lowest memory). "selective": additionally save the named tensors
    # tagged in _block (rotary q/k/v, attention output, pre-activation FFN)
    # — the expensive-to-recompute matmul outputs — cutting backward
    # recompute to layernorms + the attention quadratic term for ~2.5x less
    # activation memory than no remat at all.
    remat_policy: str = "full"  # "full" | "selective"
    # Tokens per cross-entropy chunk (0 = unchunked). The [tokens, vocab]
    # fp32 logits and their cotangent are the single largest activation in
    # training; chunking streams them through a lax.scan so peak HBM holds
    # one chunk instead of the full batch (each chunk's logits matmul is
    # recomputed in backward — ~2*d*vocab extra FLOPs/token, a few percent).
    loss_chunk: int = 0
    attn_impl: str = "dot"  # "dot" | "flash" | "ring" | "ulysses"
    # Flash-attention tile sizes. 512x512 keeps both the Q tile and the
    # streamed KV tile comfortably in VMEM on v5e (measured ~4% faster
    # than 1024x1024 on the 410M single-chip recipe); _pick_block clamps
    # them for short sequences.
    attn_blk_q: int = 512
    attn_blk_k: int = 512
    layernorm_eps: float = 1e-5
    # Mixture-of-experts: n_experts > 0 replaces every block's dense FFN
    # with a top-k routed MoE FFN (expert weights sharded over the "ep"
    # mesh axis; dispatch/combine einsums lower to ICI all-to-all under
    # GSPMD). The reference has no EP at all (SURVEY.md §2.5) — this is a
    # new TPU-native capability.
    n_experts: int = 0
    expert_top_k: int = 2
    capacity_factor: float = 1.25
    router_aux_weight: float = 0.01

    @property
    def is_moe(self) -> bool:
        return self.n_experts > 0

    @property
    def head_dim(self) -> int:
        return self.d_model // self.n_heads

    @property
    def kv_heads(self) -> int:
        return self.n_kv_heads or self.n_heads

    def num_params(self) -> int:
        d, f, v, L = self.d_model, self.d_ff, self.vocab_size, self.n_layers
        kvh = self.kv_heads * self.head_dim
        if self.n_experts:
            ffn = self.n_experts * (2 * d * f + f) + d * self.n_experts
        else:
            ffn = 2 * d * f + f
        per_layer = d * d + 2 * d * kvh + d * d + ffn + d + 2 * d
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
    # MoE variants (expert parallelism over the "ep" mesh axis).
    "gpt-moe-tiny": GPTConfig(
        vocab_size=256, n_layers=2, d_model=64, n_heads=4, d_ff=128,
        rotary_dim=8, max_seq_len=128, dtype=jnp.float32, remat=False,
        n_experts=4),
    "gpt-moe-8x410m": GPTConfig(
        vocab_size=50304, n_layers=24, d_model=1024, n_heads=16,
        d_ff=4096, rotary_dim=32, max_seq_len=1024, n_experts=8),
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
    }
    if cfg.is_moe:
        E = cfg.n_experts
        layers["router"] = stack(ks[4], (d, E))
        k_in, k_out = jax.random.split(ks[5])
        layers["w_in"] = norm(k_in, (L, E, d, f))
        layers["b_in"] = jnp.zeros((L, E, f), pd)
        layers["w_out"] = norm(k_out, (L, E, f, d), out_std)
    else:
        layers["w_in"] = stack(ks[4], (d, f))
        layers["b_in"] = jnp.zeros((L, f), pd)
        layers["w_out"] = stack(ks[5], (f, d), out_std)
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
    }
    if cfg.is_moe:
        layers["router"] = r.spec("layers", "embed", None)
        layers["w_in"] = r.spec("layers", "expert", "embed", "mlp")
        layers["b_in"] = r.spec("layers", "expert", "mlp")
        layers["w_out"] = r.spec("layers", "expert", "mlp", "embed")
    else:
        layers["w_in"] = r.spec("layers", "embed", "mlp")
        layers["b_in"] = r.spec("layers", "mlp")
        layers["w_out"] = r.spec("layers", "mlp", "embed")
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


def batch_spec(rules: ShardingRules) -> PartitionSpec:
    return rules.spec("batch", "sequence")


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


def _dot_attention(q, k, v):
    """Causal attention; fp32 softmax. q: [B, S, H, D], k: [B, S, KVH, D],
    v: [B, S, KVH, Dv] (Dv may differ from D) -> [B, S, H, Dv]."""
    B, S, H, D = q.shape
    kvh = k.shape[2]
    if kvh != H:  # GQA: repeat KV heads
        rep = H // kvh
        k = jnp.repeat(k, rep, axis=2)
        v = jnp.repeat(v, rep, axis=2)
    scale = 1.0 / math.sqrt(D)
    logits = jnp.einsum("bqhd,bkhd->bhqk", q, k) * scale
    logits = logits.astype(jnp.float32)
    qpos = jax.lax.broadcasted_iota(jnp.int32, (S, S), 0)
    kpos = jax.lax.broadcasted_iota(jnp.int32, (S, S), 1)
    causal = qpos >= kpos
    logits = jnp.where(causal[None, None], logits, -1e30)
    probs = jax.nn.softmax(logits, axis=-1).astype(q.dtype)
    return jnp.einsum("bhqk,bkhd->bqhd", probs, v)


def _attention_specs(mesh, n_heads: int, n_kv_heads: int, seq_axis):
    """shard_map specs for [B, S, H, D] activations: batch as the rules
    have it (where ``hidden_states`` puts the residual stream), sequence
    over ``seq_axis``, heads over tp. A head count tp does not divide
    (GQA/MQA KV heads) stays replicated, and the per-shard op must then
    bridge sharded-q / replicated-kv heads itself (ring_attention's
    _repeat_kv does)."""
    tp = dict(zip(mesh.axis_names, mesh.devices.shape)).get("tp", 1)
    batch = ambient_spec(mesh, "batch")[0]
    q_spec = PartitionSpec(batch, seq_axis,
                           "tp" if n_heads % tp == 0 else None, None)
    kv_spec = PartitionSpec(batch, seq_axis,
                            "tp" if n_kv_heads % tp == 0 else None, None)
    return q_spec, kv_spec


def _attention(q, k, v, cfg):
    """Causal attention by ``cfg.attn_impl`` (and, for the flash kernels,
    ``cfg.attn_blk_q`` / ``cfg.attn_blk_k``): the one dispatch every model
    of this package goes through. cfg is any model's config."""
    if cfg.attn_impl == "dot":
        return _dot_attention(q, k, v)
    if cfg.attn_impl == "flash":
        from ray_tpu.ops.flash_attention import flash_attention
        from ray_tpu.parallel.mesh import current_mesh
        fn = partial(flash_attention, causal=True,
                     blk_q=cfg.attn_blk_q, blk_k=cfg.attn_blk_k)
        mesh = current_mesh()
        if mesh is None or mesh.size == 1:
            return fn(q, k, v)
        # GSPMD cannot partition a Mosaic kernel, so under a mesh it runs
        # per shard. Attention is independent per (batch row, head): each
        # shard sees whole sequences, and heads split over tp only when
        # the KV heads split with them (the kernel pairs q and kv heads
        # by position within the shard).
        from ray_tpu._private.jax_compat import shard_map
        q_spec, kv_spec = _attention_specs(
            mesh, q.shape[2], k.shape[2], seq_axis=None)
        if kv_spec[2] is None:
            q_spec = kv_spec
        return shard_map(fn, mesh=mesh, in_specs=(q_spec, kv_spec, kv_spec),
                         out_specs=q_spec, check_vma=False)(q, k, v)
    if cfg.attn_impl == "ring":
        from ray_tpu.ops.ring_attention import make_ring_attention
        from ray_tpu.parallel.mesh import current_mesh
        mesh = current_mesh()
        if mesh is None or "sp" not in mesh.axis_names:
            raise ValueError(
                "attn_impl='ring' needs a registered mesh with an 'sp' "
                "axis (parallel.mesh.set_current_mesh; make_train_step/"
                "make_eval_step do this automatically)")
        q_spec, kv_spec = _attention_specs(
            mesh, q.shape[2], k.shape[2], seq_axis="sp")
        fn = make_ring_attention(mesh, "sp", causal=True, q_spec=q_spec,
                                 kv_spec=kv_spec)
        return fn(q, k, v)
    if cfg.attn_impl == "ulysses":
        from ray_tpu.ops.ulysses import make_ulysses_attention
        from ray_tpu.parallel.mesh import current_mesh
        mesh = current_mesh()
        if mesh is None or "sp" not in mesh.axis_names:
            raise ValueError(
                "attn_impl='ulysses' needs a registered mesh with an 'sp' "
                "axis (parallel.mesh.set_current_mesh)")
        return make_ulysses_attention(mesh)(q, k, v)
    raise ValueError(f"Unknown attn_impl {cfg.attn_impl!r}")


def _moe_ffn(cfg: GPTConfig, h, layer):
    """Top-k routed mixture-of-experts FFN with capacity-based token drop.

    Dispatch/combine are dense einsums against one-hot routing tensors (the
    canonical GSPMD MoE formulation): with ``w_in``/``w_out`` sharded over
    the "ep" mesh axis, XLA lowers the [tokens → experts] einsum to an ICI
    all-to-all — no hand-written communication. Returns (out, aux_loss)
    where aux_loss is the Switch-style load-balancing term.
    h: [B, S, d] → out [B, S, d]."""
    dt = cfg.dtype
    B, S, d = h.shape
    E = cfg.n_experts
    K = min(cfg.expert_top_k, E)
    C = max(1, int(cfg.capacity_factor * S * K / E))

    router_logits = jnp.einsum(
        "bsd,de->bse", h.astype(jnp.float32),
        layer["router"].astype(jnp.float32))
    probs = jax.nn.softmax(router_logits, axis=-1)  # [B, S, E] fp32
    gate_vals, gate_idx = jax.lax.top_k(probs, K)  # [B, S, K]
    gate_vals = gate_vals / jnp.maximum(
        gate_vals.sum(-1, keepdims=True), 1e-9)
    onehot = jax.nn.one_hot(gate_idx, E, dtype=jnp.float32)  # [B,S,K,E]

    # Position of each assignment within its expert's buffer, counted in
    # (sequence, k) order; assignments past capacity C are dropped.
    flat = onehot.reshape(B, S * K, E)
    pos = (jnp.cumsum(flat, axis=1) - flat).reshape(B, S, K, E)
    keep = onehot * (pos < C)
    cap_onehot = jax.nn.one_hot(
        jnp.minimum(pos, C - 1).astype(jnp.int32), C,
        dtype=jnp.float32)  # [B, S, K, E, C]
    dispatch = (keep[..., None] * cap_onehot).sum(axis=2)  # [B, S, E, C]
    combine = (gate_vals[..., None, None] * keep[..., None]
               * cap_onehot).sum(axis=2)  # [B, S, E, C]

    x_e = jnp.einsum("bsec,bsd->ebcd", dispatch.astype(dt), h)
    y = jnp.einsum("ebcd,edf->ebcf", x_e, layer["w_in"].astype(dt))
    y = jax.nn.gelu(y + layer["b_in"][:, None, None, :].astype(dt))
    y = jnp.einsum("ebcf,efd->ebcd", y, layer["w_out"].astype(dt))
    out = jnp.einsum("bsec,ebcd->bsd", combine.astype(dt), y)

    # Load-balancing aux (Switch Transformer): E * Σ_e f_e · p_e, where f_e
    # is the fraction of tokens whose top-1 choice is e and p_e the mean
    # router probability for e.
    f_e = onehot[:, :, 0, :].mean(axis=(0, 1))
    p_e = probs.mean(axis=(0, 1))
    aux = E * jnp.sum(f_e * p_e)
    return out, aux


def _block(cfg: GPTConfig, x, layer, positions):
    """One transformer block. x: [B, S, D]. Returns (x, aux_loss)."""
    dt = cfg.dtype
    h = _layernorm(x, layer["ln1_scale"], layer["ln1_bias"],
                   cfg.layernorm_eps)
    # Scope names are metadata: they name the operations in a profile.
    with jax.named_scope("attention"):
        q = jnp.einsum("bsd,dhk->bshk", h, layer["wq"].astype(dt))
        k = jnp.einsum("bsd,dhk->bshk", h, layer["wk"].astype(dt))
        v = jnp.einsum("bsd,dhk->bshk", h, layer["wv"].astype(dt))
        q = checkpoint_name(_rotary(q, positions, cfg.rotary_dim), "attn_q")
        k = checkpoint_name(_rotary(k, positions, cfg.rotary_dim), "attn_k")
        v = checkpoint_name(v, "attn_v")
        attn = checkpoint_name(_attention(q, k, v, cfg), "attn_raw")
        attn_out = jnp.einsum("bshk,hkd->bsd", attn, layer["wo"].astype(dt))

    if cfg.parallel_block:
        mlp_in = h  # GPT-J: shared LN feeds both branches
    else:
        x = x + attn_out
        mlp_in = _layernorm(x, layer["ln2_scale"], layer["ln2_bias"],
                            cfg.layernorm_eps)
    aux = jnp.zeros((), jnp.float32)
    with jax.named_scope("moe" if cfg.is_moe else "mlp"):
        if cfg.is_moe:
            mlp_out, aux = _moe_ffn(cfg, mlp_in, layer)
        else:
            ff = checkpoint_name(
                jnp.einsum("bsd,df->bsf", mlp_in, layer["w_in"].astype(dt)),
                "ffn_in")
            ff = jax.nn.gelu(ff + layer["b_in"].astype(dt))
            mlp_out = jnp.einsum("bsf,fd->bsd", ff,
                                 layer["w_out"].astype(dt))

    b_out = layer["b_out"].astype(dt)
    if cfg.parallel_block:
        # Under tp both products are partial sums. Added to each other
        # before anything else they are reduced over tp together: one
        # all-reduce of [B, S, d] a layer, not one each.
        return x + ((attn_out + mlp_out) + b_out), aux
    return x + (mlp_out + b_out), aux


def scan_blocks(cfg, block, x, layers, positions):
    """``block(x, layer, positions) -> (x, aux)`` over stacked layer
    parameters in one ``lax.scan``, each block rematerialised by
    ``cfg.remat`` / ``cfg.remat_policy``. Returns (x, aux stacked over
    layers). Shared by every model that scans its layers; cfg is that
    model's config."""
    if cfg.remat:
        if cfg.remat_policy == "selective":
            policy = jax.checkpoint_policies.save_only_these_names(
                "attn_q", "attn_k", "attn_v", "attn_raw", "ffn_in")
        elif cfg.remat_policy == "full":
            policy = jax.checkpoint_policies.nothing_saveable
        else:
            raise ValueError(
                f"Unknown remat_policy {cfg.remat_policy!r}; "
                "expected 'full' or 'selective'")
        block = jax.checkpoint(block, policy=policy)

    def scan_body(x, layer):
        with jax.named_scope("block"):
            return block(x, layer, positions)

    return jax.lax.scan(scan_body, x, layers)


def embed(wte, tokens, dtype):
    """wte[tokens] in ``dtype``, batch-split: the residual stream is split
    by batch (and sequence, under context parallelism) from the lookup to
    the loss and says so at both ends of the layer scan; derived from the
    weights it would be d over fsdp, resharded wherever an operation wants
    the batch split. The lookup itself leaves d split as the table has it.
    Stated so first, rows are cut where they lie, and the change that
    follows is one all-to-all over those axes (from the lookup's own
    layout the partitioner can only replicate x whole when dp and fsdp
    are both above 1). Shared by every language model of this package."""
    x = jnp.take(wte, tokens, axis=0).astype(dtype)
    x = constrain(x, "batch", "sequence", "embed")
    return constrain(x, "batch", "sequence", None)


def hidden_states(params: Dict[str, Any], cfg: GPTConfig,
                  tokens: jax.Array,
                  positions: Optional[jax.Array] = None):
    """tokens [B, S] int32 → (final-layernormed hidden [B, S, d], aux)."""
    B, S = tokens.shape
    if positions is None:
        positions = jnp.broadcast_to(jnp.arange(S, dtype=jnp.int32), (B, S))
    x = embed(params["wte"], tokens, cfg.dtype)
    x, aux = scan_blocks(cfg, partial(_block, cfg), x, params["layers"],
                         positions)
    x = constrain(x, "batch", "sequence", None)
    x = _layernorm(x, params["lnf_scale"], params["lnf_bias"],
                   cfg.layernorm_eps)
    return x, aux.sum()


def _head(params: Dict[str, Any], cfg: GPTConfig, x: jax.Array) -> jax.Array:
    """Hidden [..., d] → logits [..., vocab] (compute dtype)."""
    if cfg.tie_embeddings:
        return jnp.einsum("...d,vd->...v", x, params["wte"].astype(cfg.dtype))
    logits = jnp.einsum("...d,dv->...v", x, params["lm_head"].astype(cfg.dtype))
    return logits + params["lm_head_bias"].astype(cfg.dtype)


def forward_with_aux(params: Dict[str, Any], cfg: GPTConfig,
                     tokens: jax.Array,
                     positions: Optional[jax.Array] = None):
    """tokens [B, S] int32 → (logits [B, S, vocab], aux_loss scalar).
    aux_loss is the summed MoE load-balancing term (0 for dense models)."""
    x, aux = hidden_states(params, cfg, tokens, positions)
    return _head(params, cfg, x), aux


def forward(params: Dict[str, Any], cfg: GPTConfig, tokens: jax.Array,
            positions: Optional[jax.Array] = None) -> jax.Array:
    """tokens [B, S] int32 → logits [B, S, vocab] (compute dtype)."""
    return forward_with_aux(params, cfg, tokens, positions)[0]


def _ce_stats(logits: jax.Array, targets: jax.Array, mask: jax.Array,
              z_loss: float) -> Tuple[jax.Array, jax.Array]:
    """fp32 CE pieces for one [..., vocab] logits slab → (Σ nll·m, Σ hit·m)."""
    logits = logits.astype(jnp.float32)
    logz = jax.scipy.special.logsumexp(logits, axis=-1)
    tgt_logit = jnp.take_along_axis(
        logits, targets[..., None], axis=-1)[..., 0]
    nll = logz - tgt_logit
    if z_loss:
        nll = nll + z_loss * logz ** 2
    hits = (logits.argmax(-1) == targets).astype(jnp.float32)
    return (nll * mask).sum(), (hits * mask).sum()


def chunked_ce(head, x: jax.Array, targets: jax.Array, mask32: jax.Array,
               chunk: int, z_loss: float = 0.0
               ) -> Tuple[jax.Array, jax.Array]:
    """(Σ nll·mask, Σ hit·mask) of ``head(x)`` against targets, in fp32.

    ``head`` maps hidden [..., d] to logits [..., vocab]; x is [B, S, d].
    With ``chunk > 0`` the head matmul and the fp32 softmax run ``chunk``
    tokens at a time under a rematerialised lax.scan, so the [tokens, vocab]
    fp32 logits never exist whole. A chunk is a slice of S across the whole
    batch, [B, S / n, d]: the scanned dimension is not the one the batch's
    sharding lies on, so every data shard walks its own tokens and no chip
    sees another's (chunks of whole rows put the sharding on the scanned
    dimension, and the partitioner then splits d and sums every chunk's
    logits instead). Shared by every language model of this package."""
    with jax.named_scope("head_loss"):
        B, S = targets.shape
        if not (chunk and B * S > chunk):
            return _ce_stats(head(x), targets, mask32, z_loss)
        # The fewest slices of S that hold at most ``chunk`` tokens each:
        # where ``chunk`` does not divide, the largest slice under it that
        # does, never the whole logits (the feature's memory bound stands).
        n = next((n for n in range(2, S)
                  if S % n == 0 and B * S // n <= chunk), S)

        def slices(a):
            a = a.reshape(B, n, S // n, *a.shape[2:]).swapaxes(0, 1)
            return constrain(a, None, "batch", "sequence",
                             *[None] * (a.ndim - 3))

        @jax.checkpoint
        def chunk_stats(carry, xtm):
            # One row of tokens: [B * S / n, ...], as head and loss see it
            # on one device too.
            x_c, t_c, m_c = (a.reshape(-1, *a.shape[2:]) for a in xtm)
            nll_sum, hit_sum = _ce_stats(head(x_c), t_c, m_c, z_loss)
            return (carry[0] + nll_sum, carry[1] + hit_sum), None

        sums, _ = jax.lax.scan(
            chunk_stats, (jnp.zeros((), jnp.float32),) * 2,
            (slices(x), slices(targets), slices(mask32)))
        return sums


def head_gathered(params: Dict[str, Any], tied: bool) -> Dict[str, Any]:
    """params with the head's weight (``wte`` if ``tied``, else ``lm_head``)
    whole along d and split over the vocabulary alone: stated before the
    chunk loop, it is gathered over fsdp once a step and its gradient
    summed over the chunks before it is reduced, once; left to the
    partitioner both happen in every chunk."""
    if tied:
        return dict(params, wte=constrain(params["wte"], "vocab", None))
    return dict(params, lm_head=constrain(params["lm_head"], None, "vocab"))


def loss_fn(params: Dict[str, Any], cfg: GPTConfig, tokens: jax.Array,
            targets: jax.Array, mask: Optional[jax.Array] = None,
            z_loss: float = 0.0) -> Tuple[jax.Array, Dict[str, jax.Array]]:
    """Next-token cross-entropy in fp32 (+ optional z-loss regularizer and,
    for MoE configs, the router load-balancing aux term).

    With ``cfg.loss_chunk > 0`` the head matmul + fp32 softmax run chunked
    (see ``chunked_ce`` and GPTConfig.loss_chunk)."""
    x, aux = hidden_states(params, cfg, tokens)
    if mask is None:
        mask32 = jnp.ones(tokens.shape, jnp.float32)
    else:
        mask32 = mask.astype(jnp.float32)
    denom = jnp.maximum(mask32.sum(), 1.0)
    head = partial(_head, head_gathered(params, cfg.tie_embeddings), cfg)
    nll_sum, hit_sum = chunked_ce(head, x, targets, mask32, cfg.loss_chunk,
                                  z_loss)

    ce = nll_sum / denom
    loss = ce
    if cfg.is_moe:
        loss = ce + cfg.router_aux_weight * aux
    acc = hit_sum / denom
    # Perplexity from the cross-entropy alone (not the aux-regularized
    # loss), so MoE and dense perplexities are comparable.
    return loss, {"loss": loss, "accuracy": acc,
                  "perplexity": jnp.exp(jnp.minimum(ce, 20.0))}


def flops_per_token(cfg: GPTConfig) -> float:
    """Approximate training FLOPs/token (6N_active + attention quadratic
    term). For MoE, only the top-k routed experts do work per token, so the
    FFN share counts k experts, not all of them (MFU must not be inflated
    by inactive experts)."""
    n = cfg.num_params()
    if cfg.is_moe:
        d, f, L, E = cfg.d_model, cfg.d_ff, cfg.n_layers, cfg.n_experts
        K = min(cfg.expert_top_k, E)
        inactive_ffn = L * (E - K) * (2 * d * f + f)
        n -= inactive_ffn
    attn = 12 * cfg.n_layers * cfg.d_model * cfg.max_seq_len
    return 6.0 * n + attn
