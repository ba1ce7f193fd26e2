"""The program's side of a configuration whose ``program.family`` is
``deepseek_v3``: ``ray_tpu/models/deepseek.py`` trained by
``ray_tpu/parallel/train_step.py`` (which takes the model as an argument),
described by a published config under the ``DeepseekV3Config`` key names
(``hidden_size``, ``num_hidden_layers``, ``kv_lora_rank``,
``n_routed_experts``, ...). It offers what ``families/gpt.py``'s docstring
lists, and ``picked_experts`` for the routing comparison of
``check_routing.py``.

The benchmark makes the weights: the program's one jitted init from the
seed, then (``draw_vectors``) every RMSNorm scale redrawn N(0,
``program.norm_scale_sigma``) around one and every expert layer's correction
bias drawn from the seed, N(0, 1) scaled so that the layer's largest entry
is ``program.router_bias_max``.
That entry sets how uneven the routing is (the busiest expert's load over
the mean); scaling to a fixed largest entry keeps it the same from seed to
seed where a plain N(0, sigma) would let it swing with the maximum of 64
draws.
"""

from __future__ import annotations

from dataclasses import fields, replace
from typing import Any, Dict, List

#: Published keys the program's config carries under the same name.
PUBLISHED = ("vocab_size", "hidden_size", "num_hidden_layers",
             "first_k_dense_replace", "num_attention_heads", "kv_lora_rank",
             "qk_nope_head_dim", "qk_rope_head_dim", "v_head_dim",
             "rope_theta", "intermediate_size", "moe_intermediate_size",
             "n_routed_experts", "num_experts_per_tok", "n_shared_experts",
             "routed_scaling_factor", "norm_topk_prob", "rms_norm_eps",
             "max_position_embeddings")
#: Published keys the program implements one value of.
FIXED = {"model_type": "deepseek_v3", "q_lora_rank": None,
         "hidden_act": "silu", "scoring_func": "sigmoid",
         "topk_method": "noaux_tc", "n_group": 1, "topk_group": 1,
         "moe_layer_freq": 1, "attention_bias": False,
         "tie_word_embeddings": False, "num_nextn_predict_layers": 0,
         "ep_size": 1}


def _model():
    from ray_tpu.models import deepseek
    return deepseek


def config(program: Dict[str, Any]):
    """The program's ``DeepseekConfig`` from a configuration file's
    ``program`` group: a preset and overrides, dtypes by name."""
    import jax.numpy as jnp
    overrides = dict(program["overrides"])
    for key in ("dtype", "param_dtype"):
        if key in overrides:
            overrides[key] = jnp.dtype(overrides[key]).type
    return _model().config(program["preset"], **overrides)


def problems(published: Dict[str, Any], cfg) -> List[str]:
    """The program's config against the configuration file's published
    keys: the cell runs the widths it says it runs, and the file asks for
    nothing the program does not compute."""
    out = [f"{key}: program {getattr(cfg, key)!r}, file {published[key]!r}"
           for key in PUBLISHED if getattr(cfg, key) != published[key]]
    out += [f"{key}: the program computes {want!r} only, file "
            f"{published[key]!r}" for key, want in FIXED.items()
            if published.get(key, want) != want]
    if published["num_key_value_heads"] != published["num_attention_heads"]:
        out.append("num_key_value_heads: latent attention has one K/V per "
                   "query head")
    return out


def vocab_size(cfg) -> int:
    return cfg.vocab_size


def _rules_and_optimizer(program: Dict[str, Any]):
    from ray_tpu.parallel.sharding import ShardingRules
    from ray_tpu.parallel.train_step import memory_efficient_optimizer
    opt = program["optimizer"]
    return ShardingRules(), memory_efficient_optimizer(
        learning_rate=opt["learning_rate"], warmup_steps=opt["warmup_steps"])


def state_and_step(cfg, mesh, program: Dict[str, Any], seed: int):
    """The train state on the device from the seed and the jitted step
    ``(state, batch) -> (state, metrics)``: the product's own builders,
    given the model."""
    from ray_tpu.parallel.train_step import init_train_state, make_train_step
    rules, optimizer = _rules_and_optimizer(program)
    state = init_train_state(cfg, mesh, rules, optimizer, seed=seed,
                             model=_model())
    step = make_train_step(cfg, mesh, rules, optimizer, model=_model())
    state["params"] = draw_vectors(state["params"], seed + 1, program)
    return state, step


def abstract_state_and_step(cfg, mesh, program: Dict[str, Any]):
    """As ``state_and_step`` with nothing made: shapes and shardings."""
    from ray_tpu.parallel.train_step import (abstract_train_state,
                                             make_train_step)
    rules, optimizer = _rules_and_optimizer(program)
    return (abstract_train_state(cfg, mesh, rules, optimizer,
                                 model=_model()),
            make_train_step(cfg, mesh, rules, optimizer, model=_model()))


def batch_sharding(mesh):
    from jax.sharding import NamedSharding, PartitionSpec
    return NamedSharding(mesh, PartitionSpec(("dp", "fsdp"), None))


def draw_vectors(params, seed: int, program: Dict[str, Any]):
    """The program's init leaves every RMSNorm scale at 1 and the correction
    bias at 0, where no dropped or misplaced term would show and every
    expert is as busy as the next. Redrawn from the seed in one jitted
    pass, in place, same shardings: scales N(0, ``norm_scale_sigma``) around
    1, the bias N(0, 1) scaled per layer to a largest entry of
    ``router_bias_max`` (both from the configuration's ``program``)."""
    import jax
    import jax.numpy as jnp
    router_bias_max = program["router_bias_max"]
    sigma = program["norm_scale_sigma"]

    def drawn(tree, key, rank):
        out = {}
        for k, (name, leaf) in zip(jax.random.split(key, len(tree)),
                                   sorted(tree.items())):
            if name == "router_bias":
                z = jax.random.normal(k, leaf.shape, jnp.float32)
                leaf = (z / z.max(-1, keepdims=True) * router_bias_max
                        ).astype(leaf.dtype)
            elif leaf.ndim == rank:
                leaf = leaf + (sigma * jax.random.normal(
                    k, leaf.shape, jnp.float32)).astype(leaf.dtype)
            out[name] = leaf
        return out

    def vectors_drawn(params, key):
        keys = jax.random.split(key, 3)
        stacks = ("dense_layers", "moe_layers")
        rest = {k: v for k, v in params.items() if k not in stacks}
        # Stacked over layers, a vector has rank 2.
        return dict(drawn(rest, keys[0], 1), **{
            name: drawn(params[name], k, 2)
            for name, k in zip(stacks, keys[1:])})

    shardings = jax.tree.map(lambda a: a.sharding, params)
    return jax.jit(vectors_drawn, donate_argnums=(0,),
                   out_shardings=shardings)(params, jax.random.PRNGKey(seed))


def _one_sequence_a_chunk(cfg, tokens):
    """The chunked loss takes its path only above loss_chunk tokens; with
    few sequences it is held to half a sequence a chunk."""
    n_seq, seq = tokens.shape
    if cfg.loss_chunk and n_seq * seq <= cfg.loss_chunk:
        return replace(cfg, loss_chunk=seq // 2)
    return cfg


def logits_and_losses(params, cfg, tokens, targets):
    """The program's own forward, and the loss the train step differentiates
    taken one sequence at a time (a mask of one row). Traced inside the
    caller's jit, under the caller's mesh."""
    import jax.numpy as jnp
    model = _model()
    cfg = _one_sequence_a_chunk(cfg, tokens)
    logits = model.forward(params, cfg, tokens)
    losses = [model.loss_fn(params, cfg, tokens, targets,
                            mask=jnp.zeros(tokens.shape, jnp.float32
                                           ).at[i].set(1.0))[0]
              for i in range(tokens.shape[0])]
    return logits, jnp.stack(losses)


def picked_experts(params, cfg, tokens):
    """(logits [B, S, vocab], picked [L_moe, B, S, K]): the program's
    forward with the router's choice, the model's auxiliary output."""
    logits, aux = _model().forward_with_aux(params, cfg, tokens)
    return logits, aux["picked"]


def loss(params, cfg, tokens, targets):
    """The loss the train step differentiates (``check_grads_deepseek``)."""
    return _model().loss_fn(params, _one_sequence_a_chunk(cfg, tokens),
                            tokens, targets)[0]


def init(cfg, seed: int, program: Dict[str, Any]):
    """Parameters alone, as ``state_and_step`` makes them."""
    import jax
    params = jax.jit(lambda key: _model().init(cfg, key))(
        jax.random.PRNGKey(seed))
    return draw_vectors(params, seed + 1, program)


def with_layers(program: Dict[str, Any], layers: int) -> Dict[str, Any]:
    """The ``program`` group cut to ``layers`` layers, the dense first."""
    return dict(program, overrides=dict(program["overrides"],
                                        num_hidden_layers=layers))


def tiny(config: Dict[str, Any]) -> Dict[str, Any]:
    """The configuration at a tiny size for a run on the CPU: one dense and
    two expert layers, width 256, two heads of 128 + 64 | 128, 8 experts of
    width 128 with 3 a token, 512 tokens of vocabulary, sequences of 256.
    Same code path and layout; nothing it measures means anything."""
    sizes = dict(hidden_size=256, num_attention_heads=2,
                 num_hidden_layers=3, kv_lora_rank=128,
                 intermediate_size=512, moe_intermediate_size=128,
                 n_routed_experts=8, num_experts_per_tok=3, vocab_size=512,
                 max_position_embeddings=256)
    config = dict(config, num_key_value_heads=2, **sizes)
    program = dict(config["program"])
    program["overrides"] = dict(program["overrides"], loss_chunk=256,
                                **sizes)
    config["program"] = program
    config["layout"] = dict(config["layout"], seq_len=256)
    return config
