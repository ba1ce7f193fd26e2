"""The program's side of a configuration whose ``program.family`` is
``kimi_linear``: ``ray_tpu/models/kimi_linear.py`` trained by
``ray_tpu/parallel/train_step.py`` (which takes the model as an argument),
described by a published config under the ``KimiLinearConfig`` key names
(``hidden_size``, ``linear_attn_config``, ``kv_lora_rank``, ``mla_use_nope``,
``num_experts``, ``num_experts_per_token``, ...). It offers what
``families/gpt.py``'s docstring lists, ``picked_experts`` for a routing
comparison, and ``loss``, ``init`` and ``with_layers`` for the gradient check
(``check_grads_kimi_linear.py``).

**The chip's share.** A configuration of this family may be one chip's share
of a deployment that divides every layer over several chips; its
``deployment`` group says so. The file's ``num_experts`` is then how many
experts are held here (``deployment.experts_held``: ``first``, ``count``, and
``of``, the published count and the router's width), and its ``vocab_size``
the chip's slice of the vocabulary (``deployment.vocab_slice``): token ids,
logits and loss are over the slice, so the traffic draws its ids from
``vocab_size(cfg)`` as for any other vocabulary.

The benchmark makes the weights: the program's one jitted init from the
seed (which draws the decay's two vectors as published: ``A_log`` = log
U(1, 16) a head, ``dt_bias`` the inverse softplus of a log-uniform (0.001,
0.1) a channel, so log-decays from -0.001 to -1.6 a step), then
(``draw_vectors``) every RMSNorm scale (the latent's and the delta rule's
output norm too) redrawn N(0, ``program.norm_scale_sigma``) around one and
every expert layer's correction bias drawn from the seed, N(0, 1) scaled so
that the layer's largest entry is ``program.router_bias_max``, as
``families/deepseek_v3.py`` draws it, and a latent layer's ``wq`` multiplied
by ``program.attention_q_gain``: at the init's 0.02 the scores of 16k keys
spread by 0.6 and the softmax is nearly flat, so that a rotation of q and k
(a rope put back) would move the logits by less than bfloat16 does; the
gain spreads the scores, the "rope" dimensions' part among them.
"""

from __future__ import annotations

from dataclasses import replace
from typing import Any, Dict, List

#: Published keys the program's config carries under the same name.
PUBLISHED = ("hidden_size", "num_hidden_layers", "first_k_dense_replace",
             "num_attention_heads", "kv_lora_rank", "qk_nope_head_dim",
             "qk_rope_head_dim", "v_head_dim", "mla_use_nope", "rope_theta",
             "intermediate_size", "moe_intermediate_size",
             "num_experts_per_token", "num_shared_experts",
             "moe_renormalize", "routed_scaling_factor", "rms_norm_eps",
             "model_max_length", "vocab_size")
#: Published keys the program implements one value of.
FIXED = {"model_type": "kimi_linear", "hidden_act": "silu",
         "moe_router_activation_func": "sigmoid", "num_expert_group": 1,
         "topk_group": 1, "moe_layer_freq": 1, "q_lora_rank": None,
         "rope_scaling": None, "tie_word_embeddings": False,
         "num_nextn_predict_layers": 0}


def _model():
    from ray_tpu.models import kimi_linear
    return kimi_linear


def config(program: Dict[str, Any]):
    """The program's ``KimiLinearConfig`` from a configuration file's
    ``program`` group: a preset and overrides, dtypes by name."""
    import jax.numpy as jnp
    overrides = dict(program["overrides"])
    for key in ("dtype", "param_dtype"):
        if key in overrides:
            overrides[key] = jnp.dtype(overrides[key]).type
    return _model().config(program["preset"], **overrides)


def problems(published: Dict[str, Any], cfg) -> List[str]:
    """The program's config against the configuration file's published
    keys: the cell runs the widths, the layer pattern and the share it says
    it runs, and the file asks for nothing the program does not compute."""
    out = [f"{key}: program {getattr(cfg, key)!r}, file {published[key]!r}"
           for key in PUBLISHED if getattr(cfg, key) != published[key]]
    linear, got = published["linear_attn_config"], cfg.linear_attn_config
    out += [f"linear_attn_config.{key}: program {getattr(got, key)!r}, file "
            f"{want!r}" for key, want in linear.items()
            if getattr(got, key) != (tuple(want) if isinstance(want, list)
                                     else want)]
    out += [f"{key}: the program computes {want!r} only, file "
            f"{published[key]!r}" for key, want in FIXED.items()
            if published.get(key, want) != want]
    if published["num_key_value_heads"] != published["num_attention_heads"]:
        out.append("num_key_value_heads: the latent layer has one key and "
                   "value head a query head")
    held = published.get("deployment", {}).get("experts_held")
    if held is None:
        held = {"first": 0, "count": published["num_experts"],
                "of": published["num_experts"]}
    if published["num_experts"] != held["count"] \
            or cfg.num_experts != held["of"] \
            or (cfg.experts_held or (0, cfg.num_experts)) != (
                held["first"], held["count"]):
        out.append(f"num_experts: file {published['num_experts']} held of "
                   f"{held}, program {cfg.experts_held} of "
                   f"{cfg.num_experts}")
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
    ``router_bias_max``, a latent layer's ``wq`` times ``attention_q_gain``
    (all from the configuration's ``program``). The decay's vectors stay as
    the program's init drew them."""
    import jax
    import jax.numpy as jnp
    router_bias_max = program["router_bias_max"]
    sigma, q_gain = program["norm_scale_sigma"], program["attention_q_gain"]

    def drawn(tree, key):
        out = {}
        for k, (name, leaf) in zip(jax.random.split(key, len(tree)),
                                   sorted(tree.items())):
            if name == "router_bias":
                z = jax.random.normal(k, leaf.shape, jnp.float32)
                leaf = (z / z.max(-1, keepdims=True) * router_bias_max
                        ).astype(leaf.dtype)
            elif name.endswith("_scale"):
                leaf = (leaf.astype(jnp.float32) + sigma * jax.random.normal(
                    k, leaf.shape, jnp.float32)).astype(leaf.dtype)
            elif name == "wq" and "w_kv_a" in tree:
                leaf = (q_gain * leaf.astype(jnp.float32)).astype(leaf.dtype)
            out[name] = leaf
        return out

    def vectors_drawn(params, key):
        stacks = sorted(k for k in params if k.startswith("run"))
        keys = jax.random.split(key, 1 + len(stacks))
        rest = {k: v for k, v in params.items() if k not in stacks}
        return dict(drawn(rest, keys[0]), **{
            name: drawn(params[name], k)
            for name, k in zip(stacks, keys[1:])})

    shardings = jax.tree.map(lambda a: a.sharding, params)
    return jax.jit(vectors_drawn, donate_argnums=(0,),
                   out_shardings=shardings)(params, jax.random.PRNGKey(seed))


def _a_chunked_loss(cfg, tokens):
    """The chunked loss takes its path only above loss_chunk tokens; with
    few sequences it is held to half a sequence a chunk."""
    n_seq, seq = tokens.shape
    if cfg.loss_chunk and n_seq * seq <= cfg.loss_chunk:
        return replace(cfg, loss_chunk=seq // 2)
    return cfg


def logits_and_losses(params, cfg, tokens, targets):
    """The program's own forward, and the loss the train step differentiates
    taken one sequence at a time (a mask of one row), both from one pass
    through the layers. Traced inside the caller's jit, under the caller's
    mesh."""
    import jax.numpy as jnp
    model = _model()
    cfg = _a_chunked_loss(cfg, tokens)
    hidden, aux = model.hidden_states(params, cfg, tokens)
    losses = [model.loss_of_hidden(
        params, cfg, hidden, aux, targets,
        mask=jnp.zeros(tokens.shape, jnp.float32).at[i].set(1.0))[0]
        for i in range(tokens.shape[0])]
    return model.head(params, cfg, hidden), jnp.stack(losses)


def picked_experts(params, cfg, tokens):
    """(logits [B, S, vocab], picked [L_moe, B, S, K]): the program's
    forward with the router's choice, the model's auxiliary output."""
    logits, aux = _model().forward_with_aux(params, cfg, tokens)
    return logits, aux["picked"]


def loss(params, cfg, tokens, targets):
    """The loss the train step differentiates (``check_grads_kimi_linear``)."""
    return _model().loss_fn(params, _a_chunked_loss(cfg, tokens), tokens,
                            targets)[0]


def init(cfg, seed: int, program: Dict[str, Any]):
    """Parameters alone, as ``state_and_step`` makes them."""
    import jax
    params = jax.jit(lambda key: _model().init(cfg, key))(
        jax.random.PRNGKey(seed))
    return draw_vectors(params, seed + 1, program)


def with_layers(config: Dict[str, Any], layers: int) -> Dict[str, Any]:
    """The configuration cut to its first ``layers`` layers (file and
    program alike)."""
    program = dict(config["program"])
    program["overrides"] = dict(program["overrides"],
                                num_hidden_layers=layers)
    return dict(config, num_hidden_layers=layers, program=program)


def tiny(config: Dict[str, Any]) -> Dict[str, Any]:
    """The configuration at a tiny size for a run on the CPU: width 128,
    the file's own number of layers, leading dense layers and pattern, two
    delta-rule heads of 128 (so that the kernels tile and run interpreted),
    four latent heads of 32 | 16 | 32 over a latent of 64, a dense SwiGLU of
    256, experts of 128 with the file's share of 16 (held: the file's own
    run, cut to 4) and 2 a token, 512 tokens of vocabulary, everything in
    float32 (where nothing routes differently from the float32 reference:
    the chip's own tolerances, for bfloat16 and the real share, are the
    configuration's). Same code path and layout; nothing it measures means
    anything."""
    first = config.get("deployment", {}).get("experts_held", {}).get(
        "first", 0)
    held = {"first": min(first, 12), "count": 4, "of": 16}
    linear = dict(config["linear_attn_config"], num_heads=2)
    sizes = dict(hidden_size=128, num_attention_heads=4,
                 kv_lora_rank=64, qk_nope_head_dim=32, qk_rope_head_dim=16,
                 v_head_dim=32, intermediate_size=256,
                 moe_intermediate_size=128, num_experts_per_token=2,
                 vocab_size=512, model_max_length=256)
    config = dict(config, num_experts=held["count"], num_key_value_heads=4,
                  linear_attn_config=linear, **sizes)
    config["deployment"] = dict(config.get("deployment", {}),
                                experts_held=held)
    program = dict(config["program"])
    program["overrides"] = dict(
        program["overrides"], loss_chunk=128, num_experts=16,
        experts_held=[held["first"], held["count"]], dtype="float32",
        param_dtype="float32", linear_attn_config=linear, **sizes)
    config["program"] = program
    config["layout"] = dict(config["layout"], seq_len=256)
    return config
