"""The program's side of a configuration whose ``program.family`` is
``glm_moe_dsa``: ``ray_tpu/models/glm_moe_dsa.py`` trained by
``ray_tpu/parallel/train_step.py`` (which takes the model as an argument),
described by a published config under the ``GlmMoeDsaConfig`` key names
(``hidden_size``, ``q_lora_rank``, ``kv_lora_rank``, ``index_n_heads``,
``index_topk``, ``indexer_types``, ``n_routed_experts``, ...). It offers what
``families/gpt.py``'s docstring lists, ``picked_experts`` for a routing
comparison, and ``loss``, ``init`` and ``with_layers`` for the gradient check
(``check_grads_glm_moe_dsa.py``).

**The cut and the chip's share.** A configuration of this family may run a
stretch of the published layers: its ``layers_run`` lists their published
indices (the program's ``first_layer`` and ``num_hidden_layers``), while
``first_k_dense_replace`` and ``indexer_types`` stay the published ones. It
may be one chip's share of a deployment that divides every layer over
several chips (``deployment``): the file's ``n_routed_experts`` is then how
many experts are held here (``deployment.experts_held``: ``first``,
``count``, and ``of``, the published count and the router's width), and its
``vocab_size`` the chip's slice of the vocabulary.

The benchmark makes the weights: the program's one jitted init from the
seed, then (``draw_vectors``) every norm's scale (the two latents' and the
indexer key's LayerNorm too) redrawn N(0, ``program.norm_scale_sigma``)
around one and that LayerNorm's bias around zero, every expert layer's
correction bias drawn N(0, 1) scaled so that the layer's largest entry is
``program.router_bias_max``, as ``families/deepseek_v3.py`` draws it, and
four gains that set a layer's branches against each other as a trained
model's are (at the init's 0.02 and these widths they are not: the
configuration's ``assumed.weights`` has the measurements): ``w_q_b`` times
``program.attention_q_gain``, ``wte`` times ``program.embedding_gain``,
``lm_head`` times ``program.head_gain``, and the dense SwiGLU's and the shared
expert's ``w_down`` times ``program.ffn_out_gain``.
"""

from __future__ import annotations

from dataclasses import replace
from typing import Any, Dict, List

#: Published keys the program's config carries under the same name.
PUBLISHED = ("hidden_size", "num_hidden_layers", "first_k_dense_replace",
             "num_attention_heads", "q_lora_rank", "kv_lora_rank",
             "qk_nope_head_dim", "qk_rope_head_dim", "v_head_dim",
             "index_n_heads", "index_head_dim", "index_topk",
             "intermediate_size", "moe_intermediate_size",
             "num_experts_per_tok", "n_shared_experts",
             "routed_scaling_factor", "norm_topk_prob", "rms_norm_eps",
             "max_position_embeddings", "vocab_size")
#: Published keys the program implements one value of.
FIXED = {"model_type": "glm_moe_dsa", "hidden_act": "silu",
         "scoring_func": "sigmoid", "topk_method": "noaux_tc", "n_group": 1,
         "topk_group": 1, "moe_layer_freq": 1, "attention_bias": False,
         "tie_word_embeddings": False, "rope_interleave": True,
         "indexer_rope_interleave": True, "num_nextn_predict_layers": 0,
         "ep_size": 1}


def _model():
    from ray_tpu.models import glm_moe_dsa
    return glm_moe_dsa


def config(program: Dict[str, Any]):
    """The program's ``GlmMoeDsaConfig`` from a configuration file's
    ``program`` group: a preset and overrides, dtypes by name."""
    import jax.numpy as jnp
    overrides = dict(program["overrides"])
    for key in ("dtype", "param_dtype"):
        if key in overrides:
            overrides[key] = jnp.dtype(overrides[key]).type
    return _model().config(program["preset"], **overrides)


def problems(published: Dict[str, Any], cfg) -> List[str]:
    """The program's config against the configuration file's published
    keys: the cell runs the widths, the layers, the indexers and the share
    it says it runs, and the file asks for nothing the program does not
    compute."""
    out = [f"{key}: program {getattr(cfg, key)!r}, file {published[key]!r}"
           for key in PUBLISHED if getattr(cfg, key) != published[key]]
    out += [f"{key}: the program computes {want!r} only, file "
            f"{published[key]!r}" for key, want in FIXED.items()
            if published.get(key, want) != want]
    if tuple(published["indexer_types"]) != cfg.indexer_types:
        out.append("indexer_types: the program's differ from the file's")
    run = list(published.get("layers_run",
                             range(published["num_hidden_layers"])))
    if run != list(range(cfg.first_layer,
                         cfg.first_layer + cfg.num_hidden_layers)):
        out.append(f"layers_run: file {run}, program {cfg.num_hidden_layers} "
                   f"layers from {cfg.first_layer}")
    dense = published["first_k_dense_replace"]
    if "mlp_layer_types" in published and published["mlp_layer_types"] != [
            "dense" if l < dense else "sparse"
            for l in range(len(published["mlp_layer_types"]))]:
        out.append("mlp_layer_types: not first_k_dense_replace dense layers "
                   "and then sparse ones")
    theta = published["rope_parameters"]
    if theta.get("rope_type", "default") != "default" \
            or float(theta["rope_theta"]) != cfg.rope_theta:
        out.append(f"rope_parameters: file {theta}, program default rope "
                   f"of theta {cfg.rope_theta}")
    if published["qk_head_dim"] != published["qk_nope_head_dim"] \
            + published["qk_rope_head_dim"]:
        out.append("qk_head_dim: not qk_nope_head_dim + qk_rope_head_dim")
    if published["num_key_value_heads"] != published["num_attention_heads"]:
        out.append("num_key_value_heads: the latent layer has one key and "
                   "value head a query head")
    held = published.get("deployment", {}).get("experts_held")
    if held is None:
        held = {"first": 0, "count": published["n_routed_experts"],
                "of": published["n_routed_experts"]}
    if published["n_routed_experts"] != held["count"] \
            or cfg.n_routed_experts != held["of"] \
            or (cfg.experts_held or (0, cfg.n_routed_experts)) != (
                held["first"], held["count"]):
        out.append(f"n_routed_experts: file {published['n_routed_experts']} "
                   f"held of {held}, program {cfg.experts_held} of "
                   f"{cfg.n_routed_experts}")
    assumed = published.get("assumed", {}).get("sizes", {})
    for key in ("indexer_loss_coef", "index_norm_eps"):
        if key in assumed and assumed[key] != getattr(cfg, key):
            out.append(f"assumed.sizes.{key}: file {assumed[key]!r}, program "
                       f"{getattr(cfg, key)!r}")
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
    1 (the indexer key's LayerNorm's bias around 0), the correction bias
    N(0, 1) scaled per layer to a largest entry of ``router_bias_max``,
    ``w_q_b`` times ``attention_q_gain``, ``wte`` times ``embedding_gain``,
    ``lm_head`` times ``head_gain``, and the dense SwiGLU's and the shared expert's ``w_down`` (not the
    routed experts') times ``ffn_out_gain`` (all from the configuration's
    ``program``)."""
    import jax
    import jax.numpy as jnp
    router_bias_max = program["router_bias_max"]
    sigma = program["norm_scale_sigma"]
    gains = {"w_q_b": program["attention_q_gain"],
             "wte": program["embedding_gain"],
             "lm_head": program["head_gain"]}
    ffn_gain = program["ffn_out_gain"]

    def drawn(tree, key):
        out = {}
        for k, (name, leaf) in zip(jax.random.split(key, len(tree)),
                                   sorted(tree.items())):
            if name == "router_bias":
                z = jax.random.normal(k, leaf.shape, jnp.float32)
                leaf = (z / z.max(-1, keepdims=True) * router_bias_max
                        ).astype(leaf.dtype)
            elif name.endswith("_scale") or name == "ik_norm_bias":
                leaf = (leaf.astype(jnp.float32) + sigma * jax.random.normal(
                    k, leaf.shape, jnp.float32)).astype(leaf.dtype)
            elif name in gains or name == "shared_w_down" or (
                    name == "w_down" and "router" not in tree):
                leaf = (gains.get(name, ffn_gain) * leaf.astype(jnp.float32)
                        ).astype(leaf.dtype)
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
    """The loss the train step differentiates (``check_grads_glm_moe_dsa``):
    both terms."""
    return _model().loss_fn(params, _a_chunked_loss(cfg, tokens), tokens,
                            targets)[0]


def init(cfg, seed: int, program: Dict[str, Any]):
    """Parameters alone, as ``state_and_step`` makes them."""
    import jax
    params = jax.jit(lambda key: _model().init(cfg, key))(
        jax.random.PRNGKey(seed))
    return draw_vectors(params, seed + 1, program)


def with_layers(config: Dict[str, Any], layers: int) -> Dict[str, Any]:
    """The configuration cut to the first ``layers`` of the layers it runs
    (file and program alike)."""
    run = list(config.get("layers_run", range(config["num_hidden_layers"])))
    program = dict(config["program"])
    program["overrides"] = dict(program["overrides"],
                                num_hidden_layers=layers)
    return dict(config, num_hidden_layers=layers, layers_run=run[:layers],
                program=program)


def tiny(config: Dict[str, Any]) -> Dict[str, Any]:
    """The configuration at a tiny size for a run on the CPU: width 128,
    the file's own layers, leading dense layers and indexers, four latent
    heads of 32 | 16 | 32 over latents of 64, the indexer's 32 heads at 32
    wide (fewer heads and whole rows of scores tie at 0, where the
    program's threshold and the reference's top-k part ways) keeping 64 of
    up to 256 keys, a dense SwiGLU of 256, experts of 128 with a share of 4
    of 16 and 2 a token, 512 tokens of vocabulary, one sequence of 256 (the
    selection's kernels tile by 128 and run interpreted), everything in
    float32. Same code path and layout; nothing it measures means
    anything."""
    first = config.get("deployment", {}).get("experts_held", {}).get(
        "first", 0)
    held = {"first": min(first, 12), "count": 4, "of": 16}
    sizes = dict(hidden_size=128, num_attention_heads=4, q_lora_rank=64,
                 kv_lora_rank=64, qk_nope_head_dim=32, qk_rope_head_dim=16,
                 v_head_dim=32, index_n_heads=32, index_head_dim=32,
                 index_topk=64, intermediate_size=256,
                 moe_intermediate_size=128, num_experts_per_tok=2,
                 vocab_size=512, max_position_embeddings=256)
    config = dict(config, n_routed_experts=held["count"],
                  num_key_value_heads=4, qk_head_dim=48, **sizes)
    config["deployment"] = dict(config.get("deployment", {}),
                                experts_held=held)
    program = dict(config["program"])
    program["overrides"] = dict(
        program["overrides"], loss_chunk=128, n_routed_experts=16,
        experts_held=[held["first"], held["count"]], dtype="float32",
        param_dtype="float32", attn_blk_q=128, attn_blk_k=128, **sizes)
    config["program"] = program
    config["layout"] = dict(config["layout"], seq_len=256)
    return config
