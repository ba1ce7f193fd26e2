"""The program's side of a configuration whose ``program.family`` is
``dots3_note``: ``ray_tpu/models/dots3_note.py`` trained by
``ray_tpu/parallel/train_step.py`` (which takes the model as an argument),
described by a published config under the ``Dots3NoteConfig`` key names
(``hidden_size``, ``layer_types``, the plain latent keys of the full layers
and the ``swa_`` ones of the window layers, ``sliding_window_size``,
``index_n_heads``, ``index_topk``, ``n_routed_experts``, ...). It offers what
``families/gpt.py``'s docstring lists, ``picked_experts`` for a routing
comparison, and ``loss``, ``init`` and ``with_layers`` for the gradient check
(``check_grads_dots3_note.py``).

**The cut and the chip's share** are ``families/glm_moe_dsa.py``'s: a
configuration's ``layers_run`` lists the published layers it runs (the
program's ``first_layer`` and ``num_hidden_layers``), while
``first_k_dense_replace`` and ``layer_types`` stay the published ones; its
``n_routed_experts`` is how many experts are held here
(``deployment.experts_held``: ``first``, ``count``, and ``of``, the published
count and the router's width), and its ``vocab_size`` the chip's slice of the
vocabulary.

The benchmark makes the weights: the program's one jitted init from the
seed, then (``draw_vectors``) every norm's scale (the two latents' and the
indexer key's LayerNorm too) redrawn N(0, ``program.norm_scale_sigma``)
around one and that LayerNorm's bias around zero, every expert layer's
correction bias drawn N(0, 1) scaled so that the layer's largest entry is
``program.router_bias_max``, and the gains that set a layer's branches
against each other as a trained model's are (the configuration's
``assumed.weights`` has the measurements): ``w_q_b`` times
``program.attention_q_gain``, ``wte`` times ``program.embedding_gain``,
``lm_head`` times ``program.head_gain``, and the dense SwiGLU's and the
shared expert's ``w_down`` times ``program.ffn_out_gain``.
"""

from __future__ import annotations

from dataclasses import replace
from typing import Any, Dict, List

#: The latent keys a full layer reads plain and a window layer under
#: ``swa_``.
LATENT = ("num_attention_heads", "q_lora_rank", "kv_lora_rank",
          "qk_nope_head_dim", "qk_rope_head_dim", "v_head_dim")
#: Published keys the program's config carries under the same name.
PUBLISHED = ("hidden_size", "num_hidden_layers", "first_k_dense_replace",
             *LATENT, *("swa_" + key for key in LATENT),
             "sliding_window_size", "apply_mla_qkv_lora_rescale",
             "attention_gate_type", "swa_attention_gate_type",
             "index_n_heads", "index_head_dim", "index_topk",
             "intermediate_size", "moe_intermediate_size",
             "num_experts_per_tok", "n_shared_experts",
             "routed_scaling_factor", "norm_topk_prob", "rms_norm_eps",
             "max_position_embeddings", "vocab_size")
#: Published keys the program implements one value of.
FIXED = {"model_type": "dots3_note", "hidden_act": "silu",
         "scoring_func": "sigmoid", "topk_method": "noaux_tc",
         "moe_layer_freq": 1, "attention_bias": False,
         "tie_word_embeddings": False, "rope_scaling": None}


def _model():
    from ray_tpu.models import dots3_note
    return dots3_note


def config(program: Dict[str, Any]):
    """The program's ``Dots3NoteConfig`` from a configuration file's
    ``program`` group: a preset and overrides, dtypes by name."""
    import jax.numpy as jnp
    overrides = dict(program["overrides"])
    for key in ("dtype", "param_dtype"):
        if key in overrides:
            overrides[key] = jnp.dtype(overrides[key]).type
    return _model().config(program["preset"], **overrides)


def problems(published: Dict[str, Any], cfg) -> List[str]:
    """The program's config against the configuration file's published
    keys: the cell runs the widths, the layers, the two geometries and the
    share it says it runs, and the file asks for nothing the program does
    not compute."""
    out = [f"{key}: program {getattr(cfg, key)!r}, file {published[key]!r}"
           for key in PUBLISHED if getattr(cfg, key) != published[key]]
    out += [f"{key}: the program computes {want!r} only, file "
            f"{published[key]!r}" for key, want in FIXED.items()
            if published.get(key, want) != want]
    if tuple(published["layer_types"]) != cfg.layer_types:
        out.append("layer_types: the program's differ from the file's")
    run = list(published.get("layers_run",
                             range(published["num_hidden_layers"])))
    if run != list(range(cfg.first_layer,
                         cfg.first_layer + cfg.num_hidden_layers)):
        out.append(f"layers_run: file {run}, program {cfg.num_hidden_layers} "
                   f"layers from {cfg.first_layer}")
    for prefix in ("", "swa_"):
        theta = prefix + "rope_theta"
        if float(published[theta]) != getattr(cfg, theta):
            out.append(f"{theta}: file {published[theta]}, program "
                       f"{getattr(cfg, theta)}")
        heads = prefix + "num_attention_heads"
        if published[prefix + "num_key_value_heads"] != published[heads]:
            out.append(f"{prefix}num_key_value_heads: the latent layer has "
                       "one key and value head a query head")
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
    ``lm_head`` times ``head_gain``, and the dense SwiGLU's and the shared
    expert's ``w_down`` (not the routed experts') times ``ffn_out_gain``
    (all from the configuration's ``program``)."""
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
    """The loss the train step differentiates (``check_grads_dots3_note``):
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
    the file's own layers and kinds, four full heads of 32 | 16 | 32 over
    latents of 64 beside two window heads of 48 | 16 | 32 over latents of 64
    and 96 in a window of 129 (one key past the tile), the indexer's 32
    heads at 32 wide (fewer heads and whole rows of scores tie at 0, where
    the program's threshold and the reference's top-k part ways) keeping 64
    of up to 256 keys, a dense SwiGLU of 256, experts of 128 with a share
    of 4 of 16 and 2 a token, 512 tokens of vocabulary, one sequence of 256
    (the kernels tile by 128 and run interpreted), everything in float32,
    ``attention_q_gain`` 32 (at these widths the rescaled latents give
    scores that spread by 0.05 where the published widths give 2, and a key
    wrongly seen would move nothing). Same code path and layout; nothing it
    measures means anything."""
    first = config.get("deployment", {}).get("experts_held", {}).get(
        "first", 0)
    held = {"first": min(first, 12), "count": 4, "of": 16}
    sizes = dict(hidden_size=128, num_attention_heads=4, q_lora_rank=64,
                 kv_lora_rank=64, qk_nope_head_dim=32, qk_rope_head_dim=16,
                 v_head_dim=32, swa_num_attention_heads=2, swa_q_lora_rank=64,
                 swa_kv_lora_rank=96, swa_qk_nope_head_dim=48,
                 swa_qk_rope_head_dim=16, swa_v_head_dim=32,
                 sliding_window_size=129, index_n_heads=32, index_head_dim=32,
                 index_topk=64, intermediate_size=256,
                 moe_intermediate_size=128, num_experts_per_tok=2,
                 vocab_size=512, max_position_embeddings=256)
    config = dict(config, n_routed_experts=held["count"],
                  num_key_value_heads=4, swa_num_key_value_heads=2, **sizes)
    config["deployment"] = dict(config.get("deployment", {}),
                                experts_held=held)
    program = dict(config["program"], attention_q_gain=32.0)
    program["overrides"] = dict(
        program["overrides"], loss_chunk=128, n_routed_experts=16,
        experts_held=[held["first"], held["count"]], dtype="float32",
        param_dtype="float32", attn_blk_q=128, attn_blk_k=128, **sizes)
    config["program"] = program
    config["layout"] = dict(config["layout"], seq_len=256)
    return config
