"""The program's side of a configuration whose ``program.family`` is
``mellum``: ``ray_tpu/models/mellum.py`` trained by
``ray_tpu/parallel/train_step.py`` (which takes the model as an argument),
described by a published config under the ``MellumConfig`` key names
(``hidden_size``, ``layer_types``, ``sliding_window``, ``rope_parameters``
by kind of layer, ``num_experts``, ``norm_topk_prob``, ...). It offers what
``families/gpt.py``'s docstring lists, ``picked_experts`` for a routing
comparison, and ``loss``, ``init`` and ``with_layers`` for the gradient
check (``check_grads_mellum.py``).

The benchmark makes the weights: the program's one jitted init from the
seed (matrices normal(0, 0.02), every norm's scale one), then
(``draw_vectors``) every norm's scale (the q and k norms' too) redrawn N(1,
``program.norm_scale_sigma``), the q and k norms' scales times
``program.attention_qk_gain`` (after those norms q and k have an RMS of one
whatever Wq and Wk are, so the gain, squared, is what sharpens the scores;
on Wq or Wk it would be normed away), and every expert layer's router
columns times ``program.router_gain * program.router_spread ** (z / max
z)``, z N(0, 1) an expert from the seed, clipped at -1. The spread: the
family has no bias to make routing uneven by, and a column of a larger norm
gives its expert logits of a larger spread, which a top-8 of 64 picks more
often; scaled to a fixed largest gain, because the largest of 64 plain draws
swings the imbalance from seed to seed. The gain on all columns alike moves
no pick (the top of the logits is the top of their multiple) and sharpens
the softmax over them: what of a token's probability its picked experts
hold (``moe.picked_mass``), and with it how little the eighth expert
weighs, which is what a pick that differs between bfloat16 and float32
costs the comparison.
"""

from __future__ import annotations

from dataclasses import replace
from typing import Any, Dict, List

#: Published keys the program's config carries under the same name.
PUBLISHED = ("hidden_size", "num_hidden_layers", "sliding_window",
             "num_attention_heads", "num_key_value_heads", "head_dim",
             "moe_intermediate_size", "num_experts_per_tok", "norm_topk_prob",
             "rms_norm_eps", "max_position_embeddings", "vocab_size")
#: Published keys the program implements one value of.
FIXED = {"model_type": "mellum", "hidden_act": "silu",
         "attention_bias": False, "tie_word_embeddings": False,
         "use_sliding_window": True}


def _model():
    from ray_tpu.models import mellum
    return mellum


def config(program: Dict[str, Any]):
    """The program's ``MellumConfig`` from a configuration file's
    ``program`` group: a preset and overrides, dtypes by name."""
    import jax.numpy as jnp
    overrides = dict(program["overrides"])
    for key in ("dtype", "param_dtype"):
        if key in overrides:
            overrides[key] = jnp.dtype(overrides[key]).type
    return _model().config(program["preset"], **overrides)


def problems(published: Dict[str, Any], cfg) -> List[str]:
    """The program's config against the configuration file's published
    keys: the cell runs the widths, the layer pattern, the rope parameters
    of each kind of layer and the experts it says it runs, and the file asks
    for nothing the program does not compute."""
    out = [f"{key}: program {getattr(cfg, key)!r}, file {published[key]!r}"
           for key in PUBLISHED if getattr(cfg, key) != published[key]]
    if tuple(published["layer_types"]) != cfg.layer_types:
        out.append(f"layer_types: program {cfg.layer_types!r}, file "
                   f"{published['layer_types']!r}")
    if dict(cfg.rope_parameters) != published["rope_parameters"]:
        out.append(f"rope_parameters: program {dict(cfg.rope_parameters)!r},"
                   f" file {published['rope_parameters']!r}")
    out += [f"{key}: the program computes {want!r} only, file "
            f"{published[key]!r}" for key, want in FIXED.items()
            if published.get(key, want) != want]
    if set(published.get("mlp_layer_types", ["sparse"])) != {"sparse"}:
        out.append("mlp_layer_types: every layer's FFN is the expert layer")
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
    if published["layout"]["seq_len"] > cfg.max_position_embeddings:
        out.append(f"layout.seq_len {published['layout']['seq_len']} is past "
                   f"the {cfg.max_position_embeddings} positions declared")
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
    """The program's init leaves every RMSNorm scale at 1, where no dropped
    or misplaced term would show and both kinds of softmax are nearly flat,
    and every router column at one size, where every expert is as busy as
    the next. Redrawn from the seed in one jitted pass, in place, same
    shardings (see the top of this file)."""
    import jax
    import jax.numpy as jnp
    sigma, qk_gain = program["norm_scale_sigma"], program["attention_qk_gain"]
    spread, router_gain = program["router_spread"], program["router_gain"]

    def drawn(tree, key):
        out = {}
        for k, (name, leaf) in zip(jax.random.split(key, len(tree)),
                                   sorted(tree.items())):
            if name == "router":  # [layers, d, experts]
                z = jax.random.normal(
                    k, (leaf.shape[0], 1, leaf.shape[-1]), jnp.float32)
                z = jnp.clip(z / z.max(-1, keepdims=True), -1.0, 1.0)
                leaf = leaf.astype(jnp.float32) * (router_gain * spread ** z)
            elif name.endswith("_scale"):
                gain = qk_gain if name in ("q_norm_scale", "k_norm_scale") \
                    else 1.0
                leaf = gain * (leaf.astype(jnp.float32) + sigma
                               * jax.random.normal(k, leaf.shape,
                                                   jnp.float32))
            out[name] = leaf.astype(tree[name].dtype)
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
    """(logits [B, S, vocab], picked [L, B, S, K]): the program's forward
    with the router's choice, the model's auxiliary output."""
    logits, aux = _model().forward_with_aux(params, cfg, tokens)
    return logits, aux["picked"]


def loss(params, cfg, tokens, targets):
    """The loss the train step differentiates (``check_grads_mellum``)."""
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
    the file's own number of layers and its own pattern, four query heads
    over two KV heads of the published 128 (so that the kernels tile), a
    window of 128 on sequences of 256 (so that the window's table and
    kernels run), the file's rope parameters with the YaRN ramp brought
    over an original length of 64 (its ramp then runs over pairs 0-12 of
    the 64), 16 experts of 128 with 2 a token, all held, 512 tokens of
    vocabulary, everything in float32: bfloat16 against the float32
    reference picks another expert at every tenth position, and each such
    position is off by more than the rehearsal's fixed tolerances (the
    chip's own, for bfloat16 and 8 of 64, are the configuration's). Same
    code path and layout; nothing it measures means anything."""
    ropes = {kind: dict(parameters, **(
        {"original_max_position_embeddings": 64}
        if parameters.get("rope_type") == "yarn" else {}))
        for kind, parameters in config["rope_parameters"].items()}
    sizes = dict(hidden_size=128, num_attention_heads=4,
                 num_key_value_heads=2, sliding_window=128,
                 moe_intermediate_size=128, num_experts=16,
                 num_experts_per_tok=2, vocab_size=512,
                 max_position_embeddings=1024, rope_parameters=ropes)
    config = dict(config, **sizes)
    program = dict(config["program"])
    program["overrides"] = dict(
        program["overrides"], loss_chunk=128, dtype="float32",
        param_dtype="float32", attn_blk_q=128, attn_blk_k=128, **sizes)
    config["program"] = program
    config["layout"] = dict(config["layout"], seq_len=256)
    return config
