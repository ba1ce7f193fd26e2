"""The program's side of a configuration whose ``program.family`` is
``lfm2_moe``: ``ray_tpu/models/lfm2.py`` trained by
``ray_tpu/parallel/train_step.py`` (which takes the model as an argument),
described by a published config under the ``Lfm2Config`` key names
(``hidden_size``, ``layer_types``, ``conv_L_cache``, ``num_dense_layers``,
``num_experts``, ``num_experts_per_tok``, ``rope_parameters``, ...). It
offers what ``families/gpt.py``'s docstring lists, ``picked_experts`` for a
routing comparison, and ``loss``, ``init`` and ``with_layers`` for the
gradient check (``check_grads_lfm2.py``).

**The chip's share.** A configuration of this family may be one chip's share
of a deployment that divides every layer over several chips; its
``deployment`` group says so. The file's ``num_experts`` is then how many
experts are held here (``deployment.experts_held``: ``first``, ``count``, and
``of``, the published count and the router's width), its ``vocab_size`` the
chip's slice of the vocabulary (``deployment.vocab_slice``): token ids,
logits and loss are over the slice, so the traffic draws its ids from
``vocab_size(cfg)`` as for any other vocabulary; and its
``num_hidden_layers`` layers are the published ``layer_types`` from
``deployment.layers_run.first`` on (the program's ``first_layer``), the
first ``num_dense_layers`` of them dense.

The benchmark makes the weights: the program's one jitted init from the
seed (matrices normal 0.02, the convolutions' taps normal with the variance
of ``nn.Conv1d``'s default), then (``draw_vectors``) every RMSNorm scale
(the norms on q and k too) redrawn N(0, ``program.norm_scale_sigma``) around
one, the q and k norms' scales then multiplied by ``program.qk_norm_gain``
(after the norm a head's q and k have unit RMS whatever the weights: their
scores over 8192 keys spread by about one, the softmax is nearly flat, and
a rotation left out would move the logits by little more than bfloat16
does; the gain squared is the spread), and every expert layer's
``expert_bias`` drawn from the seed, N(0, 1) scaled so that the layer's
largest entry is ``program.router_bias_max``, as ``families/deepseek_v3.py``
draws it.
"""

from __future__ import annotations

from dataclasses import replace
from typing import Any, Dict, List

#: Published keys the program's config carries under the same name.
PUBLISHED = ("hidden_size", "num_hidden_layers", "num_dense_layers",
             "conv_L_cache", "conv_bias", "num_attention_heads",
             "num_key_value_heads", "intermediate_size",
             "moe_intermediate_size", "num_experts_per_tok",
             "norm_topk_prob", "routed_scaling_factor", "use_expert_bias",
             "norm_eps", "max_position_embeddings", "vocab_size")
#: Published keys the program implements one value of.
FIXED = {"model_type": "lfm2_moe", "tie_word_embeddings": True}


def _model():
    from ray_tpu.models import lfm2
    return lfm2


def config(program: Dict[str, Any]):
    """The program's ``Lfm2Config`` from a configuration file's ``program``
    group: a preset and overrides, dtypes by name."""
    import jax.numpy as jnp
    overrides = dict(program["overrides"])
    for key in ("dtype", "param_dtype"):
        if key in overrides:
            overrides[key] = jnp.dtype(overrides[key]).type
    return _model().config(program["preset"], **overrides)


def problems(published: Dict[str, Any], cfg) -> List[str]:
    """The program's config against the configuration file's published
    keys: the cell runs the widths, the layer pattern, the layers and the
    share it says it runs, and the file asks for nothing the program does
    not compute."""
    out = [f"{key}: program {getattr(cfg, key)!r}, file {published[key]!r}"
           for key in PUBLISHED if getattr(cfg, key) != published[key]]
    if tuple(published["layer_types"]) != cfg.layer_types:
        out.append("layer_types: the program's are not the file's")
    rope, got = published["rope_parameters"], cfg.rope_parameters
    out += [f"rope_parameters.{key}: program {getattr(got, key)!r}, file "
            f"{want!r}" for key, want in rope.items()
            if getattr(got, key) != want]
    out += [f"{key}: the program computes {want!r} only, file "
            f"{published[key]!r}" for key, want in FIXED.items()
            if published.get(key, want) != want]
    deployment = published.get("deployment", {})
    first = deployment.get("layers_run", {}).get("first", 0)
    if cfg.first_layer != first:
        out.append(f"first_layer: program {cfg.first_layer}, file "
                   f"deployment.layers_run.first {first}")
    held = deployment.get("experts_held")
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
    """The program's init leaves every RMSNorm scale at 1 and
    ``expert_bias`` at 0, where no dropped or misplaced term would show and
    every expert is as busy as the next. Redrawn from the seed in one jitted
    pass, in place, same shardings: scales N(0, ``norm_scale_sigma``) around
    1, those of the norms on q and k then times ``qk_norm_gain``, the bias
    N(0, 1) scaled per layer to a largest entry of ``router_bias_max`` (all
    from the configuration's ``program``)."""
    import jax
    import jax.numpy as jnp
    router_bias_max = program["router_bias_max"]
    sigma, qk_gain = program["norm_scale_sigma"], program["qk_norm_gain"]

    def drawn(tree, key):
        out = {}
        for k, (name, leaf) in zip(jax.random.split(key, len(tree)),
                                   sorted(tree.items())):
            if name == "router_bias":
                z = jax.random.normal(k, leaf.shape, jnp.float32)
                leaf = (z / z.max(-1, keepdims=True) * router_bias_max
                        ).astype(leaf.dtype)
            elif name.endswith("_scale"):
                gain = qk_gain if name in ("q_norm_scale", "k_norm_scale") \
                    else 1.0
                leaf = (gain * (leaf.astype(jnp.float32)
                                + sigma * jax.random.normal(
                                    k, leaf.shape, jnp.float32))
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
    """The loss the train step differentiates (``check_grads_lfm2``)."""
    return _model().loss_fn(params, _a_chunked_loss(cfg, tokens), tokens,
                            targets)[0]


def init(cfg, seed: int, program: Dict[str, Any]):
    """Parameters alone, as ``state_and_step`` makes them."""
    import jax
    params = jax.jit(lambda key: _model().init(cfg, key))(
        jax.random.PRNGKey(seed))
    return draw_vectors(params, seed + 1, program)


def with_layers(config: Dict[str, Any], layers: int) -> Dict[str, Any]:
    """The configuration cut to its first ``layers`` layers of those it
    runs (file and program alike)."""
    program = dict(config["program"])
    program["overrides"] = dict(program["overrides"],
                                num_hidden_layers=layers)
    return dict(config, num_hidden_layers=layers, program=program)


def tiny(config: Dict[str, Any]) -> Dict[str, Any]:
    """The configuration at a tiny size for a run on the CPU: width 128
    (so that the convolution's kernels tile and run interpreted), the file's
    own layers, leading dense layers and pattern, four heads of 32 over two
    KV heads, a dense SwiGLU of 256, experts of 128 with the file's share of
    16 (held: the file's own run, cut to 4) and 2 a token, 512 tokens of
    vocabulary, everything in float32 (where nothing routes differently from
    the float32 reference: the chip's own tolerances, for bfloat16 and the
    real share, are the configuration's). Same code path and layout; nothing
    it measures means anything."""
    first = config.get("deployment", {}).get("experts_held", {}).get(
        "first", 0)
    held = {"first": min(first, 12), "count": 4, "of": 16}
    sizes = dict(hidden_size=128, num_attention_heads=4,
                 num_key_value_heads=2, intermediate_size=256,
                 moe_intermediate_size=128, num_experts_per_tok=2,
                 vocab_size=512, max_position_embeddings=256)
    config = dict(config, num_experts=held["count"], **sizes)
    config["deployment"] = dict(config.get("deployment", {}),
                                experts_held=held)
    program = dict(config["program"])
    program["overrides"] = dict(
        program["overrides"], loss_chunk=128, num_experts=16,
        experts_held=[held["first"], held["count"]], dtype="float32",
        param_dtype="float32", **sizes)
    config["program"] = program
    config["layout"] = dict(config["layout"], seq_len=256)
    return config
