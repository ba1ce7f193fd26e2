"""The program's side of a configuration whose ``program.family`` is
``nemotron_h``: ``ray_tpu/models/nemotron_h.py`` trained by
``ray_tpu/parallel/train_step.py`` (which takes the model as an argument),
described by a published config under the ``NemotronHConfig`` key names
(``hidden_size``, ``hybrid_override_pattern``, ``mamba_num_heads``,
``n_groups``, ``moe_intermediate_size``, ``n_routed_experts``, ...). It
offers what ``families/gpt.py``'s docstring lists, ``picked_experts`` for a
routing comparison, and ``loss``, ``init`` and ``with_layers`` for the
gradient check (``check_grads_nemotron_h.py``).

**The chip's share.** A configuration of this family may be one chip's share
of a deployment that divides every layer over several chips; its
``deployment`` group says so. The file's ``n_routed_experts`` is then how
many experts are held here (``deployment.experts_held``: ``first``,
``count``, and ``of``, the published count and the router's width), its
``vocab_size`` the chip's slice of the vocabulary
(``deployment.vocab_slice``): token ids, logits and loss are over the slice;
and its ``num_hidden_layers`` layers are ``hybrid_override_pattern``, kept
whole, from ``deployment.layers_run.first`` on (the program's
``first_layer``).

The benchmark makes the weights: the program's one jitted init from the
seed (matrices normal 0.02, the conv's taps at the variance of
``nn.Conv1d``'s default, ``dt_bias`` the inverse softplus of a step
log-uniform between ``time_step_min`` and ``time_step_max``, ``A_log`` =
log(1..heads)), then (``draw_vectors``) every vector redrawn around its
init, so that no dropped or misplaced term hides behind a one or a zero: the
RMSNorm scales (the gated norm's too), ``D`` and ``A_log`` N(0,
``program.norm_scale_sigma``) around their init, the conv bias N(0,
``program.conv_bias_sigma``) around zero, every expert layer's correction
bias N(0, 1) scaled so that the layer's largest entry is
``program.router_bias_max`` (as ``families/deepseek_v3.py`` draws
Moonlight's: the published buffer is zeros and the rule that moves it is
training code the config does not carry), then moved towards balance
(``balanced``, below), and ``Wq`` and ``Wk`` times
``program.attention_qk_gain`` (no norm and no rotation stands between them
and the scores, so the gain, squared, is the scores' spread: at the init's
scale a query's softmax over 16,384 keys is nearly flat and attention is the
running mean of v whichever KV head a query head reads).

**The bias balances the load, as a trained one does.** The correction bias
exists to even the experts' load out (``noaux_tc``: after each step an
expert busier than the mean has its bias lowered, an idler one raised), and
the published checkpoint's holds what that left. Random weights need it
more than trained ones: a squared ReLU's hidden activations have a mean that
every token shares, so every branch adds one vector to all tokens alike, the
normed stream leans the same way at every position, and a router's column
that happens to point that way is picked far more often than the bias's own
spread would make it (the busiest of 128 experts read 7.6 times the mean at
the first reading on the chip, and which experts those are moves this
chip's share of the work with the seed). ``balanced`` runs
``program.router_balance_steps`` passes of the rule's direction in closed
form: the model's own forward on one seeded sequence of
``program.router_balance_tokens`` ids, every expert layer's histogram over
all the router's experts, and ``b -= program.router_balance_rate x
log(load / mean load)`` (the ratio clipped to [1/20, 20]; near the top-k's
threshold a load is close to exponential in the bias). The drawn bias stays
in it as the starting point, so that a dropped bias still shows as a
routing that differs.
"""

from __future__ import annotations

from dataclasses import replace
from typing import Any, Dict, List

#: Published keys the program's config carries under the same name.
PUBLISHED = ("hidden_size", "num_hidden_layers", "hybrid_override_pattern",
             "num_attention_heads", "num_key_value_heads", "head_dim",
             "mamba_num_heads", "mamba_head_dim", "ssm_state_size",
             "n_groups", "conv_kernel", "use_conv_bias", "mlp_hidden_act",
             "mamba_hidden_act", "moe_intermediate_size",
             "moe_shared_expert_intermediate_size", "n_shared_experts",
             "num_experts_per_tok", "norm_topk_prob",
             "routed_scaling_factor", "n_group", "topk_group",
             "time_step_min", "time_step_max", "time_step_floor",
             "layer_norm_epsilon", "max_position_embeddings", "vocab_size",
             "tie_word_embeddings")
#: Published keys the program implements one value of.
FIXED = {"model_type": "nemotron_h", "attention_bias": False,
         "mamba_proj_bias": False, "mlp_bias": False, "use_bias": False,
         "sliding_window": None, "residual_in_fp32": False}


def _model():
    from ray_tpu.models import nemotron_h
    return nemotron_h


def config(program: Dict[str, Any]):
    """The program's ``NemotronHConfig`` from a configuration file's
    ``program`` group: a preset and overrides, dtypes by name."""
    import jax.numpy as jnp
    overrides = dict(program["overrides"])
    for key in ("dtype", "param_dtype"):
        if key in overrides:
            overrides[key] = jnp.dtype(overrides[key]).type
    return _model().config(program["preset"], **overrides)


def problems(published: Dict[str, Any], cfg) -> List[str]:
    """The program's config against the configuration file's published
    keys: the cell runs the widths, the pattern, the layers and the share it
    says it runs, and the file asks for nothing the program does not
    compute. ``chunk_size`` is the scan's own and may differ: the file's
    ``assumed.chunk_size`` says why."""
    out = [f"{key}: program {getattr(cfg, key)!r}, file {published[key]!r}"
           for key in PUBLISHED if getattr(cfg, key) != published[key]]
    out += [f"{key}: the program computes {want!r} only, file "
            f"{published[key]!r}" for key, want in FIXED.items()
            if published.get(key, want) != want]
    if published.get("norm_eps", cfg.layer_norm_epsilon) \
            != cfg.layer_norm_epsilon:
        out.append("norm_eps: the program has one epsilon, "
                   "layer_norm_epsilon")
    deployment = published.get("deployment", {})
    first = deployment.get("layers_run", {}).get("first", 0)
    if cfg.first_layer != first:
        out.append(f"first_layer: program {cfg.first_layer}, file "
                   f"deployment.layers_run.first {first}")
    held = deployment.get("experts_held")
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
    state["params"] = balanced(
        draw_vectors(state["params"], seed + 1, program), cfg, seed + 3,
        program, mesh)
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
    """Every vector redrawn from the seed in one jitted pass, in place, same
    shardings (see the top of this file). A leaf of a unit of two layers
    carries its layer's prefix (``a_``, ``b_``) before its name."""
    import jax
    import jax.numpy as jnp
    sigma, bias_sigma = program["norm_scale_sigma"], \
        program["conv_bias_sigma"]
    bias_max, qk_gain = program["router_bias_max"], \
        program["attention_qk_gain"]

    def drawn(tree, key):
        out = {}
        for k, (name, leaf) in zip(jax.random.split(key, len(tree)),
                                   sorted(tree.items())):
            f32 = leaf.astype(jnp.float32)
            bare = name[2:] if name[:2] in ("a_", "b_") else name
            if bare == "router_bias":
                z = jax.random.normal(k, leaf.shape, jnp.float32)
                f32 = z / z.max(-1, keepdims=True) * bias_max
            elif bare in ("wq", "wk"):
                f32 = qk_gain * f32
            elif bare == "conv_b":
                f32 = f32 + bias_sigma * jax.random.normal(k, leaf.shape)
            elif bare.endswith("_scale") or bare in ("D", "A_log"):
                f32 = f32 + sigma * jax.random.normal(k, leaf.shape)
            out[name] = f32.astype(leaf.dtype)
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


def balanced(params, cfg, seed: int, program: Dict[str, Any], mesh=None):
    """The parameters with every expert layer's correction bias moved
    towards an even load (the top of this file), in place, same shardings;
    as they are where ``program.router_balance_steps`` is 0 or absent. The
    expert layers' bias leaves, a run after the other, are in the order of
    the model's ``picked`` [L, B, S, K]."""
    steps = int(program.get("router_balance_steps", 0))
    if not steps:
        return params
    import jax
    import jax.numpy as jnp
    import numpy as np
    from ray_tpu.parallel import mesh as mesh_mod
    model, rate = _model(), program["router_balance_rate"]
    tokens = jnp.asarray(np.random.default_rng(seed).integers(
        0, cfg.vocab_size, (1, program["router_balance_tokens"]),
        dtype=np.int32))
    where = [(run, leaf) for run in sorted(params) if run.startswith("run")
             for leaf in ("router_bias", "a_router_bias", "b_router_bias")
             if leaf in params[run]]

    def moved(params):
        _, aux = model.hidden_states(params, cfg, tokens)
        picked = aux["picked"].reshape(aux["picked"].shape[0], -1, 1)
        loads = (picked == jnp.arange(cfg.n_routed_experts)).sum(
            1, dtype=jnp.float32)                           # [L, experts]
        ratio = jnp.clip(loads / loads.mean(-1, keepdims=True), 0.05, 20.0)
        params, at = dict(params), 0
        for run, leaf in where:
            bias = params[run][leaf]
            step = rate * jnp.log(ratio[at:at + bias.shape[0]])
            params[run] = dict(params[run], **{leaf: (
                bias.astype(jnp.float32) - step).astype(bias.dtype)})
            at += bias.shape[0]
        return params

    shardings = jax.tree.map(lambda a: a.sharding, params)
    move = jax.jit(moved, donate_argnums=(0,), out_shardings=shardings)
    # The flash kernels read the ambient mesh, as inside a train step.
    previous = mesh_mod.current_mesh()
    mesh_mod.set_current_mesh(mesh)
    try:
        for _ in range(steps):
            params = move(params)
    finally:
        mesh_mod.set_current_mesh(previous)
    return params


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
    """(logits [B, S, vocab], picked [L expert layers, B, S, K]): the
    program's forward with the router's choice, the model's auxiliary
    output."""
    logits, aux = _model().forward_with_aux(params, cfg, tokens)
    return logits, aux["picked"]


def loss(params, cfg, tokens, targets):
    """The loss the train step differentiates
    (``check_grads_nemotron_h``)."""
    return _model().loss_fn(params, _a_chunked_loss(cfg, tokens), tokens,
                            targets)[0]


def init(cfg, seed: int, program: Dict[str, Any]):
    """Parameters alone, as ``state_and_step`` makes them."""
    import jax
    params = jax.jit(lambda key: _model().init(cfg, key))(
        jax.random.PRNGKey(seed))
    return balanced(draw_vectors(params, seed + 1, program), cfg, seed + 3,
                    program)


def with_layers(config: Dict[str, Any], layers: int) -> Dict[str, Any]:
    """The configuration cut to the first ``layers`` of the layers it runs
    (file and program alike)."""
    program = dict(config["program"])
    program["overrides"] = dict(program["overrides"],
                                num_hidden_layers=layers)
    return dict(config, num_hidden_layers=layers, program=program)


def tiny(config: Dict[str, Any]) -> Dict[str, Any]:
    """The configuration at a tiny size for a run on the CPU: width 128, the
    file's own layers of its own pattern, four state-space heads of 64 in
    two B/C groups with a state of 128 and chunks of 128 (a head block a
    group, so that the scan's, the conv's and the grouped norm's kernels
    tile and run interpreted), four query heads over two KV heads of 128,
    experts of 192 (no multiple of 128: the grouped product's irregular
    tiles) with the file's share of 16 (held: the file's own run, cut to 4)
    at 2 a token, a shared expert of 384, 512 tokens of vocabulary,
    sequences of 256, everything in float32 (where nothing routes
    differently from the float32 reference: the chip's own tolerances, for
    bfloat16 and the real share, are the configuration's). Same code path
    and layout; nothing it measures means anything."""
    first = config.get("deployment", {}).get("experts_held", {}).get(
        "first", 0)
    held = {"first": min(first, 12), "count": 4, "of": 16}
    sizes = dict(hidden_size=128, num_attention_heads=4,
                 num_key_value_heads=2, head_dim=128, mamba_num_heads=4,
                 mamba_head_dim=64, ssm_state_size=128, n_groups=2,
                 moe_intermediate_size=192,
                 moe_shared_expert_intermediate_size=384,
                 num_experts_per_tok=2, vocab_size=512,
                 max_position_embeddings=256)
    config = dict(config, n_routed_experts=held["count"], **sizes)
    config["deployment"] = dict(config.get("deployment", {}),
                                experts_held=held)
    program = dict(config["program"])
    program["overrides"] = dict(
        program["overrides"], loss_chunk=128, chunk_size=128,
        n_routed_experts=16, experts_held=[held["first"], held["count"]],
        dtype="float32", param_dtype="float32", attn_blk_q=128,
        attn_blk_k=128, **sizes)
    if "router_balance_tokens" in program:
        program["router_balance_tokens"] = 256
    config["program"] = program
    config["layout"] = dict(config["layout"], seq_len=256)
    return config
