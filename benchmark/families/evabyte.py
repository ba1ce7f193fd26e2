"""The program's side of a configuration whose ``program.family`` is
``evabyte``: ``ray_tpu/models/evabyte.py`` trained by
``ray_tpu/parallel/train_step.py`` (which takes the model as an argument),
described by a published config under the ``EvaByteConfig`` key names
(``hidden_size``, ``num_attention_heads``, ``intermediate_size``,
``window_size``, ``chunk_size``, ``num_pred_heads``, ...). It offers what
``families/gpt.py``'s docstring lists, and ``loss``, ``init`` and
``with_layers`` for the gradient check (``check_grads_evabyte.py``).

The program's logits are ``[B, S, num_pred_heads, vocab]``; the runner
samples positions along axis 1 of a ``[B, S, width]`` array, so
``logits_and_losses`` hands it all the heads of a position side by side,
``[B, S, num_pred_heads x vocab]``, as the reference does. ``vocab_size`` is
what the traffic draws its bytes from and what a head's uniform loss is the
logarithm of: 320.

The benchmark makes the weights: the program's one jitted init from the
seed (matrices at the published ``init_std``, ``phi`` and ``mu`` at
head_dim^-1/2, the norms' offsets zero), then (``draw_vectors``) every
norm's offset g redrawn N(0, ``program.norm_offset_sigma``) and every leaf
named in ``program.gains`` multiplied by its gain: the configuration's
``assumed.weights`` says why each (a flat softmax hides a wrong mask; a
``mu`` of nothing hides itself).
"""

from __future__ import annotations

from dataclasses import replace
from typing import Any, Dict, List

#: Published keys the program's config carries under the same name.
PUBLISHED = ("hidden_size", "num_hidden_layers", "num_attention_heads",
             "num_key_value_heads", "intermediate_size", "window_size",
             "chunk_size", "num_pred_heads", "rms_norm_eps", "init_std",
             "max_position_embeddings", "vocab_size")
#: Published keys the program implements one value of.
FIXED = {"model_type": "evabyte", "attention_class": "eva",
         "hidden_act": "silu", "attention_bias": False,
         "norm_add_unit_offset": True, "fp32_skip_add": True,
         "fp32_logits": True, "fp32_ln": False, "mixedp_attn": True,
         "tie_word_embeddings": False, "rope_scaling": None,
         "num_chunks": None}


def _model():
    from ray_tpu.models import evabyte
    return evabyte


def config(program: Dict[str, Any]):
    """The program's ``EvaByteConfig`` from a configuration file's
    ``program`` group: a preset and overrides, dtypes by name."""
    import jax.numpy as jnp
    overrides = dict(program["overrides"])
    for key in ("dtype", "param_dtype"):
        if key in overrides:
            overrides[key] = jnp.dtype(overrides[key]).type
    return _model().config(program["preset"], **overrides)


def problems(published: Dict[str, Any], cfg) -> List[str]:
    """The program's config against the configuration file's published
    keys: the cell runs the widths, the window, the chunks and the heads it
    says it runs, and the file asks for nothing the program does not
    compute."""
    out = [f"{key}: program {getattr(cfg, key)!r}, file {published[key]!r}"
           for key in PUBLISHED if getattr(cfg, key) != published[key]]
    out += [f"{key}: the program computes {want!r} only, file "
            f"{published[key]!r}" for key, want in FIXED.items()
            if published.get(key, want) != want]
    if float(published["rope_theta"]) != cfg.rope_theta:
        out.append(f"rope_theta: program {cfg.rope_theta!r}, file "
                   f"{published['rope_theta']!r}")
    if published.get("max_seq_length", cfg.max_position_embeddings) \
            != cfg.max_position_embeddings:
        out.append("max_seq_length: not max_position_embeddings")
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
    """The program's init leaves every norm's offset at 0, where ``g`` for
    ``1 + g`` would not show, and draws W_q, W_k, ``mu`` and W_o at scales at
    which the softmax is flat and the summaries' offset and the whole
    attention branch are small beside the SwiGLU's. Redrawn from the seed
    in one jitted pass, in place, same shardings: offsets N(0,
    ``norm_offset_sigma``), every leaf named in ``gains`` times its gain
    (both from the configuration's ``program``)."""
    import jax
    import jax.numpy as jnp
    sigma, gains = program["norm_offset_sigma"], program["gains"]

    def drawn(tree, key):
        out = {}
        for k, (name, leaf) in zip(jax.random.split(key, len(tree)),
                                   sorted(tree.items())):
            if name.endswith("_scale"):
                leaf = (leaf.astype(jnp.float32) + sigma * jax.random.normal(
                    k, leaf.shape, jnp.float32)).astype(leaf.dtype)
            elif name in gains:
                leaf = (gains[name] * leaf.astype(jnp.float32)
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
    """The program's own forward, all the heads of a position side by side
    ([B, S, heads x vocab], float32), and the loss the train step
    differentiates (the mean of the heads') taken one sequence at a time (a
    mask of one row), both from one pass through the layers. Traced inside
    the caller's jit, under the caller's mesh."""
    import jax.numpy as jnp
    model = _model()
    cfg = _a_chunked_loss(cfg, tokens)
    hidden, aux = model.hidden_states(params, cfg, tokens)
    losses = [model.loss_of_hidden(
        params, cfg, hidden, aux, targets,
        mask=jnp.zeros(tokens.shape, jnp.float32).at[i].set(1.0))[0]
        for i in range(tokens.shape[0])]
    logits = model.head(params, cfg, hidden)
    return logits.reshape(*logits.shape[:2], -1), jnp.stack(losses)


def loss(params, cfg, tokens, targets):
    """The loss the train step differentiates (``check_grads_evabyte``)."""
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
    """The configuration at a tiny size for a run on the CPU: the file's
    own layers, width 128, four heads of 32, a SwiGLU of 256, windows of 64
    in chunks of 8, four prediction heads, one sequence of 256 (four
    windows, 24 summaries; the kernels tile by 128 and run interpreted),
    everything in float32. Same code path and layout; nothing it measures
    means anything."""
    sizes = dict(hidden_size=128, num_attention_heads=4,
                 num_key_value_heads=4, intermediate_size=256,
                 window_size=64, chunk_size=8, num_pred_heads=4,
                 max_position_embeddings=256)
    config = dict(config, max_seq_length=256, **sizes)
    program = dict(config["program"])
    program["overrides"] = dict(
        program["overrides"], loss_chunk=128, dtype="float32",
        param_dtype="float32", attn_blk_q=128, attn_blk_k=128, **sizes)
    config["program"] = program
    config["layout"] = dict(config["layout"], seq_len=256)
    return config
