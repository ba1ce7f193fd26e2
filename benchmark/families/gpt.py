"""The program's side of a configuration whose ``program.family`` is ``gpt``:
``ray_tpu/models/gpt.py`` trained by ``ray_tpu/parallel/train_step.py``,
described by a published config under GPT-2 / GPT-J key names (``n_layer``,
``n_embd``, ``n_head``, ``n_inner``, ``rotary_dim``, ``vocab_size``).

The train runner knows no model module. Everything that depends on one it
takes from ``families/<program.family>.py``, which offers:

    config(program)                          the program's config object
    problems(published, cfg)                 where cfg departs from the file
    vocab_size(cfg)
    state_and_step(cfg, mesh, program, seed) train state on the device, and
                                             the jitted step
    abstract_state_and_step(cfg, mesh, program)   the same, nothing made
    batch_sharding(mesh)                     of a [batch, seq] token array
    logits_and_losses(params, cfg, tokens, targets)
    tiny(config)                             the configuration for a CPU rehearsal
"""

from __future__ import annotations

from dataclasses import replace
from typing import Any, Dict, List


def config(program: Dict[str, Any]):
    """The program's ``GPTConfig`` from a configuration file's ``program``
    group: a preset and overrides, dtypes by name."""
    import jax.numpy as jnp

    from ray_tpu.models import gpt
    overrides = dict(program["overrides"])
    for key in ("dtype", "param_dtype"):
        if key in overrides:
            overrides[key] = jnp.dtype(overrides[key]).type
    return gpt.config(program["preset"], **overrides)


def problems(published: Dict[str, Any], cfg) -> List[str]:
    """The program's config against the configuration file's published
    keys: the cell runs the widths it says it runs."""
    n_inner = published.get("n_inner") or 4 * published["n_embd"]
    pairs = [("n_layer", cfg.n_layers, published["n_layer"]),
             ("n_embd", cfg.d_model, published["n_embd"]),
             ("n_head", cfg.n_heads, published["n_head"]),
             ("n_inner", cfg.d_ff, n_inner),
             ("rotary_dim", cfg.rotary_dim, published["rotary_dim"]),
             ("vocab_size", cfg.vocab_size, published["vocab_size"]),
             ("layer_norm_epsilon", cfg.layernorm_eps,
              published["layer_norm_epsilon"]),
             ("tie_word_embeddings", cfg.tie_embeddings,
              published.get("tie_word_embeddings", False))]
    return [f"{key}: program {got!r}, file {want!r}"
            for key, got, want in pairs if got != want]


def vocab_size(cfg) -> int:
    return cfg.vocab_size


def _rules_and_optimizer(program: Dict[str, Any]):
    from ray_tpu.parallel.sharding import ShardingRules
    from ray_tpu.parallel.train_step import memory_efficient_optimizer
    opt = program["optimizer"]
    return ShardingRules(), memory_efficient_optimizer(
        learning_rate=opt["learning_rate"], warmup_steps=opt["warmup_steps"])


def state_and_step(cfg, mesh, program: Dict[str, Any], seed: int):
    """The train state on the device from the seed (the program's one
    jitted init, then the vectors it leaves at 0 and 1 redrawn) and the
    jitted step ``(state, batch) -> (state, metrics)``."""
    from ray_tpu.parallel.train_step import init_train_state, make_train_step
    rules, optimizer = _rules_and_optimizer(program)
    state = init_train_state(cfg, mesh, rules, optimizer, seed=seed)
    step = make_train_step(cfg, mesh, rules, optimizer)
    state["params"] = draw_vectors(state["params"], seed + 1)
    return state, step


def abstract_state_and_step(cfg, mesh, program: Dict[str, Any]):
    """As ``state_and_step`` with nothing made: shapes and shardings."""
    from ray_tpu.parallel.train_step import (abstract_train_state,
                                             make_train_step)
    rules, optimizer = _rules_and_optimizer(program)
    return (abstract_train_state(cfg, mesh, rules, optimizer),
            make_train_step(cfg, mesh, rules, optimizer))


def batch_sharding(mesh):
    from jax.sharding import NamedSharding, PartitionSpec
    return NamedSharding(mesh, PartitionSpec(("dp", "fsdp"), None))


def draw_vectors(params, seed: int):
    """The program's init leaves every bias at 0 and every LayerNorm scale
    at 1, where no dropped or misplaced term would show. The benchmark
    makes the weights, so it redraws those vectors N(0, 0.02) around their
    init from the seed too: one jitted pass, in place, same shardings."""
    import jax
    import jax.numpy as jnp

    def drawn(tree, key, rank):
        leaves, treedef = jax.tree.flatten(tree)
        keys = jax.random.split(key, len(leaves))
        return jax.tree.unflatten(treedef, [
            leaf + (0.02 * jax.random.normal(k, leaf.shape, jnp.float32)
                    ).astype(leaf.dtype) if leaf.ndim == rank else leaf
            for leaf, k in zip(leaves, keys)])

    def vectors_drawn(params, key):
        k_layers, k_rest = jax.random.split(key)
        rest = {k: v for k, v in params.items() if k != "layers"}
        # Stacked over layers, a vector has rank 2.
        return dict(drawn(rest, k_rest, 1),
                    layers=drawn(params["layers"], k_layers, 2))

    shardings = jax.tree.map(lambda a: a.sharding, params)
    return jax.jit(vectors_drawn, donate_argnums=(0,),
                   out_shardings=shardings)(params, jax.random.PRNGKey(seed))


def logits_and_losses(params, cfg, tokens, targets):
    """The program's own forward, and the loss the train step differentiates
    taken one sequence at a time (a mask of one row). Traced inside the
    caller's jit, under the caller's mesh."""
    import jax.numpy as jnp

    from ray_tpu.models import gpt
    n_seq, seq = tokens.shape
    # The chunked loss takes its path only above loss_chunk tokens; with
    # few sequences it is held to one sequence a chunk.
    if cfg.loss_chunk and n_seq * seq <= cfg.loss_chunk:
        cfg = replace(cfg, loss_chunk=seq)
    logits = gpt.forward(params, cfg, tokens)
    losses = [gpt.loss_fn(params, cfg, tokens, targets,
                          mask=jnp.zeros(tokens.shape, jnp.float32
                                         ).at[i].set(1.0))[0]
              for i in range(n_seq)]
    return logits, jnp.stack(losses)


def tiny(config: Dict[str, Any]) -> Dict[str, Any]:
    """The configuration at a tiny size for a run on the CPU: two layers,
    width 256, two heads of 128, 512 tokens of vocabulary, sequences of
    256. Same code path and layout; nothing it measures means anything."""
    config = dict(config)
    config.update(n_embd=256, n_head=2, n_layer=2, vocab_size=512,
                  n_inner=None)
    program = dict(config["program"])
    program["overrides"] = dict(
        program["overrides"], n_layers=2, d_model=256, n_heads=2, d_ff=1024,
        vocab_size=512, max_seq_len=256, loss_chunk=256)
    config["program"] = program
    config["layout"] = dict(config["layout"], seq_len=256)
    return config
