"""The program's side of a configuration whose ``program.family`` is
``minicpm_sala``: ``ray_tpu/models/minicpm_sala.py`` trained by
``ray_tpu/parallel/train_step.py`` (which takes the model as an argument),
described by a published config under the ``minicpm_sala`` key names
(``hidden_size``, ``num_attention_heads``, ``num_key_value_heads``,
``mixer_types``, ``lightning_nh``, ``scale_emb``, ``scale_depth``,
``dim_model_base``, ...) and the ``minicpm4`` mixer's ``sparse_config``
under ``assumed.sparse_config``. It offers what ``families/gpt.py``'s
docstring lists, and ``loss``, ``init`` and ``with_layers`` for the gradient
check (``check_grads_minicpm_sala.py``).

The benchmark makes the weights: the program's one jitted init from the
seed (matrices normal(0, 0.02), every norm's scale one), then
(``draw_vectors``) every norm's scale redrawn N(1,
``program.norm_scale_sigma``) and every leaf named in ``program.gains``
multiplied by its gain: the configuration's ``assumed.weights`` says why
each (a flat softmax hides a wrong selection; a mixer a hundredth of the
SwiGLU's power hides itself).

The program's logits are float32 ``[B, S, 73448]``, 4.8 GB of one sequence
of 16384: ``logits_and_losses`` makes them ``HEAD_BLOCKS`` slices of the
vocabulary side by side, the same products, of which the runner's gather of
64 positions holds one at a time (``families/phi4flash.py`` has the
measurement).
"""

from __future__ import annotations

from dataclasses import replace
from typing import Any, Dict, List

#: Published keys the program's config carries under the same name.
PUBLISHED = ("hidden_size", "num_hidden_layers", "num_attention_heads",
             "num_key_value_heads", "head_dim", "intermediate_size",
             "lightning_nh", "lightning_nkv", "lightning_head_dim",
             "rms_norm_eps", "scale_emb", "scale_depth", "dim_model_base",
             "max_position_embeddings", "vocab_size")
#: Published keys the program implements one value of.
FIXED = {"model_type": "minicpm_sala", "attention_bias": False,
         "attn_use_rope": False, "hidden_act": "silu",
         "lightning_scale": "1/sqrt(d)", "lightning_use_rope": True,
         "qk_norm": True, "tie_word_embeddings": False,
         "use_output_gate": True, "use_output_norm": True,
         "attn_use_output_gate": True}
#: Slices of the vocabulary ``logits_and_losses`` makes the logits in.
HEAD_BLOCKS = 4


def _model():
    from ray_tpu.models import minicpm_sala
    return minicpm_sala


def config(program: Dict[str, Any]):
    """The program's ``MiniCPMSALAConfig`` from a configuration file's
    ``program`` group: a preset and overrides, dtypes by name."""
    import jax.numpy as jnp
    overrides = dict(program["overrides"])
    for key in ("dtype", "param_dtype"):
        if key in overrides:
            overrides[key] = jnp.dtype(overrides[key]).type
    return _model().config(program["preset"], **overrides)


def problems(published: Dict[str, Any], cfg) -> List[str]:
    """The program's config against the configuration file's published
    keys and its assumed ``sparse_config``: the cell runs the widths, the
    heads, the mixers and the selection's sizes it says it runs, and the
    file asks for nothing the program does not compute."""
    out = [f"{key}: program {getattr(cfg, key)!r}, file {published[key]!r}"
           for key in PUBLISHED if getattr(cfg, key) != published[key]]
    out += [f"{key}: the program computes {want!r} only, file "
            f"{published[key]!r}" for key, want in FIXED.items()
            if published.get(key, want) != want]
    if tuple(published["mixer_types"]) != cfg.mixer_types:
        out.append("mixer_types: not the program's")
    if float(published["rope_theta"]) != cfg.rope_theta:
        out.append(f"rope_theta: program {cfg.rope_theta!r}, file "
                   f"{published['rope_theta']!r}")
    out += [f"sparse_config.{key}: program {getattr(cfg, 'sparse_' + key)!r}"
            f", file {size!r}"
            for key, size in published["assumed"]["sparse_config"].items()
            if getattr(cfg, "sparse_" + key) != size]
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
    """The program's init leaves every norm's scale at 1, where a scale
    left out would not show, and draws W_o at a size at which either mixer's
    branch is a hundredth of the SwiGLU's power; the q/k norms' scales of 1
    leave both softmaxes nearly flat. Redrawn from the seed in one jitted
    pass, in place, same shardings: every ``*_scale`` N(1,
    ``norm_scale_sigma``), then every leaf named in ``gains`` times its
    gain, ``<leaf>`` in every stack and outside them or ``<kind>.<leaf>`` in
    the stacks of one kind of layer (both from the configuration's
    ``program``)."""
    import jax
    import jax.numpy as jnp
    sigma, gains = program["norm_scale_sigma"], program["gains"]

    def drawn(tree, key, kind=None):
        out = {}
        for k, (name, leaf) in zip(jax.random.split(key, len(tree)),
                                   sorted(tree.items())):
            gain = gains.get(f"{kind}.{name}", gains.get(name))
            if name.endswith("_scale"):
                leaf = leaf.astype(jnp.float32) + sigma * jax.random.normal(
                    k, leaf.shape, jnp.float32)
            if gain is not None:
                leaf = gain * leaf.astype(jnp.float32)
            out[name] = leaf.astype(tree[name].dtype)
        return out

    def vectors_drawn(params, key):
        stacks = sorted(k for k in params if k.startswith("run"))
        keys = jax.random.split(key, 1 + len(stacks))
        rest = {k: v for k, v in params.items() if k not in stacks}
        return dict(drawn(rest, keys[0]), **{
            name: drawn(params[name], k, name.split("_", 1)[1])
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
    """The program's own forward (float32 logits [B, S, vocab], the head on
    ``HEAD_BLOCKS`` slices of the vocabulary side by side: the same
    products) and the loss the train step differentiates taken one sequence
    at a time (a mask of one row), both from one pass through the layers.
    Traced inside the caller's jit, under the caller's mesh."""
    import jax.numpy as jnp
    model = _model()
    cfg = _a_chunked_loss(cfg, tokens)
    hidden, aux = model.hidden_states(params, cfg, tokens)
    losses = [model.loss_of_hidden(
        params, cfg, hidden, aux, targets,
        mask=jnp.zeros(tokens.shape, jnp.float32).at[i].set(1.0))[0]
        for i in range(tokens.shape[0])]
    width = -(-cfg.vocab_size // HEAD_BLOCKS)
    logits = jnp.concatenate([
        model.head(dict(params, lm_head=params["lm_head"][:, at:at + width]),
                   cfg, hidden)
        for at in range(0, cfg.vocab_size, width)], axis=-1)
    return logits, jnp.stack(losses)


def loss(params, cfg, tokens, targets):
    """The loss the train step differentiates
    (``check_grads_minicpm_sala``)."""
    return _model().loss_fn(params, _a_chunked_loss(cfg, tokens), tokens,
                            targets)[0]


def init(cfg, seed: int, program: Dict[str, Any]):
    """Parameters alone, as ``state_and_step`` makes them."""
    import jax
    params = jax.jit(lambda key: _model().init(cfg, key))(
        jax.random.PRNGKey(seed))
    return draw_vectors(params, seed + 1, program)


def with_layers(config: Dict[str, Any], layers: int, dense_len=None
                ) -> Dict[str, Any]:
    """The configuration cut to its first ``layers`` layers and, if given,
    to a ``dense_len`` of its own (file and program alike)."""
    overrides = dict(config["program"]["overrides"],
                     num_hidden_layers=layers)
    config = dict(config, num_hidden_layers=layers)
    if dense_len is not None:
        overrides["sparse_dense_len"] = dense_len
        config["assumed"] = dict(config["assumed"], sparse_config=dict(
            config["assumed"]["sparse_config"], dense_len=dense_len))
    config["program"] = dict(config["program"], overrides=overrides)
    return config


def tiny(config: Dict[str, Any]) -> Dict[str, Any]:
    """The configuration at a tiny size for a run on the CPU: the file's
    own layers and mixers, width 128, four heads of the published 128 over
    two KV heads, two linear heads, a SwiGLU of 256, a vocabulary of 512,
    the selection's top 4 of 8 blocks with a window of two and a
    ``dense_len`` of 256, one sequence of 512 (over it: the sparse path
    runs; the kernels tile by 128 and run interpreted), everything in
    float32. Same code path and layout; nothing it measures means
    anything."""
    sizes = dict(hidden_size=128, num_attention_heads=4,
                 num_key_value_heads=2, head_dim=128, lightning_nh=2,
                 lightning_nkv=2, lightning_head_dim=128,
                 intermediate_size=256, vocab_size=512, dim_model_base=32,
                 max_position_embeddings=1024)
    sparse = dict(topk=4, window_size=128, dense_len=256)
    config = dict(config, **sizes)
    config["assumed"] = dict(config["assumed"], sparse_config=dict(
        config["assumed"]["sparse_config"], **sparse))
    program = dict(config["program"])
    program["overrides"] = dict(
        program["overrides"], loss_chunk=128, dtype="float32",
        param_dtype="float32", attn_blk_q=128, attn_blk_k=128, **sizes,
        **{"sparse_" + key: size for key, size in sparse.items()})
    config["program"] = program
    config["layout"] = dict(config["layout"], seq_len=512)
    return config
