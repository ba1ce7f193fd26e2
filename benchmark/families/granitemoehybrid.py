"""The program's side of a configuration whose ``program.family`` is
``granitemoehybrid``: ``ray_tpu/models/granite.py`` trained by
``ray_tpu/parallel/train_step.py`` (which takes the model as an argument),
described by a published config under the ``GraniteMoeHybridConfig`` key
names (``hidden_size``, ``layer_types``, ``mamba_d_state``,
``attention_multiplier``, ...). It offers what ``families/gpt.py``'s
docstring lists, and ``loss``, ``init`` and ``with_layers`` for the gradient
check (``check_grads_granite.py``).

The benchmark makes the weights: the program's one jitted init from the
seed, then (``draw_vectors``) every vector redrawn around its init, so that
no dropped or misplaced term hides behind a one or a zero: the RMSNorm
scales (the gated norm's too), ``D`` and ``A_log`` N(0,
``program.norm_scale_sigma``) around their init, the conv bias N(0,
``program.conv_bias_sigma``) around zero, and ``dt_bias`` the inverse
softplus of a step size drawn log-uniform in ``program.dt_range`` (Mamba-2's
published initialisation; the init's ``dt_bias`` of one gives steps near
1.3, at which no head remembers more than a few tokens and the state
carried from chunk to chunk is nothing). ``Wq`` and ``Wk`` are multiplied by
``program.attention_qk_gain``: at the init's scale the scores of a random
model are a tenth of a unit, every softmax is flat over its tens of
thousands of keys, and attention is the running mean of v whichever KV head
a query head reads.
"""

from __future__ import annotations

from dataclasses import replace
from typing import Any, Dict, List

#: Published keys the program's config carries under the same name.
PUBLISHED = ("vocab_size", "hidden_size", "num_hidden_layers",
             "num_attention_heads", "num_key_value_heads",
             "attention_multiplier", "embedding_multiplier",
             "residual_multiplier", "logits_scaling",
             "shared_intermediate_size", "num_local_experts",
             "mamba_n_heads", "mamba_d_head", "mamba_d_state",
             "mamba_n_groups", "mamba_d_conv", "mamba_expand",
             "mamba_chunk_size", "rms_norm_eps", "max_position_embeddings",
             "tie_word_embeddings")
#: Published keys the program implements one value of.
FIXED = {"model_type": "granitemoehybrid", "attention_bias": False,
         "hidden_act": "silu", "mamba_conv_bias": True,
         "mamba_proj_bias": False, "normalization_function": "rmsnorm",
         "position_embedding_type": "nope", "num_experts_per_tok": 0}


#: ``logits_and_losses`` makes its logits in this many pieces.
QUARTERS = 4


def _model():
    from ray_tpu.models import granite
    return granite


def config(program: Dict[str, Any]):
    """The program's ``GraniteConfig`` from a configuration file's
    ``program`` group: a preset and overrides, dtypes by name."""
    import jax.numpy as jnp
    overrides = dict(program["overrides"])
    for key in ("dtype", "param_dtype"):
        if key in overrides:
            overrides[key] = jnp.dtype(overrides[key]).type
    return _model().config(program["preset"], **overrides)


def problems(published: Dict[str, Any], cfg) -> List[str]:
    """The program's config against the configuration file's published
    keys: the cell runs the widths and the layer pattern it says it runs,
    and the file asks for nothing the program does not compute."""
    out = [f"{key}: program {getattr(cfg, key)!r}, file {published[key]!r}"
           for key in PUBLISHED if getattr(cfg, key) != published[key]]
    if tuple(published["layer_types"]) != cfg.layer_types:
        out.append(f"layer_types: program {cfg.layer_types!r}, file "
                   f"{published['layer_types']!r}")
    out += [f"{key}: the program computes {want!r} only, file "
            f"{published[key]!r}" for key, want in FIXED.items()
            if published.get(key, want) != want]
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
    """Every vector redrawn from the seed in one jitted pass, in place, same
    shardings (see the top of this file): ``dt_bias`` from
    ``program.dt_range``, the conv bias by ``conv_bias_sigma``, the others
    by ``norm_scale_sigma`` around their init; ``Wq`` and ``Wk`` times
    ``attention_qk_gain``."""
    import jax
    import jax.numpy as jnp
    sigma, bias_sigma = program["norm_scale_sigma"], program["conv_bias_sigma"]
    dt_min, dt_max = program["dt_range"]
    qk_gain = program["attention_qk_gain"]

    def drawn(tree, key, rank):
        out = {}
        for k, (name, leaf) in zip(jax.random.split(key, len(tree)),
                                   sorted(tree.items())):
            if name == "dt_bias":
                dt = jnp.exp(jax.random.uniform(
                    k, leaf.shape, jnp.float32, jnp.log(dt_min),
                    jnp.log(dt_max)))
                leaf = (dt + jnp.log(-jnp.expm1(-dt))).astype(leaf.dtype)
            elif name in ("wq", "wk"):
                leaf = (qk_gain * leaf.astype(jnp.float32)).astype(leaf.dtype)
            elif leaf.ndim == rank:
                leaf = leaf + ((bias_sigma if name == "conv_b" else sigma)
                               * jax.random.normal(k, leaf.shape, jnp.float32)
                               ).astype(leaf.dtype)
            out[name] = leaf
        return out

    def vectors_drawn(params, key):
        stacks = sorted(k for k in params if k.startswith("run"))
        keys = jax.random.split(key, 1 + len(stacks))
        rest = {k: v for k, v in params.items() if k not in stacks}
        # Stacked over layers, a vector has rank 2.
        return dict(drawn(rest, keys[0], 1), **{
            name: drawn(params[name], k, 2)
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
    through the layers: ``forward`` is ``head`` of ``hidden_states`` and
    ``loss_fn`` ``loss_of_hidden`` of it. (Two passes, as the other
    families make, hold this model's [32768, vocab] logits, 6.6 GB, across
    the second pass's temporaries.) Traced inside the caller's jit, under
    the caller's mesh."""
    import jax.numpy as jnp
    model = _model()
    cfg = _a_chunked_loss(cfg, tokens)
    hidden = model.hidden_states(params, cfg, tokens)
    losses = [model.loss_of_hidden(
        params, cfg, hidden, targets,
        mask=jnp.zeros(tokens.shape, jnp.float32).at[i].set(1.0))[0]
        for i in range(tokens.shape[0])]
    # The head in QUARTERS of the vocabulary, joined: the chip's gather of
    # an operand above 2 GiB (these logits are 6.6 GB) cuts it into four
    # along the vocabulary, and cut where they were joined the quarters are
    # used as they are; made whole, each is copied, 6.6 GB more, which
    # with the parameters fills the chip before the reference runs (off the
    # chip the program's temporaries compile to 2.6 GB so, 13.2 GB whole).
    wte = params["wte"]
    cuts = [wte.shape[0] * i // QUARTERS for i in range(QUARTERS + 1)]
    logits = jnp.concatenate(
        [model.head(dict(params, wte=wte[lo:hi]), cfg, hidden)
         for lo, hi in zip(cuts, cuts[1:])], axis=-1)
    return logits, jnp.stack(losses)


def loss(params, cfg, tokens, targets):
    """The loss the train step differentiates (``check_grads_granite``)."""
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
    layers mamba, attention, mamba, mamba; four state-space heads of 64
    with a state of 128 and a chunk of 128 (so that the kernels tile), four
    query heads over two KV heads of 32, a SwiGLU of 256, 512 tokens of
    vocabulary, sequences of 256. Same code path and layout; nothing it
    measures means anything."""
    sizes = dict(hidden_size=128, num_hidden_layers=4,
                 layer_types=["mamba", "attention", "mamba", "mamba"],
                 num_attention_heads=4, num_key_value_heads=2,
                 shared_intermediate_size=256, mamba_n_heads=4,
                 mamba_chunk_size=128, vocab_size=512,
                 max_position_embeddings=256)
    config = dict(config, **sizes)
    program = dict(config["program"])
    program["overrides"] = dict(program["overrides"], loss_chunk=128,
                                **sizes)
    config["program"] = program
    config["layout"] = dict(config["layout"], seq_len=256)
    return config
