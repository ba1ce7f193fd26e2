"""The program's side of a configuration whose ``program.family`` is
``phi4flash``: ``ray_tpu/models/phi4flash.py`` trained by
``ray_tpu/parallel/train_step.py`` (which takes the model as an argument),
described by a published config under the ``Phi4FlashConfig`` key names
(``hidden_size``, ``num_attention_heads``, ``num_key_value_heads``,
``sliding_window``, ``mb_per_layer``, ``layer_norm_eps``, ...). It offers what
``families/gpt.py``'s docstring lists, and ``loss``, ``init`` and
``with_layers`` for the gradient check (``check_grads_phi4flash.py``).

**The cut.** A configuration of this family may run some of the published
layers: its ``layers_run`` lists their published indices (whole pairs; the
program's ``layers_run``), its ``num_hidden_layers`` counts them, and
``reduced.num_hidden_layers.published`` is the published depth, which the
program keeps under ``num_hidden_layers`` (where the middle pair lies, and
``l0`` of a layer, are functions of the published index). The Mamba-1 sizes
the published config does not carry are the file's ``assumed.mamba_sizes``.

The benchmark makes the weights: the program's one jitted init from the
seed (matrices normal 0.02, ``b_dt`` and ``A_log`` as Mamba-1 publishes
them, the lambda vectors normal 0.1), then (``draw_vectors``) so that no
term hides behind a one, a zero or a flat softmax: ``A_log``, ``D``, every
LayerNorm's scale and ``subln``'s redrawn N(0, ``program.vector_sigma``)
around their init, every bias (the LayerNorms', the attention
projections', the convolution's; not ``b_dt``, which its init spreads)
N(0, ``program.vector_sigma``), and Wq and Wk multiplied by
``program.qk_gain`` (the scores' spread is its square).
"""

from __future__ import annotations

from dataclasses import replace
from typing import Any, Dict, List

#: Published keys the program's config carries under the same name.
PUBLISHED = ("hidden_size", "intermediate_size", "num_attention_heads",
             "num_key_value_heads", "sliding_window", "mb_per_layer",
             "layer_norm_eps", "max_position_embeddings", "vocab_size",
             "tie_word_embeddings", "mlp_bias", "lm_head_bias", "hidden_act")
#: Published keys the program implements one value of.
FIXED = {"model_type": "phi4flash", "embd_pdrop": 0, "resid_pdrop": 0}
#: The Mamba-1 sizes a file states under ``assumed.mamba_sizes``, by the
#: program's names.
MAMBA = ("mamba_d_state", "mamba_d_conv", "mamba_expand", "mamba_dt_rank")


def _model():
    from ray_tpu.models import phi4flash
    return phi4flash


def config(program: Dict[str, Any]):
    """The program's ``Phi4FlashConfig`` from a configuration file's
    ``program`` group: a preset and overrides, dtypes by name."""
    import jax.numpy as jnp
    overrides = dict(program["overrides"])
    for key in ("dtype", "param_dtype"):
        if key in overrides:
            overrides[key] = jnp.dtype(overrides[key]).type
    return _model().config(program["preset"], **overrides)


def published_depth(published: Dict[str, Any]) -> int:
    return published.get("reduced", {}).get("num_hidden_layers", {}).get(
        "published", published["num_hidden_layers"])


def problems(published: Dict[str, Any], cfg) -> List[str]:
    """The program's config against the configuration file's published
    keys: the cell runs the widths and the layers it says it runs, and the
    file asks for nothing the program does not compute."""
    out = [f"{key}: program {getattr(cfg, key)!r}, file {published[key]!r}"
           for key in PUBLISHED if getattr(cfg, key) != published[key]]
    out += [f"{key}: the program computes {want!r} only, file "
            f"{published[key]!r}" for key, want in FIXED.items()
            if published.get(key, want) != want]
    depth = published_depth(published)
    run = tuple(published.get("layers_run", range(depth)))
    if cfg.num_hidden_layers != depth:
        out.append(f"num_hidden_layers: program's published depth "
                   f"{cfg.num_hidden_layers}, file {depth}")
    if cfg.layers != run or len(run) != published["num_hidden_layers"]:
        out.append(f"layers_run: program {cfg.layers}, file {run} of "
                   f"num_hidden_layers {published['num_hidden_layers']}")
    sizes = published.get("assumed", {}).get("mamba_sizes", {})
    got = dict(mamba_d_state=cfg.mamba_d_state, mamba_d_conv=cfg.mamba_d_conv,
               mamba_expand=cfg.mamba_expand, mamba_dt_rank=cfg.dt_rank)
    out += [f"{key}: program {got[key]!r}, file's assumed.mamba_sizes "
            f"{sizes.get(key)!r}" for key in MAMBA
            if got[key] != sizes.get(key)]
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


#: Leaves (without a pair's ``a_`` / ``b_``) redrawn around their init.
_AROUND_INIT = ("A_log", "D", "ln1_scale", "ln2_scale", "subln_scale",
                "final_norm_scale")
#: Leaves drawn around zero.
_BIASES = ("ln1_bias", "ln2_bias", "final_norm_bias", "conv_b", "bq", "bk",
           "bv", "bo")
#: Leaves multiplied by ``qk_gain``.
_GAINED = ("wq", "wk")


def draw_vectors(params, seed: int, program: Dict[str, Any]):
    """The program's init leaves every norm's scale and ``D`` at 1, every
    bias at 0 and ``A_log`` the same in every channel, where no dropped or
    misplaced term would show, and scores whose two softmax maps are nearly
    alike. Redrawn from the seed in one jitted pass, in place, same
    shardings (the module text; sigma and gain from the configuration's
    ``program``)."""
    import jax
    import jax.numpy as jnp
    sigma, gain = program["vector_sigma"], program["qk_gain"]

    def drawn(tree, key):
        out = {}
        for k, (name, leaf) in zip(jax.random.split(key, len(tree)),
                                   sorted(tree.items())):
            bare = name[2:] if name[:2] in ("a_", "b_") else name
            if bare in _AROUND_INIT or bare in _BIASES:
                leaf = (leaf.astype(jnp.float32) + sigma * jax.random.normal(
                    k, leaf.shape, jnp.float32)).astype(leaf.dtype)
            elif bare in _GAINED:
                leaf = (leaf.astype(jnp.float32) * gain).astype(leaf.dtype)
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


#: Tokens of the vocabulary whose logits ``logits_and_losses`` makes by one
#: call of the program's head.
HEAD_BLOCK = 4096


def logits_and_losses(params, cfg, tokens, targets):
    """The program's own forward, and the loss the train step differentiates
    taken one sequence at a time (a mask of one row), both from one pass
    through the layers. Traced inside the caller's jit, under the caller's
    mesh.

    The logits are the program's head on ``HEAD_BLOCK`` rows of the tied
    table at a time, side by side: the same products. The caller
    (``runners/train.py`` ``_reference_check``) keeps 1024 positions of
    them, and the chip's compiler takes such a gather of whole rows 28,672
    columns at a time, each from a copy of those columns: of one
    ``[16384, 200064]`` product it holds the product, the copies and a second
    product (12.28 GB at any depth; PERF.md section 6, PR 42), of blocks that
    its pieces are made of it holds one piece at a time (2.56 GB, less than
    the step). ``tests/test_chip_compile.py`` holds that program to the
    smaller size."""
    import jax.numpy as jnp
    model = _model()
    cfg = _a_chunked_loss(cfg, tokens)
    hidden, aux = model.hidden_states(params, cfg, tokens)
    losses = [model.loss_of_hidden(
        params, cfg, hidden, aux, targets,
        mask=jnp.zeros(tokens.shape, jnp.float32).at[i].set(1.0))[0]
        for i in range(tokens.shape[0])]
    logits = jnp.concatenate([
        model.head(dict(params, wte=params["wte"][at:at + HEAD_BLOCK]), cfg,
                   hidden)
        for at in range(0, cfg.vocab_size, HEAD_BLOCK)], axis=-1)
    return logits, jnp.stack(losses)


def loss(params, cfg, tokens, targets):
    """The loss the train step differentiates (``check_grads_phi4flash``)."""
    return _model().loss_fn(params, _a_chunked_loss(cfg, tokens), tokens,
                            targets)[0]


def init(cfg, seed: int, program: Dict[str, Any]):
    """Parameters alone, as ``state_and_step`` makes them."""
    import jax
    params = jax.jit(lambda key: _model().init(cfg, key))(
        jax.random.PRNGKey(seed))
    return draw_vectors(params, seed + 1, program)


def with_layers(config: Dict[str, Any], layers) -> Dict[str, Any]:
    """The configuration cut to the published layers ``layers`` (whole
    pairs, in order; file and program alike)."""
    layers = [int(i) for i in layers]
    program = dict(config["program"])
    program["overrides"] = dict(program["overrides"], layers_run=layers)
    return dict(config, num_hidden_layers=len(layers), layers_run=layers,
                program=program)


def tiny(config: Dict[str, Any]) -> Dict[str, Any]:
    """The configuration at a tiny size for a run on the CPU: a published
    depth of 12 of which eight layers run (two self pairs, the middle pair,
    the second cross pair: a cut, so that a layer's place and its published
    index differ), width 128 and an inner width of 256 (so that the scan's
    and the convolution's kernels tile and run interpreted), four heads of
    32 over two KV heads (two differential heads of one group), a SwiGLU of
    256, a window of 64, 512 tokens of vocabulary, one sequence of 256,
    everything in float32. Same code path and layout; nothing it measures
    means anything."""
    sizes = dict(hidden_size=128, intermediate_size=256,
                 num_attention_heads=4, num_key_value_heads=2,
                 sliding_window=64, vocab_size=512,
                 max_position_embeddings=256)
    layers = [0, 1, 2, 3, 6, 7, 10, 11]
    config = dict(config, num_hidden_layers=len(layers), layers_run=layers,
                  **sizes)
    config["reduced"] = {"num_hidden_layers": {"published": 12}}
    config["assumed"] = dict(config.get("assumed", {}), mamba_sizes=dict(
        mamba_d_state=16, mamba_d_conv=4, mamba_expand=2, mamba_dt_rank=8))
    program = dict(config["program"])
    program["overrides"] = dict(
        program["overrides"], loss_chunk=128, num_hidden_layers=12,
        layers_run=layers, attn_blk_q=128, attn_blk_k=128, dtype="float32",
        param_dtype="float32", **sizes)
    config["program"] = program
    config["layout"] = dict(config["layout"], seq_len=256)
    return config
