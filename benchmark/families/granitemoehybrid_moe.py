"""The program's side of a configuration whose ``program.family`` is
``granitemoehybrid_moe``: ``ray_tpu/models/granite.py`` with
``num_local_experts`` > 0 (granite-4.0-h-small's block: a routed expert
layer beside the shared SwiGLU in every layer) trained by
``ray_tpu/parallel/train_step.py``, described by a published config under
the ``GraniteMoeHybridConfig`` key names. ``families/granitemoehybrid.py``
is the same program without experts and is left as it is; what the two
share (the recipe's optimizer, the batch's sharding, the vectors' draw, the
chunked loss's size, the cut in depth) is taken from it. It offers what
``families/gpt.py``'s docstring lists, ``picked_experts`` for a routing
comparison, and ``loss``, ``init`` and ``with_layers`` for the gradient
check (``check_grads_granite_moe.py``).

**The chip's share.** A configuration of this family may be one chip's share
of a deployment that divides every layer over several chips; its
``deployment`` group says so. The file's ``num_local_experts`` is then how
many experts are held here (``deployment.experts_held``: ``first``,
``count``, and ``of``, the published count and the router's width), and its
``vocab_size`` the chip's slice of the vocabulary
(``deployment.vocab_slice``): token ids, logits and loss are over the slice.

The benchmark makes the weights: the program's one jitted init from the
seed, then ``families/granitemoehybrid.py``'s ``draw_vectors`` (every vector
redrawn around its init, ``dt_bias`` from ``program.dt_range``, ``Wq`` and
``Wk`` times ``program.attention_qk_gain``: that file's docstring), then
this family's own two rules for the expert layer (``experts_drawn``):

- every expert's ``w_down`` times ``program.expert_gain``. At the init's
  scale a token's routed sum is a convex mix of ten incoherent expert
  outputs half as wide as the shared SwiGLU, a third of its size, and the
  part of it this chip holds a third of that again: a dropped or misrouted
  expert would move the logits by less than bfloat16 does.
- **the router's common component taken out** (``program.
  router_centre_tokens`` > 0). Granite's router has no bias, and nothing
  balances a random one: where the normed stream of a random model leans one
  way at every position, a router's column that happens to point that way
  is picked more often than the others, and which columns those are moves
  this chip's share of the rows, and its step, with the seed (on the chip,
  PR 68: ``moe.held_share`` 0.1208-0.1299 over six seeds where even routing
  reads 0.125, ``tokens_per_s`` spreading by 0.46 %, at the 0.5 % a new cell
  is admitted under; centred, 0.1248-0.1255 and under 0.1 %). A trained
  router's logits carry no such offset: the balancing loss it was trained
  under removes it. The rule, in closed form and layer by layer on the
  model's own forward over one seeded sequence: ``m`` the mean of layer l's
  normed FFN input over the tokens, ``W_r <- W_r - m^ (m^ . W_r)`` with ``m^
  = m / |m|``, so that the mean input scores every expert alike, and the
  layers behind see the centred layer's output. A property of the drawn
  weights: program and reference read the same router, the model is
  unchanged and nothing is added to the step. No bias is invented.
"""

from __future__ import annotations

import contextlib
from typing import Any, Dict, List

import harness

_PLAIN = harness.load_module("families", "granitemoehybrid")

#: Published keys the program's config carries under the same name.
PUBLISHED = tuple(key for key in _PLAIN.PUBLISHED
                  if key not in ("num_local_experts", "vocab_size")) \
    + ("intermediate_size", "num_experts_per_tok")
#: Published keys the program implements one value of.
FIXED = {key: want for key, want in _PLAIN.FIXED.items()
         if key != "num_experts_per_tok"}

# The same program (``models/granite.py``), recipe and layout.
_model, config, vocab_size = _PLAIN._model, _PLAIN.config, _PLAIN.vocab_size
_rules_and_optimizer = _PLAIN._rules_and_optimizer
abstract_state_and_step = _PLAIN.abstract_state_and_step
batch_sharding, with_layers = _PLAIN.batch_sharding, _PLAIN.with_layers
_a_chunked_loss, loss = _PLAIN._a_chunked_loss, _PLAIN.loss


def problems(published: Dict[str, Any], cfg) -> List[str]:
    """The program's config against the configuration file's published
    keys: the cell runs the widths, the layer pattern and the share it says
    it runs, and the file asks for nothing the program does not compute."""
    out = [f"{key}: program {getattr(cfg, key)!r}, file {published[key]!r}"
           for key in PUBLISHED if getattr(cfg, key) != published[key]]
    if tuple(published["layer_types"]) != cfg.layer_types:
        out.append(f"layer_types: program {cfg.layer_types!r}, file "
                   f"{published['layer_types']!r}")
    out += [f"{key}: the program computes {want!r} only, file "
            f"{published[key]!r}" for key, want in FIXED.items()
            if published.get(key, want) != want]
    deployment = published.get("deployment", {})
    held = deployment.get("experts_held") or {
        "first": 0, "count": published["num_local_experts"],
        "of": published["num_local_experts"]}
    if published["num_local_experts"] != held["count"] \
            or cfg.num_local_experts != held["of"] \
            or (cfg.experts_held or (0, cfg.num_local_experts)) != (
                held["first"], held["count"]):
        out.append(f"num_local_experts: file {published['num_local_experts']}"
                   f" held of {held}, program {cfg.experts_held} of "
                   f"{cfg.num_local_experts}")
    rows = deployment.get("vocab_slice", {}).get("count",
                                                 published["vocab_size"])
    if not published["vocab_size"] == rows == cfg.vocab_size:
        out.append(f"vocab_size: file {published['vocab_size']}, slice "
                   f"{rows}, program {cfg.vocab_size}")
    if published["layout"]["seq_len"] > cfg.max_position_embeddings:
        out.append(f"layout.seq_len {published['layout']['seq_len']} is past "
                   f"the {cfg.max_position_embeddings} positions declared")
    return out


def state_and_step(cfg, mesh, program: Dict[str, Any], seed: int):
    """The train state on the device from the seed and the jitted step
    ``(state, batch) -> (state, metrics)``: the product's own builders,
    given the model."""
    from ray_tpu.parallel.train_step import init_train_state, make_train_step
    rules, optimizer = _rules_and_optimizer(program)
    state = init_train_state(cfg, mesh, rules, optimizer, seed=seed,
                             model=_model())
    step = make_train_step(cfg, mesh, rules, optimizer, model=_model())
    state["params"] = experts_drawn(
        _PLAIN.draw_vectors(state["params"], seed + 1, program), cfg,
        seed + 3, program, mesh)
    return state, step


@contextlib.contextmanager
def _centring_routers(centred: list):
    """While it holds, ``lm.expert_ffn`` takes the router's component along
    its input's mean out of the router it is given before it routes, and
    appends the router it used to ``centred``: the model's own block then
    computes a layer with its centred router from the layer's leaves as
    they were."""
    import jax.numpy as jnp
    from ray_tpu.models import lm
    plain = lm.expert_ffn

    def expert_ffn(x, layer, **kw):
        f32 = jnp.float32
        mean = x.astype(f32).reshape(-1, x.shape[-1]).mean(0)
        along = mean / jnp.linalg.norm(mean)
        router = layer["router"].astype(f32)
        router = (router - jnp.outer(along, along @ router)).astype(
            layer["router"].dtype)
        centred.append(router)
        return plain(x, dict(layer, router=router), **kw)

    lm.expert_ffn = expert_ffn
    try:
        yield
    finally:
        lm.expert_ffn = plain


def experts_drawn(params, cfg, seed: int, program: Dict[str, Any],
                  mesh=None):
    """The expert layers' two rules (the top of this file), same shardings:
    every ``w_down`` times ``program.expert_gain`` and, with
    ``program.router_centre_tokens``, every router centred, a layer after
    the other through the model's own block (``granite._block``, outside the
    layer scan, one compiled layer a kind) on one seeded sequence of that
    many ids."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from ray_tpu.models import lm
    from ray_tpu.parallel import mesh as mesh_mod
    model, gain = _model(), float(program.get("expert_gain", 1.0))
    shardings = jax.tree.map(lambda a: a.sharding, params)
    stacks = [run for run, _, _ in lm.runs(cfg.layers)]

    def gained(params):
        return dict(params, **{run: dict(params[run], w_down=(
            gain * params[run]["w_down"].astype(jnp.float32)).astype(
                params[run]["w_down"].dtype)) for run in stacks})

    params = jax.jit(gained, donate_argnums=(0,), out_shardings=shardings)(
        params)
    n_tokens = int(program.get("router_centre_tokens", 0))
    if not n_tokens:
        return params
    tokens = jnp.asarray(np.random.default_rng(seed).integers(
        0, cfg.vocab_size, (1, n_tokens), dtype=np.int32))

    def layer_of(kind):
        def one(h, stack, index):
            centred = []
            layer = jax.tree.map(lambda a: jax.lax.dynamic_index_in_dim(
                a, index, 0, keepdims=False), stack)
            with _centring_routers(centred):
                h, _ = model._block(cfg, kind, h, layer, None)
            return h, centred[0]
        return jax.jit(one)

    one_layer = {kind: layer_of(kind) for kind in set(cfg.layers)}
    # The flash kernels read the ambient mesh, as inside a train step.
    previous = mesh_mod.current_mesh()
    mesh_mod.set_current_mesh(mesh)
    try:
        h = jax.jit(lambda wte: lm.embed(wte, tokens, cfg.dtype)
                    * jnp.asarray(cfg.embedding_multiplier, cfg.dtype))(
                        params["wte"])
        for run, kind, depth in lm.runs(cfg.layers):
            routers = []
            for index in range(depth):
                h, router = one_layer[kind](h, params[run], jnp.int32(index))
                routers.append(router)
            params[run] = dict(params[run], router=jax.device_put(
                jnp.stack(routers), shardings[run]["router"]))
    finally:
        mesh_mod.set_current_mesh(previous)
    return params


def logits_and_losses(params, cfg, tokens, targets):
    """The program's own forward, and the loss the train step differentiates
    taken one sequence at a time (a mask of one row), both from one pass
    through the layers: ``forward`` is ``head`` of ``hidden_states`` and
    ``loss_fn`` ``loss_of_hidden`` of it. Traced inside the caller's jit,
    under the caller's mesh."""
    import jax.numpy as jnp
    model = _model()
    cfg = _a_chunked_loss(cfg, tokens)
    hidden = model.hidden_states(params, cfg, tokens)
    losses = [model.loss_of_hidden(
        params, cfg, hidden, targets,
        mask=jnp.zeros(tokens.shape, jnp.float32).at[i].set(1.0))[0]
        for i in range(tokens.shape[0])]
    return model.head(params, cfg, hidden), jnp.stack(losses)


def picked_experts(params, cfg, tokens):
    """(logits [B, S, vocab], picked [L, B, S, K]): the program's forward
    with the router's choice, the model's auxiliary output."""
    logits, aux = _model().forward_with_aux(params, cfg, tokens)
    return logits, aux["picked"]


def init(cfg, seed: int, program: Dict[str, Any], mesh=None):
    """Parameters alone, as ``state_and_step`` makes them."""
    import jax
    params = jax.jit(lambda key: _model().init(cfg, key))(
        jax.random.PRNGKey(seed))
    return experts_drawn(_PLAIN.draw_vectors(params, seed + 1, program), cfg,
                         seed + 3, program, mesh)


def tiny(config: Dict[str, Any]) -> Dict[str, Any]:
    """The configuration at a tiny size for a run on the CPU: width 128,
    layers mamba, attention, mamba, mamba; four state-space heads of 64 with
    a state of 128 and a chunk of 128 (so that the kernels tile), four query
    heads over two KV heads of 32, experts of 128 (the grouped product's
    kernels, interpreted) with the file's share of 16 (held: the file's own
    run, cut to 4) at 4 a token beside a SwiGLU of 256, 512 tokens of
    vocabulary, sequences of 256, everything in float32 (where nothing
    routes differently from the float32 reference: the chip's own
    tolerances, for bfloat16 and the real share, are the configuration's).
    Same code path and layout; nothing it measures means anything."""
    first = config.get("deployment", {}).get("experts_held", {}).get(
        "first", 0)
    held = {"first": min(first, 12), "count": 4, "of": 16}
    sizes = dict(hidden_size=128, num_hidden_layers=4,
                 layer_types=["mamba", "attention", "mamba", "mamba"],
                 num_attention_heads=4, num_key_value_heads=2,
                 shared_intermediate_size=256, intermediate_size=128,
                 num_experts_per_tok=4, mamba_n_heads=4,
                 mamba_chunk_size=128, vocab_size=512,
                 max_position_embeddings=256)
    config = dict(config, num_local_experts=held["count"], **sizes)
    config["deployment"] = dict(
        config.get("deployment", {}), experts_held=held,
        vocab_slice={"first": 0, "count": 512, "of": 512})
    program = dict(config["program"])
    program["overrides"] = dict(
        program["overrides"], loss_chunk=128, num_local_experts=16,
        experts_held=[held["first"], held["count"]], dtype="float32",
        param_dtype="float32", attn_blk_q=128, attn_blk_k=128, **sizes)
    if program.get("router_centre_tokens"):
        program["router_centre_tokens"] = 256
    config["program"] = program
    config["layout"] = dict(config["layout"], seq_len=256)
    return config
