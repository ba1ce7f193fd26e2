"""Sharded training step: init + step builders over a device mesh.

The GSPMD successor to the reference's prepare_model/prepare_optimizer
wrappers (train/torch/train_loop_utils.py:51): instead of wrapping the model
in DDP/FSDP modules, we jit one functional train step whose inputs carry
NamedShardings; XLA inserts the gradient psums / param all-gathers over ICI.
Parameters are *initialized inside jit with out_shardings* so a 6B-param
model never materializes unsharded on any single host.

The step is typed to no model and imports none. Every builder here takes
``model``: anything (a module of ``models/``, as a rule) that offers

    init(cfg, key) -> params
    param_specs(cfg, rules) -> PartitionSpec tree like params
    loss_fn(params, cfg, tokens, targets, mask) -> (loss, metrics)

and, optionally, ``SUMMED_METRICS``, the names of metrics that are counts
of a batch (summed, not averaged, over accumulation microbatches), and
``RECORDED_METRICS``, {name of a metric: what records its value in the
registry}. ``cfg`` is that model's own config object and is only handed
back to it. The default is the module that defines ``type(cfg)``, so
callers that train a ``GPTConfig`` or a ``DeepseekConfig`` say nothing. A
batch is [batch, seq] token arrays, laid over the mesh as the rules lay
("batch", "sequence").
"""

from __future__ import annotations

import functools
import sys
from functools import partial
from typing import Any, Callable, Dict, Optional

import jax
import jax.numpy as jnp
import optax
from jax.sharding import NamedSharding, PartitionSpec

from ray_tpu._private import builtin_metrics
from ray_tpu._private.jax_compat import enable_compile_cache
from ray_tpu.parallel import compile_events
from ray_tpu.parallel import mesh as mesh_mod
from ray_tpu.parallel.sharding import ShardingRules, tree_shardings


def default_optimizer(learning_rate=3e-4, weight_decay=0.1,
                      warmup_steps: int = 100,
                      total_steps: int = 10_000) -> optax.GradientTransformation:
    schedule = optax.warmup_cosine_decay_schedule(
        0.0, learning_rate, warmup_steps, max(total_steps, warmup_steps + 1))
    return optax.chain(
        optax.clip_by_global_norm(1.0),
        optax.adamw(schedule, b1=0.9, b2=0.95, eps=1e-8,
                    weight_decay=weight_decay),
    )


def memory_efficient_optimizer(learning_rate=1e-4,
                               warmup_steps: int = 100,
                               total_steps: int = 10_000
                               ) -> optax.GradientTransformation:
    """Adafactor: factored second moments, no first moment — optimizer
    state shrinks from 2 fp32 copies of the params (adam, ~8 bytes/param)
    to O(rows + cols) per matrix. The single-chip recipe for models
    whose adam state would blow HBM (gpt-1.3b on a 16GB chip: params
    2.6GB bf16 + grads 2.6GB + adam 10.4GB does not fit; with adafactor
    the whole train state does). The ZeRO-equivalent GSPMD path shards
    adam state across chips instead — this is the one-chip analog."""
    schedule = optax.warmup_cosine_decay_schedule(
        0.0, learning_rate, warmup_steps,
        max(total_steps, warmup_steps + 1))
    return optax.chain(
        optax.clip_by_global_norm(1.0),
        optax.adafactor(learning_rate=schedule, momentum=None),
    )


def _model_of(cfg: Any, model: Any) -> Any:
    """``model``, or the module that defines ``type(cfg)``."""
    return model or sys.modules[type(cfg).__module__]


def _state_layout(cfg: Any, mesh, rules: ShardingRules,
                  optimizer: optax.GradientTransformation, model: Any):
    """(shapes, shardings) of the train state {params, opt_state, step}.

    Params shard by the rules. An optimizer sub-tree shaped like the params
    (adam's moments) shards leaf for leaf like them; every other leaf
    (adafactor's factored moments, counters) is replicated. One layout,
    stated up front, for what init builds, what a step takes and what it
    returns: a state that came back from a step with shardings the compiler
    picked would make the next call a different program.
    """
    replicated = NamedSharding(mesh, PartitionSpec())
    pshard = tree_shardings(mesh, model.param_specs(cfg, rules))
    params = jax.eval_shape(partial(model.init, cfg), jax.random.PRNGKey(0))
    opt_state = jax.eval_shape(optimizer.init, params)
    like_params = jax.tree.structure(params)

    def is_param_shaped(node):
        return jax.tree.structure(node) == like_params

    def shard(node):
        if is_param_shaped(node):
            return jax.tree.map(
                lambda leaf, p, sharding:
                    sharding if leaf.shape == p.shape else replicated,
                node, params, pshard)
        return replicated

    shapes = {"params": params, "opt_state": opt_state,
              "step": jax.ShapeDtypeStruct((), jnp.int32)}
    shardings = {"params": pshard,
                 "opt_state": jax.tree.map(shard, opt_state,
                                           is_leaf=is_param_shaped),
                 "step": replicated}
    return shapes, shardings


def init_train_state(cfg: Any, mesh,
                     rules: Optional[ShardingRules] = None,
                     optimizer: Optional[optax.GradientTransformation] = None,
                     seed: int = 0, model: Any = None) -> Dict[str, Any]:
    """Build {params, opt_state, step}, created directly in sharded form.
    The arrays are dispatched, not waited for."""
    enable_compile_cache()
    compile_events.install()
    rules = rules or ShardingRules()
    optimizer = optimizer or default_optimizer()
    model = _model_of(cfg, model)
    with builtin_metrics.setup_stage("state_init",
                                     "setup::state_init") as span:
        _, shardings = _state_layout(cfg, mesh, rules, optimizer, model)

        @partial(jax.jit, out_shardings=shardings)
        def init(key):
            params = model.init(cfg, key)
            return {"params": params, "opt_state": optimizer.init(params),
                    "step": jnp.zeros((), jnp.int32)}

        state = init(jax.random.PRNGKey(seed))
        if span is not None:
            leaves = jax.tree.leaves(state)
            span.attributes.update(
                leaves=len(leaves), bytes=sum(x.nbytes for x in leaves))
    return state


def abstract_train_state(cfg: Any, mesh,
                         rules: Optional[ShardingRules] = None,
                         optimizer: Optional[
                             optax.GradientTransformation] = None,
                         model: Any = None) -> Dict[str, Any]:
    """init_train_state's result as ShapeDtypeStructs carrying shardings:
    what a step is lowered with when no device can hold the arrays (a
    compile for a described TPU topology, a per-device memory proof)."""
    shapes, shardings = _state_layout(
        cfg, mesh, rules or ShardingRules(), optimizer or default_optimizer(),
        _model_of(cfg, model))
    return jax.tree.map(
        lambda shape, sharding: jax.ShapeDtypeStruct(
            shape.shape, shape.dtype, sharding=sharding),
        shapes, shardings)


def _with_mesh_registered(jitted, mesh, rules, after_call=None):
    """Register ``mesh`` (and the step's ``rules``, by which a model states
    its activations' sharding) as current around every call, not once at
    build time: jit traces lazily (first call / new shapes), so the registry
    must hold THIS step's mesh whenever a trace may happen — two steps built
    over different meshes would otherwise trace against the wrong one. The
    previous mesh comes back afterwards, so model code called outside any
    step never sees a stale one. ``.lower`` traces too and gets the same
    treatment. ``after_call`` sees what each call returns.

    A call is the set-up stage ``first_call`` when it made a program
    (``compile_events.first_call``), ``.lower`` the stage ``aot_lower``: what
    JAX reports of tracing and lowering inside either is the step's, not
    some other program's. A call that found its program is timed there too
    (its dispatch, ``after_call`` included, and the interval since the last
    call of this wrapped step), and is a span ``train::step``."""
    clock = compile_events.StepClock(getattr(jitted, "__name__", "step"))

    def under_mesh(fn, stage, then=None):
        @functools.wraps(fn)
        def call(*args, **kwargs):
            previous = mesh_mod.current_mesh(), mesh_mod.current_rules()
            mesh_mod.set_current_mesh(mesh, rules)
            try:
                with stage() as site:
                    out = fn(*args, **kwargs)
                    if then is not None:
                        site.after(then, out)
            finally:
                mesh_mod.set_current_mesh(*previous)
            return out
        return call

    wrapped = under_mesh(
        jitted, lambda: compile_events.first_call(jitted, clock),
        after_call)
    wrapped.lower = under_mesh(
        jitted.lower,
        lambda: builtin_metrics.setup_stage("aot_lower", "step::lower"))
    return wrapped


class _DeferredRecorder:
    """Feeds the scalars a step returns under the names of ``recorded``
    ({metric: what records its value}, a model's ``RECORDED_METRICS``) to
    the registry without a device sync in the loop: a call's scalars are
    read once they are ready, or by the next call at the latest (that step
    is then queued behind them on the device, so the read waits for nothing
    the device would not do anyway). The newest call's may therefore still
    be pending when the loop ends. A name the step does not return is
    passed over: a family's members may differ in what they have to record
    (``models/granite.py``: expert layers or none)."""

    def __init__(self, recorded: Dict[str, Callable[[float], None]]) -> None:
        self.recorded = recorded
        self.pending: list = []

    def __call__(self, out) -> None:
        scalars = [(record, out[1][name])
                   for name, record in self.recorded.items()
                   if name in out[1]]
        for _, scalar in scalars:
            scalar.copy_to_host_async()
        self.pending.append(scalars)
        while self.pending and (len(self.pending) > 1 or all(
                scalar.is_ready() for _, scalar in self.pending[0])):
            for record, scalar in self.pending.pop(0):
                record(float(scalar))


def make_train_step(cfg: Any, mesh,
                    rules: Optional[ShardingRules] = None,
                    optimizer: Optional[optax.GradientTransformation] = None,
                    accum_steps: int = 1, model: Any = None) -> Callable:
    """Returns jitted step(state, batch) -> (state, metrics).

    batch = {"tokens": [B, S] int32, "targets": [B, S] int32,
             "mask": optional [B, S]}. With accum_steps > 1 the leading batch
    dim must be divisible by it; microbatches run in a lax.scan (the
    microbatching substrate pipeline parallelism reuses).
    """
    enable_compile_cache()
    compile_events.install()
    rules = rules or ShardingRules()
    optimizer = optimizer or default_optimizer()
    model = _model_of(cfg, model)
    bspec = rules.spec("batch", "sequence")
    summed = frozenset(getattr(model, "SUMMED_METRICS", ()))
    recorded = getattr(model, "RECORDED_METRICS", None)

    def loss_for(params, micro):
        return model.loss_fn(params, cfg, micro["tokens"], micro["targets"],
                             micro.get("mask"))

    def step(state, batch):
        params = state["params"]
        batch = {
            k: jax.lax.with_sharding_constraint(
                v, NamedSharding(mesh, bspec))
            for k, v in batch.items()
        }
        grad_fn = jax.value_and_grad(loss_for, has_aux=True)
        if accum_steps == 1:
            (_, metrics), grads = grad_fn(params, batch)
        else:
            def micro_body(carry, micro):
                g_acc, m_acc = carry
                (_, m), g = grad_fn(params, micro)
                g_acc = jax.tree.map(jnp.add, g_acc, g)
                m_acc = jax.tree.map(jnp.add, m_acc, m)
                return (g_acc, m_acc), None

            micros = jax.tree.map(
                lambda x: x.reshape((accum_steps, -1) + x.shape[1:]), batch)
            zeros_g = jax.tree.map(
                lambda p: jnp.zeros(p.shape, jnp.float32), params)
            one = jax.tree.map(lambda x: x[0], micros)
            zeros_m = jax.tree.map(
                lambda m: jnp.zeros(m.shape, jnp.float32),
                jax.eval_shape(grad_fn, params, one)[0][1])
            (grads, metrics), _ = jax.lax.scan(
                micro_body, (zeros_g, zeros_m), micros)
            grads = jax.tree.map(lambda g: g / accum_steps, grads)
            metrics = {k: m if k in summed else m / accum_steps
                       for k, m in metrics.items()}
        with jax.named_scope("optimizer"):
            updates, opt_state = optimizer.update(
                grads, state["opt_state"], params)
            params = optax.apply_updates(params, updates)
        return ({"params": params, "opt_state": opt_state,
                 "step": state["step"] + 1}, metrics)

    # The state goes out as it came in (see _state_layout), so every call
    # after the first finds the same program, and donation can alias.
    _, shardings = _state_layout(cfg, mesh, rules, optimizer, model)
    return _with_mesh_registered(
        jax.jit(step, donate_argnums=(0,), out_shardings=(shardings, None)),
        mesh, rules,
        after_call=_DeferredRecorder(recorded) if recorded else None)


def make_eval_step(cfg: Any, mesh,
                   rules: Optional[ShardingRules] = None,
                   model: Any = None) -> Callable:
    enable_compile_cache()
    compile_events.install()
    rules = rules or ShardingRules()
    model = _model_of(cfg, model)
    bspec = rules.spec("batch", "sequence")

    def eval_step(params, batch):
        batch = {
            k: jax.lax.with_sharding_constraint(
                v, NamedSharding(mesh, bspec))
            for k, v in batch.items()
        }
        _, metrics = model.loss_fn(params, cfg, batch["tokens"],
                                   batch["targets"], batch.get("mask"))
        return metrics

    # Its own name: the ``program`` of its spans and series.
    return _with_mesh_registered(jax.jit(eval_step), mesh, rules)
