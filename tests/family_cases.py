"""What the test files of the model families share: one harness for
``test_afmoe.py``, ``test_kimi_linear.py``, ``test_lfm2.py``,
``test_phi4flash.py``, ``test_glm_moe_dsa.py``, ``test_deepseek.py``,
``test_granite.py``, ``test_evabyte.py`` and ``test_minicpm_sala.py``.
pytest does not collect this module; a family's file takes its cases with
``globals().update(family_cases.cases(FAMILY))``, under the names they have
here, and stays a file of its own (under ``--dist loadfile`` a file is one
worker's).

**The next family's test file**, in the order its builder needs it:

1. ``CFG`` (the tiny preset, every kind of layer in it) at ``SEQ``, and
   ``FLASH`` at ``FLASH_SEQ``: the same model with the kernels
   (interpreted), remat, the chunked loss and, where it has experts, a share
   of them, at the shortest length that runs every kernel (a whole chunk, a
   whole tile, a window that is no multiple of the tile).
2. ``published(cfg)``: the keys ``reference.arguments`` reads, as a
   configuration file of the benchmark has them, and ``moved(name, leaf,
   key)``: the rule that moves a drawn leaf off its one or zero and sharpens
   the softmaxes (the family's gains: at the init's scale attention is
   flat, and a wrong mask, pairing or rotation would move nothing).
3. A ``Family`` of those, and ``cases(FAMILY)``. That gives ``both`` and
   ``both_flash`` (``compared``: two compiled programs a configuration,
   the program's and the reference's), logits, loss, routing and every
   leaf's gradient against the reference, the dropped terms, the sliced
   head, the module's ``loss_fn`` and ``forward`` held to the shell's, the
   reference copy held to the benchmark's, ``param_specs``, the
   refused configurations, the refused expert-parallel mesh, the runs
   scanned against the layers one by one, the train step with its
   counters (``trained``: one compiled step an ``accum_steps``), bfloat16
   and the lowered kernels' names: each where the ``Family`` names what
   it needs, under ids that do not depend on the family.
4. What is the family's own, written in its file: the cases of its block and
   its table (the kinds of layer, the cut configuration's shapes and
   count), its comparisons with a published implementation
   (``transformers``), what its step's gauges read (from ``trained``), and
   ``drop(dropped, params, cfg, monkeypatch)``, which takes one term out of
   the program.

Everything runs on the CPU at tiny widths in float32 under the highest
matmul precision, where both sides compute the same sums in another order:
1e-3 of the logits' RMS and 1e-4 of a gradient leaf's norm (the defaults; a
family may ask for less) leave room for float32 reassociation across a few
hundred terms and nothing else.
"""

import functools
import math
import os
import zlib
from dataclasses import dataclass, field, replace
from functools import partial
from typing import Any, Callable, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ray_tpu.models import lm
from ray_tpu.parallel import MeshConfig, build_mesh
from ray_tpu.parallel.sharding import ShardingRules
from ray_tpu.parallel.train_step import (abstract_train_state,
                                         init_train_state, make_train_step)
from ray_tpu.util import metrics as metrics_mod

BENCHMARK = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "benchmark")


@dataclass(frozen=True, eq=False)
class Family:
    """A family as its tests see it. Only the first six are needed; a case
    that reads a field left empty is not made."""
    #: ``ray_tpu.models.<family>`` (its ``_SHELL`` is the ``lm.Decoder``)
    #: and ``tests/reference_<name>.py``, the benchmark's plain reference.
    module: Any
    reference: Any
    cfg: Any
    seq: int
    #: cfg -> what ``reference.arguments`` reads.
    published: Callable
    #: (leaf's ``keystr``, leaf, key) -> the leaf ``drawn`` holds.
    moved: Callable
    flash: Any = None
    flash_seq: int = 0
    rows: int = 2
    #: ``reference.forward``'s ``with_<name>`` flags: what it returns
    #: behind the RMS, the first of them ``picked`` where the family routes.
    extras: Tuple[str, ...] = ()
    #: ``reference.forward`` goes through the host (phi's sampled logits)
    #: and cannot be part of a compiled program; its gradient still is.
    reference_forward_traces: bool = True
    #: Of the logits' RMS, and the least RMS that says anything.
    logits_tol: float = 1e-3
    rms_floor: float = 0.01
    #: (dropped, params, cfg, monkeypatch) -> (params, cfg), and the terms.
    drop: Optional[Callable] = None
    dropped: Tuple[str, ...] = ()
    #: Experts a token, and ids the sliced head keeps.
    top_k: int = 0
    sliced_vocab: int = 64
    #: ``replace(cfg, **wrong)`` raises one of ``refuses``.
    wrong: Tuple[dict, ...] = ()
    wrong_ids: Optional[Callable] = None
    refuses: Tuple[type, ...] = (ValueError,)
    #: The train step's ``accum_steps`` (``FLASH`` at ``FLASH_SEQ``), and
    #: whether it starts from ``drawn`` (selections that are not ties).
    accum_steps: Tuple[int, ...] = ()
    train_drawn: bool = False
    #: The runs scanned against the layers one by one: the tolerance.
    scan_atol: Optional[float] = None
    #: ``FLASH`` in bfloat16 (every expert held: no routing flip).
    bfloat16: Any = None
    #: Names in the lowered gradient of ``FLASH``'s loss.
    flash_kernels: Tuple[str, ...] = ()

    @property
    def shell(self) -> lm.Decoder:
        return self.module._SHELL


def leaves(family):
    """``keystr`` of every leaf of the tiny configuration, sorted."""
    shapes = jax.eval_shape(partial(family.module.init, family.cfg),
                            jax.random.PRNGKey(0))
    return sorted(jax.tree_util.keystr(path) for path, _ in
                  jax.tree_util.tree_leaves_with_path(shapes))


def by_name(tree):
    return {jax.tree_util.keystr(path): leaf for path, leaf in
            jax.tree_util.tree_leaves_with_path(tree)}


def drawn(family, cfg, seed=0):
    """The init, every leaf through the family's ``moved`` with a key of
    its own (of the seed and the leaf's name). One tree a seed for the
    process, for all the configurations whose init is one program (they
    differ in what the forward alone reads: ``remat``, a window, the
    kernels): the cases read it and change copies."""
    return _drawn(family, seed, _init_program(family.module, cfg))


@dataclass(frozen=True)
class _Init:
    """A configuration's init as a program: equal where the jaxpr and its
    constants are, whatever configuration it was traced from."""
    jaxpr: str
    consts: Tuple[bytes, ...]
    cfg: Any = field(compare=False)


@functools.lru_cache(maxsize=None)
def _init_program(module, cfg):
    """A trace: tenths of a second, once a configuration."""
    traced = jax.make_jaxpr(partial(module.init, cfg))(jax.random.PRNGKey(0))
    return _Init(str(traced.jaxpr), tuple(
        np.asarray(c).tobytes() for c in traced.consts), cfg)


@functools.lru_cache(maxsize=None)
def _drawn(family, seed, init_program):
    """``moved`` runs leaf by leaf outside the program, where a product and
    a sum round apart: inside it XLA's CPU backend makes one fused
    multiply-add of ``leaf + 0.2 * draw`` and the vectors come out an ulp
    away from what the references were held to."""
    params = jax.jit(partial(family.module.init, init_program.cfg))(
        jax.random.PRNGKey(seed))
    key = jax.random.PRNGKey(seed + 1)

    def moved(path, leaf):
        name = jax.tree_util.keystr(path)
        return family.moved(name, leaf, jax.random.fold_in(
            key, zlib.crc32(name.encode()) % (2 ** 31)))

    return jax.tree_util.tree_map_with_path(moved, params)


def batch(cfg, seq, seed=0, rows=2):
    toks = np.random.default_rng(seed).integers(
        0, cfg.vocab_size, (rows, seq + 1), dtype=np.int32)
    return jnp.asarray(toks[:, :-1]), jnp.asarray(toks[:, 1:])


def _everywhere(tokens):
    return jnp.broadcast_to(jnp.arange(tokens.shape[1], dtype=jnp.int32),
                            tokens.shape)


def program_side(family, cfg, seq):
    """One compiled program: logits, the blocks' aux, loss, the loss's
    metrics and gradients, from one trace of the forward: the shell's
    pieces, which its ``loss_fn`` and ``forward`` compose and
    ``test_the_entry_points_are_the_shells`` holds the module's to."""
    shell = family.shell
    tokens, targets = batch(cfg, seq, rows=family.rows)

    def run(params):
        def loss(p):
            x, aux = shell.hidden_states(p, cfg, tokens)
            total, metrics = shell.loss_of_hidden(p, cfg, x, aux, targets)
            return total, (shell.head(p, cfg, x), aux, metrics)
        (total, rest), grads = jax.value_and_grad(loss, has_aux=True)(params)
        return (total,) + rest + (grads,)

    with jax.default_matmul_precision("highest"):
        total, logits, aux, metrics, grads = jax.jit(run)(drawn(family, cfg))
    return {"logits": logits, "aux": aux, "loss": total, "metrics": metrics,
            "grads": grads}


@functools.lru_cache(maxsize=None)
def reference_side(family, cfg, seq, gradients=True):
    """One compiled program: ``reference.forward`` at every position and,
    with ``gradients``, the gradient of ``reference.loss``."""
    reference = family.reference
    kw = reference.arguments(family.published(cfg))
    flags = {"with_" + name: True for name in family.extras}
    tokens, targets = batch(cfg, seq, rows=family.rows)

    def forward(params):
        return reference.forward(params, tokens, targets,
                                 _everywhere(tokens), **flags, **kw)

    def gradient(params):
        return jax.grad(lambda p: reference.loss(p, tokens, targets, **kw))(
            params) if gradients else None

    params = drawn(family, cfg)
    if family.reference_forward_traces:
        out, grads = jax.jit(lambda p: (forward(p), gradient(p)))(params)
    else:
        out, grads = forward(params), jax.jit(gradient)(params)
    logits, losses, rms, *extras = out
    return {"logits": logits, "loss": losses.mean(), "losses": losses,
            "rms": float(rms), "extras": extras, "grads": grads}


@functools.lru_cache(maxsize=None)
def compared(family, cfg, seq, reference_of=None):
    """Program and reference on one batch: ``logits``, ``loss`` and
    ``grads`` as (got, want), the logits' ``rms``, and what each side
    holds besides (``aux``, ``metrics``; ``extras``, ``losses``).
    ``reference_of``: the configuration whose reference this one is held to,
    where they differ in what the program alone reads (the same parameters,
    the same ``published``)."""
    got = program_side(family, cfg, seq)
    want = reference_side(family, reference_of or cfg, seq)
    return {"logits": (got["logits"], want["logits"]), "rms": want["rms"],
            "loss": (got["loss"], want["loss"]),
            "grads": (got["grads"], want["grads"]), "aux": got["aux"],
            "metrics": got["metrics"], "extras": want["extras"],
            "losses": want["losses"], "seq": seq, "cfg": cfg}


@functools.lru_cache(maxsize=None)
def _forward(family, cfg):
    return jax.jit(partial(family.module.forward, cfg=cfg))


def forward_alone(family, params, cfg, tokens, patched=False):
    """The forward by itself, one jitted function a configuration for the
    process: a case that changes leaves or ids runs the program of the case
    before it. ``patched``: the case took a term out of the program
    (``monkeypatch``), so it compiles its own, which no other case sees."""
    forward = (_forward.__wrapped__ if patched else _forward)(family, cfg)
    with jax.default_matmul_precision("highest"):
        return forward(params, tokens=tokens)


class Patches:
    """``monkeypatch``, and whether a drop asked anything of it."""

    def __init__(self, monkeypatch):
        self.monkeypatch, self.made = monkeypatch, False

    def __getattr__(self, name):
        self.made = True
        return getattr(self.monkeypatch, name)


def in_every_run(params, change):
    """``params`` with every stack of layers through ``change``."""
    return {name: change(dict(stack)) if isinstance(stack, dict) else stack
            for name, stack in params.items()}


def one_chip():
    return build_mesh(MeshConfig(dp=1, fsdp=1, tp=1),
                      devices=jax.devices()[:1])


def series():
    """{name: the sum over a metric's series} of everything fed so far."""
    return {entry["name"]: sum(entry["series"].values())
            for entry in metrics_mod.snapshot() if entry["series"]}


@functools.lru_cache(maxsize=None)
def _initial_state(family):
    """(optimizer, ``init_train_state`` of ``FLASH`` with it on one chip):
    one compiled init for every ``accum_steps``."""
    import optax
    optimizer = optax.adam(3e-3)
    return optimizer, init_train_state(family.flash, one_chip(),
                                       ShardingRules(), optimizer, seed=0)


@functools.lru_cache(maxsize=None)
def trained(family, accum_steps):
    """Three steps of ``make_train_step`` (which finds the model from
    ``type(cfg)``) on one repeated batch of ``FLASH`` at ``FLASH_SEQ``: one
    compiled step an ``accum_steps`` for the process. ``metrics``: every
    step's; ``fed``: what the counters rose by; ``gauges``: what every
    series reads after the last step."""
    cfg, mesh = family.flash, one_chip()
    rules, (optimizer, state) = ShardingRules(), _initial_state(family)
    # A copy: the step donates its state, and these are the process's.
    state = jax.tree.map(jnp.copy, state)
    if family.train_drawn:
        state["params"] = jax.tree.map(jnp.copy, drawn(family, cfg))
    step = make_train_step(cfg, mesh, rules, optimizer,
                           accum_steps=accum_steps)
    tokens, targets = batch(cfg, family.flash_seq)
    before, seen = series(), []
    for _ in range(3):
        state, metrics = step(state, {"tokens": tokens, "targets": targets})
        seen.append({name: float(value) for name, value in metrics.items()
                     if value.ndim == 0})
    after = series()
    losses = [m["loss"] for m in seen]
    assert all(np.isfinite(losses)) and losses[-1] < losses[0]
    return {"metrics": seen, "gauges": after, "tokens": tokens.size,
            "fed": {name: value - before.get(name, 0.0)
                    for name, value in after.items()}}


def expert_layer(experts=16, tokens=96, d=32, f=16, seed=0, shared=True,
                 rank=2):
    """An expert layer's leaves and its input: [tokens, d], or [1, tokens,
    d] with ``rank`` 3."""
    ks = jax.random.split(jax.random.PRNGKey(seed), 9)
    normal = jax.random.normal
    w = {"ln2_scale": jnp.ones((d,)),
         "router": normal(ks[0], (d, experts)) / math.sqrt(d),
         "router_bias": 0.2 * normal(ks[1], (experts,)),
         "w_gate": normal(ks[2], (experts, d, f)) / math.sqrt(d),
         "w_up": normal(ks[3], (experts, d, f)) / math.sqrt(d),
         "w_down": normal(ks[4], (experts, f, d)) / math.sqrt(f)}
    if shared:
        w.update(shared_w_gate=normal(ks[5], (d, f)) / math.sqrt(d),
                 shared_w_up=normal(ks[6], (d, f)) / math.sqrt(d),
                 shared_w_down=normal(ks[7], (f, d)) / math.sqrt(f))
    return w, normal(ks[8], (1,) * (rank - 2) + (tokens, d))


def share_of(w, first, count):
    return dict(w, **{name: w[name][first:first + count]
                      for name in ("w_gate", "w_up", "w_down")})


def shares_add_up(reference, experts, shares, top_k, scale):
    """The routed parts that the shares of an expert layer of
    ``lm.expert_ffn`` give, plus the shared expert once, are the uncut layer
    of the reference; and every share computes exactly the assignments the
    router gave its experts."""
    w, h = expert_layer(experts, rank=3)
    count = experts // shares
    kw = dict(top_k=top_k, scaling=scale, renormalize=True, eps=0.0,
              first_expert=0)
    x = reference._rmsnorm(h, w["ln2_scale"], 0.0)
    with jax.default_matmul_precision("highest"):
        want, picked = reference._ffn(h, w, **kw)
        total, computed = h, 0
        for first in range(0, experts, count):
            share = share_of(w, first, count)
            routed, shared, aux = lm.expert_ffn(
                x, share, top_k=top_k, scaling=scale, normalize=True,
                held=(first, count))
            mine = ((picked >= first) & (picked < first + count)).sum()
            assert int(aux["group_sizes"].sum()) == int(mine) \
                == int(aux.get("asked", mine))
            # The shared expert is every chip's alike: counted once.
            total = total + routed + (shared if first == 0 else 0.0)
            computed += int(mine)
            ref_part = reference._ffn(h, share, **dict(
                kw, first_expert=first))[0]
            np.testing.assert_allclose(h + routed + shared, ref_part,
                                       atol=5e-5)
    assert computed == h.shape[1] * top_k
    np.testing.assert_allclose(total, want, atol=1e-4)


def cases(f):
    """{name: fixture or test} for a family's file, each made where ``f``
    names what it reads."""
    module, shell, reference = f.module, f.shell, f.reference
    which = pytest.mark.parametrize(
        "which", ["both", "both_flash"] if f.flash else ["both"])

    @pytest.fixture(scope="module")
    def both():
        return compared(f, f.cfg, f.seq)

    @pytest.fixture(scope="module")
    def both_flash():
        return compared(f, f.flash, f.flash_seq)

    @which
    def test_logits_loss_and_routing_match_the_reference(which, request):
        found = request.getfixturevalue(which)
        got, want = found["logits"]
        assert found["rms"] > f.rms_floor
        assert float(jnp.abs(got - want).max()) < f.logits_tol * found["rms"]
        np.testing.assert_allclose(*found["loss"], rtol=1e-5)
        if f.extras[:1] == ("picked",):
            assert (np.sort(found["aux"]["picked"], -1)
                    == np.sort(found["extras"][0], -1)).all()

    @pytest.mark.parametrize("leaf", leaves(f))
    @which
    def test_gradients_match_the_reference(which, leaf, request):
        found = request.getfixturevalue(which)
        got, want = (by_name(tree)[leaf] for tree in found["grads"])
        norm = float(jnp.linalg.norm(want.ravel()))
        if "router_bias" in leaf:  # selection only: no gradient, either side
            assert norm == 0.0 and not np.any(got)
            return
        assert norm > 0.0
        assert float(jnp.linalg.norm((got - want).ravel())) < 1e-4 * norm

    @pytest.mark.parametrize("dropped", f.dropped)
    def test_a_dropped_term_shows(both, dropped, monkeypatch):
        """Each of the terms a fast path could lose, taken out of the
        program, moves the logits by far more than the agreement above
        allows."""
        patches = Patches(monkeypatch)
        params, cfg = f.drop(dropped, drawn(f, f.cfg), f.cfg, patches)
        tokens, _ = batch(f.cfg, f.seq, rows=f.rows)
        got = forward_alone(f, params, cfg, tokens, patched=patches.made)
        _, want = both["logits"]
        assert float(jnp.abs(got - want).max()) > 0.05 * both["rms"]

    def test_the_sliced_heads_loss_is_the_whole_heads_on_the_slice():
        """A slice of the vocabulary is a smaller vocabulary: on ids of the
        slice, the cross-entropy of the model that holds the slice's rows of
        ``wte`` and columns of the head (the table's rows alone where it is
        the head) is the whole model's with its logits restricted to those
        columns."""
        held = f.sliced_vocab
        cut = replace(f.cfg, vocab_size=held)
        params = drawn(f, f.cfg)
        tokens, targets = batch(cut, f.seq)
        sliced = dict(params, wte=params["wte"][:held])
        if not shell.tied:
            sliced["lm_head"] = params["lm_head"][:, :held]
        with jax.default_matmul_precision("highest"):
            _, metrics = jax.jit(lambda p: module.loss_fn(
                p, cut, tokens, targets))(sliced)
        logits = forward_alone(f, params, f.cfg, tokens)[..., :held]
        logp = jax.nn.log_softmax(logits.astype(jnp.float32), axis=-1)
        want = -jnp.take_along_axis(logp, targets[..., None], -1).mean()
        np.testing.assert_allclose(metrics["loss"], want, rtol=1e-6)
        assert abs(float(metrics["loss"]) - math.log(held)) < 1.0
        assert float(metrics["moe_routed"]) == \
            tokens.size * f.top_k * f.cfg.n_moe_layers

    @pytest.mark.parametrize("accum_steps", f.accum_steps)
    def test_trains_and_feeds_the_shares_counters(accum_steps):
        """The loss falls on a repeated batch (the kernels, remat, the
        chunked loss, a share of the experts), and the counters say what the
        share did: every assignment to a held expert computed, and those a
        part of all the router made."""
        found = trained(f, accum_steps)
        routed = found["tokens"] * f.top_k * f.flash.n_moe_layers
        for metrics in found["metrics"]:
            assert metrics["moe_routed"] == routed
            assert metrics["moe_assignments"] == metrics["moe_tokens"]
            assert 0 < metrics["moe_tokens"] < routed
            # The busiest held expert's load over the held experts' mean.
            assert 1.0 <= metrics["moe_load_max_over_mean"] <= 3.0
        assigned, asked, all_routed = (
            found["fed"]["ray_tpu_train_moe_" + name + "_total"]
            for name in ("assignments", "tokens", "routed"))
        # Fed one call late at most: after three blocking steps, two or
        # three.
        assert assigned == asked and all_routed in (2 * routed, 3 * routed)
        # 3 of 8 experts held: about three eighths of the routing's work.
        assert 0.2 < asked / all_routed < 0.6

    @pytest.mark.parametrize("accum_steps", f.accum_steps)
    def test_a_step_feeds_the_calls_and_those_within_the_bound(accum_steps):
        """An expert-layer call a layer and microbatch, and each within the
        bound: 3 of 8 experts held get about three eighths of the
        assignments, and the buffer is all of them (twice the even share is
        three quarters, a whole row tile is more than all)."""
        found = trained(f, accum_steps)
        calls = f.flash.n_moe_layers * accum_steps
        for metrics in found["metrics"]:
            assert metrics["moe_calls"] == calls \
                == metrics["moe_calls_within_bound"]
        in_all, within = (found["fed"]["ray_tpu_train_moe_" + name]
                          for name in ("calls_total",
                                       "calls_within_bound_total"))
        assert in_all == within and in_all in (2 * calls, 3 * calls)

    def test_expert_parallel_mesh_is_refused():
        mesh = build_mesh(MeshConfig(dp=1, fsdp=1, tp=1, ep=2),
                          devices=jax.devices()[:2])
        step = make_train_step(f.cfg, mesh)
        tokens, targets = batch(f.cfg, f.seq)
        # The trace raises it, before any value is read: no state is made.
        with pytest.raises(NotImplementedError, match="expert parallelism"):
            step.lower(abstract_train_state(f.cfg, mesh),
                       {"tokens": tokens, "targets": targets})

    @functools.lru_cache(maxsize=None)
    def one_by_one():
        """The layers applied one by one: one program for both scans (a
        block does not read ``cfg.remat``: ``lm.rematerialised`` is the
        scan's)."""
        cfg = f.cfg
        tokens, _ = batch(cfg, f.seq)
        positions = lm.positions_of(tokens)

        def run(params):
            x = lm.embed(params["wte"], tokens, cfg.dtype)
            if shell.embed_scale:
                x = x * shell.embed_scale(cfg)
            returned = {}
            for run, kind, depth in shell.runs_of(cfg):
                for j in range(depth):
                    x, one = module._block(cfg, kind, x, jax.tree.map(
                        lambda a: a[j], params[run]), positions)
                    for name, value in (one or {}).items():
                        returned.setdefault(name, []).append(value)
            return lm.rmsnorm(x, params[shell.final_norm],
                              getattr(cfg, shell.eps)), returned

        with jax.default_matmul_precision("highest"):
            return jax.jit(run)(drawn(f, cfg))

    @pytest.mark.parametrize("remat", [False, True])
    def test_scan_blocks_over_the_runs(remat):
        """The runs scanned, one stack a run, are the layers applied one by
        one in order: hidden states, and the layers' auxiliary outputs each
        stacked over the layers that return it."""
        cfg = replace(f.cfg, remat=remat)
        tokens, _ = batch(cfg, f.seq)
        with jax.default_matmul_precision("highest"):
            got, aux = jax.jit(partial(shell.hidden_states, cfg=cfg))(
                drawn(f, cfg), tokens=tokens)
        want, returned = one_by_one()
        np.testing.assert_allclose(got, want, atol=f.scan_atol)
        assert sorted(returned) == sorted(aux or {})
        for name, values in returned.items():
            np.testing.assert_allclose(aux[name], jnp.stack(values),
                                       rtol=1e-6)

    def test_the_entry_points_are_the_shells():
        """What ``make_train_step`` and the benchmark call is the shell's
        own composition of the pieces ``program_side`` differentiates."""
        assert module.loss_fn == shell.loss_fn
        assert module.forward == shell.forward

    def test_a_field_the_init_does_not_read_draws_nothing():
        """One tree for all the configurations whose init is one program:
        the second asks for nothing to be compiled or drawn."""
        assert drawn(f, replace(f.cfg, remat=not f.cfg.remat)) \
            is drawn(f, f.cfg)

    def test_param_specs_match_init():
        is_spec = lambda s: isinstance(s, jax.sharding.PartitionSpec)  # noqa: E731
        for cfg in (f.cfg, f.flash) if f.flash else (f.cfg,):
            shapes = jax.eval_shape(partial(module.init, cfg),
                                    jax.random.PRNGKey(0))
            specs = module.param_specs(cfg, ShardingRules())
            assert jax.tree.structure(shapes) == jax.tree.structure(
                specs, is_leaf=is_spec)
            for leaf, spec in zip(jax.tree.leaves(shapes),
                                  jax.tree.leaves(specs, is_leaf=is_spec)):
                assert len(spec) == leaf.ndim

    @pytest.mark.parametrize("wrong", f.wrong, ids=f.wrong_ids)
    def test_config_refuses_what_it_cannot_hold(wrong):
        with pytest.raises(f.refuses):
            replace(f.cfg, **wrong)

    def test_the_reference_is_the_benchmarks_byte_for_byte():
        name = os.path.basename(reference.__file__)
        with open(reference.__file__, "rb") as mine, \
                open(os.path.join(BENCHMARK, "reference",
                                  name[len("reference_"):]), "rb") as theirs:
            assert mine.read() == theirs.read()

    def test_bfloat16_with_the_kernels_is_the_same_function():
        """The shipped precision on the CPU: the logits stay within a few
        per cent of the float32 reference's RMS. It says that the
        low-precision path is the same function, not how close it is."""
        # ``both_flash``'s reference where it is this model's too.
        same = f.published(f.bfloat16) == f.published(f.flash) \
            and drawn(f, f.bfloat16) is drawn(f, f.flash)
        want = reference_side(f, f.flash if same else f.bfloat16,
                              f.flash_seq, gradients=same)
        tokens, _ = batch(f.bfloat16, f.flash_seq, rows=f.rows)
        got = jax.jit(partial(module.forward, cfg=f.bfloat16))(
            drawn(f, f.bfloat16), tokens=tokens)
        err = float(jnp.sqrt(((got.astype(jnp.float32)
                               - want["logits"]) ** 2).mean()))
        assert err < 0.05 * want["rms"]

    def test_the_flash_size_runs_the_kernels():
        """At the kernels' size every kernel of the family is a Pallas call
        of the lowered step."""
        params = jax.eval_shape(partial(module.init, f.flash),
                                jax.random.PRNGKey(0))
        tokens, targets = batch(f.flash, f.flash_seq, rows=1)
        text = jax.jit(jax.grad(
            lambda p: module.loss_fn(p, f.flash, tokens, targets)[0])).lower(
            params).as_text(debug_info=True)
        for kernel in f.flash_kernels:
            assert kernel in text, kernel

    wanted = {
        "both": True, "both_flash": f.flash,
        "test_logits_loss_and_routing_match_the_reference": True,
        "test_gradients_match_the_reference": True,
        "test_a_dropped_term_shows": f.dropped,
        "test_the_sliced_heads_loss_is_the_whole_heads_on_the_slice": f.top_k,
        "test_trains_and_feeds_the_shares_counters":
            f.accum_steps and shell.experts,
        "test_a_step_feeds_the_calls_and_those_within_the_bound":
            f.accum_steps and shell.experts,
        "test_expert_parallel_mesh_is_refused": shell.experts,
        "test_scan_blocks_over_the_runs": f.scan_atol,
        "test_the_entry_points_are_the_shells": True,
        "test_a_field_the_init_does_not_read_draws_nothing": True,
        "test_param_specs_match_init": True,
        "test_config_refuses_what_it_cannot_hold": f.wrong,
        "test_the_reference_is_the_benchmarks_byte_for_byte": True,
        "test_bfloat16_with_the_kernels_is_the_same_function": f.bfloat16,
        "test_the_flash_size_runs_the_kernels": f.flash_kernels}
    made = locals()
    return {name: made[name] for name, needed in wanted.items() if needed}
