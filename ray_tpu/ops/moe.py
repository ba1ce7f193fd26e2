"""A dropless routed-expert layer: sort, group sizes, ragged matmuls.

``routed_experts`` is the routed half of a DeepSeek-V3-style expert layer
(``models/deepseek.py`` adds the shared expert). Every token's ``top_k``
assignments are computed: there is no capacity and nothing is dropped, and
no tensor of ``tokens x experts x capacity`` exists at any size. The
``tokens x top_k`` assignments are sorted by expert (a stable sort), each
expert multiplies its own contiguous group of rows (``grouped_matmul``), and
the rows go back to token order for the weighted sum.

The grouped matmul is JAX's Pallas kernel set ``megablox`` (``gmm`` and, for
the weights' gradient, ``tgmm``) wherever the shapes tile (rows and both
widths multiples of 128), and ``jax.lax.ragged_dot`` elsewhere (tiny test
widths). Both cost the groups' FLOPs, not the dense ``experts x`` product. On
the v5e, at Moonlight's widths (98,304 rows, 2048 x 1408, 64 groups), the
three products of an expert SwiGLU forward and backward took 95.9 ms with
``ragged_dot`` (XLA's own grouped kernel, 53 TFLOP/s) and 52.8 ms with
``megablox`` at tiles of 512 x 512 x 1408 (97 TFLOP/s): PERF.md, PR 25.

The backward pass of the two permutations is written out: the cotangent of
a gather by a permutation is the gather by its inverse, where autodiff would
emit a scatter-add, which a TPU serialises. So is that of the weighted sum,
which would otherwise store float32 copies of ``[tokens, top_k, d]``.

**Held experts.** A chip that shares a layer's experts with others holds a
contiguous run of them, ``held = (first, count)``, and gives
``routed_experts`` the weights of those alone. The router keeps its whole
width: scores, ``top_k`` and the normalisation are over all the experts.
Only the assignments to held experts are multiplied (the grouped matmul
starts at group ``first`` and computes ``count`` groups; the other rows
come out zero), and the result is ``sum_{i picked and held} w_i
Expert_i(x)``: this chip's part of the layer's sum. What the absent
experts would have added is left out, and nothing stands in for the other
chips or for an exchange with them: the share is not expert parallelism,
which a mesh with ``ep`` > 1 would ask for and no model here implements.
Dropless on the share: every assignment to a held expert is computed, and
``aux["group_sizes"]`` counts them. The rows are still sorted, gathered
and combined at ``tokens x top_k``, the only size that holds whatever the
router decides.
"""

from __future__ import annotations

import importlib
from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp

# The module, not the function ``ray_tpu.ops`` re-exports under its name:
# one ``_interpret`` says for every kernel of this package whether a TPU
# is there.
_flash = importlib.import_module("ray_tpu.ops.flash_attention")

#: Rows and contraction depth of a ``megablox`` tile, and the widest output
#: tile: measured on the v5e at 98,304 x 2048 x 1408 (see the module text);
#: 1024 rows, or 1024 deep at this width, do not fit the scoped VMEM.
_TILE_M, _TILE_K, _TILE_N_MAX = 512, 512, 1408


def grouped_matmul(rows, weights, group_sizes, first=None):
    """rows [M, k], sorted into ``len(group_sizes)`` contiguous groups, times
    each group's own weights [E, k, n] -> [M, n] in rows' dtype. With
    ``first``, ``weights`` are those of the groups ``first`` to ``first +
    len(weights)`` alone: only their rows are multiplied, the others come
    out zero."""
    (m, k), n = rows.shape, weights.shape[-1]
    if m % 128 or k % 128 or n % 128:
        if first is not None:
            # Three stretches of rows: before, held, after; the outer two
            # against zero weights.
            last = first + weights.shape[0]
            nothing = jnp.zeros((1, k, n), weights.dtype)
            weights = jnp.concatenate([nothing, weights, nothing])
            group_sizes = jnp.concatenate([
                group_sizes[:first].sum()[None], group_sizes[first:last],
                group_sizes[last:].sum()[None]])
        return jax.lax.ragged_dot(rows, weights, group_sizes)
    from jax.experimental.pallas.ops.tpu.megablox import ops as megablox
    tile_m = next(t for t in (_TILE_M, 256, 128) if m % t == 0)
    tile_n = next(t for t in range(min(n, _TILE_N_MAX), 0, -128)
                  if n % t == 0)
    offset = None if first is None else jnp.asarray(first, jnp.int32)
    return megablox.gmm(rows, weights, group_sizes, rows.dtype,
                        (tile_m, min(k, _TILE_K), tile_n), offset,
                        interpret=_flash._interpret())


def route(x, router, bias, top_k: int, scaling: float, normalize: bool
          ) -> Tuple[jax.Array, jax.Array]:
    """Sigmoid scores in float32 and the ``top_k`` experts of each token.

    x [T, d], router [d, E], bias [E] -> (picked [T, K] int32, weights
    [T, K] float32). The experts are picked by ``score + bias`` (the
    correction bias of ``topk_method: noaux_tc``: selection only, no
    gradient); the weights are the picked scores themselves, normalised to
    sum to one when ``normalize``, times ``scaling``."""
    scores = jax.nn.sigmoid(jnp.dot(
        x.astype(jnp.float32), router.astype(jnp.float32),
        precision=jax.lax.Precision.HIGHEST))
    _, picked = jax.lax.top_k(
        scores + jax.lax.stop_gradient(bias.astype(jnp.float32)), top_k)
    weights = jnp.take_along_axis(scores, picked, axis=-1)
    if normalize and top_k > 1:
        weights = weights / (weights.sum(-1, keepdims=True) + 1e-20)
    return picked, weights * scaling


# Assignments are numbered choice-major: a = k * T + t. The [K * T, d] rows
# in that order are K whole [T, d] slabs, so going between the flat rows and
# the per-choice view is free; token-major [T, K, d] would pad K to the
# tile (6 -> 8) and turn every reshape into a copy.


@jax.custom_vjp
def _dispatch(x, order, inverse):
    """Rows of x [T, d] in expert order: x[order % T]."""
    return jnp.take(x, order % x.shape[0], axis=0)


def _dispatch_fwd(x, order, inverse):
    return _dispatch(x, order, inverse), (inverse, x.shape[0])


def _dispatch_bwd(residuals, g):
    inverse, tokens = residuals
    back = jnp.take(g, inverse, axis=0).reshape(-1, tokens, g.shape[-1])
    return back.astype(jnp.float32).sum(0).astype(g.dtype), None, None


_dispatch.defvjp(_dispatch_fwd, _dispatch_bwd)


@jax.custom_vjp
def _unsort(y, order, inverse):
    """Rows of y [K * T, d] back in assignment (choice-major) order."""
    return jnp.take(y, inverse, axis=0)


def _unsort_fwd(y, order, inverse):
    return _unsort(y, order, inverse), order


def _unsort_bwd(order, g):
    return jnp.take(g, order, axis=0), None, None


_unsort.defvjp(_unsort_fwd, _unsort_bwd)


@jax.custom_vjp
def _combine(flat, weights):
    """sum_k weights[k, t] * flat[k * T + t, :] in float32 -> [T, d] in
    flat's dtype. Written out with its backward pass, slab by slab, so that
    no float32 copy of the [K * T, d] rows is ever stored: the products
    accumulate in float32 straight from the stored dtype."""
    tokens = weights.shape[1]
    acc = sum(flat[k * tokens:(k + 1) * tokens].astype(jnp.float32)
              * weights[k][:, None] for k in range(weights.shape[0]))
    return acc.astype(flat.dtype)


def _combine_fwd(flat, weights):
    return _combine(flat, weights), (flat, weights)


def _combine_bwd(residuals, g):
    flat, weights = residuals
    tokens = weights.shape[1]
    g32 = g.astype(jnp.float32)
    slabs = range(weights.shape[0])
    d_flat = jnp.concatenate(
        [(g32 * weights[k][:, None]).astype(flat.dtype) for k in slabs])
    d_weights = jnp.stack(
        [(flat[k * tokens:(k + 1) * tokens].astype(jnp.float32) * g32
          ).sum(-1) for k in slabs])
    return d_flat, d_weights


_combine.defvjp(_combine_fwd, _combine_bwd)


def routed_experts(x, router, bias, w_gate, w_up, w_down, *, top_k: int,
                   scaling: float, normalize: bool = True,
                   held: Optional[Tuple[int, int]] = None
                   ) -> Tuple[jax.Array, Dict[str, jax.Array]]:
    """sum_i w_i Expert_i(x) over each token's ``top_k`` experts, dropless.

    x [T, d] (compute dtype); router [d, E]; bias [E]; w_gate, w_up
    [E, d, f]; w_down [E, f, d]. Expert_i is SwiGLU:
    ``(silu(x w_gate_i) * x w_up_i) w_down_i``. Returns (y [T, d], aux) with
    ``aux["picked"]`` [T, K] (the router's choice, for a reference to compare
    with) and ``aux["group_sizes"]`` [E] (assignments each expert computed;
    their sum is T * K, or something was dropped).

    ``held = (first, count)``: the weights are those of experts ``first`` to
    ``first + count`` alone, [count, ...], of the router's E (the module
    text). The sum is then over the picked experts that are held,
    ``aux["group_sizes"]`` is [count], the held experts', and
    ``aux["asked"]`` counts the assignments the router gave them (equal to
    their sum, or something was dropped). None, or all E held, is the whole
    layer."""
    tokens, n_experts = x.shape[0], router.shape[-1]
    dt = x.dtype
    first = None
    if held is not None and tuple(held) != (0, n_experts):
        first, count = held
        if first < 0 or count < 1 or first + count > n_experts:
            raise ValueError(f"held={held} of {n_experts} experts")
    if w_gate.shape[0] != (n_experts if first is None else count):
        raise ValueError(
            f"weights of {w_gate.shape[0]} experts, router of {n_experts}, "
            f"held={held}")
    with jax.named_scope("moe_route"):
        picked, weights = route(x, router, bias, top_k, scaling, normalize)
    with jax.named_scope("moe_dispatch"):
        expert_of = picked.T.reshape(-1)  # assignment a = k * T + t
        order = jnp.argsort(expert_of, stable=True).astype(jnp.int32)
        inverse = jnp.zeros_like(order).at[order].set(
            jnp.arange(order.shape[0], dtype=jnp.int32))
        group_sizes = jnp.zeros((n_experts,), jnp.int32).at[expert_of].add(1)
        rows = _dispatch(x, order, inverse)  # [K*T, d], grouped by expert
    with jax.named_scope("moe_experts"):
        gate = grouped_matmul(rows, w_gate.astype(dt), group_sizes, first)
        up = grouped_matmul(rows, w_up.astype(dt), group_sizes, first)
        out = grouped_matmul(jax.nn.silu(gate) * up, w_down.astype(dt),
                             group_sizes, first)
    with jax.named_scope("moe_combine"):
        y = _combine(_unsort(out, order, inverse), weights.T)
    if first is None:
        return y, {"picked": picked, "group_sizes": group_sizes}
    asked = ((picked >= first) & (picked < first + count)).sum()
    return y, {"picked": picked, "asked": asked,
               "group_sizes": group_sizes[first:first + count]}
