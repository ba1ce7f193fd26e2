"""``ops/moe.py`` on a share of the experts: the held experts' rows go
through buffers of a static bound, and on every routing the result is what
the same layer gives at ``tokens x top_k`` rows (the path this file keeps
as the reference: the layer as it stood before the buffers)."""

import math

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ray_tpu.ops import moe

EXPERTS, TOKENS, TOP_K, COUNT = 16, 512, 2, 4
#: Twice the even share of 4 of 16 experts at 512 x 2 assignments: one tile.
BOUND = 512


def _full_size(x, router, bias, w_gate, w_up, w_down, *, top_k, scaling,
               held=None):
    """``routed_experts`` with every pass at ``tokens x top_k`` rows: sorted
    by expert, the grouped matmuls from group ``first`` on, the other rows
    zero."""
    n_experts, dt = router.shape[-1], x.dtype
    first, count = None, n_experts
    if held is not None and tuple(held) != (0, n_experts):
        first, count = held

    def matmul(rows, weights):
        (m, k), n = rows.shape, weights.shape[-1]
        if first is None or m % 128 or k % 128 or n % 128:
            sizes = group_sizes
            if first is not None:
                nothing = jnp.zeros((1, k, n), weights.dtype)
                weights = jnp.concatenate([nothing, weights, nothing])
                sizes = jnp.concatenate([
                    sizes[:first].sum()[None], sizes[first:first + count],
                    sizes[first + count:].sum()[None]])
            if m % 128 or k % 128 or n % 128:
                return jax.lax.ragged_dot(rows, weights, sizes)
            return moe.grouped_matmul(rows, weights, sizes)
        from jax.experimental.pallas.ops.tpu.megablox import ops as megablox
        return megablox.gmm(rows, weights, group_sizes, rows.dtype,
                            (128, 128, 128), jnp.asarray(first, jnp.int32),
                            interpret=True)

    with jax.named_scope("moe_route"):
        picked, weights = moe.route(x, router, bias, top_k, scaling, True)
    with jax.named_scope("moe_dispatch"):
        expert_of = picked.T.reshape(-1)
        order = jnp.argsort(expert_of, stable=True).astype(jnp.int32)
        inverse = jnp.zeros_like(order).at[order].set(
            jnp.arange(order.shape[0], dtype=jnp.int32))
        group_sizes = jnp.zeros((n_experts,), jnp.int32).at[expert_of].add(1)
        rows = moe._dispatch(x, order, inverse)
    with jax.named_scope("moe_experts"):
        gate = matmul(rows, w_gate.astype(dt))
        up = matmul(rows, w_up.astype(dt))
        out = matmul(jax.nn.silu(gate) * up, w_down.astype(dt))
    with jax.named_scope("moe_combine"):
        y = moe._combine(moe._unsort(out, order, inverse), weights.T)
    if first is None:
        return y, {"picked": picked, "group_sizes": group_sizes}
    return y, {"picked": picked,
               "group_sizes": group_sizes[first:first + count]}


def _layer(d, f, seed=0):
    ks = jax.random.split(jax.random.PRNGKey(seed), 6)
    normal = jax.random.normal
    return (normal(ks[0], (TOKENS, d)),
            normal(ks[1], (d, EXPERTS)) / math.sqrt(d),
            0.2 * normal(ks[2], (EXPERTS,)),
            normal(ks[3], (EXPERTS, d, f)) / math.sqrt(d),
            normal(ks[4], (EXPERTS, d, f)) / math.sqrt(d),
            normal(ks[5], (EXPERTS, f, d)) / math.sqrt(f))


def _planted(first, both, one):
    """A routing [T, K] that gives the held experts ``2 * both + one``
    assignments: the first ``both`` tokens pick two of them, the next
    ``one`` tokens one of them and one that is not held, the others two that
    are not, spread over the experts of each kind."""
    held = np.arange(first, first + COUNT)
    others = np.setdiff1d(np.arange(EXPERTS), held)
    t = np.arange(TOKENS)
    mine = np.stack([held[t % COUNT], held[(t + 1) % COUNT]], -1)
    theirs = np.stack([others[t % len(others)],
                       others[(t + 5) % len(others)]], -1)
    picked = np.where((t < both)[:, None], mine, theirs)
    picked[both:both + one, 1] = mine[both:both + one, 0]
    return jnp.asarray(picked, jnp.int32)


#: name: (tokens that pick two held experts, tokens that pick one).
ROUTINGS = {
    "under": (100, 60),             # 260 rows of 512
    "at": (200, 112),               # 512: the whole buffer, and no more
    "one_over": (200, 113),         # 513: a second buffer for one row
    "over": (300, 100),             # 700: two buffers
    "all": (TOKENS, 0),             # 1024: every assignment, two buffers
    "none": (0, 0),                 # no buffer at all
}


def _route_as(picked):
    """``moe.route`` with the choice planted and the weights still the
    router's own scores of it (so the router has a gradient)."""
    def route(x, router, bias, top_k, scaling, normalize):
        scores = jax.nn.sigmoid(jnp.dot(
            x.astype(jnp.float32), router.astype(jnp.float32),
            precision=jax.lax.Precision.HIGHEST))
        weights = jnp.take_along_axis(scores, picked, axis=-1)
        weights = weights / (weights.sum(-1, keepdims=True) + 1e-20)
        return picked, weights * scaling
    return route


def _run(layer_fn, args, first):
    x, router, bias, *experts = args
    cut = [w[first:first + COUNT] for w in experts]

    def loss(x, router, *cut):
        y, aux = layer_fn(x, router, bias, *cut, top_k=TOP_K, scaling=2.0,
                          held=(first, COUNT))
        return (y * jnp.cos(jnp.arange(y.size).reshape(y.shape))).sum(), \
            (y, aux)

    with jax.default_matmul_precision("highest"):
        (_, (y, aux)), grads = jax.jit(jax.value_and_grad(
            loss, argnums=(0, 1, 2, 3, 4), has_aux=True))(x, router, *cut)
    return y, aux, grads


@pytest.mark.parametrize("first", [0, 9])
@pytest.mark.parametrize("routing", list(ROUTINGS))
@pytest.mark.parametrize("widths", [(32, 16), (128, 128)],
                         ids=["ragged_dot", "megablox"])
def test_the_buffers_give_the_full_size_layer(monkeypatch, widths, routing,
                                              first):
    """Output and the gradients to x, the router and the three weights are
    those of the layer at ``tokens x top_k`` rows on a routing under the
    bound, exactly at it, over it (more buffers), all on held experts and
    none on them, for a held run from expert 0 and one from the middle; the
    rows counted are the rows asked, and a buffer count cut to one counts
    fewer on a routing over the bound."""
    assert moe._held_bound(TOKENS, TOP_K, COUNT, EXPERTS) == BOUND
    both, one = ROUTINGS[routing]
    asked = 2 * both + one
    picked = _planted(first, both, one)
    monkeypatch.setattr(moe, "route", _route_as(picked))
    args = _layer(*widths)

    y, aux, grads = _run(moe.routed_experts, args, first)
    want_y, want_aux, want_grads = _run(_full_size, args, first)
    assert int(aux["asked"]) == asked == int(aux["group_sizes"].sum())
    assert (aux["group_sizes"] == want_aux["group_sizes"]).all()
    assert int(aux["within_bound"]) == (asked <= BOUND)
    scale = float(jnp.abs(want_y).max()) or 1.0
    np.testing.assert_allclose(y, want_y, atol=1e-5 * scale)
    for got, want in zip(grads, want_grads):
        np.testing.assert_allclose(
            got, want, atol=1e-5 * (float(jnp.abs(want).max()) or 1.0))
    if not asked:
        assert not np.any(np.asarray(y))

    monkeypatch.setattr(moe, "_buffers_needed",
                        lambda asked, bound: jnp.minimum(asked, 1))
    _, cut_aux, _ = _run(moe.routed_experts, args, first)
    assert int(cut_aux["group_sizes"].sum()) == min(asked, BOUND)


def test_the_bound_is_twice_the_even_share_in_whole_tiles():
    # Kimi-Linear's and Trinity's shares at 16k tokens.
    assert moe._held_bound(16384, 8, 32, 256) == 32768
    assert moe._held_bound(16384, 4, 8, 256) == 4096
    # Rounded up to the row tile, and never past all the assignments.
    assert moe._held_bound(96, 4, 3, 16) == 512
    assert moe._held_bound(4096, 2, 12, 16) == 8192


@pytest.mark.parametrize("held", [None, (0, EXPERTS)])
@pytest.mark.parametrize("widths", [(32, 16), (128, 128)],
                         ids=["ragged_dot", "megablox"])
def test_with_every_expert_held_the_program_is_as_it_was(widths, held):
    """The whole layer does not go through the buffers: its lowered text is
    that of the layer as it stood."""
    args = _layer(*widths)

    def lowered(layer_fn):
        def layer(*args):
            return layer_fn(*args, top_k=TOP_K, scaling=2.0, held=held)
        return jax.jit(layer).lower(*args).as_text()

    assert lowered(moe.routed_experts) == lowered(_full_size)
