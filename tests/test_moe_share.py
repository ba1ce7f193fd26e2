"""``ops/moe.py`` on a share of the experts: the held experts' rows go
through buffers of a static bound, and on every routing the result is what
the same layer gives at ``tokens x top_k`` rows (the path this file keeps
as the reference: the layer as it stood before the buffers)."""

import functools
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
    zero. The row passes are ``jax.numpy`` indexing under autodiff: nothing
    of ``ops/moe.py`` but the router and the grouped matmul."""
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

    picked, weights, _ = moe.route(x, router, bias, top_k, scaling, True)
    expert_of = picked.T.reshape(-1)
    order = jnp.argsort(expert_of, stable=True)
    group_sizes = (expert_of[:, None] == jnp.arange(n_experts)).sum(0)
    rows = x[order % x.shape[0]]
    gate = matmul(rows, w_gate.astype(dt))
    up = matmul(rows, w_up.astype(dt))
    out = matmul(jax.nn.silu(gate) * up, w_down.astype(dt))
    flat = out[jnp.argsort(order)].reshape(top_k, -1, out.shape[-1])
    y = (flat.astype(jnp.float32) * weights.T[:, :, None]).sum(0).astype(dt)
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
    def route(x, router, bias, top_k, scaling, normalize,
              score="sigmoid"):
        scores = jax.nn.sigmoid(jnp.dot(
            x.astype(jnp.float32), router.astype(jnp.float32),
            precision=jax.lax.Precision.HIGHEST))
        weights = jnp.take_along_axis(scores, picked, axis=-1)
        weights = weights / (weights.sum(-1, keepdims=True) + 1e-20)
        return picked, weights * scaling, None
    return route


@functools.lru_cache(maxsize=None)
def _planted_layer(layer_fn, first, route_as, patched):
    """The compiled layer on a held run from ``first``, output, aux and the
    five gradients, with the routing an argument: ``moe.route`` is
    ``route_as`` of the traced picks while the layer is traced, so one
    program serves every routing. ``patched`` (what ``_run`` finds under the
    names of ``moe`` that this file's cases replace) keeps the programs
    traced under a replacement apart from those traced without."""
    def loss(picked, bias, x, router, *cut):
        with pytest.MonkeyPatch.context() as patch:
            patch.setattr(moe, "route", route_as(picked))
            y, aux = layer_fn(x, router, bias, *cut, top_k=TOP_K,
                              scaling=2.0, held=(first, COUNT))
        return (y * jnp.cos(jnp.arange(y.size).reshape(y.shape))).sum(), \
            (y, aux)

    return jax.jit(jax.value_and_grad(loss, argnums=(2, 3, 4, 5, 6),
                                      has_aux=True))


def _run(layer_fn, args, first, picked, route_as=_route_as):
    x, router, bias, *experts = args
    cut = [w[first:first + COUNT] for w in experts]
    with jax.default_matmul_precision("highest"):
        (_, (y, aux)), grads = _planted_layer(
            layer_fn, first, route_as,
            (moe._buffers_needed, moe._token_tile))(
                picked, bias, x, router, *cut)
    return y, aux, grads


def _one_buffer(asked, bound):
    return jnp.minimum(asked, 1)


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
    fewer on a routing over the bound. The six routings are data: a pair
    of widths and ``first`` compiles the three programs once."""
    assert moe._held_bound(TOKENS, TOP_K, COUNT, EXPERTS) == BOUND
    both, one = ROUTINGS[routing]
    asked = 2 * both + one
    picked = _planted(first, both, one)
    args = _layer(*widths)

    y, aux, grads = _run(moe.routed_experts, args, first, picked)
    want_y, want_aux, want_grads = _run(_full_size, args, first, picked)
    assert int(aux["asked"]) == asked == int(aux["group_sizes"].sum())
    assert (aux["group_sizes"] == want_aux["group_sizes"]).all()
    assert int(aux["within_bound"]) == (asked <= BOUND)
    # The rows the way back read: the held ones through the kernel (widths
    # of 128), every assignment a buffer through the gathers.
    assert int(aux["rows_summed"]) == (
        asked if widths[0] % 128 == 0
        else TOKENS * TOP_K * max(1, -(-asked // BOUND)))
    scale = float(jnp.abs(want_y).max()) or 1.0
    np.testing.assert_allclose(y, want_y, atol=1e-5 * scale)
    for got, want in zip(grads, want_grads):
        np.testing.assert_allclose(
            got, want, atol=1e-5 * (float(jnp.abs(want).max()) or 1.0))
    if not asked:
        assert not np.any(np.asarray(y))

    monkeypatch.setattr(moe, "_buffers_needed", _one_buffer)
    _, cut_aux, _ = _run(moe.routed_experts, args, first, picked)
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
    """The whole layer does not go through the buffers: said either way,
    its lowered text is one program, with neither the buffers' functions
    nor their kernel in it (a share's has both)."""
    args = _layer(*widths)

    def lowered(held, cut=EXPERTS):
        def layer(x, router, bias, *experts):
            return moe.routed_experts(
                x, router, bias, *(w[:cut] for w in experts), top_k=TOP_K,
                scaling=2.0, held=held)
        return jax.jit(layer).lower(*args).as_text()

    text = lowered(held)
    assert text == lowered((0, EXPERTS) if held is None else None)
    assert "_buffer_forward" not in text
    assert "_buffer_forward" in lowered((0, COUNT), COUNT)


# -- the whole layer: every assignment's row, by gathers alone ---------------


def _every_expert(x, router, bias, w_gate, w_up, w_down, *, top_k, scaling,
                  score):
    """The layer with no sort and no group: every expert's SwiGLU on every
    token, and a token's sum over the experts weighted by what the router
    gave each (zero where it was not picked)."""
    picked, weights, _ = moe.route(x, router, bias, top_k, scaling, True,
                                   score)
    of_expert = (weights[:, :, None] * jax.nn.one_hot(
        picked, router.shape[-1])).sum(1)
    gate = jnp.einsum("td,edf->etf", x, w_gate)
    up = jnp.einsum("td,edf->etf", x, w_up)
    out = jnp.einsum("etf,efd->etd", jax.nn.silu(gate) * up, w_down)
    return jnp.einsum("te,etd->td", of_expert, out), {"picked": picked}


@functools.lru_cache(maxsize=None)
def _whole_layer(layer_fn, score):
    """``layer_fn`` on all the experts compiled with its five gradients."""
    def loss(x, router, bias, *experts):
        y, aux = layer_fn(x, router, bias, *experts, top_k=TOP_K,
                          scaling=2.0, score=score)
        return (y * jnp.cos(jnp.arange(y.size).reshape(y.shape))).sum(), \
            (y, aux["picked"])
    return jax.jit(jax.value_and_grad(loss, argnums=(0, 1, 3, 4, 5),
                                      has_aux=True))


@pytest.mark.parametrize("score", ["sigmoid", "softmax"])
@pytest.mark.parametrize("widths", [(32, 16), (128, 128)],
                         ids=["ragged_dot", "megablox"])
def test_the_whole_layer_is_every_experts_weighted_sum(widths, score):
    """Output and the gradients to x, the router and the three expert
    leaves of the whole layer are those of the layer computed expert by
    expert on every token, under a sigmoid and a softmax router, at widths
    that take ``ragged_dot`` and at widths that tile."""
    x, router, bias, *experts = _layer(*widths)
    bias = bias if score == "sigmoid" else None
    with jax.default_matmul_precision("highest"):
        (_, (y, picked)), grads = _whole_layer(moe.routed_experts, score)(
            x, router, bias, *experts)
        (_, (want_y, want_picked)), want_grads = _whole_layer(
            _every_expert, score)(x, router, bias, *experts)
    assert (picked == want_picked).all()
    np.testing.assert_allclose(
        y, want_y, atol=1e-5 * float(jnp.abs(want_y).max()))
    for got, want in zip(grads, want_grads):
        assert np.asarray(want).any()
        np.testing.assert_allclose(
            got, want, atol=1e-5 * float(jnp.abs(want).max()))


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                         ids=["float32", "bfloat16"])
def test_the_whole_layers_row_passes_are_the_gathers_autodiff_transposes(
        dtype):
    """``_dispatch`` and ``_all_to_tokens`` with their written-out backward
    passes against ``jax.numpy`` indexing under autodiff: the rows, the sum
    and the cotangents of x, of the sorted rows and of the weights [K, T].
    In bfloat16 the forward's bits are those of the float32 sum over k."""
    d, exact = 32, dtype == jnp.float32
    keys = jax.random.split(jax.random.PRNGKey(4), 4)
    expert_of = jax.random.randint(keys[0], (TOP_K * TOKENS,), 0, EXPERTS)
    order = jnp.argsort(expert_of, stable=True).astype(jnp.int32)
    place = jnp.argsort(order).astype(jnp.int32)
    np.testing.assert_array_equal(moe._permuted(order, place), order[order])
    x = jax.random.normal(keys[1], (TOKENS, d)).astype(dtype)
    out = jax.random.normal(keys[2], (TOP_K * TOKENS, d)).astype(dtype)
    weights = jax.random.uniform(keys[3], (TOP_K, TOKENS), minval=0.1)

    def ours(x, out, weights):
        return (moe._dispatch(x, order, place),
                moe._all_to_tokens(out, weights, order, place))

    def plain(x, out, weights):
        flat = out[place].reshape(TOP_K, TOKENS, d).astype(jnp.float32)
        return x[order % TOKENS], sum(
            flat[k] * weights[k][:, None] for k in range(TOP_K)
        ).astype(dtype)

    def grads(fn):
        def loss(*args):
            rows, y = fn(*args)
            wave = jnp.cos(jnp.arange(rows.size, dtype=jnp.float32))
            return ((rows.astype(jnp.float32) * wave.reshape(rows.shape)
                     ).sum() + (y.astype(jnp.float32)
                                * wave[:y.size].reshape(y.shape)).sum())
        return jax.grad(loss, argnums=(0, 1, 2))(x, out, weights)

    for got, want in zip(ours(x, out, weights), plain(x, out, weights)):
        np.testing.assert_array_equal(np.asarray(got, np.float32),
                                      np.asarray(want, np.float32))
    for got, want in zip(grads(ours), grads(plain)):
        assert got.dtype == want.dtype
        np.testing.assert_allclose(
            np.asarray(got, np.float32), np.asarray(want, np.float32),
            rtol=1e-6 if exact else 2e-2, atol=1e-5 if exact else 2e-2)


def _moved(jaxpr, inside=()):
    """Every equation of ``jaxpr`` and of what it calls, each with the names
    of the jitted functions and loops it lies in: the layer's own, so not
    the bodies of Pallas kernels (interpreted off the chip) and not
    ``megablox``'s jitted ``gmm`` / ``tgmm``, which scatter, gather and
    search among their [groups] and [tiles] metadata."""
    from ray_tpu.parallel.collectives import sub_jaxprs
    for eqn in jaxpr.eqns:
        name = eqn.primitive.name
        yield eqn, inside
        if name == "pallas_call":
            continue
        here = inside
        if name in ("jit", "pjit"):
            if eqn.params["name"] in ("gmm", "tgmm"):
                continue
            here += (eqn.params["name"],)
        elif name in ("while", "scan"):
            here += (name,)
        for sub in sub_jaxprs(eqn):
            yield from _moved(sub, here)


@pytest.mark.parametrize("held", [None, (0, COUNT)], ids=["whole", "share"])
@pytest.mark.parametrize("score", ["sigmoid", "softmax"])
@pytest.mark.parametrize("widths", [(32, 16), (128, 128)],
                         ids=["ragged_dot", "megablox"])
def test_the_whole_layer_lowers_to_no_scatter_and_no_select_of_rows(
        widths, score, held):
    """The expert layer, whole and on a share, forward and with its
    gradients. Its lowered text has no ``select`` over a [K * T, d] array (a
    gather that promises its indices in bounds has none). Its equations
    (``_moved``: the grouped matmul's own aside) hold no scatter at all, the
    router's pick of the scores being a sum whose transpose is a sum; no
    gather but of rows of width d (none of scalars from a rank-1 operand,
    none of the scores); and no loop (a search is one) but the share's over
    its buffers, once forward and once backward."""
    x, router, bias, *experts = _layer(*widths)
    bias = bias if score == "sigmoid" else None
    if held is not None:
        experts = [w[:COUNT] for w in experts]

    def layer(x, router, *experts):
        return moe.routed_experts(x, router, bias, *experts, top_k=TOP_K,
                                  scaling=2.0, score=score, held=held)[0]

    def loss(*args):
        return layer(*args).sum()

    rows = f"tensor<{TOP_K * TOKENS}x{widths[0]}x"
    with_grads = jax.grad(loss, argnums=tuple(range(5)))
    for fn, loops in ((layer, ["_buffer_forward"]),
                      (with_grads, ["_buffer_forward", "_buffer_backward"])):
        text = jax.jit(fn).lower(x, router, *experts).as_text()
        assert "stablehlo.gather" in text
        assert not [line for line in text.splitlines()
                    if "stablehlo.select" in line and rows in line]
        moved = list(_moved(jax.make_jaxpr(fn)(x, router, *experts).jaxpr))
        assert not [eqn for eqn, _ in moved
                    if eqn.primitive.name.startswith("scatter")]
        tables = {eqn.invars[0].aval.shape[1:] for eqn, _ in moved
                  if eqn.primitive.name == "gather"}
        assert tables == {(widths[0],)}
        # Top-level loops alone, each calling the buffer's function.
        assert [inside for eqn, inside in moved
                if eqn.primitive.name in ("while", "scan")] == [
                    () for _ in (loops if held else [])]
        assert [eqn.params["name"] for eqn, inside in moved
                if inside == ("while",) and "name" in eqn.params
                ] == (loops if held else [])


@pytest.mark.parametrize("normalize", [True, False],
                         ids=["normalised", "as_scored"])
@pytest.mark.parametrize("score", ["sigmoid", "softmax"])
def test_the_routers_pick_is_the_gathers_to_the_bit(monkeypatch, score,
                                                    normalize):
    """``route``'s picked weights, their gradients to x and to the router,
    the choice and the picked mass are those of ``route`` with
    ``take_along_axis`` for its pick, under autodiff, bit for bit: a sigmoid
    router with its bias (selection only) and a softmax router, normalised
    and not, three choices of sixteen."""
    x, router, bias, *_ = _layer(32, 16)
    bias = bias if score == "sigmoid" else None

    def routed():
        def loss(x, router):
            picked, weights, mass = moe.route(x, router, bias, 3, 2.5,
                                              normalize, score)
            wave = jnp.cos(jnp.arange(weights.size, dtype=jnp.float32))
            return (weights * wave.reshape(weights.shape)).sum(), (
                picked, weights, mass)
        return jax.jit(jax.value_and_grad(loss, argnums=(0, 1),
                                          has_aux=True))(x, router)

    (_, got), got_grads = routed()
    monkeypatch.setattr(moe, "_picked_scores", functools.partial(
        jnp.take_along_axis, axis=-1))
    (_, want), want_grads = routed()
    assert (got[2] is None) == (score == "sigmoid")
    for ours, theirs in zip(jax.tree.leaves((got, got_grads)),
                            jax.tree.leaves((want, want_grads))):
        assert np.asarray(theirs).any()
        np.testing.assert_array_equal(np.asarray(ours), np.asarray(theirs))


#: name: (routing of ``ROUTINGS``, rows of a buffer): one buffer, a full
#: one, three, four, three whose last runs past the assignments, none.
MOVES = {
    "within": ("under", 512), "at": ("at", 512), "three": ("over", 256),
    "four": ("all", 256), "past_the_end": ("all", 384), "none": ("none", 512),
}


@pytest.mark.parametrize("case", list(MOVES))
def test_the_shares_scalars_ride_sorts_to_where_gathers_took_them(case):
    """The share's scalars by comparison and by sorts against the forms
    they replace, written out in ``jax.numpy``, to the bit: the held
    experts' sizes (a search of the sorted keys), a buffer's weights in
    expert order (a gather by its rows' assignments) and ``d weights`` back
    in assignment order (a gather a buffer by ``_at`` with a select, summed
    over the buffers), on routings of one buffer and of several."""
    routing, bound = MOVES[case]
    picked = _planted(0, *ROUTINGS[routing])
    assigned = TOP_K * TOKENS
    key = jnp.where(picked.T.reshape(-1) < COUNT, picked.T.reshape(-1), COUNT)
    order = jnp.argsort(key, stable=True).astype(jnp.int32)
    place = jnp.argsort(order).astype(jnp.int32)
    starts = jnp.searchsorted(key[order], jnp.arange(COUNT + 1), side="left")
    sizes = moe._group_sizes(key, COUNT)
    np.testing.assert_array_equal(sizes, starts[1:] - starts[:-1])
    needed = int(moe._buffers_needed(sizes.sum(), bound))
    assert needed == -(-(2 * ROUTINGS[routing][0] + ROUTINGS[routing][1])
                       // bound)

    padded = -(-assigned // bound) * bound
    order = jnp.concatenate([order, jnp.arange(assigned, padded,
                                               dtype=jnp.int32)])
    keys = jax.random.split(jax.random.PRNGKey(5), 1 + max(needed, 1))
    weights = jax.random.uniform(keys[0], (assigned,), minval=0.1)
    w_sorted = jnp.pad(moe._permuted(weights, place), (0, padded - assigned))
    d_w_sorted, want_d_weights = jnp.zeros(padded), jnp.zeros(assigned)
    for i in range(max(needed, 1)):
        groups, rows_of, w_rows = moe._buffer(i, bound, sizes, order,
                                              w_sorted)
        real = np.asarray(rows_of) < assigned
        np.testing.assert_array_equal(
            np.asarray(w_rows)[real], np.asarray(weights[rows_of])[real])
        d_w_rows = jax.random.normal(keys[1 + i], (bound,))
        d_w_sorted += moe._laid(d_w_rows, i, bound, groups, padded)
        want_d_weights += moe._rows_or_zero(
            d_w_rows, moe._at(place, i, bound, groups))
    d_weights = moe._permuted(d_w_sorted, order)[:assigned]
    np.testing.assert_array_equal(d_weights, want_d_weights)
    assert int((np.asarray(d_weights) != 0).sum()) == min(
        int(sizes.sum()), max(needed, 1) * bound)


# -- the way back to tokens: the kernel against the gathers ------------------


def _planted_at(tokens, top_k, bound, pattern, seed=0):
    """``at`` [K * T] for a buffer of ``bound`` rows, every held assignment
    at a row of its own, in no order. ``mixed``: a token holds none, one,
    all K or a random few of its choices, by ``t % 4``; ``empty_tile``: as
    mixed, but no token of 128 to 255 holds any; ``none``: every ``at`` is
    the bound; ``full``: every row of the buffer is some assignment's."""
    rng = np.random.default_rng(seed)
    t = np.arange(tokens)
    if pattern == "none":
        held = np.zeros((top_k, tokens), bool)
    elif pattern == "full":
        held = np.zeros(top_k * tokens, bool)
        held[rng.permutation(top_k * tokens)[:bound]] = True
        held = held.reshape(top_k, tokens)
    else:
        held = rng.random((top_k, tokens)) < 0.4
        held[:, t % 4 == 0] = False
        one = t[t % 4 == 1]
        held[:, one] = False
        held[rng.integers(0, top_k, len(one)), one] = True
        held[:, t % 4 == 2] = True
        if pattern == "empty_tile":
            held[:, 128:256] = False
    assert held.sum() <= bound
    at = np.full(top_k * tokens, bound, np.int32)
    at[held.reshape(-1)] = rng.permutation(bound)[:held.sum()]
    return jnp.asarray(at)


#: name: (tokens, top_k, d, bound, dtype of the rows, pattern). Tokens of
#: 384 are three grid steps of 128, 128 one.
WAYS_BACK = {
    "mixed": (384, 4, 256, 1024, jnp.bfloat16, "mixed"),
    "mixed_float32": (384, 2, 128, 512, jnp.float32, "mixed"),
    "empty_tile": (384, 4, 128, 1024, jnp.bfloat16, "empty_tile"),
    "none": (384, 2, 128, 512, jnp.bfloat16, "none"),
    "full": (384, 4, 128, 512, jnp.bfloat16, "full"),
    "kimi_width": (128, 8, 2304, 512, jnp.bfloat16, "mixed"),
    "trinity_width": (128, 4, 3072, 512, jnp.bfloat16, "mixed"),
}


def _short_weights(key, shape):
    """Weights of eight significant bits: their product with a bfloat16 is
    exact in float32. The CPU's compiler fuses a product into a sum, in the
    kernel's program and in the gathers' not at the same places, and what
    is held to the bit here is the sum and its order."""
    return jax.random.uniform(key, shape, minval=0.1).astype(
        jnp.bfloat16).astype(jnp.float32)


@pytest.mark.parametrize("weighted", [True, False],
                         ids=["weighted", "unweighted"])
@pytest.mark.parametrize("case", list(WAYS_BACK))
def test_the_kernel_sums_what_the_gathers_sum_to_the_bit(case, weighted):
    """``moe_rows_to_tokens`` (interpreted) is ``_to_tokens_xla`` bit for
    bit: with and without weights, on tokens that hold none, one and all of
    their choices, a grid step with nothing to fetch, a buffer no
    assignment is in and one that is full, rows of bfloat16 (fetched as
    packed pairs) and of float32, at Kimi's and Trinity's widths."""
    tokens, top_k, d, bound, dtype, pattern = WAYS_BACK[case]
    at = _planted_at(tokens, top_k, bound, pattern)
    # float32 rows of eight significant bits too, for the same reason.
    rows = jax.random.normal(jax.random.PRNGKey(1), (bound, d)).astype(
        jnp.bfloat16).astype(dtype)
    weights = (_short_weights(jax.random.PRNGKey(2), (top_k, tokens))
               if weighted else None)
    assert moe._token_tile(rows, at, tokens) == 128
    held = np.asarray(at).reshape(top_k, tokens) < bound
    if pattern in ("mixed", "empty_tile"):
        assert set(np.unique(held.sum(0))) >= {0, 1, top_k}
    if pattern == "empty_tile":
        assert not held[:, 128:256].any() and held[:, 256:].any()
    got = jax.jit(moe._to_tokens, static_argnums=2)(rows, at, tokens, weights)
    want = moe._to_tokens_xla(rows, at, tokens, weights)
    assert got.dtype == want.dtype == jnp.float32
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
    if pattern == "none":
        assert not np.asarray(got).any()


@pytest.mark.parametrize("shape", [
    (96, 128, jnp.bfloat16), (128, 96, jnp.bfloat16),
    (128, 128, jnp.float16), (512, 16384, jnp.bfloat16)],
    ids=["tokens", "width", "dtype", "vmem"])
def test_a_shape_that_does_not_tile_takes_the_gathers(shape):
    """Tokens no multiple of a tile, a width no multiple of 128, a dtype
    the kernel does not unpack: ``_to_tokens`` is the ``jax.numpy`` form,
    chosen on what it is given and by nothing else. Rows so wide that the
    largest tile's output block does not fit take a smaller tile."""
    tokens, d, dtype = shape
    rows = jnp.ones((512, d), dtype)
    at = _planted_at(tokens, 2, 512, "mixed")
    text = str(jax.make_jaxpr(
        lambda rows, at: moe._to_tokens(rows, at, tokens))(rows, at))
    if d == 16384:
        assert moe._token_tile(rows, at, tokens) == 256
        assert "moe_rows_to_tokens" in text
        return
    assert moe._token_tile(rows, at, tokens) is None
    assert "pallas_call" not in text
    np.testing.assert_array_equal(
        np.asarray(moe._to_tokens(rows, at, tokens)),
        np.asarray(moe._to_tokens_xla(rows, at, tokens)))


def _no_tile(rows, at, tokens):
    return None


@pytest.fixture
def gathers_alone(monkeypatch):
    """Call it, and ``_to_tokens`` is the ``jax.numpy`` form at every shape
    until the test ends. ``_buffer_forward`` and ``_buffer_backward`` are
    jitted: what they traced with the other form is dropped both times
    (theirs alone: another test's file may count on what the process has
    compiled)."""
    def forget():
        moe._buffer_forward.clear_cache()
        moe._buffer_backward.clear_cache()

    def switch():
        monkeypatch.setattr(moe, "_token_tile", _no_tile)
        forget()
    yield switch
    monkeypatch.undo()
    forget()


def _short_route_as(picked):
    """The choice planted and weights of eight significant bits (no
    gradient to the router but zeros)."""
    def route(x, router, bias, top_k, scaling, normalize,
              score="sigmoid"):
        weights = _short_weights(jax.random.PRNGKey(3), picked.shape)
        return picked, weights * jnp.sum(router) * 0 + weights, None
    return route


@pytest.mark.parametrize("routing", ["under", "over", "all", "none"])
def test_the_layer_with_the_kernel_is_the_layer_with_the_gathers(
        gathers_alone, routing):
    """``routed_experts(held=...)`` in bfloat16 through the kernel, forward
    (the weighted sum) and backward (``d x``), gives the bits it gives
    through the gathers: on a routing within the bound, on ones whose rows
    lie in a second buffer, and on none; and counts the rows it read. The
    routings are data to the two programs."""
    both, one = ROUTINGS[routing]
    asked = 2 * both + one
    picked = _planted(0, both, one)
    args = [a.astype(jnp.bfloat16) for a in _layer(128, 128)]

    y, aux, grads = _run(moe.routed_experts, args, 0, picked,
                         _short_route_as)
    assert int(aux["rows_summed"]) == asked == int(aux["asked"])
    gathers_alone()
    want_y, want_aux, want_grads = _run(
        moe.routed_experts, args, 0, picked, _short_route_as)
    assert int(want_aux["rows_summed"]) == \
        TOKENS * TOP_K * max(1, -(-asked // BOUND))
    np.testing.assert_array_equal(np.asarray(y, np.float32),
                                  np.asarray(want_y, np.float32))
    for got, want in zip(grads, want_grads):
        np.testing.assert_array_equal(np.asarray(got, np.float32),
                                      np.asarray(want, np.float32))
    assert bool(np.asarray(y, np.float32).any()) == bool(asked)


@pytest.mark.parametrize("widths", [(32, 16), (128, 128)],
                         ids=["gathers", "kernel"])
def test_the_metrics_say_whether_the_kernel_ran(widths):
    """``lm.moe_metrics``' ``moe_rows_summed`` on a routing within the
    bound: ``moe_tokens`` where the way back to tokens was the kernel,
    ``moe_routed`` where it was the gathers."""
    from ray_tpu.models import lm
    x, router, bias, *experts = _layer(*widths)
    layer = dict(zip(("w_gate", "w_up", "w_down"),
                     (w[:COUNT] for w in experts)),
                 router=router, router_bias=bias)
    layer.update({"shared_" + name: w[0] for name, w in zip(
        ("w_gate", "w_up", "w_down"), experts)})
    _, _, aux = lm.expert_ffn(x.reshape(2, TOKENS // 2, -1), layer,
                              top_k=TOP_K, scaling=1.0, normalize=True,
                              held=(0, COUNT))
    assert int(aux["within_bound"]) == 1
    metrics = lm.moe_metrics(
        {name: jnp.stack([value, value]) for name, value in aux.items()},
        TOKENS * TOP_K)
    assert float(metrics["moe_routed"]) == 2 * TOKENS * TOP_K
    assert 0 < float(metrics["moe_tokens"]) < TOKENS * TOP_K
    assert float(metrics["moe_rows_summed"]) == float(
        metrics["moe_tokens" if widths[0] % 128 == 0 else "moe_routed"])
