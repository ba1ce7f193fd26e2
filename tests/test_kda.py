"""ops/kda.py: the Pallas kernel pair (interpreted) against the chunked
``jax.numpy`` form against the literal recurrence, outputs and every
gradient, all three on q and k as a layer has them (not normalised) and on
the log-decays themselves; at log-decays of -1.6 a step over several
chunks, where a factor ``exp(-cum)`` would overflow float32 inside one
chunk; at a length that is not a multiple of the chunk; with rows of zeros
in q and in k; the chunk's running sum against float64's; and the literal
recurrence against the installed ``transformers``' gated delta rule where
the decay is equal over a head's channels.

Everything runs on the CPU in float32 under the highest matmul precision,
where all three compute the same sums in another order.
"""

from functools import partial

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ray_tpu.ops import kda

ARGS = "qkvab"


def inputs(seed, batch=1, seq=256, heads=2, width=128, strong=False,
           v_width=None, zero_rows=False):
    ks = jax.random.split(jax.random.PRNGKey(seed), 5)
    shape = (batch, seq, heads, width)
    # As a layer's convolutions leave them: rows of any length (0.5 .. 3
    # times the draw's), which the rule brings to 1 and width^-0.5 itself.
    q = jax.random.normal(ks[0], shape) * jnp.linspace(0.5, 3.0, seq)[
        None, :, None, None]
    k = 0.3 * jax.random.normal(ks[1], shape)
    if zero_rows:
        # Rows the 1e-6 floor holds: zeros in q, in k, and in both at once.
        q = q.at[:, 5::17].set(0.0)
        k = k.at[:, 7::17].set(0.0).at[:, 5 + 17].set(0.0)
    v = jax.random.normal(ks[2], shape[:3] + (v_width or width,))
    # The published initialisation's range, -0.001 to -1.6 a step ...
    a = -jnp.exp(jax.random.uniform(ks[3], shape, minval=np.log(1e-3),
                                    maxval=np.log(1.6)))
    if strong:
        # ... and half of a head's channels at its strongest throughout:
        # -102 over a chunk of 64 beside channels that hardly decay.
        a = a.at[:, :, 0, :width // 2].set(-1.6)
    beta = jax.nn.sigmoid(jax.random.normal(ks[4], shape[:3]))
    return q, k, v, a, beta


def both(fn, args, seed=9):
    """(output, gradients of a seeded linear functional of it)."""
    with jax.default_matmul_precision("highest"):
        out = jax.jit(fn)(*args)
        w = jax.random.normal(jax.random.PRNGKey(seed), out.shape)
        grads = jax.jit(jax.grad(lambda *a: (fn(*a) * w).sum(),
                                 argnums=tuple(range(5))))(*args)
    return out, grads


def assert_close(got, want, tol=1e-5):
    assert bool(jnp.isfinite(got).all())
    norm = float(jnp.linalg.norm(want.ravel()))
    assert norm > 0.0
    assert float(jnp.linalg.norm((got - want).ravel())) < tol * norm


def three_ways(**drawn):
    args = inputs(0, strong=True, **drawn)
    return {"recurrent": both(kda.kda_recurrent, args),
            "chunked": both(partial(kda.kda_chunked, chunk=64), args),
            "kernels": both(partial(kda.kda, chunk=64), args)}


@pytest.fixture(scope="module")
def strong():
    """Four chunks of 64 at the strongest decay: kernels, chunked form and
    recurrence."""
    return three_ways()


@pytest.fixture(scope="module")
def zero_rows():
    """The same with rows of zeros in q and in k, which the normalisation
    leaves zeros (their length is held above 1e-6)."""
    return three_ways(zero_rows=True)


DRAWS = pytest.mark.parametrize("draw", ["strong", "zero_rows"])


@DRAWS
@pytest.mark.parametrize("which", ["chunked", "kernels"])
def test_outputs_match_the_recurrence_at_the_strongest_decay(
        draw, which, request):
    found = request.getfixturevalue(draw)
    assert_close(found[which][0], found["recurrent"][0])


@DRAWS
@pytest.mark.parametrize("arg", range(5), ids=list(ARGS))
@pytest.mark.parametrize("which", ["chunked", "kernels"])
def test_gradients_match_the_recurrence_at_the_strongest_decay(
        draw, which, arg, request):
    found = request.getfixturevalue(draw)
    got, want = found[which][1][arg], found["recurrent"][1][arg]
    if draw == "zero_rows" and arg < 2:
        # A zero row's own gradient is the floor's slope, 1e6 times its
        # cotangent: finite, and held on its own so that it does not hide
        # the other rows'.
        at = slice(5, None, 17) if arg == 0 else slice(7, None, 17)
        assert_close(got[:, at], want[:, at])
        got, want = got.at[:, at].set(0.0), want.at[:, at].set(0.0)
    assert_close(got, want)


def test_the_kernels_are_the_chunked_form(strong):
    """One chunk function under both: what the grid carries from chunk to
    chunk, forward and in reverse, is what the scan carries."""
    np.testing.assert_allclose(strong["kernels"][0], strong["chunked"][0],
                               atol=1e-6)
    for got, want in zip(strong["kernels"][1], strong["chunked"][1]):
        np.testing.assert_allclose(
            got, want, atol=1e-5 * float(jnp.abs(want).max()))


def test_the_step_runs_both_kernels():
    from ray_tpu.parallel.collectives import kernel_census
    args = inputs(1, seq=128)
    jaxpr = jax.make_jaxpr(jax.grad(
        lambda *a: kda.kda(*a, chunk=64).sum(), argnums=(0, 1, 2, 3, 4)))(
            *args)
    assert kernel_census(jaxpr) == {"kda_fwd": 1, "kda_bwd": 1}


@pytest.mark.parametrize("chunk", [32, 128])
def test_other_chunks_agree(chunk):
    args = inputs(2, seq=256)
    want = both(kda.kda_recurrent, args)
    got = both(partial(kda.kda, chunk=chunk), args)
    assert_close(got[0], want[0])
    for g, w in zip(got[1], want[1]):
        assert_close(g, w)


def test_a_length_that_is_no_multiple_of_the_chunk():
    """200 positions in chunks of 64: the jax.numpy form, its tail padded
    with steps that decay nothing and write nothing."""
    args = inputs(3, seq=200, strong=True)
    want = both(kda.kda_recurrent, args)
    got = both(partial(kda.kda, chunk=64), args)
    assert got[0].shape == want[0].shape
    assert_close(got[0], want[0])
    for g, w in zip(got[1], want[1]):
        assert_close(g, w)


def test_shapes_that_do_not_tile_take_the_chunked_form():
    """Heads of 32: no kernel call, same numbers."""
    args = inputs(4, seq=128, width=32)
    jaxpr = jax.make_jaxpr(partial(kda.kda, chunk=64))(*args)
    assert "pallas_call" not in str(jaxpr)
    assert_close(both(partial(kda.kda, chunk=64), args)[0],
                 both(kda.kda_recurrent, args)[0])


def test_no_factor_overflows_where_exp_of_minus_cum_would():
    """At -1.6 a step the running sum passes -88 inside one chunk of 64:
    exp(-cum) is inf in float32 there, and every factor the chunk forms
    stays finite (bfloat16 inputs, as the step runs them)."""
    args = inputs(5, seq=128, strong=True)
    cum = jax.vmap(jax.vmap(kda._running_sum, 1, 1))(
        args[3].reshape(2, 64, 2, 128))
    assert float(cum.min()) < -100 and bool(jnp.isinf(jnp.exp(-cum)).any())
    assert float(kda.decay_floor(args[3], 64)) == pytest.approx(
        float(cum.min()), rel=1e-6)
    low = tuple(x.astype(jnp.bfloat16) for x in args[:3]) + args[3:]
    out = jax.jit(partial(kda.kda, chunk=64))(*low)
    grads = jax.jit(jax.grad(lambda *a: kda.kda(*a, chunk=64).astype(
        jnp.float32).sum(), argnums=(0, 1, 2, 3, 4)))(*low)
    assert out.dtype == jnp.bfloat16
    assert all(bool(jnp.isfinite(x.astype(jnp.float32)).all())
               for x in (out,) + grads)
    # bfloat16 inputs take the inverse's products at sixteen bits: output
    # and gradients stay where bfloat16 operands put them, 0.4 %.
    want = jax.jit(kda.kda_recurrent)(*low)
    want_grads = jax.jit(jax.grad(lambda *a: kda.kda_recurrent(*a).sum(),
                                  argnums=(0, 1, 2, 3, 4)))(*low)
    for got, ref in zip((out,) + grads, (want,) + want_grads):
        assert_close(got.astype(jnp.float32), ref, tol=0.02)


@pytest.mark.parametrize("chunk", [64, 128])
@pytest.mark.parametrize("upwards", [False, True], ids=["down", "upwards"])
def test_the_running_sum_to_float32s_last_bits(chunk, upwards):
    """``_running_sum`` (and its cotangent, the same sum from the last row
    upwards) on log-decays of which half are -1.6 a step, against
    ``numpy``'s cumsum in float64: every entry within two units in
    float32's last place of its own size (down to -205 over 128 rows), and
    no further off than ``jnp.cumsum`` in float32 is."""
    a = np.asarray(inputs(8, seq=chunk, strong=True)[3][0, :, 0])
    want = np.cumsum(a[::-1].astype(np.float64), 0)[::-1] if upwards \
        else np.cumsum(a.astype(np.float64), 0)
    if upwards:
        got = jax.vjp(kda._running_sum, jnp.asarray(a))[1](jnp.asarray(a))[0]
        plain = jnp.cumsum(jnp.asarray(a)[::-1], 0)[::-1]
    else:
        got = kda._running_sum(jnp.asarray(a))
        plain = jnp.cumsum(jnp.asarray(a), 0)
    assert got.dtype == jnp.float32 and float(np.abs(want).max()) > 1.5 * chunk
    off = lambda x: np.abs(np.asarray(x, np.float64) - want)
    assert (off(got) <= 2 * np.spacing(np.abs(want).astype(np.float32))).all()
    assert off(got).max() <= off(plain).max()


def test_sixteen_bits_of_a_float32_product():
    """``_mm_16_bits``: each operand as two bfloat16 pieces, three products:
    2^-16 of the exact product's size, values and cotangents."""
    a, b = (jax.random.normal(k, (64, 64)) for k in
            jax.random.split(jax.random.PRNGKey(0)))
    with jax.default_matmul_precision("highest"):
        exact, pull = jax.vjp(jnp.matmul, a, b)
        got, got_pull = jax.vjp(kda._mm_16_bits, a, b)
        pairs = [(got, exact)] + list(zip(got_pull(exact), pull(exact)))
    for found, want in pairs:
        error = float(jnp.abs(found - want).max() / jnp.abs(want).max())
        assert 1e-7 < error < 2 ** -14
    high, low = kda._two_pieces(a)
    assert high.dtype == low.dtype == jnp.bfloat16
    assert float(jnp.abs(high.astype(jnp.float32) + low.astype(jnp.float32)
                         - a).max()) < 2 ** -15 * float(jnp.abs(a).max())


def strongest_lower(chunk, exact):
    """``A`` of the module text for one chunk of ``chunk`` positions of the
    head whose channels decay at their strongest, as ``_chunk`` forms it:
    float32 inputs if ``exact``, else bfloat16 ones."""
    q, k, _, a, beta = (x[0, :, 0] for x in inputs(7, seq=chunk, strong=True))
    dtype = jnp.float32 if exact else jnp.bfloat16
    q, k = (kda._unit_rows(x.astype(dtype), scale).astype(jnp.float32)
            for x, scale in ((q, 128 ** -0.5), (k, 1.0)))
    with jax.default_matmul_precision("highest"):
        _, kk = kda._pair_products(q, k, jnp.cumsum(a, 0), dtype,
                                   *kda._levels(chunk))
    return kk * beta[:, None]


INVERSE_CASES = pytest.mark.parametrize("chunk", [128, 256])
INVERSE_TOLS = pytest.mark.parametrize(
    "exact,tol", [(True, 1e-5), (False, 3e-4)],
    ids=["float32", "sixteen_bits"])


@INVERSE_CASES
@INVERSE_TOLS
def test_the_inverses_cotangent_is_its_own_identitys(chunk, exact, tol):
    """``_unit_lower_inverse`` hands back ``-(T^T ct T^T)`` on the strictly
    lower triangle from the T it made: what ``jax.vjp`` finds by going
    through every level of the doubling, without going through one."""
    lower = strongest_lower(chunk, exact)
    ct = jax.random.normal(jax.random.PRNGKey(8), lower.shape)
    with jax.default_matmul_precision("highest"):
        want, through_levels = jax.vjp(
            partial(kda._inverse_by_levels, exact=exact, block=1), lower)
        got, by_identity = jax.vjp(
            partial(kda._unit_lower_inverse, exact=exact), lower)
        want_ct, got_ct = through_levels(ct)[0], by_identity(ct)[0]
    assert_close(got, want, tol)
    assert_close(got_ct, want_ct, tol)
    assert float(jnp.abs(jnp.triu(got_ct)).max()) == 0.0
    assert "while" not in str(jax.make_jaxpr(by_identity)(ct))
    # ... and only two products of the levels' precision: six of pieces at
    # sixteen bits, against the levels' own and their transposes'.
    dots = lambda pull: str(jax.make_jaxpr(pull)(ct)).count("dot_general")
    assert dots(by_identity) == (2 if exact else 6)
    assert dots(through_levels) > 10 * dots(by_identity)


@INVERSE_CASES
@INVERSE_TOLS
def test_the_blocked_inverse_is_the_doublings(chunk, exact, tol):
    """The diagonal blocks of ``_BLOCK`` rows by substitution and the levels
    from there: the matrix that doubling from single rows gives, and no
    further than it from ``numpy``'s float64 inverse."""
    lower = strongest_lower(chunk, exact)
    with jax.default_matmul_precision("highest"):
        doubled = kda._inverse_by_levels(lower, exact, 1)
        blocked = kda._inverse_by_levels(lower, exact, kda._BLOCK)
        blocks = kda._block_inverses(lower, kda._BLOCK, exact)
    assert_close(blocked, doubled, tol)
    lower64 = np.asarray(lower, np.float64)
    want = np.linalg.inv(np.eye(chunk) + lower64)
    off = lambda x: float(np.linalg.norm(np.asarray(x, np.float64) - want))
    assert off(blocked) < tol * np.linalg.norm(want)
    assert off(blocked) < 2 * off(doubled) + 1e-7 * np.linalg.norm(want)
    # The blocks alone: each the inverse of its own block, zeros between.
    same = np.arange(chunk)[:, None] // kda._BLOCK \
        == np.arange(chunk)[None] // kda._BLOCK
    assert float(jnp.abs(jnp.where(same, 0.0, blocks)).max()) == 0.0
    np.testing.assert_allclose(
        blocks, np.linalg.inv(np.eye(chunk) + np.where(same, lower64, 0.0)),
        atol=1e-6 if exact else 2e-5)


def test_the_backward_kernel_reads_the_forwards_inverse():
    """``kda_fwd`` writes a chunk's inverse beside its entry state and
    ``kda_bwd`` takes it: [batch, H, chunks, L, L] float32 among the
    residuals, and no level of the doubling in the backward kernel, whose
    products are then fewer than the forward kernel's."""
    args = inputs(1, seq=128)
    chunk = 64
    jaxpr = jax.make_jaxpr(jax.grad(
        lambda *a: kda.kda(*a, chunk=chunk).sum(), argnums=(0, 1, 2, 3, 4)))(
            *args)
    calls = {e.params["name"]: e for e in jaxpr.jaxpr.eqns
             if e.primitive.name == "pallas_call"}
    inverses = (1, 2, 128 // chunk, chunk, chunk)
    assert [v.aval.shape for v in calls["kda_fwd"].outvars][-1] == inverses
    assert inverses in [v.aval.shape for v in calls["kda_bwd"].invars]
    assert calls["kda_fwd"].outvars[-1] in calls["kda_bwd"].invars
    dots = {name: str(e.params["jaxpr"]).count("dot_general")
            for name, e in calls.items()}
    assert dots["kda_bwd"] < 3 * dots["kda_fwd"]


def test_a_delta_rule_not_an_additive_state():
    """Writing the same key twice replaces what it read: with beta 1 and no
    decay the second value comes back, not the sum."""
    k = jnp.zeros((1, 2, 1, 128)).at[..., 0].set(1.0)
    v = jnp.stack([jnp.full((1, 1, 128), 1.0), jnp.full((1, 1, 128), 5.0)],
                  axis=1)
    # q = k, which the rule brings to length 128^-0.5.
    out = kda.kda_recurrent(k, k, v, jnp.zeros_like(k), jnp.ones((1, 2, 1)))
    np.testing.assert_allclose(out[0, 1, 0], 5.0 * 128 ** -0.5, rtol=1e-6)
    np.testing.assert_allclose(kda.kda_chunked(
        k, k, v, jnp.zeros_like(k), jnp.ones((1, 2, 1)), chunk=8)[0, 1, 0],
        5.0 * 128 ** -0.5, rtol=1e-5)


def test_the_recurrence_is_transformers_gated_delta_rule():
    """With the decay equal over a head's channels KDA is the gated delta
    rule of the installed ``transformers`` (qwen3_next's torch loop)."""
    torch = pytest.importorskip("torch")
    try:
        from transformers.models.qwen3_next.modeling_qwen3_next import \
            torch_recurrent_gated_delta_rule
    except ImportError as exc:
        pytest.skip(f"no gated delta rule in transformers: {exc}")
    q, k, v, a, beta = inputs(6, batch=2, seq=48, heads=3, width=32)
    g = a[..., 0]                                   # one decay a head
    with jax.default_matmul_precision("highest"):
        got = kda.kda_recurrent(
            q, k, v, jnp.broadcast_to(g[..., None], a.shape), beta)
    as_torch = lambda x: torch.from_numpy(np.array(x, np.float32))
    # It normalises q and k and scales q by width^-0.5 itself, as ours does.
    want, _ = torch_recurrent_gated_delta_rule(
        as_torch(q), as_torch(k), as_torch(v), as_torch(g),
        as_torch(beta), initial_state=None, output_final_state=False,
        use_qk_l2norm_in_kernel=True)
    np.testing.assert_allclose(got, want.numpy(), atol=2e-6)
