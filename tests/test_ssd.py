"""ops/ssd.py: the chunked state-space scan's kernel pair (interpreted on the
CPU) against the chunked ``jax.numpy`` form and against the literal
recurrence, forward and every cotangent, at lengths that are and are not a
multiple of the chunk.

float32 there: the three compute the same sums in another order, over a
few hundred terms, so 1e-5 of each array's largest entry is reassociation
and nothing else. At the bottom the cells' dtype (bfloat16 u, B, C at chunks
of 256) against the chunked form in float32, and what the backward kernel's
program holds. B and C with a group axis (2 and 8 groups of the 16 heads,
and 2 of 32, so that a group is one head block and two) go the same way,
the literal recurrence then ``reference_nemotron_h``'s; one group given
with and without its axis is the same program to the bit.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ray_tpu.ops import ssd as ssd_mod
import reference_granitemoehybrid as reference
import reference_nemotron_h as grouped_reference


def _recurrence(u, dt, A, B, C, D):
    """The reference's literal recurrence from a zero state."""
    state = jnp.zeros(u.shape[:1] + u.shape[2:] + B.shape[-1:])
    return reference._recurrence(state, u, dt, A, B, C, D)[1]

NAMES = ("y", "du", "ddt", "dA", "dB", "dC", "dD")
HEADS, WIDTH, STATE, CHUNK = 16, 64, 128, 128  # two blocks of 8 heads


def _data(seed, batch, seq):
    ks = jax.random.split(jax.random.PRNGKey(seed), 7)
    u = jax.random.normal(ks[0], (batch, seq, HEADS, WIDTH))
    dt = jax.nn.softplus(jax.random.normal(ks[1], (batch, seq, HEADS)) - 2.0)
    A = -jnp.exp(0.5 * jax.random.normal(ks[2], (HEADS,)))
    B = 0.3 * jax.random.normal(ks[3], (batch, seq, STATE))
    C = 0.3 * jax.random.normal(ks[4], (batch, seq, STATE))
    D = jax.random.normal(ks[5], (HEADS,))
    return (u, dt, A, B, C, D), jax.random.normal(ks[6], u.shape)


def _all(fn, args, g):
    """Value and the six cotangents, one compiled program."""
    def run(args, g):
        y, vjp = jax.vjp(fn, *args)
        return (y,) + vjp(g)
    return jax.jit(run)(args, g)


@pytest.fixture(scope="module")
def results():
    """{seq: {path: (y, du, ddt, dA, dB, dC, dD)}} for a length that is a
    multiple of the chunk (three chunks: the kernels) and one that is not
    (``ssd`` takes the chunked form, padded)."""
    out = {}
    for seq in (3 * CHUNK, 200):
        args, g = _data(seq, 2, seq)
        out[seq] = {
            "literal": _all(_recurrence, args, g),
            "chunked": _all(lambda *a: ssd_mod.ssd_chunked(
                *a, chunk=CHUNK), args, g),
            "ssd": _all(lambda *a: ssd_mod.ssd(*a, chunk=CHUNK), args, g)}
    return out


@pytest.mark.parametrize("index", range(len(NAMES)), ids=NAMES)
@pytest.mark.parametrize("against", ["literal", "chunked"])
@pytest.mark.parametrize("seq", [3 * CHUNK, 200])
def test_ssd_matches(results, seq, against, index):
    got, want = results[seq]["ssd"][index], results[seq][against][index]
    assert got.shape == want.shape
    np.testing.assert_allclose(got, want, rtol=0,
                               atol=1e-5 * float(jnp.abs(want).max()))


def test_the_kernels_ran_where_the_shapes_tile(monkeypatch):
    """A multiple of the chunk goes to the kernels, anything else to the
    chunked form: the comparison above is not the oracle with itself."""
    calls = []
    real = ssd_mod._ssd_kernels
    monkeypatch.setattr(ssd_mod, "_ssd_kernels",
                        lambda *a: calls.append(a[0].shape) or real(*a))
    for seq, expected in ((2 * CHUNK, 1), (200, 1)):
        args, _ = _data(0, 1, seq)
        ssd_mod.ssd(*args, chunk=CHUNK)
        assert len(calls) == expected
    assert ssd_mod.heads_per_block(64, 64) == 8
    assert ssd_mod.heads_per_block(4, 64) == 4
    assert ssd_mod.heads_per_block(3, 64) == 0


def test_a_state_is_carried_across_chunks():
    """With slow decay the output late in the sequence depends on the first
    chunk's input: the carried state does the work, not the chunk's own
    quadratic form."""
    (u, dt, A, B, C, D), _ = _data(1, 1, 3 * CHUNK)
    dt, A = 0.01 * jnp.ones_like(dt), -jnp.ones_like(A)
    y = ssd_mod.ssd(u, dt, A, B, C, D, chunk=CHUNK)
    moved = ssd_mod.ssd(u.at[:, :CHUNK].multiply(2.0), dt, A, B, C, D,
                        chunk=CHUNK)
    assert float(jnp.abs(moved - y)[:, 2 * CHUNK:].max()) > 1e-3
    np.testing.assert_allclose(
        moved, _recurrence(u.at[:, :CHUNK].multiply(2.0), dt, A, B, C, D),
        atol=1e-4)


# -- B and C in groups -------------------------------------------------------

GROUPED = {"2 groups": (2, HEADS), "8 groups": (8, HEADS),
           "2 groups, 2 blocks a group": (2, 2 * HEADS)}


def _grouped_data(groups, heads, seq=2 * CHUNK, width=WIDTH):
    ks = jax.random.split(jax.random.PRNGKey(groups + heads), 7)
    u = jax.random.normal(ks[0], (1, seq, heads, width))
    dt = jax.nn.softplus(jax.random.normal(ks[1], (1, seq, heads)) - 2.0)
    A = -jnp.exp(0.5 * jax.random.normal(ks[2], (heads,)))
    B = 0.3 * jax.random.normal(ks[3], (1, seq, groups, STATE))
    C = 0.3 * jax.random.normal(ks[4], (1, seq, groups, STATE))
    D = jax.random.normal(ks[5], (heads,))
    return (u, dt, A, B, C, D), jax.random.normal(ks[6], u.shape)


def _grouped_recurrence(u, dt, A, B, C, D):
    state = jnp.zeros(u.shape[:1] + u.shape[2:] + B.shape[-1:])
    return grouped_reference._recurrence(state, u, dt, A, B, C, D)[1]


@pytest.fixture(scope="module")
def grouped():
    out = {}
    for name, (groups, heads) in GROUPED.items():
        args, g = _grouped_data(groups, heads)
        assert ssd_mod.heads_per_block(heads, WIDTH, groups) == min(
            8, heads // groups)
        out[name] = {
            "literal": _all(_grouped_recurrence, args, g),
            "chunked": _all(lambda *a: ssd_mod.ssd_chunked(
                *a, chunk=CHUNK), args, g),
            "ssd": _all(lambda *a: ssd_mod.ssd(*a, chunk=CHUNK), args, g)}
    return out


@pytest.mark.parametrize("index", range(len(NAMES)), ids=NAMES)
@pytest.mark.parametrize("against", ["literal", "chunked"])
@pytest.mark.parametrize("name", GROUPED)
def test_grouped_ssd_matches(grouped, name, against, index):
    got, want = grouped[name]["ssd"][index], grouped[name][against][index]
    assert got.shape == want.shape
    np.testing.assert_allclose(got, want, rtol=0,
                               atol=1e-5 * float(jnp.abs(want).max()))


def test_a_head_reads_its_own_group():
    """Group 1's B and C replaced by group 0's moves the heads of group 1
    and no head of group 0."""
    (u, dt, A, B, C, D), _ = _grouped_data(2, HEADS)
    y = ssd_mod.ssd(u, dt, A, B, C, D, chunk=CHUNK)
    same = ssd_mod.ssd(u, dt, A, B.at[:, :, 1].set(B[:, :, 0]),
                       C.at[:, :, 1].set(C[:, :, 0]), D, chunk=CHUNK)
    half = HEADS // 2
    assert (same[:, :, :half] == y[:, :, :half]).all()
    assert float(jnp.abs(same - y)[:, :, half:].max()) > 1e-2
    with pytest.raises(ValueError, match="groups"):
        ssd_mod.ssd(u, dt, A, B[:, :, :1].repeat(3, 2), C, D, chunk=CHUNK)


def test_one_group_with_and_without_its_axis_is_one_program(results):
    """granite's call ([batch, S, N]) and the same B and C as [batch, S, 1,
    N]: the kernels' results and every cotangent, bit for bit."""
    args, g = _data(3 * CHUNK, 2, 3 * CHUNK)
    u, dt, A, B, C, D = args
    with_axis = _all(lambda *a: ssd_mod.ssd(*a, chunk=CHUNK),
                     (u, dt, A, B[:, :, None], C[:, :, None], D), g)
    for got, want in zip(with_axis, results[3 * CHUNK]["ssd"]):
        assert (got.reshape(want.shape) == want).all()


# -- the cells' dtype ---------------------------------------------------------

BF16 = {"8 heads of 64, one group": (8, 64, 1),
        "64 heads of 64, eight groups": (64, 64, 8),
        "2 heads of 128": (2, 128, 1)}


@pytest.fixture(scope="module")
def bf16():
    """{case: (kernels on bfloat16 u, B, C; ``ssd_chunked`` in float32 on the
    same values)}, two chunks of 256."""
    out = {}
    for name, (heads, width, groups) in BF16.items():
        (u, dt, A, B, C, D), g = _grouped_data(groups, heads, 2 * 256, width)
        low = jnp.bfloat16
        args = (u.astype(low), dt, A, B.astype(low), C.astype(low), D)
        assert ssd_mod.heads_per_block(heads, width, groups)
        out[name] = (
            _all(lambda *a: ssd_mod.ssd(*a, chunk=256), args, g.astype(low)),
            _all(lambda *a: ssd_mod.ssd_chunked(*a, chunk=256),
                 [a.astype(jnp.float32) for a in args],
                 g.astype(low).astype(jnp.float32)))
    return out


@pytest.mark.parametrize("index", range(len(NAMES)), ids=NAMES)
@pytest.mark.parametrize("name", BF16)
def test_bfloat16_ssd_matches_the_float32_form(bf16, name, index):
    """The kernels round ``C B^T`` times the decay, ``dt u`` and each
    cotangent's operands to bfloat16 (8 bits) before products that
    accumulate in float32: 2 ** -6 of each array's largest entry holds that
    rounding (0.2-0.9 % read) and no wrong term. dA is the one to watch: cum
    is owed a difference of two sums, and made from operands rounded in two
    ways they read 3-14 % off."""
    got, want = (side[index] for side in bf16[name])
    assert got.shape == want.shape
    np.testing.assert_allclose(got.astype(jnp.float32), want, rtol=0,
                               atol=2.0 ** -6 * float(jnp.abs(want).max()))


def test_the_backward_kernel_sums_no_square():
    """Every cotangent of dt and cum is a sum over a head's lanes of
    products a head wide: the kernel's program makes the decay ([128, L]
    tiles of it under an ``exp``) and sums no array of that shape, and it
    returns du, dB, dC, the rows of dt and cum and D's lanes (no columns
    beside the rows)."""
    chunk, heads, width = 256, 8, 64
    shaped = jax.ShapeDtypeStruct
    by_chunk = shaped((1, 2, chunk, heads), jnp.float32)
    wide = shaped((1, 2 * chunk, heads * width), jnp.bfloat16)
    grouped = shaped((1, 2 * chunk, 1, STATE), jnp.bfloat16)
    jaxpr = jax.make_jaxpr(lambda *a: ssd_mod._backward(*a, heads))(
        wide, by_chunk, by_chunk, grouped, grouped,
        shaped((heads,), jnp.float32),
        shaped((1, 2, 1, STATE, heads * width), jnp.float32), wide)

    def equations(jaxpr):
        for eqn in jaxpr.eqns:
            yield eqn
            for sub in jax.core.jaxprs_in_params(eqn.params):
                yield from equations(sub)

    calls = [e for e in equations(jaxpr.jaxpr)
             if e.primitive.name == "pallas_call"]
    assert len(calls) == 1 and len(calls[0].outvars) == 5
    inside = list(equations(calls[0].params["jaxpr"]))
    of_the_decay = {e.invars[0].aval.shape for e in inside
                    if e.primitive.name == "exp"}
    assert (128, chunk) in of_the_decay
    summed = {e.invars[0].aval.shape for e in inside
              if e.primitive.name == "reduce_sum"}
    assert summed and not summed & (of_the_decay | {(chunk, chunk)})
