"""ops/selective_scan.py: Mamba-1's selective scan as a kernel pair
(interpreted on the CPU) against its ``jax.numpy`` form (a ``lax.scan`` over
time) and against a literal Python loop over tokens, forward and all six
cotangents, at a length that is a multiple of the chunk (two chunks: the
state and its cotangent cross a chunk border) and one that is not (refused
by the kernels, not padded: ``selective_scan`` takes the ``jax.numpy`` form).

float32 throughout: the three compute the same sums in another order, over a
few hundred terms, so 1e-5 of each array's largest entry is reassociation
and nothing else.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ray_tpu.ops import selective_scan as op

NAMES = ("y", "dxs", "ddelta", "dA", "dB", "dC", "dD")
CHANNELS, STATE, CHUNK = 384, 16, 128  # three blocks of 128 channels


@pytest.fixture(autouse=True, scope="module")
def chunks_of_128():
    """The kernels take chunks of ``CHUNK`` here, not the module's 256: two
    chunks in 256 tokens, which the interpreter walks in seconds."""
    with pytest.MonkeyPatch.context() as patch:
        patch.setattr(op, "CHUNK", CHUNK)
        yield


def _literal(xs, delta, A, B, C, D):
    """The recurrence token by token, from a zero state: a compiled loop
    over t (``fori_loop``) of the literal arithmetic."""
    def token(t, carry):
        state, ys = carry
        state = jnp.exp(delta[:, t, :, None] * A) * state \
            + (delta[:, t] * xs[:, t])[..., None] * B[:, t, None, :]
        return state, ys.at[:, t].set(
            (state * C[:, t, None, :]).sum(-1) + D * xs[:, t])
    return jax.lax.fori_loop(
        0, xs.shape[1], token,
        (jnp.zeros((xs.shape[0],) + A.shape), jnp.zeros_like(xs)))[1]


def _data(seed, batch, seq, channels=CHANNELS):
    ks = jax.random.split(jax.random.PRNGKey(seed), 7)
    xs = jax.random.normal(ks[0], (batch, seq, channels))
    delta = jax.nn.softplus(jax.random.normal(ks[1], xs.shape) - 2.0)
    A = -jnp.exp(0.5 * jax.random.normal(ks[2], (channels, STATE)))
    B = jax.random.normal(ks[3], (batch, seq, STATE))
    C = jax.random.normal(ks[4], (batch, seq, STATE))
    D = jax.random.normal(ks[5], (channels,))
    return (xs, delta, A, B, C, D), jax.random.normal(ks[6], xs.shape)


def _all(fn, args, g):
    """Value and the six cotangents, one compiled program."""
    def run(args, g):
        y, vjp = jax.vjp(fn, *args)
        return (y,) + vjp(g)
    return jax.jit(run)(args, g)


@pytest.fixture(scope="module")
def results():
    """{seq: {path: (y, dxs, ddelta, dA, dB, dC, dD)}}."""
    out = {}
    for seq in (2 * CHUNK, 72):
        args, g = _data(seq, 1, seq)
        out[seq] = {
            "literal": _all(_literal, args, g),
            "scan": _all(op.selective_scan_xla, args, g),
            "selective_scan": _all(op.selective_scan, args, g)}
    return out


@pytest.mark.parametrize("index", range(len(NAMES)), ids=NAMES)
@pytest.mark.parametrize("against", ["literal", "scan"])
@pytest.mark.parametrize("seq", [2 * CHUNK, 72])
def test_selective_scan_matches(results, seq, against, index):
    got = results[seq]["selective_scan"][index]
    want = results[seq][against][index]
    assert got.shape == want.shape
    np.testing.assert_allclose(got, want, rtol=0,
                               atol=1e-5 * float(jnp.abs(want).max()))


@pytest.mark.parametrize("seq, channels, kernels", [
    (2 * CHUNK, CHANNELS, 1), (200, CHANNELS, 0), (2 * CHUNK, 192, 0)],
    ids=["tiles", "odd-length", "odd-width"])
def test_the_kernels_run_where_the_shapes_tile(monkeypatch, seq, channels,
                                               kernels):
    """A multiple of the chunk over whole lane tiles of channels goes to the
    kernels, anything else to the ``jax.numpy`` form, unpadded: the
    comparison above is not the oracle with itself."""
    calls = []
    real = op._kernels
    monkeypatch.setattr(op, "_kernels",
                        lambda *a: calls.append(a[0].shape) or real(*a))
    args, _ = _data(0, 1, seq, channels)
    y = op.selective_scan(*args)
    assert len(calls) == kernels and y.shape == args[0].shape


def test_bfloat16_crosses_hbm_and_float32_carries_the_state():
    """bfloat16 ``xs`` and ``delta`` give a bfloat16 ``y`` and bfloat16
    cotangents of the two, within bfloat16's rounding of the float32
    result on the same (rounded) inputs: the state and the products inside
    are float32."""
    args, g = _data(3, 1, 2 * CHUNK)
    bf = jnp.bfloat16
    low = tuple(a.astype(bf) for a in args[:2]) + args[2:]
    rounded = tuple(a.astype(jnp.float32) for a in low)
    got = _all(op.selective_scan, low, g.astype(bf))
    want = _all(op.selective_scan_xla, rounded,
                g.astype(bf).astype(jnp.float32))
    assert [a.dtype for a in got[:3]] == [bf, bf, bf]
    for name, a, b in zip(NAMES, got, want):
        err = float(jnp.abs(a.astype(jnp.float32) - b).max())
        assert err <= 2.0 ** -7 * float(jnp.abs(b).max()), name


def test_exp_is_of_the_step_itself_and_nothing_overflows():
    """Steps large enough that a running sum of ``delta A`` over a chunk
    would pass float32's exp range (-2000 a chunk) leave every value and
    cotangent finite: the kernels take exp of ``delta_t A`` alone."""
    (xs, delta, A, B, C, D), g = _data(4, 1, 2 * CHUNK)
    args = (xs, delta + 1.0, 16.0 * A, B, C, D)
    for a, b in zip(_all(op.selective_scan, args, g),
                    _all(op.selective_scan_xla, args, g)):
        assert bool(jnp.isfinite(a).all())
        np.testing.assert_allclose(a, b, rtol=0,
                                   atol=1e-5 * float(jnp.abs(b).max()))


def test_decay_floor_is_the_least_step_times_rate():
    (_, delta, A, *_), _ = _data(5, 2, 64)
    want = (delta[..., None] * A).min()
    np.testing.assert_allclose(op.decay_floor(delta, A), want, rtol=1e-6)
