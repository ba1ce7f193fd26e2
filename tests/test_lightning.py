"""Lightning linear attention (``ops/lightning.py``): the Pallas kernel pair in
interpret mode against the chunked ``jax.numpy`` form against the literal
recurrence, outputs and the cotangents of q, k and v, at slopes from a head
that forgets in a token to one that hardly forgets; the scale, the fallback
for shapes the kernels cannot tile and the kernels' names."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ray_tpu.ops import lightning as op
from ray_tpu.parallel.collectives import kernel_census

# 2^(-8 (h + 1) / 32) at h = 0 and h = 31, the layer factors of the
# published first and last layers beside them.
SLOPES = [0.84, 0.1, 2.0 ** -8, 1e-5]


def _qkv(shape, v_width=None, seed=0, dtype=jnp.float32):
    B, S, H, K = shape
    keys = jax.random.split(jax.random.PRNGKey(seed), 3)
    widths = (K, K, v_width or K)
    return tuple((0.5 * jax.random.normal(k, (B, S, H, w))).astype(dtype)
                 for k, w in zip(keys, widths))


def _slope(heads):
    return jnp.asarray((SLOPES * heads)[:heads], jnp.float32)


def _weighted(fn, slope, **kw):
    """A scalar of ``fn``'s output with a cotangent that differs by position
    and channel."""
    def loss(q, k, v):
        out = fn(q, k, v, slope, **kw).astype(jnp.float32)
        weight = jnp.cos(jnp.arange(out.size, dtype=jnp.float32) * 0.37)
        return (out * weight.reshape(out.shape)).sum()
    return loss


@pytest.fixture(scope="module")
def literal():
    """The recurrence token by token at [1, 512, 2, 128]: output and the
    cotangents of q, k, v."""
    q, k, v = _qkv((1, 512, 2, 128))
    slope = jnp.asarray([0.84, 2.0 ** -8], jnp.float32)
    return {"qkv": (q, k, v), "slope": slope,
            "out": op.lightning_recurrent(q, k, v, slope),
            "grads": jax.grad(_weighted(op.lightning_recurrent, slope),
                              (0, 1, 2))(q, k, v)}


def _close(got, want, tol):
    scale = float(jnp.abs(want).max())
    assert float(jnp.abs(got.astype(jnp.float32) - want).max()) < tol * scale


@pytest.mark.parametrize("chunk", [128, 256])
def test_chunked_is_the_recurrence(literal, chunk):
    q, k, v = literal["qkv"]
    out = op.lightning_chunked(q, k, v, literal["slope"], chunk=chunk)
    _close(out, literal["out"], 1e-5)
    grads = jax.jit(jax.grad(_weighted(
        op.lightning_chunked, literal["slope"], chunk=chunk), (0, 1, 2)))(
        q, k, v)
    for got, want in zip(grads, literal["grads"]):
        _close(got, want, 1e-5)


def test_kernels_are_the_recurrence(literal):
    """Two chunks of 256: the state crosses a chunk's edge forward and its
    cotangent backward."""
    q, k, v = literal["qkv"]
    _close(op.lightning(q, k, v, literal["slope"]), literal["out"], 1e-5)
    grads = jax.grad(_weighted(op.lightning, literal["slope"]),
                     (0, 1, 2))(q, k, v)
    for got, want in zip(grads, literal["grads"]):
        _close(got, want, 1e-5)


@pytest.mark.parametrize("shape,v_width", [
    ((2, 256, 3, 128), None),     # two rows, three heads: a head a block
    ((1, 256, 2, 128), 256),      # a block of two; values twice as wide
], ids=["batch2_heads3", "wide_v"])
def test_kernels_match_chunked(shape, v_width):
    q, k, v = _qkv(shape, v_width, seed=1)
    slope = _slope(shape[2])
    assert op.heads_per_block(shape[2], 128, v_width or 128)
    want = op.lightning_chunked(q, k, v, slope)
    _close(op.lightning(q, k, v, slope), want, 1e-5)
    got = jax.jit(jax.grad(_weighted(op.lightning, slope), (0, 1, 2)))(
        q, k, v)
    ref = jax.jit(jax.grad(_weighted(op.lightning_chunked, slope),
                           (0, 1, 2)))(q, k, v)
    for g, w in zip(got, ref):
        _close(g, w, 1e-5)


@pytest.mark.parametrize("seq", [200, 300])
def test_a_ragged_length_takes_the_chunked_form(seq):
    """No kernel in the program, the tail padded with keys that add nothing:
    the recurrence's own numbers."""
    q, k, v = _qkv((1, seq, 2, 128), seed=2)
    slope = _slope(2)
    jaxpr = jax.make_jaxpr(lambda *a: op.lightning(*a, slope))(q, k, v)
    assert kernel_census(jaxpr) == {}
    _close(op.lightning(q, k, v, slope),
           op.lightning_recurrent(q, k, v, slope), 1e-5)


def test_heads_off_the_lane_width_take_the_chunked_form():
    q, k, v = _qkv((1, 256, 4, 64), seed=3)
    slope = _slope(4)
    assert op.heads_per_block(4, 64, 64) == 0
    jaxpr = jax.make_jaxpr(lambda *a: op.lightning(*a, slope))(q, k, v)
    assert kernel_census(jaxpr) == {}
    _close(op.lightning(q, k, v, slope),
           op.lightning_recurrent(q, k, v, slope), 1e-5)


def test_the_kernels_names():
    q, k, v = _qkv((1, 256, 2, 128))
    slope = _slope(2)
    fwd = jax.make_jaxpr(lambda *a: op.lightning(*a, slope))(q, k, v)
    assert kernel_census(fwd) == {"lightning_fwd": 1}
    both = jax.make_jaxpr(jax.grad(_weighted(op.lightning, slope),
                                   (0, 1, 2)))(q, k, v)
    assert kernel_census(both) == {"lightning_fwd": 1, "lightning_bwd": 1}


@pytest.mark.parametrize("fn", [op.lightning_recurrent, op.lightning_chunked,
                                op.lightning],
                         ids=["recurrent", "chunked", "kernels"])
def test_the_scale_is_one_over_root_k_unless_given(fn):
    """``scale=1`` is sqrt(128) times the default in every form: the term
    the benchmark's comparison cannot see behind the output norm."""
    q, k, v = _qkv((1, 256, 2, 128), seed=4)
    slope = _slope(2)
    plain = fn(q, k, v, slope)
    unscaled = fn(q, k, v, slope, scale=1.0)
    _close(unscaled, plain * np.sqrt(128.0), 1e-5)


@pytest.mark.parametrize("seq", [256, 200], ids=["kernels", "chunked"])
def test_the_slope_takes_no_gradient(seq):
    """A constant of the layer, whichever form ``lightning`` takes."""
    q, k, v = _qkv((1, seq, 2, 128), seed=5)
    grad = jax.grad(lambda s: op.lightning(q, k, v, s).sum())(_slope(2))
    assert float(jnp.abs(grad).max()) == 0.0


@pytest.mark.parametrize("slope", SLOPES)
def test_a_head_remembers_what_its_slope_allows(slope):
    """One key at position 0, queries everywhere: the output at t is
    ``lambda^t`` of the first, through every chunk's edge, without a power
    that under- or overflows."""
    S, K = 512, 128
    q = jnp.ones((1, S, 1, K)) / K
    k = jnp.zeros((1, S, 1, K)).at[0, 0].set(1.0)
    v = jnp.zeros((1, S, 1, K)).at[0, 0].set(1.0)
    out = op.lightning(q, k, v, jnp.asarray([slope], jnp.float32), scale=1.0)
    want = np.exp(-slope * np.arange(S))
    assert np.isfinite(np.asarray(out)).all()
    np.testing.assert_allclose(np.asarray(out[0, :, 0, 0]), want,
                               rtol=1e-4, atol=1e-30)


def test_bfloat16_operands_float32_sums():
    """The kernels on bfloat16 against the float32 recurrence on the same
    rounded inputs: the products' rounding and nothing that grows with S."""
    q, k, v = _qkv((1, 512, 2, 128), seed=6, dtype=jnp.bfloat16)
    slope = _slope(2)
    want = op.lightning_recurrent(*(a.astype(jnp.float32) for a in (q, k, v)),
                                  slope)
    got = op.lightning(q, k, v, slope)
    assert got.dtype == jnp.bfloat16
    _close(got, want, 2e-2)
