"""ops/gated_norm.py: the gate and the RMSNorm behind a recurrence. The
Pallas pair (interpreted on the CPU) against its ``jax.numpy`` form, which is
the layers' own code moved: both published orders (Mamba-2's ``RMSNorm(x *
silu(z))`` over the whole row, Kimi Delta Attention's ``RMSNorm(x) *
sigmoid(z)`` over a head), the output and all three cotangents, float32 and
bfloat16 operands, across every tile edge and with more than one sequence,
the gate's argument read out of a wider array; the shapes that take the
plain form; and that each term a fast path could lose moves the results by
far more than the agreement allows.

Float32 on the CPU: both sides compute the same sums in another order, so
2e-5 of the largest value leaves room for that (the sum over 512 rows of ``d
scale``) and nothing else. With bfloat16 operands both sides round the same
float32 values once, and differ by a rounding where those straddle one.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ray_tpu.models import lm
from ray_tpu.ops import gated_norm as gn
from ray_tpu.parallel.collectives import kernel_census

EPS = 1e-3
#: (gate_first, activation, group of a width of 256): granite's, Kimi's.
FORMS = {"gate_then_norm": (True, "silu", 256),
         "norm_then_gate": (False, "sigmoid", 128)}


def _inputs(form, dtype=jnp.float32, seq=4 * gn.ROWS, wide=256):
    """x, z, scale, d out: rows of very different sizes, so that ``eps``
    counts in some and not in others."""
    group = FORMS[form][2]
    ks = jax.random.split(jax.random.PRNGKey(7), 5)
    size = jnp.exp(2.0 * jax.random.normal(ks[4], (2, seq, 1))) * 0.05
    x = jax.random.normal(ks[0], (2, seq, 256), jnp.float32) * size
    z = jax.random.normal(ks[1], (2, seq, wide), jnp.float32)
    scale = 1.0 + 0.3 * jax.random.normal(ks[2], (group,), jnp.float32)
    dout = jax.random.normal(ks[3], (2, seq, 256), jnp.float32)
    return x.astype(dtype), z.astype(dtype), scale, dout.astype(dtype)


def _plain(form, eps=EPS, flipped=False):
    gate_first, activation, _ = FORMS[form]

    def fn(x, z, scale):
        return gn.gated_norm_xla(x, z[..., :x.shape[2]], scale, eps,
                                 gate_first != flipped, activation)
    return fn


def _entry(form, entry=gn.gated_norm):
    gate_first, activation, _ = FORMS[form]

    def fn(x, z, scale):
        return entry(x, z, scale, EPS, gate_first=gate_first,
                     activation=activation)
    return fn


def _close(got, want, tol=2e-5):
    got, want = (np.asarray(a, np.float64) for a in (got, want))
    assert got.shape == want.shape
    assert np.abs(got - want).max() <= tol * np.abs(want).max()


def _both(fn, x, z, scale, dout):
    """(out, dx, dz, d scale) of ``fn``."""
    out, vjp = jax.vjp(fn, x, z, scale)
    return (out,) + vjp(dout)


CASES = {
    "float32": (jnp.float32, 256, 2e-5),
    # One rounding of a bfloat16 result: 2^-8 of its size.
    "bfloat16": (jnp.bfloat16, 256, 2 ** -7),
    "z_in_a_wider_array": (jnp.float32, 640, 2e-5),
}


@pytest.fixture(scope="module", params=[
    (form, case) for form in FORMS for case in CASES],
    ids=lambda p: "-".join(p))
def pair(request):
    form, case = request.param
    dtype, wide, tol = CASES[case]
    args = _inputs(form, dtype, wide=wide)
    return _both(_entry(form), *args), _both(_plain(form), *args), tol


@pytest.mark.parametrize("which", range(4), ids=["out", "dx", "dz", "dscale"])
def test_the_kernels_match_the_plain_form(pair, which):
    got, want, tol = pair
    assert got[which].dtype == want[which].dtype
    _close(got[which], want[which], tol)


@pytest.mark.parametrize("form", FORMS)
def test_the_kernels_run_where_the_shapes_tile(form):
    x, z, scale, dout = _inputs(form)
    for entry in (gn.gated_norm, lm.gated_norm):
        fn = _entry(form, entry)
        assert kernel_census(jax.make_jaxpr(fn)(x, z, scale)) == {
            "gated_norm_fwd": 1}
        assert kernel_census(jax.make_jaxpr(
            lambda *args: _both(fn, *args)[1:])(x, z, scale, dout)) == {
            "gated_norm_fwd": 1, "gated_norm_bwd": 1}


@pytest.mark.parametrize("seq,width,group,wide,dtype", [
    (gn.ROWS + 8, 256, 128, 256, jnp.float32),   # no whole row tiles
    (gn.ROWS, 192, 64, 192, jnp.float32),        # a group off the lanes
    (gn.ROWS, 256, 128, 256, jnp.bfloat16),      # z in another dtype
], ids=["rows", "lanes", "dtype"])
def test_the_plain_form_where_they_do_not(seq, width, group, wide, dtype):
    ks = jax.random.split(jax.random.PRNGKey(3), 2)
    x = jax.random.normal(ks[0], (2, seq, width), jnp.float32)
    z = jax.random.normal(ks[1], (2, seq, wide), dtype)
    scale = jnp.ones((group,))
    for entry in (gn.gated_norm, lm.gated_norm):
        fn = lambda x, z, scale: entry(  # noqa: E731
            x, z, scale, EPS, gate_first=False, activation="sigmoid")
        assert kernel_census(jax.make_jaxpr(fn)(x, z, scale)) == {}
        _close(fn(x, z, scale), gn.gated_norm_xla(
            x, z[..., :width], scale, EPS, False, "sigmoid"),
            tol=1e-6)


def test_an_activation_it_does_not_know_is_an_error():
    x = jnp.zeros((1, gn.ROWS, 128))
    with pytest.raises(ValueError, match="silu or sigmoid"):
        gn.gated_norm(x, x, jnp.ones((128,)), EPS, gate_first=True,
                      activation="gelu")


def _without_the_mean_term(form):
    """The plain form with ``d pre = rstd * s``: the norm's backward without
    ``- prehat * mean(s * prehat)``, as if ``rstd`` were a constant."""
    gate_first, activation, group = FORMS[form]

    def fn(x, z, scale):
        gate = getattr(jax.nn, activation)(z)
        pre = x * gate if gate_first else x
        pre = pre.reshape(x.shape[:2] + (-1, group))
        rstd = jax.lax.stop_gradient(
            jax.lax.rsqrt((pre ** 2).mean(-1, keepdims=True) + EPS))
        out = (pre * rstd * scale).reshape(x.shape)
        return out if gate_first else out * gate
    return fn


@pytest.mark.parametrize("dropped", ["gate", "mean_term", "eps", "order"])
@pytest.mark.parametrize("form", FORMS)
def test_a_dropped_term_shows(form, dropped):
    """Each of the terms a fast path could lose moves the output or a
    cotangent by far more than the agreement above allows: the kernels are
    no nearer to the function that lacks it than 100 times that."""
    args = _inputs(form)
    got = _both(_entry(form), *args)
    _close(got[1], _both(_plain(form), *args)[1])
    if dropped == "gate":
        x, z, scale, dout = args
        faulty = _both(_plain(form), x, jnp.full_like(z, 30.0), scale, dout)
    elif dropped == "mean_term":
        faulty = _both(_without_the_mean_term(form), *args)
    elif dropped == "eps":
        faulty = _both(_plain(form, eps=0.0), *args)
    else:
        faulty = _both(_plain(form, flipped=True), *args)
    with pytest.raises(AssertionError):
        _close(got[1], faulty[1], tol=2e-3)


def test_a_row_of_zeros_is_zeros_and_hands_back_no_nan():
    x, z, scale, dout = _inputs("norm_then_gate")
    x = x.at[:, :3].set(0.0)
    out, dx, dz, dscale = _both(_entry("norm_then_gate"), x, z, scale, dout)
    assert not np.asarray(out[:, :3]).any()
    for a in (out, dx, dz, dscale):
        assert np.isfinite(np.asarray(a)).all()
