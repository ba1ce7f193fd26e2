"""ops/short_conv.py: the double-gated short convolution ``C * conv(B * x)``.
Its ``jax.numpy`` form against the installed ``transformers``'
``Lfm2ShortConv.slow_forward`` (the chunk order B, C, x; the taps' order;
zeros before the first token), and the Pallas pair (interpreted on the CPU)
against that form: outputs and all three gradients, across every tile edge,
with more than one sequence, and the first K - 1 positions by themselves.
Then ``conv_silu``, ``silu(b + conv(x))`` over a slice of a wider array's
columns: its pair against ``silu(causal_conv(x, w, b))``, value and the
three gradients (x, w, b), over taps, bias, dtype, batch and slice, and the
shapes that take the plain form.

Float32 on the CPU: both sides compute the same K-term sums, so 1e-5 of the
largest value leaves room for the order of three additions and nothing else.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ray_tpu.models import lm
from ray_tpu.ops import short_conv as sc


def _inputs(batch, seq, d, taps, dtype=jnp.float32, seed=0):
    ks = jax.random.split(jax.random.PRNGKey(seed), 3)
    bcx = jax.random.normal(ks[0], (batch, seq, 3 * d), jnp.float32)
    w = jax.random.normal(ks[1], (taps, d), jnp.float32) / taps ** 0.5
    dy = jax.random.normal(ks[2], (batch, seq, d), jnp.float32)
    return bcx.astype(dtype), w, dy.astype(dtype)


def _close(got, want, tol=1e-5):
    got, want = (np.asarray(a, np.float64) for a in (got, want))
    assert got.shape == want.shape
    assert np.abs(got - want).max() <= tol * np.abs(want).max()


@pytest.mark.parametrize("taps", [3, 4])
def test_the_plain_form_is_transformers_short_conv(taps):
    """``Lfm2ShortConv.slow_forward`` between its two projections: with
    ``in_proj`` and ``out_proj`` the identity's pieces, what is left is ``C
    * conv(B * x)`` on the chunks in the order B, C, x."""
    torch = pytest.importorskip("torch")
    try:
        from transformers.models.lfm2.configuration_lfm2 import Lfm2Config
        from transformers.models.lfm2.modeling_lfm2 import Lfm2ShortConv
    except ImportError as exc:
        pytest.skip(f"no lfm2 in transformers: {exc}")
    d, seq = 16, 24
    config = Lfm2Config(hidden_size=d, conv_L_cache=taps, conv_bias=False,
                        num_hidden_layers=1, layer_types=["conv"])
    module = Lfm2ShortConv(config, 0).float()
    ks = jax.random.split(jax.random.PRNGKey(1), 4)
    x = jax.random.normal(ks[0], (2, seq, d), jnp.float32)
    w_in = jax.random.normal(ks[1], (d, 3 * d), jnp.float32) / d ** 0.5
    w_out = jax.random.normal(ks[2], (d, d), jnp.float32) / d ** 0.5
    w = jax.random.normal(ks[3], (taps, d), jnp.float32)
    as_torch = lambda a: torch.from_numpy(np.array(a, np.float32))  # noqa: E731
    with torch.no_grad():
        module.in_proj.weight.copy_(as_torch(w_in.T))
        module.out_proj.weight.copy_(as_torch(w_out.T))
        # nn.Conv1d's weight is [channels, 1, K], tap K - 1 on the newest.
        module.conv.weight.copy_(as_torch(w.T[:, None, :]))
        want = module.slow_forward(as_torch(x)).numpy()
    with jax.default_matmul_precision("highest"):
        got = sc.short_conv_xla(x @ w_in, w) @ w_out
    _close(got, want)


def test_the_plain_form_stands_on_causal_conv():
    bcx, w, _ = _inputs(2, 40, 8, 3)
    gate_b, gate_c, x = jnp.split(bcx, 3, axis=-1)
    _close(sc.short_conv_xla(bcx, w),
           gate_c * lm.causal_conv(gate_b * x, w), tol=1e-7)


def _both(batch, seq, d, taps, dtype=jnp.float32):
    bcx, w, dy = _inputs(batch, seq, d, taps, dtype)

    def run(fn):  # value and both cotangents, one compiled program
        def both(bcx, w, dy):
            out, vjp = jax.vjp(fn, bcx, w)
            return out, vjp(dy)
        return jax.jit(both)(bcx, w, dy)

    (got, got_grads), (want, want_grads) = run(sc.short_conv), \
        run(sc.short_conv_xla)
    return (got, want), tuple(zip(got_grads, want_grads))


@pytest.fixture(scope="module")
def tiled():
    """Three tiles of rows and two sequences: every edge of a tile crossed
    from both sides, and a sequence's end next to another's beginning."""
    return _both(2, 3 * sc.ROWS, 128, 3)


def test_the_kernels_run_where_the_shapes_tile():
    bcx, w, _ = _inputs(2, 3 * sc.ROWS, 128, 3)
    text = jax.jit(jax.grad(
        lambda b, w: sc.short_conv(b, w).sum(), argnums=(0, 1))
    ).lower(bcx, w).as_text(debug_info=True)
    assert "short_conv_bwd" in text
    # A sequence that is no whole number of tiles takes the plain form.
    bcx, w, _ = _inputs(1, sc.ROWS + 8, 128, 3)
    assert "short_conv_fwd" not in jax.jit(sc.short_conv).lower(
        bcx, w).as_text(debug_info=True)


def test_kernel_outputs_match_the_plain_form(tiled):
    _close(*tiled[0])


@pytest.mark.parametrize("which", [0, 1], ids=["dbcx", "dw"])
def test_kernel_gradients_match_the_plain_form(tiled, which):
    got, want = tiled[1][which]
    assert got.dtype == want.dtype
    _close(got, want)


def test_each_chunks_cotangent_matches_by_itself(tiled):
    """dB, dC and dx apart: one wrong chunk must not hide behind the
    largest of the three."""
    got, want = tiled[1][0]
    for got_chunk, want_chunk in zip(jnp.split(got, 3, -1),
                                     jnp.split(want, 3, -1)):
        _close(got_chunk, want_chunk)


def test_the_first_positions_see_zeros_not_another_sequence(tiled):
    """Rows 0 .. K - 2 of every sequence reach before the first token, and
    the last K - 1 rows' cotangents reach past the last: both read zeros,
    not the neighbouring sequence of the batch nor the tile's own far
    end."""
    (got, want), ((got_dbcx, want_dbcx), _) = tiled
    _close(got[:, :2], want[:, :2])
    _close(got_dbcx[:, -2:], want_dbcx[:, -2:])
    bcx, w, _ = _inputs(2, 3 * sc.ROWS, 128, 3)
    gate_b, gate_c, x = jnp.split(bcx[:, 0], 3, axis=-1)
    np.testing.assert_allclose(
        sc.short_conv(bcx, w)[:, 0], gate_c * w[2] * gate_b * x, rtol=1e-5)


def test_another_tap_count_and_bfloat16():
    (got, want), grads = _both(1, 2 * sc.ROWS, 128, 4, jnp.bfloat16)
    assert got.dtype == jnp.bfloat16
    _close(got, want, tol=1e-2)
    for g, w in grads:
        _close(g, w, tol=1e-2)


def test_the_dispatch_is_the_one_entry():
    bcx, w, _ = _inputs(1, sc.ROWS, 128, 3)
    _close(lm.short_conv(bcx, w), sc.short_conv_xla(bcx, w))


# -- conv_silu: silu(b + conv(x)) over a slice of columns --------------------

def _plain(x, w, b, start, width):
    """The ``jax.numpy`` form on the slice: ``silu(causal_conv(x, w, b))``."""
    return jax.nn.silu(lm.causal_conv(
        x[..., start:start + width], w, b)).astype(x.dtype)


# Granite's in-projection is z | xBC | dt = 4096 | 4352 | 64 columns: the
# convolution's are 4096 .. 8448 of 8512. Scaled down by 16: 256 | 272 | 4
# would not tile, so the widths here are 256 | 384 | 128 (a tile of 128
# lanes from the third on, as there 256 from the seventeenth on).
WHOLE, SLICE = (128, 0, 128), (768, 256, 384)


@pytest.fixture(scope="module", params=[
    # taps, bias, dtype, batch, (W, start, width)
    (4, False, jnp.float32, 1, WHOLE), (4, True, jnp.float32, 1, WHOLE),
    (2, False, jnp.float32, 2, WHOLE), (2, True, jnp.float32, 2, WHOLE),
    (4, False, jnp.bfloat16, 2, WHOLE), (4, True, jnp.bfloat16, 1, WHOLE),
    (4, True, jnp.float32, 2, SLICE), (4, True, jnp.bfloat16, 1, SLICE),
    (2, False, jnp.float32, 1, SLICE),
], ids=lambda p: "K{}-{}-{}-batch{}-{}".format(
    p[0], "bias" if p[1] else "nobias", p[2].__name__, p[3],
    "whole" if p[4] is WHOLE else "slice"))
def silu_case(request):
    """Three tiles of rows: every edge of a tile crossed from both sides
    and, with two sequences, one's end next to the other's beginning."""
    taps, bias, dtype, batch, (wide, start, width) = request.param
    ks = jax.random.split(jax.random.PRNGKey(2), 4)
    x = jax.random.normal(ks[0], (batch, 3 * sc.ROWS, wide)).astype(dtype)
    w = jax.random.normal(ks[1], (taps, width)) / taps ** 0.5
    b = jax.random.normal(ks[2], (width,)) if bias else None
    dy = jax.random.normal(ks[3], (batch, 3 * sc.ROWS, width)).astype(dtype)

    def run(fn):  # value and the three cotangents, one compiled program
        def both(x, w, b, dy):
            out, vjp = jax.vjp(lambda x, w, b: fn(x, w, b, start, width),
                               x, w, b)
            return out, vjp(dy)
        return jax.jit(both)(x, w, b, dy)

    (got, got_grads), (want, want_grads) = run(sc.conv_silu), run(_plain)
    tol = 1e-5 if dtype == jnp.float32 else 1e-2
    return (got, want), tuple(zip(got_grads, want_grads)), tol, \
        (x, w, b, start, width)


def test_conv_silu_matches_the_plain_form(silu_case):
    (got, want), _, tol, _ = silu_case
    assert got.dtype == want.dtype
    _close(got, want, tol)


@pytest.mark.parametrize("which", [0, 1, 2], ids=["dx", "dw", "db"])
def test_conv_silu_gradients_match_the_plain_form(silu_case, which):
    """dx over every column of the wider array (zeros beside the slice),
    the taps' and the bias's gradient (None where there is none)."""
    _, grads, tol, _ = silu_case
    got, want = grads[which]
    if want is None:
        assert got is None
        return
    assert got.dtype == want.dtype
    _close(got, want, tol)


def test_conv_silu_reaches_across_no_sequence(silu_case):
    """The first K - 1 rows of every sequence see zeros before them, the
    last K - 1 rows' cotangents nothing after them: not the neighbouring
    sequence of the batch, nor the tile's own far end."""
    (got, want), ((got_dx, want_dx), *_), tol, (x, w, b, start, width) = \
        silu_case
    taps = w.shape[0]
    _close(got[:, :taps - 1], want[:, :taps - 1], tol)
    _close(got_dx[:, -taps:], want_dx[:, -taps:], tol)
    first = w[-1] * x[:, 0, start:start + width].astype(jnp.float32) \
        + (0.0 if b is None else b)
    _close(got[:, 0], jax.nn.silu(first), tol)


def _lowered(fn, *args):
    return jax.jit(fn).lower(*args).as_text(debug_info=True)


def test_conv_silu_runs_the_kernels_where_the_shapes_tile():
    x = jnp.zeros((1, 2 * sc.ROWS, 640))
    w, b = jnp.zeros((4, 384)), jnp.zeros((384,))
    fn = lambda x, w, b: lm.conv_silu(x, w, b, 256, 384)  # noqa: E731
    text = _lowered(fn, x, w, b)
    assert "conv_silu_fwd" in text and "short_conv_fwd" not in text
    assert "conv_silu_bwd" in _lowered(jax.grad(
        lambda *args: fn(*args).sum(), argnums=(0, 1, 2)), x, w, b)


@pytest.mark.parametrize("seq,wide,start,width,taps,bias", [
    (sc.ROWS + 8, 128, 0, 128, 4, False),   # no whole number of row tiles
    (sc.ROWS, 192, 0, 192, 4, False),       # channels no multiple of 128
    (sc.ROWS, 448, 64, 384, 4, True),       # a slice off the lane tiles
    (sc.ROWS, 128, 0, 128, 8, True),        # taps and bias past 8 rows
], ids=["rows", "channels", "offset", "taps"])
def test_conv_silu_takes_the_plain_form_where_they_do_not(
        seq, wide, start, width, taps, bias):
    x = jax.random.normal(jax.random.PRNGKey(3), (2, seq, wide))
    w = jnp.ones((taps, width)) / taps
    b = jnp.ones((width,)) if bias else None
    for entry in (sc.conv_silu, lm.conv_silu):
        fn = lambda x, w: entry(x, w, b, start, width)  # noqa: E731
        assert "conv_silu_fwd" not in _lowered(fn, x, w)
        _close(fn(x, w), _plain(x, w, b, start, width))
