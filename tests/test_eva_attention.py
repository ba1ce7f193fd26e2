"""EVA attention (``ops/eva.py``) on the flash kernels' tables
(``ops/flash_attention.py`` ``Summaries``): the tables against the closed
form, the three kernels in interpret mode against ``dot`` attention over
the explicit mask with the summaries' cotangents, and the causal and window
tables as they were."""

import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ray_tpu.ops import eva

# ``ray_tpu.ops`` binds the function over the module's name.
fa = sys.modules["ray_tpu.ops.flash_attention"]

# (S, window, chunk, tile): a Q tile that spans two windows (the mask's
# general form), windows of whole tiles (its one-compare form), a window of
# two tiles, and summaries that fill more than one tile.
SHAPES = [(256, 64, 8, 128), (512, 128, 16, 128), (512, 256, 8, 128),
          (1024, 256, 4, 128)]


def _dense_mask(S, window, chunk, blk_q, blk_k):
    """[S, rows + S] bool, tile by tile from the kernels' own mask and
    table: False where the table has no step."""
    rows = fa.summary_rows(S, chunk, blk_k)
    spec = fa.Summaries(window, chunk, rows)
    out = np.zeros((S, rows + S), bool)
    qi_tab, ki_tab = fa._tile_pairs(S, blk_q, blk_k, True, False, spec)
    for qi, ki in zip(qi_tab, ki_tab):
        out[qi * blk_q:(qi + 1) * blk_q, ki * blk_k:(ki + 1) * blk_k] = \
            np.asarray(fa._causal_mask(int(qi), int(ki), blk_q, blk_k, spec))
    return out, rows


@pytest.mark.parametrize("S,window,chunk,blk", SHAPES)
def test_mask_is_the_closed_form(S, window, chunk, blk):
    """Row t allows exactly |L_t| + |R_t| pairs: t - start + 1 keys of its
    own window and start / chunk summaries, none of its own window's chunks
    among them and none at all in window 0; the explicit mask
    (``eva.allowed``) is the same set."""
    mask, rows = _dense_mask(S, window, chunk, blk, blk)
    t = np.arange(S)
    start = t - t % window
    assert (mask[:, rows:].sum(1) == t - start + 1).all()
    assert (mask[:, :rows].sum(1) == start // chunk).all()
    assert not mask[:window, :rows].any()
    j = np.arange(rows)
    own = (j[None, :] * chunk >= start[:, None])
    assert not (mask[:, :rows] & own).any()
    want = np.asarray(eva.allowed(S, window, chunk))
    n = S // chunk
    assert (mask[:, :n] == want[:, :n]).all()
    assert (mask[:, rows:] == want[:, n:]).all()
    assert not mask[:, n:rows].any()


def test_census_at_the_cell_shape():
    census = fa.eva_tile_census(32768, 2048, 16, 512, 512)
    assert (census["local"], census["diagonal"], census["summary"],
            census["executed"]) == (160, 64, 144, 304)
    assert census["pairs"] == 65_028_096
    assert census["causal_pairs"] == 536_887_296
    assert round(census["pairs"] / census["causal_pairs"], 5) == 0.12112
    assert census["summary_pairs"] == 120 * 512 * 512
    # KV-major, every stacked tile has a step: 64 key tiles' and 4 summary
    # tiles' cotangents are all written.
    spec = fa.Summaries(2048, 16, 2048)
    _, ki_tab = fa._tile_pairs(32768, 512, 512, True, True, spec)
    assert sorted(set(ki_tab.tolist())) == list(range(68))
    assert len(ki_tab) == 304


@pytest.mark.parametrize("S,window,blk,executed", [
    (1024, None, 128, 36), (1024, 256, 128, 21), (32768, 4096, 512, 540),
    (16384, 4096, 512, 252)])
def test_causal_and_window_tables_unchanged(S, window, blk, executed):
    """A call without summaries builds the tables it always did."""
    census = fa.window_tile_census(S, window, blk, blk)
    assert census["executed"] == executed
    qi, ki = fa._tile_pairs(S, blk, blk, True, False, window)
    assert len(qi) == executed
    n = S // blk
    want = [(a, b) for a in range(n) for b in range(n)
            if b <= a and (window is None
                           or (b + 1) * blk - 1 > a * blk - window)]
    assert list(zip(qi.tolist(), ki.tolist())) == want


def _inputs(S, chunk, heads=2, dim=32, seed=0):
    keys = jax.random.split(jax.random.PRNGKey(seed), 6)
    q, k, v = (jax.random.normal(key, (1, S, heads, dim), jnp.float32)
               for key in keys[:3])
    phi = jax.random.normal(keys[3], (heads, dim)) * 0.5
    mu = jax.random.normal(keys[4], (heads, dim)) * 0.5
    g = jax.random.normal(keys[5], (1, S, heads, dim), jnp.float32)
    return q, k, v, phi, mu, g


@pytest.mark.parametrize("S,window,chunk,blk", SHAPES[:3])
def test_kernels_match_dot_over_the_explicit_mask(S, window, chunk, blk):
    """Forward, both backward kernels and the way through the pooling:
    out, mass, dq, dk, dv, d phi, d mu, and the summaries' own cotangents
    d kc, d vc."""
    q, k, v, phi, mu, g = _inputs(S, chunk)

    def through(attend):
        def f(q, k, v, phi, mu):
            kc, vc = eva.pool(k, v, phi, mu, chunk)
            out, mass = attend(q, k, v, kc, vc)
            return (out * g).sum(), (out, mass)
        return jax.value_and_grad(f, argnums=(0, 1, 2, 3, 4), has_aux=True)

    kernels = lambda *a: eva.eva_attention(*a, window, chunk, blk, blk)
    dot = lambda *a: eva.dot_eva_attention(*a, window, chunk)
    (_, (out, mass)), grads = through(kernels)(q, k, v, phi, mu)
    (_, (want, want_mass)), want_grads = through(dot)(q, k, v, phi, mu)
    np.testing.assert_allclose(out, want, atol=2e-5)
    np.testing.assert_allclose(mass, want_mass, atol=2e-5)
    assert float(mass[:, :, :window].max()) == 0.0
    assert float(mass[:, :, window:].min()) > 0.0
    for got, ref, name in zip(grads, want_grads, "q k v phi mu".split()):
        np.testing.assert_allclose(got, ref, atol=1e-4, err_msg=name)
        assert float(jnp.abs(ref).max()) > 0

    kc, vc = eva.pool(k, v, phi, mu, chunk)
    got = jax.grad(lambda kc, vc: (kernels(q, k, v, kc, vc)[0] * g).sum(),
                   argnums=(0, 1))(kc, vc)
    ref = jax.grad(lambda kc, vc: (dot(q, k, v, kc, vc)[0] * g).sum(),
                   argnums=(0, 1))(kc, vc)
    for a, b, name in zip(got, ref, ("kc", "vc")):
        np.testing.assert_allclose(a, b, atol=1e-4, err_msg=name)
        # The last window's summaries are seen by nobody.
        assert float(jnp.abs(a[:, -(window // chunk):]).max()) == 0.0
        assert float(jnp.abs(a[:, :window // chunk]).max()) > 0


def test_pool_is_a_softmax_over_the_chunk():
    _, k, v, phi, mu, _ = _inputs(64, 8)
    kc, vc = eva.pool(k, v, phi, mu, 8)
    for j in (0, 5):
        for h in (0, 1):
            rows = slice(8 * j, 8 * j + 8)
            a = jax.nn.softmax(k[0, rows, h] @ phi[h])
            np.testing.assert_allclose(kc[0, j, h], a @ k[0, rows, h]
                                       + mu[h], atol=1e-5)
            np.testing.assert_allclose(vc[0, j, h], a @ v[0, rows, h],
                                       atol=1e-5)


def test_kernel_names_and_no_flash_call():
    from ray_tpu.parallel.collectives import kernel_census
    q, k, v, phi, mu, g = _inputs(256, 8)

    def f(q, k, v, phi, mu):
        kc, vc = eva.pool(k, v, phi, mu, 8)
        return (eva.eva_attention(q, k, v, kc, vc, 64, 8, 128, 128)[0]
                * g).sum()

    census = kernel_census(jax.make_jaxpr(jax.grad(f, (0, 1, 2, 3, 4)))(
        q, k, v, phi, mu))
    assert census == {"eva_fwd": 1, "eva_bwd_dq": 1, "eva_bwd_dkv": 1}


def test_what_the_tables_refuse():
    with pytest.raises(ValueError, match="whole chunks"):
        fa.Summaries(100, 16, 128)
    with pytest.raises(ValueError, match="multiple of 128"):
        eva.eva_attention(*[jnp.zeros((1, 200, 1, 8))] * 3,
                          *[jnp.zeros((1, 25, 1, 8))] * 2, 40, 8)
