"""Block-sparse attention by the model's own scores (``ops/infllm.py``): the
compressed keys, the blocks' scores and the selection against brute force
written out in numpy (rows with fewer blocks than ``topk``, the forced
blocks, a planted tie), the three kernels in interpret mode against ``dot``
attention over the token-level mask, a group's heads on one K/V head, and
the gauges against their closed forms."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ray_tpu.ops import infllm
from ray_tpu.ops.infllm import Sizes
from ray_tpu.parallel.collectives import kernel_census

# S = 512 is 8 blocks of 64 and 31 kernels: a late query keeps 4 blocks, the
# first, the two of its window and one by score.
SMALL = Sizes(kernel=32, stride=16, block=64, topk=4, init_blocks=1,
              window=128)
# Wider kernels and blocks, more forced at the start.
OTHER = Sizes(kernel=64, stride=16, block=128, topk=3, init_blocks=1,
              window=128)


def _qkv(S, H=4, G=2, D=32, seed=0, B=1):
    keys = jax.random.split(jax.random.PRNGKey(seed), 3)
    return (jax.random.normal(keys[0], (B, S, H, D)),
            jax.random.normal(keys[1], (B, S, G, D)),
            jax.random.normal(keys[2], (B, S, G, D)))


def _brute_scores(q, k, sizes):
    """B_g[t, b] token by token in numpy float64; -inf where no kernel of a
    block is visible."""
    q, k = np.asarray(q, np.float64), np.asarray(k, np.float64)
    B, S, H, D = q.shape
    G = k.shape[2]
    n = (S - sizes.kernel) // sizes.stride + 1
    kc = np.stack([k[:, j * sizes.stride:j * sizes.stride + sizes.kernel]
                   .mean(1) for j in range(n)], 1)         # [B, n, G, D]
    blocks = S // sizes.block
    out = np.full((B, G, S, blocks), -np.inf)
    per, ratio = sizes.kernel // sizes.stride, sizes.block // sizes.stride
    for t in range(S):
        seen = [j for j in range(n)
                if j * sizes.stride + sizes.kernel - 1 <= t]
        if not seen:
            continue
        for g in range(G):
            total = np.zeros(len(seen))
            for h in range(g * H // G, (g + 1) * H // G):
                s = kc[0, seen, g] @ q[0, t, h] / np.sqrt(D)
                e = np.exp(s - s.max())
                total += e / e.sum()
            for b in range(blocks):
                mine = [i for i, j in enumerate(seen)
                        if ratio * b - (per - 1) <= j <= ratio * b + ratio - 1]
                if mine:
                    out[0, g, t, b] = total[mine].max()
    return out


def _brute_select(scores, sizes):
    """The forced blocks, then the best by a sort (ties to the lowest
    index), up to ``topk`` among blocks 0 .. own."""
    scores = np.asarray(scores, np.float64)
    B, G, S, blocks = scores.shape
    out = np.zeros(scores.shape, np.int8)
    for t in range(S):
        own = t // sizes.block
        must = {b for b in range(own + 1) if b < sizes.init_blocks
                or b > own - sizes.window // sizes.block}
        for g in range(G):
            rest = sorted((b for b in range(own + 1) if b not in must),
                          key=lambda b: (-scores[0, g, t, b], b))
            kept = list(must) + rest[:max(sizes.topk - len(must), 0)]
            out[0, g, t, kept] = 1
    return out


@pytest.mark.parametrize("sizes", [SMALL, OTHER], ids=["32by16", "64by16"])
def test_compress_is_the_mean_of_every_whole_kernel(sizes):
    _, k, _ = _qkv(256)
    got = np.asarray(infllm.compress(k, sizes))
    n = (256 - sizes.kernel) // sizes.stride + 1
    assert got.shape == (1, n, 2, 32)
    for j in (0, 1, n - 1):
        want = np.asarray(k[:, j * sizes.stride:j * sizes.stride
                            + sizes.kernel]).mean(1)
        np.testing.assert_allclose(got[:, j], want, rtol=1e-5, atol=1e-6)


@pytest.fixture(scope="module", params=[(512, SMALL), (512, OTHER)],
                ids=["32by16", "64by16"])
def scored(request):
    S, sizes = request.param
    q, k, _ = _qkv(S, seed=3)
    got = infllm.block_scores(q, infllm.compress(k, sizes), sizes, rows=128)
    return {"sizes": sizes, "S": S, "got": np.asarray(got),
            "want": _brute_scores(q, k, sizes)}


def test_block_scores_are_the_brute_force(scored):
    """Every visible block's score; -1 exactly where no kernel of a block
    is visible to the query."""
    got, want = scored["got"], scored["want"]
    unseen = np.isinf(want)
    assert (got[unseen] == -1.0).all()
    np.testing.assert_allclose(got[~unseen], want[~unseen], rtol=2e-5,
                               atol=1e-7)
    assert (got[~unseen] >= 0).all()


def test_a_block_takes_the_kernel_that_reaches_in_from_before(scored):
    """All of a row's mass planted on the kernel before block 2's first:
    blocks 1 and 2 both take it, which is what overlapping means."""
    sizes, S = scored["sizes"], scored["S"]
    ratio = sizes.block // sizes.stride
    summed = jnp.zeros((1, 1, 1, S // sizes.stride - sizes.kernel
                        // sizes.stride + 1)).at[..., ratio * 2 - 1].set(1.0)
    pooled = np.asarray(infllm._pooled(summed, sizes, S // sizes.block))
    assert pooled[0, 0, 0, 2] == 1.0 and pooled[0, 0, 0, 1] == 1.0
    assert pooled[0, 0, 0, 3] == 0.0 and pooled[0, 0, 0, 0] == 0.0


def test_select_is_the_brute_force_sort(scored):
    sizes = scored["sizes"]
    got = np.asarray(infllm.select(jnp.asarray(scored["got"]), sizes))
    want = _brute_select(np.where(np.isinf(scored["want"]), -1.0,
                                  scored["got"]), sizes)
    assert (got == want).all()


def test_rows_keep_topk_blocks_or_all_they_have(scored):
    sizes, S = scored["sizes"], scored["S"]
    got = np.asarray(infllm.select(jnp.asarray(scored["got"]), sizes))
    own = np.arange(S) // sizes.block
    assert (got.sum(-1) == np.minimum(own + 1, sizes.topk)).all()
    b = np.arange(S // sizes.block)
    assert not got[..., b[None, :] > own[:, None]].any()


def test_the_forced_blocks_are_always_kept(scored):
    sizes, S = scored["sizes"], scored["S"]
    # Scores that would keep the forced blocks out: they score lowest.
    causal, must = (np.asarray(a) for a in infllm.forced(S, sizes))
    scores = jnp.asarray(np.where(must, 0.0, 1.0 + np.random.default_rng(0)
                                  .random(must.shape)), jnp.float32)
    got = np.asarray(infllm.select(scores[None, None], sizes))[0, 0]
    assert got[must].all()
    assert not got[~causal].any()
    assert must[:, 0].all() and must[np.arange(S), np.arange(S)
                                     // sizes.block].all()


@pytest.mark.parametrize("tied", [(1, 2), (2, 4), (1, 2, 3, 4)])
def test_a_tie_at_the_last_place_falls_to_the_lowest_index(tied):
    """Row t = 511 of ``SMALL`` keeps block 0 and blocks 6, 7 by force and
    one more by score: the tied blocks score highest and equal."""
    S, sizes = 512, SMALL
    scores = np.full((1, 1, S, 8), 0.25, np.float32)
    scores[..., list(tied)] = 0.5
    got = np.asarray(infllm.select(jnp.asarray(scores), sizes))
    assert got[0, 0, 511].tolist() == [
        int(b in (0, 6, 7, min(tied))) for b in range(8)]
    assert (got == _brute_select(scores, sizes)).all()


def test_a_sequence_of_topk_blocks_or_fewer_keeps_every_causal_block():
    S, sizes = 256, SMALL
    got = np.asarray(infllm.select(jnp.zeros((1, 2, S, 4)), sizes))
    own = np.arange(S) // 64
    assert (got[0, 0] == (np.arange(4)[None, :] <= own[:, None])).all()


@pytest.mark.parametrize("sizes,S", [
    (Sizes(kernel=32, stride=12), 512), (Sizes(block=64, window=100), 512),
    (Sizes(), 1000), (Sizes(topk=8, window=512), 1024)],
    ids=["stride", "window", "length", "forced_over_topk"])
def test_sizes_that_do_not_tile_are_refused(sizes, S):
    with pytest.raises(ValueError):
        sizes.check(S)


@pytest.mark.parametrize("S,topk,want", [(512, 4, None), (1024, 4, None),
                                         (16384, 64, 58_335_232)])
def test_selected_pairs_share_is_the_closed_form(S, topk, want):
    """From a selection that keeps ``topk`` blocks a row, whichever: the
    own block counts up to the query, the others whole; 58,335,232 of
    134,225,920 at 16384 with the top 64 of 64."""
    sizes = Sizes(topk=topk, window=128)
    blocks = S // 64
    own = np.arange(S) // 64
    b = np.arange(blocks)
    sel = ((b[None, :] <= own[:, None])
           & (b[None, :] > own[:, None] - topk)).astype(np.int8)
    pairs = sum((min(t // 64 + 1, topk) - 1) * 64 + t % 64 + 1
                for t in range(S))
    assert want is None or pairs == want
    got = float(infllm.selected_pairs_share(jnp.asarray(sel)[None, None],
                                            sizes.block))
    assert abs(got - pairs / (S * (S + 1) / 2)) < 1e-6
    mask = np.asarray(infllm.token_mask(jnp.asarray(sel)[None, None], 64)) \
        if S <= 1024 else None
    assert mask is None or int(mask.sum()) == pairs


# -- the attention over the selection -------------------------------------

def _selection(S, sizes, seed=0, G=2):
    scores = jax.random.uniform(jax.random.PRNGKey(seed),
                                (1, G, S, S // sizes.block))
    return infllm.select(scores, sizes)


def test_dot_attention_is_a_softmax_over_the_selected_keys():
    S, sizes = 256, Sizes(topk=2, window=64)
    q, k, v = _qkv(S, seed=1)
    sel = _selection(S, sizes)
    out, lse = infllm.dot_selected_attention(q, k, v, sel, sizes.block)
    mask = np.asarray(infllm.token_mask(sel, sizes.block)) != 0
    for t, h in ((255, 0), (100, 3), (17, 2)):
        g = h // 2
        s = np.asarray(k[0, :, g]) @ np.asarray(q[0, t, h]) / np.sqrt(32)
        s = np.where(mask[0, g, t], s, -np.inf)
        p = np.exp(s - s.max())
        np.testing.assert_allclose(
            np.asarray(out[0, t, h]), p @ np.asarray(v[0, :, g]) / p.sum(),
            rtol=1e-4, atol=1e-5)
        assert abs(float(lse[0, h, t]) - (np.log(p.sum()) + s.max())) < 1e-4


@pytest.fixture(scope="module", params=[(4, 2, 128), (2, 1, 64)],
                ids=["4on2", "2on1_d64"])
def attended(request):
    """q, k, v at S = 256 with a selection that leaves a tile empty for some
    draws (top 2 of 4 blocks: the first and the query's own), both
    attentions and their cotangents."""
    H, G, D = request.param
    S, sizes = 256, Sizes(topk=2, window=64)
    q, k, v = _qkv(S, H, G, D, seed=2)
    sel = _selection(S, sizes, seed=5, G=G)

    def scalar(fn):
        def loss(q, k, v):
            out = fn(q, k, v)[0]
            weight = jnp.cos(jnp.arange(out.size) * 0.37).reshape(out.shape)
            return (out * weight).sum()
        return loss

    dot = lambda q, k, v: infllm.dot_selected_attention(q, k, v, sel, 64)
    kernels = lambda q, k, v: infllm.selected_attention(q, k, v, sel, 64,
                                                        128, 128)
    return {"dot": dot(q, k, v), "kernels": kernels(q, k, v), "sel": sel,
            "dot_grads": jax.jit(jax.grad(scalar(dot), (0, 1, 2)))(q, k, v),
            "kernel_grads": jax.jit(jax.grad(scalar(kernels), (0, 1, 2)))(
                q, k, v)}


def test_kernels_match_dot_attention(attended):
    for got, want in zip(attended["kernels"], attended["dot"]):
        assert float(jnp.abs(got - want).max()) < 2e-5 * max(
            1.0, float(jnp.abs(want).max()))


def test_kernels_cotangents_match_dot_attention(attended):
    """dq a query head, dk and dv summed over a group's heads."""
    for got, want in zip(attended["kernel_grads"], attended["dot_grads"]):
        assert got.shape == want.shape
        assert float(jnp.abs(got - want).max()) < 1e-4 * float(
            jnp.abs(want).max())


def test_live_tiles_are_flagged(attended):
    live = np.asarray(infllm.live_tiles(attended["sel"], 64, 128, 128))
    assert live.shape == (2, 2)
    assert live.diagonal().all() and live[:, 0].all()   # own and first block
    share = float(infllm.live_tile_share(attended["sel"], 64, 128, 128))
    assert abs(share - live[np.tril_indices(2)].mean()) < 1e-6
    # A selection of the own block alone leaves every tile off the
    # diagonal empty.
    own = jnp.asarray(np.eye(4, dtype=np.int8)[np.arange(256) // 64])
    alone = np.asarray(infllm.live_tiles(own[None, None], 64, 128, 128))
    assert (alone == np.eye(2, dtype=bool)).all()


def test_the_kernels_names():
    S, sizes = 256, Sizes(topk=2, window=64)
    q, k, v = _qkv(S, D=128)
    sel = _selection(S, sizes)
    fn = lambda q, k, v: infllm.selected_attention(
        q, k, v, sel, 64, 128, 128)[0].sum()
    fwd = jax.make_jaxpr(fn)(q, k, v)
    assert kernel_census(fwd) == {"sala_fwd": 1}
    both = jax.make_jaxpr(jax.grad(fn, (0, 1, 2)))(q, k, v)
    assert kernel_census(both) == {"sala_fwd": 1, "sala_bwd_dq": 1,
                                   "sala_bwd_dkv": 1}


@pytest.mark.parametrize("S,Dv,want", [(16384, 128, True), (16384, 256, False),
                                       (2048, 128, False)])
def test_the_forwards_outputs_are_kept_from_topk_blocks_of_keys(S, Dv, want):
    """``worth_keeping`` asked with the 64 x 64 keys a query sees."""
    assert infllm.keeps_forward(S, Dv, Sizes()) is want


def test_free_mass_is_zero_where_nothing_is_free():
    """Every selected block forced (top 3 = the first and a window of two):
    no mass on a block chosen by score; with a fourth kept by score, some."""
    S = 512
    q, k, _ = _qkv(S, seed=4)
    forced_only = Sizes(topk=3, window=128)
    sel = _selection(S, forced_only)
    assert float(infllm.free_mass(q, k, sel, forced_only)) == 0.0
    sel = _selection(S, SMALL)
    mass = float(infllm.free_mass(q, k, sel, SMALL))
    assert 0.05 < mass < 0.6
