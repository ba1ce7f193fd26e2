"""Ask the TPU compiler, without a TPU: the main path's kernels at the
published widths and at every benchmark cell's shapes, each compiled alone
for one chip of a described ``v5e:2x2`` topology.
``tests/test_chip_compile.py`` has why such compiles exist and how they are
steered; this file is apart from it so that the kernels are another
worker's than the cells' steps.
"""

import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from chip_compile import (CELL_ATTENTION, MLA_SHAPE, MLA_V,  # noqa: F401
                          SHARE_SHAPES, _attend, _attend_loss,
                          _cell_attention, _qkv, compile_for_tpu, flash_mod,
                          topo)


# (B, S, H, D) of every head width the dense presets use, at the recorded
# single-chip batch sizes.
PRESET_SHAPES = {
    "gpt-1.3b": (12, 1024, 16, 128),
    "gpt-410m": (18, 1024, 16, 64),
    "gpt-2.7b": (8, 1024, 32, 80),
    "gptj-6b": (1, 2048, 16, 256),
}


@pytest.mark.parametrize("preset", PRESET_SHAPES)
def test_flash_forward_compiles(topo, preset):
    text = jax.jit(_attend).lower(
        *_qkv(topo, PRESET_SHAPES[preset])).compile().as_text()
    assert text.count("tpu_custom_call") >= 1


@pytest.mark.parametrize("preset", PRESET_SHAPES)
def test_flash_backward_compiles(topo, preset):
    """The forward and the one backward kernel: two Mosaic calls."""
    from ray_tpu.parallel.collectives import kernel_census
    text = jax.jit(jax.grad(_attend_loss, argnums=(0, 1, 2))).lower(
        *_qkv(topo, PRESET_SHAPES[preset])).compile().as_text()
    assert kernel_census(text) == {"flash_fwd": 1, "flash_bwd": 1}


@pytest.mark.parametrize("shape,v_dim", [
    (MLA_SHAPE, MLA_V), ((2, 8192, 16, 256), 256)])
def test_flash_compiles_at_8k_with_two_head_sizes(topo, shape, v_dim):
    """S = 8192: K and V (in the backward kernel Q and dO) of a head are
    2-4 MB each and came whole into VMEM before they were streamed by the
    grid; q/k of 192 beside v of 128 is latent attention, 256 | 256 GPT-J
    at four times its context."""
    from ray_tpu.parallel.collectives import kernel_census
    grads = jax.jit(jax.grad(_attend_loss, argnums=(0, 1, 2))).lower(
        *_qkv(topo, shape, v_dim)).compile()
    assert kernel_census(grads.as_text()) == {"flash_fwd": 1, "flash_bwd": 1}


def test_grouped_matmul_compiles_at_the_published_widths(topo):
    """The expert layer's grouped matmul at Moonlight's widths: the megablox
    kernels, forward (gmm) and both cotangents (gmm, tgmm), inside the
    scoped VMEM at the tile sizes ops/moe.py picks."""
    from ray_tpu.ops import moe
    rows, d, f, experts = 98304, 2048, 1408, 64
    one_chip = SingleDeviceSharding(topo.devices[0])
    args = (jax.ShapeDtypeStruct((rows, d), jnp.bfloat16, sharding=one_chip),
            jax.ShapeDtypeStruct((experts, d, f), jnp.bfloat16,
                                 sharding=one_chip),
            jax.ShapeDtypeStruct((experts,), jnp.int32, sharding=one_chip))

    def loss(x, w, sizes):
        return moe.grouped_matmul(x, w, sizes).astype(jnp.float32).sum()

    text = jax.jit(jax.grad(loss, argnums=(0, 1))).lower(
        *args).compile().as_text()
    assert text.count("tpu_custom_call") >= 2  # dlhs and drhs


@pytest.mark.parametrize("backward", [False, True],
                         ids=["ssd_fwd", "ssd_fwd_and_bwd"])
def test_state_space_scan_compiles_at_the_published_widths(topo, backward):
    """granite-4.0-h-micro's Mamba-2 layer at 32k tokens: 64 heads of 64, a
    state of 128, chunks of 256 (ops/ssd.py). Slices at 64 of a tile's 128
    lanes, columns broadcast from a lane, the states of all heads in VMEM
    scratch: what the interpreter lets through and Mosaic may not."""
    from ray_tpu.ops import ssd
    one_chip = SingleDeviceSharding(topo.devices[0])
    batch, seq, heads, width, state = 1, 32768, 64, 64, 128

    def arg(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    args = (arg((batch, seq, heads, width), jnp.bfloat16),
            arg((batch, seq, heads), jnp.float32), arg((heads,), jnp.float32),
            arg((batch, seq, state), jnp.bfloat16),
            arg((batch, seq, state), jnp.bfloat16), arg((heads,), jnp.float32))

    def scan(*a):
        return ssd.ssd(*a, chunk=256)

    def loss(*a):
        return scan(*a).astype(jnp.float32).sum()

    fn = jax.grad(loss, argnums=tuple(range(6))) if backward else scan
    text = jax.jit(fn).lower(*args).compile().as_text()
    assert text.count("tpu_custom_call") == (2 if backward else 1)


@pytest.mark.parametrize("backward", [False, True],
                         ids=["selective_scan_fwd",
                              "selective_scan_fwd_and_bwd"])
def test_selective_scan_compiles_at_the_published_widths(topo, backward):
    """Phi-4-mini-flash-reasoning's Mamba-1 layer at 16k tokens: 5120
    channels of 16 states, chunks of 256 (ops/selective_scan.py). A row
    spread over the sublanes from a dynamic offset, a column spread over the
    lanes after a dynamic rotation, the chunk's states [256, 16, channels]
    in VMEM scratch: what the interpreter lets through and Mosaic may
    not."""
    from ray_tpu.ops import selective_scan as op
    from ray_tpu.parallel.collectives import kernel_census
    one_chip = SingleDeviceSharding(topo.devices[0])
    batch, seq, channels, state = 1, 16384, 5120, 16

    def arg(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    args = (arg((batch, seq, channels), jnp.bfloat16),
            arg((batch, seq, channels), jnp.bfloat16),
            arg((channels, state), jnp.float32),
            arg((batch, seq, state), jnp.bfloat16),
            arg((batch, seq, state), jnp.bfloat16),
            arg((channels,), jnp.float32))

    def loss(*a):
        return op.selective_scan(*a).astype(jnp.float32).sum()

    fn = jax.grad(loss, argnums=tuple(range(6))) if backward \
        else op.selective_scan
    text = jax.jit(fn).lower(*args).compile().as_text()
    assert kernel_census(text) == (
        {"selective_scan_fwd": 1, "selective_scan_bwd": 1} if backward
        else {"selective_scan_fwd": 1})


@pytest.mark.parametrize("backward", [False, True],
                         ids=["kda_fwd", "kda_fwd_and_bwd"])
def test_delta_rule_compiles_at_the_published_widths(topo, backward):
    """Kimi-Linear-48B-A3B's KDA layer at the cell's 16k tokens: 32 heads
    with keys and values of 128, chunks of ``kda.CHUNK`` (ops/kda.py), on
    q, k in bfloat16 as the convolutions leave them and the log-decays
    themselves. Rows brought to unit length by a lane reduction and the
    running sum of ``a`` as seven shifts of the chunk's rows (three of them
    inside a sublane tile) with their adds, both differentiated in the
    backward kernel; blocks of rows reshaped by sublane tiles, the diagonal
    blocks' inverses side by side in two registers, a forward kernel that
    writes each chunk's inverse [128, 128] beside its entry state, and a
    backward kernel that is the chunk's function differentiated inside the
    kernel given that inverse: what the interpreter lets through and Mosaic
    may not."""
    from ray_tpu.ops import kda
    from ray_tpu.parallel.collectives import kernel_census
    one_chip = SingleDeviceSharding(topo.devices[0])
    batch, seq, heads, width = 1, 16384, 32, 128

    def arg(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    wide = (batch, seq, heads, width)
    args = (arg(wide, jnp.bfloat16),) * 3 + (
        arg(wide, jnp.float32), arg(wide[:3], jnp.float32))

    def loss(*a):
        return kda.kda(*a).astype(jnp.float32).sum()

    fn = jax.grad(loss, argnums=tuple(range(5))) if backward else kda.kda
    text = jax.jit(fn).lower(*args).compile().as_text()
    assert kernel_census(text) == (
        {"kda_fwd": 1, "kda_bwd": 1} if backward else {"kda_fwd": 1})
    assert "vmem_limit_bytes" not in text


@pytest.mark.parametrize("backward", [False, True],
                         ids=["short_conv_fwd", "short_conv_fwd_and_bwd"])
def test_gated_short_convolution_compiles_at_the_published_widths(
        topo, backward):
    """LFM2-24B-A2B's convolution layer at the cell's 4 x 8192 tokens: the
    projection's [4, 8192, 6144] read in place, 2048 channels, 3 taps
    (ops/short_conv.py). Rolls along sublanes, pieces that meet on a
    sublane tile's edge, a second small block of the same array, and whole
    rows double-buffered under a VMEM limit of its own: what the
    interpreter lets through and Mosaic may not."""
    from ray_tpu.ops import short_conv
    from ray_tpu.parallel.collectives import kernel_census
    one_chip = SingleDeviceSharding(topo.devices[0])
    bcx = jax.ShapeDtypeStruct((4, 8192, 6144), jnp.bfloat16,
                               sharding=one_chip)
    w = jax.ShapeDtypeStruct((3, 2048), jnp.bfloat16, sharding=one_chip)

    def loss(bcx, w):
        # The square keeps the forward alive beside the backward.
        return (short_conv.short_conv(bcx, w).astype(jnp.float32) ** 2).sum()

    fn = jax.grad(loss, argnums=(0, 1)) if backward \
        else short_conv.short_conv
    text = jax.jit(fn).lower(bcx, w).compile().as_text()
    assert kernel_census(text) == (
        {"short_conv_fwd": 1, "short_conv_bwd": 1} if backward
        else {"short_conv_fwd": 1})


@pytest.mark.parametrize("backward", [False, True],
                         ids=["conv_silu_fwd", "conv_silu_fwd_and_bwd"])
@pytest.mark.parametrize("wide,start,width,seq,bias", [
    (4096, 0, 4096, 16384, False), (8512, 4096, 4352, 32768, True)],
    ids=["kimi-linear-48b-a3b", "granite-4.0-h-micro"])
def test_conv_silu_compiles_at_the_published_widths(
        topo, wide, start, width, seq, bias, backward):
    """``silu(b + conv(x))`` with 4 taps (ops/short_conv.py ``conv_silu``):
    one of a Kimi delta-rule layer's q, k, v, an array of its own [1, 16384,
    4096], and a granite state-space layer's xBC with its bias, columns 4096
    .. 8448 taken in the kernel out of tiles of 256 whole rows of the
    in-projection's [1, 32768, 8512] (a last axis that is no whole number
    of lane tiles); the backward's seam of the tile's last rows and the
    halo after it."""
    from ray_tpu.ops import short_conv
    from ray_tpu.parallel.collectives import kernel_census
    one_chip = SingleDeviceSharding(topo.devices[0])
    x, w, b = (jax.ShapeDtypeStruct(shape, jnp.bfloat16, sharding=one_chip)
               for shape in ((1, seq, wide), (4, width), (width,)))
    b = b if bias else None

    def conv(x, w, b):
        return short_conv.conv_silu(x, w, b, start, width)

    def loss(x, w, b):
        return (conv(x, w, b).astype(jnp.float32) ** 2).sum()

    fn = jax.grad(loss, argnums=(0, 1, 2) if bias else (0, 1)) \
        if backward else conv
    text = jax.jit(fn).lower(x, w, b).compile().as_text()
    assert kernel_census(text) == (
        {"conv_silu_fwd": 1, "conv_silu_bwd": 1} if backward
        else {"conv_silu_fwd": 1})


@pytest.mark.parametrize("backward", [False, True],
                         ids=["gated_norm_fwd", "gated_norm_fwd_and_bwd"])
@pytest.mark.parametrize("seq,wide,group,gate_first,activation", [
    (16384, 4096, 128, False, "sigmoid"), (32768, 8512, 4096, True, "silu")],
    ids=["kimi-linear-48b-a3b", "granite-4.0-h-micro"])
def test_gated_norm_compiles_at_the_published_widths(
        topo, seq, wide, group, gate_first, activation, backward):
    """The gate and the RMSNorm behind a recurrence (ops/gated_norm.py) over
    4096 channels in tiles of 256 whole rows: a Kimi delta-rule layer's
    ``RMSNorm(o) * sigmoid(p)`` with a group a head of 128 (32 lane
    reductions a row), and a Mamba-2 layer's ``RMSNorm(y * silu(z))`` with
    one group of the whole row, z columns 0 .. 4096 of the in-projection's
    [1, 32768, 8512] read as a block of a last axis that is no whole number
    of them; the backward's two float32 copies of a group's rows in VMEM."""
    from ray_tpu.ops import gated_norm
    from ray_tpu.parallel.collectives import kernel_census
    one_chip = SingleDeviceSharding(topo.devices[0])
    x, z, scale = (jax.ShapeDtypeStruct(shape, jnp.bfloat16, sharding=one_chip)
                   for shape in ((1, seq, 4096), (1, seq, wide), (group,)))

    def norm(x, z, scale):
        return gated_norm.gated_norm(x, z, scale, 1e-5, gate_first=gate_first,
                                     activation=activation)

    def loss(x, z, scale):
        return (norm(x, z, scale).astype(jnp.float32) ** 2).sum()

    fn = jax.grad(loss, argnums=(0, 1, 2)) if backward else norm
    text = jax.jit(fn).lower(x, z, scale).compile().as_text()
    assert kernel_census(text) == (
        {"gated_norm_fwd": 1, "gated_norm_bwd": 1} if backward
        else {"gated_norm_fwd": 1})


# Learned sparse attention (models/glm_moe_dsa.py) at GLM-5.2's widths and
# the cell's length: 64 heads of 256 | 256 over an int8 selection, the
# indexer's 32 heads of 128 keeping 2048 of up to 4096 keys.
DSA_SHAPE, DSA_INDEX, DSA_TOPK = (1, 4096, 64, 256), (32, 128), 2048


@pytest.mark.parametrize("backward", [False, True],
                         ids=["forward", "backward"])
def test_selected_attention_compiles_at_the_published_widths(topo, backward):
    """``ops/dsa.py``'s kernels for the described chip: the forward with the
    selection's int8 tile for the causal mask and the head-summed
    probabilities (heads the inner grid axis), then the two backward
    kernels; interpret mode cannot see whether Mosaic takes an int8 tile,
    a third scalar-prefetched table or a float32 tile resident over an
    axis."""
    from ray_tpu.ops import dsa
    from ray_tpu.parallel.collectives import kernel_census
    q, k, v = _qkv(topo, DSA_SHAPE)
    B, S = DSA_SHAPE[:2]
    selection = jax.ShapeDtypeStruct(
        (B, S, S), jnp.int8, sharding=SingleDeviceSharding(topo.devices[0]))

    def fn(q, k, v, selection):
        out, lse = dsa.selected_attention(q, k, v, selection, 512, 512, None)
        probs = dsa.head_probs(*jax.lax.stop_gradient((q, k, lse)),
                               selection, 512, 512)
        return out.astype(jnp.float32).sum() + probs.sum()

    fn = jax.grad(fn, (0, 1, 2)) if backward else fn
    text = jax.jit(fn).lower(q, k, v, selection).compile().as_text()
    # No gradient reaches the probabilities: differentiated, they are gone.
    assert kernel_census(text) == (
        {"dsa_fwd": 1, "dsa_bwd_dq": 1, "dsa_bwd_dkv": 1} if backward
        else {"dsa_fwd": 1, "dsa_probs": 1})


def test_the_indexer_and_the_selection_compile_without_a_sort(topo):
    """The indexer's scores (the kernels: ``dsa_index_fwd``, and
    ``dsa_index_bwd`` for the gradients), the threshold search and the loss
    at the cell's size: no ``sort`` and no ``top-k`` custom call in the
    compiled program (the 2048th largest of a row is found by counting),
    and less than 1.5 GB of temporaries, gradients included: a head's
    products of a tile exist in VMEM and nowhere else."""
    from ray_tpu.ops import dsa
    from ray_tpu.parallel.collectives import kernel_census
    one = SingleDeviceSharding(topo.devices[0])
    B, S = DSA_SHAPE[:2]
    heads, width = DSA_INDEX
    q = jax.ShapeDtypeStruct((B, S, heads, width), jnp.bfloat16, sharding=one)
    k = jax.ShapeDtypeStruct((B, S, width), jnp.bfloat16, sharding=one)
    w = jax.ShapeDtypeStruct((B, S, heads), jnp.float32, sharding=one)

    def fn(q, k, w):
        scores = dsa.index_scores(q, k, w)
        selection = dsa.select(jax.lax.stop_gradient(scores), DSA_TOPK)
        return dsa.index_loss(scores, selection.astype(jnp.float32),
                              selection).sum(), selection

    compiled = jax.jit(jax.grad(fn, (0, 1, 2), has_aux=True)).lower(
        q, k, w).compile()
    text = compiled.as_text()
    assert " sort(" not in text and "TopK" not in text
    assert kernel_census(text) == {"dsa_index_fwd": 1, "dsa_index_bwd": 1}
    assert compiled.memory_analysis().temp_size_in_bytes < 1.5e9


@pytest.mark.parametrize("weighted,dtype", [
    (True, jnp.bfloat16), (False, jnp.bfloat16), (True, jnp.float32)],
    ids=["weighted", "unweighted", "float32"])
@pytest.mark.parametrize("cell", SHARE_SHAPES)
def test_rows_to_tokens_compiles_at_the_share_cells_shapes(topo, cell,
                                                           weighted, dtype):
    """``moe_rows_to_tokens`` (ops/moe.py) at the shapes the three share
    cells give it, the forward's weighted sum and the backward's plain one:
    tiles of 512 tokens with their float32 rows twice in VMEM (12.6 MB at
    Trinity's width), a row fetched as the packed pairs of the HBM tile it
    lies in, a tile's [K, 512] entries of ``at`` and of the weights and
    the held bits of every assignment in SMEM; and with rows of float32,
    which no cell has (a tile of 8 whole rows a fetch: twice the stage)."""
    from ray_tpu.ops import moe
    from ray_tpu.parallel.collectives import kernel_census
    tokens, top_k, d, bound = SHARE_SHAPES[cell]
    one_chip = SingleDeviceSharding(topo.devices[0])
    rows = jax.ShapeDtypeStruct((bound, d), dtype, sharding=one_chip)
    at = jax.ShapeDtypeStruct((top_k * tokens,), jnp.int32,
                              sharding=one_chip)
    weights = (jax.ShapeDtypeStruct((top_k, tokens), jnp.float32,
                                    sharding=one_chip),) * weighted
    assert moe._token_tile(rows, at, tokens) == 512
    text = jax.jit(
        lambda rows, at, *weights: moe._to_tokens(rows, at, tokens, *weights)
    ).lower(rows, at, *weights).compile().as_text()
    assert kernel_census(text) == {"moe_rows_to_tokens": 1}


def test_flash_compiles_at_4_x_8k_with_grouped_kv_heads(topo):
    """LFM2-24B-A2B's attention layer: 32 query heads over 8 KV heads of 64
    at 4 sequences of 8192, the forward and the backward kernel."""
    from ray_tpu.parallel.collectives import kernel_census
    q, k, v = _qkv(topo, (4, 8192, 32, 64))
    k = v = jax.ShapeDtypeStruct((4, 8192, 8, 64), jnp.bfloat16,
                                 sharding=k.sharding)

    def loss(q, k, v):
        return (_attend(q, k, v).astype(jnp.float32) ** 2).sum()

    text = jax.jit(jax.grad(loss, argnums=(0, 1, 2))).lower(
        q, k, v).compile().as_text()
    assert kernel_census(text) == {"flash_fwd": 1, "flash_bwd": 1}


def test_flash_compiles_at_32k_with_grouped_kv_heads(topo):
    """granite-4.0-h-micro's attention layer: 32 query heads over 8 KV heads
    of 64 at S = 32768 (2,080 executed tiles a head), the model's own score
    scale, the forward and the backward kernel, the whole of a head's dq
    resident: the longest and, at 16 MiB of float32 accumulator and as
    much of its result's two buffers, the largest any cell has."""
    shape = (1, 32768, 32, 64)
    q, k, v = _qkv(topo, shape)
    k = v = jax.ShapeDtypeStruct((1, 32768, 8, 64), jnp.bfloat16,
                                 sharding=k.sharding)

    def loss(q, k, v):
        return flash_mod.flash_attention(
            q, k, v, True, 512, 512, 1.0 / 64).astype(jnp.float32).sum()

    grads = jax.jit(jax.grad(loss, argnums=(0, 1, 2))).lower(
        q, k, v).compile()
    from ray_tpu.parallel.collectives import kernel_census
    assert kernel_census(grads.as_text()) == {"flash_fwd": 1, "flash_bwd": 1}
    assert flash_mod.causal_tile_census(32768, 512, 512)["executed"] == 2080


@pytest.mark.parametrize("window,names", [
    (4096, ("flash_fwd_win", "flash_bwd_win")),
    (None, ("flash_fwd", "flash_bwd"))])
def test_flash_compiles_at_16k_with_and_without_a_window(topo, window, names):
    """Trinity-Large-Preview's attention layers: 48 query heads over 8 KV
    heads of 128 at S = 16384, a window layer (4096: 252 executed tiles a
    head, under the windowed kernels' own names) and a full layer (528),
    the forward and the backward kernel."""
    q, k, v = _qkv(topo, (1, 16384, 48, 128))
    k = v = jax.ShapeDtypeStruct((1, 16384, 8, 128), jnp.bfloat16,
                                 sharding=k.sharding)

    def loss(q, k, v):
        return flash_mod.flash_attention(
            q, k, v, True, 512, 512, None, window).astype(jnp.float32).sum()

    text = jax.jit(jax.grad(loss, argnums=(0, 1, 2))).lower(
        q, k, v).compile().as_text()
    assert text.count("tpu_custom_call") >= 2
    for name in names:
        assert re.search(rf"\b{name}\b", text), name
    assert "flash_bwd_d" not in text
    assert "flash_fwd_win" in text if window else "flash_fwd_win" not in text
    assert flash_mod.window_tile_census(16384, window, 512, 512)[
        "executed"] == (252 if window else 528)


@pytest.mark.parametrize("window,names", [
    (512, ("flash_fwd_win", "flash_bwd_win")),
    (None, ("flash_fwd", "flash_bwd"))])
def test_flash_compiles_at_16k_with_heads_of_64_and_values_of_128(
        topo, window, names):
    """Phi-4-mini-flash-reasoning's differential attention: 40 query heads
    of 64 against K of 64 and ``V_g`` of 128 laid out to the query heads,
    at S = 16384: a window no wider than a tile (512: 63 executed tiles a
    head, every one cut) and causal (528), the forward and the backward
    kernel; the window's outputs are not worth keeping, Trinity's are."""
    from ray_tpu.parallel.collectives import kernel_census
    q, _, _ = _qkv(topo, (1, 16384, 40, 64))
    v = jax.ShapeDtypeStruct((1, 16384, 40, 128), jnp.bfloat16,
                             sharding=q.sharding)

    def loss(q, k, v):
        return flash_mod.flash_attention(
            q, k, v, True, 512, 512, None, window).astype(jnp.float32).sum()

    text = jax.jit(jax.grad(loss, argnums=(0, 1, 2))).lower(
        q, q, v).compile().as_text()
    assert kernel_census(text) == {name: 1 for name in names}
    assert flash_mod.window_tile_census(16384, window, 512, 512)[
        "executed"] == (63 if window else 528)
    assert flash_mod.worth_keeping(16384, 128, window) == (window is None)
    assert flash_mod.worth_keeping(16384, 128, 4096) \
        and flash_mod.worth_keeping(16384, 128)


@pytest.mark.parametrize("cell", CELL_ATTENTION)
def test_every_cells_backward_is_one_kernel(cell):
    """The rule alone, no chip described: a head's dq fits beside the tiles
    at every cell's (S, D, Dv) under tiles of 512 x 512."""
    (_, S, _, D), _, Dv, _ = CELL_ATTENTION[cell]
    assert flash_mod.one_backward_kernel(S, D, Dv, 512, 512)


@pytest.mark.parametrize("cell", CELL_ATTENTION)
def test_flash_backward_is_one_kernel_at_every_cells_shape(topo, cell):
    """The backward alone (on abstract ``out`` and ``lse``) at tiles of 512
    x 512: the call is the one Mosaic kernel under its name and states the
    limit the rule counts against, 64 MiB."""
    from ray_tpu.parallel.collectives import kernel_census
    one_chip = SingleDeviceSharding(topo.devices[0])
    q, k, v, window = _cell_attention(cell, one_chip)
    (B, S, H, _), Dv = q.shape, v.shape[-1]
    out = jax.ShapeDtypeStruct((B, S, H, Dv), jnp.bfloat16, sharding=one_chip)
    lse = jax.ShapeDtypeStruct((B * H, 1, S), jnp.float32, sharding=one_chip)
    lowered = jax.jit(lambda q, k, v, out, lse, g: flash_mod._flash_backward(
        q, k, v, out, lse, g, True, 512, 512, None, window)).lower(
            q, k, v, out, lse, out)
    assert kernel_census(lowered.compile().as_text()) == {
        "flash_bwd_win" if window else "flash_bwd": 1}
    assert f"\\22size\\22: {64 << 20}" in lowered.as_text()


def test_a_backward_past_the_limit_compiles_as_the_pair(topo):
    """S = 131072 at one head of 128 (no cell's): dq's accumulator alone is
    64 MiB, the rule says no, and the call compiles as ``flash_bwd_dq`` and
    ``flash_bwd_dkv`` inside the default scoped VMEM, stating no limit."""
    from ray_tpu.parallel.collectives import kernel_census
    one_chip = SingleDeviceSharding(topo.devices[0])
    q = jax.ShapeDtypeStruct((1, 131072, 1, 128), jnp.bfloat16,
                             sharding=one_chip)
    lse = jax.ShapeDtypeStruct((1, 1, 131072), jnp.float32, sharding=one_chip)
    assert not flash_mod.one_backward_kernel(131072, 128, 128, 512, 512)
    lowered = jax.jit(lambda q, lse: flash_mod._flash_backward(
        q, q, q, q, lse, q, True, 512, 512)).lower(q, lse)
    assert kernel_census(lowered.compile().as_text()) == {
        "flash_bwd_dq": 1, "flash_bwd_dkv": 1}
    assert "scoped_memory_configs" not in lowered.as_text()


@pytest.mark.parametrize("shape,axis", [((8, 8, 1024, 256), 2),
                                        ((8, 1024, 4096), 1)])
def test_place_slices_compiles_at_the_published_widths(topo, shape, axis):
    """``ops/place.py``: DMAs from HBM to HBM at an offset the chip reads
    from SMEM, for GPT-J's q, k and v halves as [b, h, s, k] in one call
    (the four-chip cell's) and for halves of the hidden states."""
    from ray_tpu.ops.place import place_slices
    one_chip = SingleDeviceSharding(topo.devices[0])
    half = jax.ShapeDtypeStruct(shape, jnp.bfloat16, sharding=one_chip)
    halves = [(half,) * 3] * 2 if axis == 2 else [half] * 2
    slots = jax.ShapeDtypeStruct((2,), jnp.int32, sharding=one_chip)
    text = jax.jit(lambda halves, slots: place_slices(
        halves, slots, axis)).lower(halves, slots).compile().as_text()
    assert text.count("tpu_custom_call") == 1


def test_ragged_sequence_is_an_error_on_tpu(topo):
    """No silent switch to the jnp blockwise path where a kernel exists."""
    with pytest.raises(ValueError, match="multiple of 128"):
        jax.jit(_attend).lower(*_qkv(topo, (2, 1000, 16, 128)))
