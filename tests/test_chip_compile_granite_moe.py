"""Ask the TPU compiler, without a TPU, about granite-4.0-h-small's kernels
at the published widths and the benchmark cell's length (the state-space
scan at 128 heads, its conv over 8448 columns of a 16,768-wide projection,
its gate-norm over whole rows of 8192, attention at heads of 128, the
grouped products at experts of 768 and the share's way back to tokens), and
count the kernels the cell's own step calls. ``tests/test_chip_compile.py``
has why such compiles exist and how they are steered; this file is apart
from it because that file is one worker's and the run's critical path. The
topology is described in a fixture, by the worker that runs this file, and
never at import; every test skips where it cannot be described (no libtpu,
or its lock held by another process that was not allowed beside it).
"""

import os
import sys

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from chip_compile import (_lowered_digest, compile_for_tpu,  # noqa: F401
                          flash_mod, the_pair_for_each_backward, topo)
from ray_tpu.ops import gated_norm, moe, short_conv, ssd
from ray_tpu.parallel import MeshConfig, build_mesh
from ray_tpu.parallel.collectives import kernel_census

CELL = "granite-4.0-h-small-1chip.steady"
# One sequence of the cell's 16384: 128 state-space heads of 64 in one B/C
# group with a state of 128; a share's buffer of twice 9 of 72 experts' even
# part of 16384 x 10 assignments, on rows of 4096 and experts of 768.
B, S, HEADS, WIDTH, STATE, CHUNK = 1, 16384, 128, 64, 128, 256
DI, CONV, PROJ = 8192, 8448, 16768
ROWS, D, EXPERT, HELD, TOP_K = 40960, 4096, 768, 9, 10
BENCHMARK = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "benchmark")
#: The cell's lowered step (``chip_compile._lowered_digest``): a PR that
#: means to change this program records the new value.
LOWERED_STEP = "df9e4976030b"  # 839633fb523f until PR 69 (one flash_bwd)


@pytest.fixture(scope="module")
def shaped(topo):
    one_chip = SingleDeviceSharding(topo.devices[0])

    def shaped(dtype, *shape):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)
    return shaped


@pytest.fixture(scope="module")
def benchmark_path():
    sys.path.insert(0, BENCHMARK)
    yield
    sys.path.remove(BENCHMARK)


def test_the_scan_compiles_at_128_heads(shaped):
    """Twice granite-4.0-h-micro's heads: a chunk's states of all the heads
    of a block in VMEM, forward and backward."""
    def scanned(u, dt, A, B_, C, D_):
        return ssd.ssd(u, dt, A, B_, C, D_, chunk=CHUNK).astype(
            jnp.float32).sum()

    one_group = shaped(jnp.bfloat16, B, S, 1, STATE)
    text = jax.jit(jax.grad(scanned, (0, 1, 3, 4))).lower(
        shaped(jnp.bfloat16, B, S, HEADS, WIDTH),
        shaped(jnp.float32, B, S, HEADS), shaped(jnp.float32, HEADS),
        one_group, one_group, shaped(jnp.float32, HEADS)).compile().as_text()
    assert kernel_census(text) == {"ssd_fwd": 1, "ssd_bwd": 1}


def test_the_conv_and_the_gate_norm_compile_at_the_projections_width(shaped):
    """xBC is columns 8192 .. 16640 (66 lane tiles) of the in-projection's
    [1, 16384, 16768]; the gate z its first 8192, and the norm is over whole
    rows of 8192."""
    def passes(proj, y, taps, bias, scale):
        xbc = short_conv.conv_silu(proj, taps, bias, DI, CONV)
        normed = gated_norm.gated_norm(y, proj, scale, 1e-5, gate_first=True,
                                       activation="silu")
        return xbc.astype(jnp.float32).sum() + normed.astype(
            jnp.float32).sum()

    text = jax.jit(jax.grad(passes, (0, 1, 2, 3, 4))).lower(
        shaped(jnp.bfloat16, B, S, PROJ), shaped(jnp.bfloat16, B, S, DI),
        shaped(jnp.bfloat16, 4, CONV), shaped(jnp.bfloat16, CONV),
        shaped(jnp.float32, DI)).compile().as_text()
    assert kernel_census(text) == {"conv_silu_bwd": 1, "gated_norm_bwd": 1}


@pytest.mark.parametrize("k,n", [(D, EXPERT), (EXPERT, D)],
                         ids=["4096x768", "768x4096"])
def test_grouped_matmul_compiles_at_the_shares_rows(shaped, k, n):
    """The narrowest experts of the benchmark: an output of 768 is one tile,
    a contraction of 768 one of 512 and one masked past 256; 9 held groups
    and the rows past them. Forward (gmm) and both cotangents (gmm, tgmm)
    inside the scoped VMEM."""
    assert moe._tile_n(EXPERT) == 768 and moe._tile_n(D) == 1024

    def loss(x, w, sizes):
        return moe.grouped_matmul(x, w, sizes).astype(jnp.float32).sum()

    text = jax.jit(jax.grad(loss, argnums=(0, 1))).lower(
        shaped(jnp.bfloat16, ROWS, k), shaped(jnp.bfloat16, HELD, k, n),
        shaped(jnp.int32, HELD + 1)).compile().as_text()
    assert kernel_census(text) == {"gmm": 1, "tgmm": 1}


@pytest.mark.parametrize("weighted", [True, False],
                         ids=["weighted", "unweighted"])
def test_rows_to_tokens_compiles_at_ten_a_token(shaped, weighted):
    """``moe_rows_to_tokens`` at the widest top-k of the benchmark: a tile's
    [10, 512] entries of ``at`` and of the weights in SMEM."""
    rows = shaped(jnp.bfloat16, ROWS, D)
    at = shaped(jnp.int32, TOP_K * S)
    weights = (shaped(jnp.float32, TOP_K, S),) * weighted
    assert moe._token_tile(rows, at, S) == 512
    text = jax.jit(
        lambda rows, at, *weights: moe._to_tokens(rows, at, S, *weights)
    ).lower(rows, at, *weights).compile().as_text()
    assert kernel_census(text) == {"moe_rows_to_tokens": 1}


def test_the_router_compiles_at_72_experts_without_a_sort_of_the_rows(
        shaped):
    """``moe.route`` at 72 experts and 10 a token (neither a power of two
    nor a multiple of the lanes): it compiles, and under a gigabyte."""
    compiled = jax.jit(lambda x, router: moe.route(
        x, router, None, TOP_K, 1.0, True, "softmax")).lower(
        shaped(jnp.bfloat16, S, D), shaped(jnp.float32, D, 72)).compile()
    assert compiled.memory_analysis().temp_size_in_bytes < 1 << 30


@pytest.fixture(scope="module")
def cell(topo, benchmark_path):
    """The benchmark cell's own step, found as ``benchmark/rehearse.py``
    finds it: (configuration, program config, the step's jaxpr, the digest
    of its lowered text). Traced and lowered here with the kernels steered
    to the chip's compiler: a fixture of the module is set up before a
    test's ``compile_for_tpu``."""
    import harness
    found = harness.load_cell(harness.load_spec(), CELL)
    layout, program = found.config["layout"], found.config["program"]
    family = harness.load_module("families", program["family"])
    mesh = build_mesh(MeshConfig(**layout["mesh"]),
                      devices=list(topo.devices[:found.chips]))
    cfg = family.config(program)
    state, step = family.abstract_state_and_step(cfg, mesh, program)
    tokens = jax.ShapeDtypeStruct(
        (layout["batch"], layout["seq_len"]), jnp.int32,
        sharding=family.batch_sharding(mesh))
    args = (state, {"tokens": tokens, "targets": tokens})
    interpret, flash_mod._interpret = flash_mod._interpret, lambda: False
    try:
        return found.config, cfg, jax.make_jaxpr(step.__wrapped__)(*args), \
            _lowered_digest(step, args)
    finally:
        flash_mod._interpret = interpret


def test_the_cells_shapes_are_this_files(cell):
    config, cfg, _, _ = cell
    layout = config["layout"]
    assert (layout["batch"], layout["seq_len"]) == (B, S)
    assert (cfg.mamba_n_heads, cfg.mamba_d_head, cfg.mamba_n_groups,
            cfg.mamba_d_state, cfg.mamba_chunk_size) == (
        HEADS, WIDTH, 1, STATE, CHUNK)
    assert (cfg.mamba_d_inner, cfg.conv_dim) == (DI, CONV)
    assert (cfg.hidden_size, cfg.intermediate_size, cfg.experts_held,
            cfg.num_local_experts, cfg.num_experts_per_tok) == (
        D, EXPERT, (0, HELD), 72, TOP_K)
    assert moe._held_bound(B * S, TOP_K, HELD, 72) == ROWS
    assert cfg.layers == ("mamba",) * 5 + ("attention",) + ("mamba",) * 4


def test_the_benchmarks_count_of_calls_is_the_steps(cell, benchmark_path):
    """``flops_granite_moe.step_kernel_calls`` (what the Mosaic roofline
    share divides by) counts the calls the traced step makes: the
    state-space layers' and the attention layer's kernels call for call,
    the gate-norm's pair among them; the share's kernels twice each in the
    trace, the first buffer's call and the call in the loop over further
    buffers, which does not run on a routing within the bound
    (``megablox``'s kernels carry no name in a jaxpr: None)."""
    import flops_granite_moe as counts
    config, cfg, jaxpr, _ = cell
    layout = config["layout"]
    calls = {name: one["calls"] for name, one in counts.step_kernel_calls(
        config, layout["batch"], layout["seq_len"], cfg.attn_blk_q,
        cfg.attn_blk_k, bool(cfg.remat)).items()}
    census = kernel_census(jaxpr, a_step=True)
    in_the_loop_too = {"moe_rows_to_tokens": calls.pop("moe_rows_to_tokens"),
                       None: calls.pop("gmm") + calls.pop("tgmm")}
    calls.update({name: 2 * n for name, n in in_the_loop_too.items()})
    assert the_pair_for_each_backward(census) == calls
    assert counts.keeps_forward(S, cfg.head_dim) == flash_mod.worth_keeping(
        S, cfg.head_dim)


def test_the_cells_step_is_the_program_it_was(cell):
    assert cell[3] == LOWERED_STEP
