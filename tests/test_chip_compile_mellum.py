"""Ask the TPU compiler, without a TPU, about Mellum2's kernels at the
published widths and the benchmark cell's length, and count the kernels the
cell's own step calls. ``tests/test_chip_compile.py`` has why such compiles
exist and how they are steered; this file is apart from it because that
file is one worker's and the run's critical path. The topology is described
in a fixture, by the worker that runs this file, and never at import; every
test skips where it cannot be described (no libtpu, or its lock held by
another process that was not allowed beside it).
"""

import os
import sys

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from chip_compile import (compile_for_tpu,  # noqa: F401
                          flash_mod, the_pair_for_each_backward, topo)
from ray_tpu.ops import moe
from ray_tpu.parallel import MeshConfig, build_mesh
from ray_tpu.parallel.collectives import kernel_census

CELL = "mellum2-12b-a2.5b-1chip.steady"
# One sequence of the cell's 16384: 32 query heads of 128 on 4 KV heads
# under a window of 1024; 64 experts of 896 on rows of 2304, 8 a token.
B, S, H, G, D, WINDOW = 1, 16384, 32, 4, 128, 1024
ROWS, WIDTH, EXPERT, EXPERTS = 8 * S, 2304, 896, 64
BENCHMARK = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "benchmark")


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


@pytest.mark.parametrize("tile", [512, 256])
@pytest.mark.parametrize("window", [WINDOW, None], ids=["window", "full"])
def test_flash_kernels_compile_at_the_cells_shape(shaped, window, tile):
    """Eight query heads a KV head, a window of two tiles of 512 (or four
    of 256): the forward and the backward kernel, under the window's names
    where there is one."""
    def attended(q, k, v):
        return flash_mod.flash_attention(
            q, k, v, True, tile, tile, window=window).astype(
                jnp.float32).sum()

    kv = shaped(jnp.bfloat16, B, S, G, D)
    text = jax.jit(jax.grad(attended, (0, 1, 2))).lower(
        shaped(jnp.bfloat16, B, S, H, D), kv, kv).compile().as_text()
    suffix = "_win" if window else ""
    assert kernel_census(text) == {
        "flash_fwd" + suffix: 1, "flash_bwd" + suffix: 1}


@pytest.mark.parametrize("k,n", [(WIDTH, EXPERT), (EXPERT, WIDTH)],
                         ids=["2304x896", "896x2304"])
def test_grouped_matmul_compiles_at_the_cells_widths(shaped, k, n):
    """The expert layer's grouped products at 131,072 rows: one 896-wide
    output tile, and a contraction of 896 = 512 + 384 under ``_TILE_K``;
    forward (gmm) and both cotangents (gmm, tgmm) inside the scoped VMEM."""
    def loss(x, w, sizes):
        return moe.grouped_matmul(x, w, sizes).astype(jnp.float32).sum()

    text = jax.jit(jax.grad(loss, argnums=(0, 1))).lower(
        shaped(jnp.bfloat16, ROWS, k), shaped(jnp.bfloat16, EXPERTS, k, n),
        shaped(jnp.int32, EXPERTS)).compile().as_text()
    assert text.count("tpu_custom_call") >= 2  # dlhs and drhs


@pytest.fixture(scope="module")
def cell(topo, benchmark_path):
    """The benchmark cell's own step, found as ``benchmark/rehearse.py``
    finds it: (configuration, program config, the step's jaxpr)."""
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
    return found.config, cfg, jax.make_jaxpr(step.__wrapped__)(
        state, {"tokens": tokens, "targets": tokens})


def test_the_cells_shapes_are_this_files(cell):
    config, cfg, _ = cell
    layout = config["layout"]
    assert (layout["batch"], layout["seq_len"]) == (B, S)
    assert (cfg.num_attention_heads, cfg.num_key_value_heads, cfg.head_dim,
            cfg.sliding_window) == (H, G, D, WINDOW)
    assert (cfg.hidden_size, cfg.moe_intermediate_size, cfg.num_experts,
            cfg.num_experts_per_tok * S) == (WIDTH, EXPERT, EXPERTS, ROWS)
    assert cfg.experts_held is None


def test_the_cells_step_calls_the_flash_kernels_by_what_remat_keeps(cell):
    """Three window layers and one full, the window's kernels under their
    own names: the full layer's forward kernel once (a query's 16384 keys
    over heads of 128 are ``worth_keeping``, so its outputs survive remat),
    a window layer's twice (1024 keys are not), the backward kernel once a
    layer; every expert held, so the whole layer's path and no
    ``moe_rows_to_tokens``; the grouped products, which ``megablox`` names
    ``gmm`` and ``tgmm``: nine and three a layer."""
    census = kernel_census(cell[2], a_step=True)
    flash = {name: calls for name, calls in census.items()
             if str(name).startswith("flash")}
    assert flash == {"flash_fwd_win": 6, "flash_bwd_win": 3,
                     "flash_fwd": 1, "flash_bwd": 1}
    assert "moe_rows_to_tokens" not in census
    assert sum(census.values()) - sum(flash.values()) == 4 * 12


def test_the_benchmarks_count_of_calls_is_the_steps(cell, benchmark_path):
    """``flops_mellum.step_kernel_calls`` (what the Mosaic roofline share
    divides by) counts the calls the traced step makes."""
    import flops_mellum as counts
    config, cfg, jaxpr = cell
    layout = config["layout"]
    calls = counts.step_kernel_calls(
        config, layout["batch"], layout["seq_len"], cfg.attn_blk_q,
        cfg.attn_blk_k, bool(cfg.remat))
    census = kernel_census(jaxpr, a_step=True)
    assert {name: one["calls"] for name, one in calls.items()
            if name.startswith("flash")} == {
        name: n for name, n in the_pair_for_each_backward(census).items()
        if str(name).startswith("flash")}
    assert calls["gmm"]["calls"] + calls["tgmm"]["calls"] == sum(
        n for name, n in census.items()
        if not str(name).startswith("flash"))
    for keys in (WINDOW, 4096, S):
        assert counts.keeps_forward(keys, D) == flash_mod.worth_keeping(
            S, D, keys)
