"""Ask the TPU compiler, without a TPU, about MiniCPM-SALA's kernels at the
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

from chip_compile import compile_for_tpu, topo  # noqa: F401
from ray_tpu.ops import infllm, lightning
from ray_tpu.parallel import MeshConfig, build_mesh
from ray_tpu.parallel.collectives import kernel_census

CELL = "minicpm-sala-1chip.steady"
# One sequence of the cell's 16384: 32 query heads of 128 on 2 KV heads, 32
# linear heads of 128, 256 blocks of 64.
B, S, H, G, D = 1, 16384, 32, 2, 128
SIZES = infllm.Sizes()
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


@pytest.mark.parametrize("backward", [False, True], ids=["fwd", "fwd+bwd"])
def test_lightning_kernels_compile_at_the_cells_shape(shaped, backward):
    """64 chunks of 256 a head, four heads a grid step, the [256, 256]
    decay matrix made in VMEM from a head's slope."""
    def mixed(q, k, v, slope):
        return lightning.lightning(q, k, v, slope).astype(jnp.float32).sum()

    fn = jax.grad(mixed, (0, 1, 2)) if backward else mixed
    qkv = shaped(jnp.bfloat16, B, S, H, D)
    text = jax.jit(fn).lower(qkv, qkv, qkv, shaped(jnp.float32, H)) \
        .compile().as_text()
    want = {"lightning_fwd": 1, "lightning_bwd": 1} if backward \
        else {"lightning_fwd": 1}
    assert kernel_census(text) == want


@pytest.mark.parametrize("backward", [False, True], ids=["fwd", "fwd+bwd"])
def test_sala_kernels_compile_at_the_cells_shape(shaped, backward):
    """528 causal tiles of 512 x 512 a query head, 16 heads on each K/V
    head where it lies, the selection widened to a [2, 16384, 16384] int8
    mask outside the kernels."""
    def attended(q, k, v, selection):
        out, _ = infllm.selected_attention(q, k, v, selection, SIZES.block,
                                           512, 512)
        return out.astype(jnp.float32).sum()

    fn = jax.grad(attended, (0, 1, 2)) if backward else attended
    kv = shaped(jnp.bfloat16, B, S, G, D)
    text = jax.jit(fn).lower(
        shaped(jnp.bfloat16, B, S, H, D), kv, kv,
        shaped(jnp.int8, B, G, S, S // SIZES.block)).compile().as_text()
    want = {"sala_fwd": 1, "sala_bwd_dq": 1, "sala_bwd_dkv": 1} if backward \
        else {"sala_fwd": 1}
    assert kernel_census(text) == want
    assert "sort(" not in text


def test_the_selection_compiles_without_a_sort_and_in_little_room(shaped):
    """Compressed keys, blocks' scores a block of 256 rows at a time and
    the threshold search: no sort, and under 1 GB of temporaries where the
    [16384, 32, 1023] float32 softmax whole would be 2.1."""
    def selected(q, k):
        return infllm.select(infllm.block_scores(
            q, infllm.compress(k, SIZES), SIZES), SIZES)

    compiled = jax.jit(selected).lower(
        shaped(jnp.bfloat16, B, S, H, D),
        shaped(jnp.bfloat16, B, S, G, D)).compile()
    assert "sort(" not in compiled.as_text()
    assert compiled.memory_analysis().temp_size_in_bytes < 1 << 30
    assert compiled.output_shardings is not None


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


def test_the_cells_step_calls_the_seven_kernels_and_no_flash(cell):
    """One sparse layer: ``sala_fwd`` once (a query's 4096 keys are
    ``worth_keeping``'s 32 x 128, so its outputs survive remat) and each
    backward kernel once; three linear layers: the recurrence's and the
    gated norm's forward twice a layer, their backward once; no
    ``flash_*`` over ``dense_len``."""
    census = kernel_census(cell[2], a_step=True)
    assert census == {"sala_fwd": 1, "sala_bwd_dq": 1, "sala_bwd_dkv": 1,
                      "lightning_fwd": 6, "lightning_bwd": 3,
                      "gated_norm_fwd": 6, "gated_norm_bwd": 3}


def test_the_benchmarks_count_of_calls_is_the_steps(cell, benchmark_path):
    """``flops_minicpm_sala.step_kernel_calls`` (what the Mosaic roofline
    share divides by) counts the calls the traced step makes, and its chunk
    is the kernels'."""
    import flops_minicpm_sala as counts
    config, cfg, jaxpr = cell
    layout = config["layout"]
    calls = counts.step_kernel_calls(config, layout["batch"],
                                     layout["seq_len"], bool(cfg.remat))
    assert {name: one["calls"] for name, one in calls.items()} \
        == kernel_census(jaxpr, a_step=True)
    assert counts.LIGHTNING_CHUNK == lightning.CHUNK
    assert counts.keeps_forward(config, layout["seq_len"]) \
        == infllm.keeps_forward(layout["seq_len"], D, cfg.sparse_sizes)
