"""Ask the TPU compiler, without a TPU, about Nemotron-3-Nano-30B-A3B's
changed kernels at the published widths and the benchmark cell's length, and
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

from chip_compile import (compile_for_tpu,  # noqa: F401
                          flash_mod, the_pair_for_each_backward, topo)
from ray_tpu.ops import gated_norm, moe, ssd
from ray_tpu.parallel import MeshConfig, build_mesh
from ray_tpu.parallel.collectives import kernel_census

CELL = "nemotron-3-nano-30b-a3b-1chip.steady"
# Two sequences of the cell's 16384: 64 state-space heads of 64 in 8 B/C
# groups with a state of 128; a share's buffer of twice 32 of 128 experts'
# even part of 32768 x 6 assignments, on rows of 2688 and experts of 1856.
B, S, HEADS, WIDTH, GROUPS, STATE = 2, 16384, 64, 64, 8, 128
ROWS, D, EXPERT, HELD = 98304, 2688, 1856, 32
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


@pytest.mark.parametrize("chunk", [128, 256])
def test_the_grouped_scan_compiles_at_the_cells_shape(shaped, chunk):
    """A head block of 8 heads of 64 is one group's: forward and backward
    kernels at the published chunk and at the cell's."""
    assert ssd.heads_per_block(HEADS, WIDTH, GROUPS) == HEADS // GROUPS

    def scanned(u, dt, A, B_, C, D_):
        return ssd.ssd(u, dt, A, B_, C, D_, chunk=chunk).astype(
            jnp.float32).sum()

    grouped = shaped(jnp.bfloat16, B, S, GROUPS, STATE)
    text = jax.jit(jax.grad(scanned, (0, 1, 3, 4))).lower(
        shaped(jnp.bfloat16, B, S, HEADS, WIDTH),
        shaped(jnp.float32, B, S, HEADS), shaped(jnp.float32, HEADS),
        grouped, grouped, shaped(jnp.float32, HEADS)).compile().as_text()
    assert kernel_census(text) == {"ssd_fwd": 1, "ssd_bwd": 1}


def test_the_grouped_norm_compiles_at_the_cells_shape(shaped):
    """Statistics over 8 groups of 512 of 4096 channels under a scale as
    wide as the row, z the first columns of the in-projection's output."""
    def normed(y, proj, scale):
        return gated_norm.gated_norm(
            y, proj, scale, 1e-5, gate_first=True, activation="silu",
            group=512).astype(jnp.float32).sum()

    text = jax.jit(jax.grad(normed, (0, 1, 2))).lower(
        shaped(jnp.bfloat16, B, S, 4096), shaped(jnp.bfloat16, B, S, 10304),
        shaped(jnp.float32, 4096)).compile().as_text()
    assert kernel_census(text) == {"gated_norm_bwd": 1}


@pytest.mark.parametrize("k,n", [(D, EXPERT), (EXPERT, D)],
                         ids=["2688x1856", "1856x2688"])
def test_grouped_matmul_compiles_at_the_shares_rows(shaped, k, n):
    """The form that shipped: ``megablox`` with an irregular last tile. An
    output of 1856 is two tiles of 1024, the second cut at 832; a
    contraction of 1856 is three of 512 and one masked past 320; 32 held
    groups and the rows past them. Forward (gmm) and both cotangents (gmm,
    tgmm) inside the scoped VMEM."""
    assert moe._tile_n(EXPERT) == 1024 and moe._tile_n(D) == 896

    def loss(x, w, sizes):
        return moe.grouped_matmul(x, w, sizes).astype(jnp.float32).sum()

    text = jax.jit(jax.grad(loss, argnums=(0, 1))).lower(
        shaped(jnp.bfloat16, ROWS, k), shaped(jnp.bfloat16, HELD, k, n),
        shaped(jnp.int32, HELD + 1)).compile().as_text()
    assert kernel_census(text) == {"gmm": 1, "tgmm": 1}


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
    assert layout["seq_len"] == S and layout["batch"] in (1, B)
    assert (cfg.mamba_num_heads, cfg.mamba_head_dim, cfg.n_groups,
            cfg.ssm_state_size) == (HEADS, WIDTH, GROUPS, STATE)
    assert (cfg.hidden_size, cfg.moe_intermediate_size, cfg.experts_held,
            cfg.n_routed_experts) == (D, EXPERT, (0, HELD), 128)
    assert moe._held_bound(B * S, cfg.num_experts_per_tok, HELD, 128) == ROWS
    assert cfg.layers == ("experts", "mamba") * 4 + ("attention",)


def test_the_benchmarks_count_of_calls_is_the_steps(cell, benchmark_path):
    """``flops_nemotron_h.step_kernel_calls`` (what the Mosaic roofline
    share divides by) counts the calls the traced step makes: the
    state-space layers' and the attention layer's kernels call for call;
    the share's kernels twice each in the trace, the first buffer's call and
    the call in the loop over further buffers, which does not run on a
    routing within the bound (``megablox``'s kernels carry no name in a
    jaxpr: None)."""
    import flops_nemotron_h as counts
    config, cfg, jaxpr = cell
    layout = config["layout"]
    calls = {name: one["calls"] for name, one in counts.step_kernel_calls(
        config, layout["batch"], layout["seq_len"], cfg.attn_blk_q,
        cfg.attn_blk_k, bool(cfg.remat), cfg.chunk_size).items()}
    census = kernel_census(jaxpr, a_step=True)
    in_the_loop_too = {"moe_rows_to_tokens": calls.pop("moe_rows_to_tokens"),
                       None: calls.pop("gmm") + calls.pop("tgmm")}
    calls.update({name: 2 * n for name, n in in_the_loop_too.items()})
    assert the_pair_for_each_backward(census) == calls
    assert counts.keeps_forward(S, cfg.head_dim) == flash_mod.worth_keeping(
        S, cfg.head_dim)
