"""Ask the TPU compiler, without a TPU, about dots3-note-prev's kernels at
the published widths and the benchmark cell's length, and count the kernels
the cell's own step calls. ``tests/test_chip_compile.py`` has why such
compiles exist and how they are steered; this file is apart from it because
that file is one worker's and the run's critical path. The topology is
described in a fixture, by the worker that runs this file, and never at
import; every test skips where it cannot be described (no libtpu, or its
lock held by another process that was not allowed beside it).
"""

import os
import sys

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from chip_compile import (_lowered_digest, compile_for_tpu,  # noqa: F401
                          flash_mod, the_pair_for_each_backward, topo)
from ray_tpu.ops import dsa
from ray_tpu.parallel import MeshConfig, build_mesh
from ray_tpu.parallel.collectives import kernel_census

CELL = "dots3-note-prev-1chip.steady"
# One sequence of the cell's 8192. A full layer: 128 heads of 192 | 128 over
# an int8 selection, the indexer's 64 heads of 128 keeping 2048 keys. A
# window layer: 64 heads of 256 | 128 in a window of 513.
B, S, TOPK, WINDOW = 1, 8192, 2048, 513
FULL, WINDOWED, INDEX = (128, 192, 128), (64, 256, 128), (64, 128)
#: ``_lowered_digest`` of the cell's step: a PR that means to change the
#: program records the new value.
LOWERED_STEP = "7fccedede5e7"  # a9386c454826 until PR 69 (one flash_bwd_win)
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


def _qkv(shaped, geometry):
    heads, d, dv = geometry
    return (shaped(jnp.bfloat16, B, S, heads, d),
            shaped(jnp.bfloat16, B, S, heads, d),
            shaped(jnp.bfloat16, B, S, heads, dv))


@pytest.mark.parametrize("backward", [False, True],
                         ids=["forward", "backward"])
def test_selected_attention_compiles_at_the_cells_shape(shaped, backward):
    """``ops/dsa.py``'s kernels at 128 heads whose query and value widths
    differ (192 | 128; GLM's are equal) over an ``[8192, 8192]`` int8
    selection: the forward and the head-summed probabilities, then the two
    backward kernels."""
    def fn(q, k, v, selection):
        out, lse = dsa.selected_attention(q, k, v, selection, 512, 512, None)
        probs = dsa.head_probs(*jax.lax.stop_gradient((q, k, lse)),
                               selection, 512, 512)
        return out.astype(jnp.float32).sum() + probs.sum()

    fn = jax.grad(fn, (0, 1, 2)) if backward else fn
    text = jax.jit(fn).lower(*_qkv(shaped, FULL), shaped(
        jnp.int8, B, S, S)).compile().as_text()
    assert kernel_census(text) == (
        {"dsa_fwd": 1, "dsa_bwd_dq": 1, "dsa_bwd_dkv": 1} if backward
        else {"dsa_fwd": 1, "dsa_probs": 1})


def test_the_indexer_and_the_selection_compile_without_a_sort(shaped):
    """The indexer's scores (the kernels: ``dsa_index_fwd``, and
    ``dsa_index_bwd`` for the gradients), the threshold search and the loss
    at 64 heads and 8192 keys: no ``sort`` and no ``top-k`` custom call,
    and less than 3 GB of temporaries, gradients included: a head's
    products of a tile exist in VMEM and nowhere else (a block of 256 query
    rows of them was 537 MB)."""
    heads, width = INDEX

    def fn(q, k, w):
        scores = dsa.index_scores(q, k, w)
        selection = dsa.select(jax.lax.stop_gradient(scores), TOPK)
        return dsa.index_loss(scores, selection.astype(jnp.float32),
                              selection).sum(), selection

    compiled = jax.jit(jax.grad(fn, (0, 1, 2), has_aux=True)).lower(
        shaped(jnp.bfloat16, B, S, heads, width),
        shaped(jnp.bfloat16, B, S, width),
        shaped(jnp.float32, B, S, heads)).compile()
    text = compiled.as_text()
    assert " sort(" not in text and "TopK" not in text
    assert kernel_census(text) == {"dsa_index_fwd": 1, "dsa_index_bwd": 1}
    assert compiled.memory_analysis().temp_size_in_bytes < 3e9


def test_the_window_kernels_compile_at_the_cells_shape(shaped):
    """64 heads of 256 | 128 in a window one key longer than the tile of
    512: the forward and the backward kernel under the window's names."""
    def attended(q, k, v):
        return flash_mod.flash_attention(
            q, k, v, True, 512, 512, window=WINDOW).astype(jnp.float32).sum()

    text = jax.jit(jax.grad(attended, (0, 1, 2))).lower(
        *_qkv(shaped, WINDOWED)).compile().as_text()
    assert kernel_census(text) == {"flash_fwd_win": 1, "flash_bwd_win": 1}
    assert flash_mod.window_tile_census(S, WINDOW, 512, 512) == {
        "executed": 31, "diagonal": 31, "full": 0, "empty": 225}


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
    full, window = cfg.latent("full"), cfg.latent("window")
    assert (full.num_attention_heads,
            full.qk_nope_head_dim + full.qk_rope_head_dim,
            full.v_head_dim) == FULL
    assert (window.num_attention_heads,
            window.qk_nope_head_dim + window.qk_rope_head_dim,
            window.v_head_dim) == WINDOWED
    assert (cfg.index_n_heads, cfg.index_head_dim) == INDEX
    assert (cfg.index_topk, cfg.sliding_window_size) == (TOPK, WINDOW)
    assert cfg.layers == ("dense_full", "moe_full", "moe_window",
                          "moe_window", "moe_window")


def test_the_benchmarks_count_of_calls_is_the_steps(cell, benchmark_path):
    """``flops_dots3_note.step_kernel_calls`` (what the Mosaic roofline
    share divides by) counts the calls the traced step makes: a full
    layer's forward kernel once (8192 keys over a value head of 128 are
    ``worth_keeping``), a window layer's twice (513 keys are not), each
    backward kernel once a layer (a window layer's one ``flash_bwd_win``
    where the benchmark still counts the pair it replaced), the head-summed probabilities and the
    indexer's forward kernel once a full layer (the indexer's loss is part
    of the rematerialised block, and ``"full"`` keeps the one array its
    backward pass reads: ``dsa.LOSS_GRADIENT_NAME``), and no causal flash
    kernel; the share's kernels (``megablox``'s carry no name in a jaxpr:
    None) twice each in the trace, the first buffer's call and the call in
    the loop over further buffers, which does not run on a routing within
    the bound."""
    import flops_dots3_note as counts
    config, cfg, jaxpr, _ = cell
    census = kernel_census(jaxpr, a_step=True)
    attention = {name: n for name, n in census.items()
                 if str(name).startswith(("dsa_", "flash_"))}
    # The indexers' kernels (PR 65) are not in the benchmark's count yet:
    # once a full layer forward, as the probabilities, and once backward.
    assert (attention.pop("dsa_index_fwd"),
            attention.pop("dsa_index_bwd")) == (2, 2)
    # Nor is what PR 67 keeps: the benchmark counts the probabilities twice
    # a full layer still, as the step ran them until then.
    assert attention.pop("dsa_probs") == 2
    assert attention == {
        "dsa_fwd": 2, "dsa_bwd_dq": 2, "dsa_bwd_dkv": 2,
        "flash_fwd_win": 6, "flash_bwd_win": 3}
    calls = {name: one["calls"] for name, one in counts.step_kernel_calls(
        config, B, S, bool(cfg.remat)).items()}
    assert calls.pop("dsa_probs") == 4
    assert {name: n for name, n in calls.items()
            if name.startswith(("dsa_", "flash_"))} == \
        the_pair_for_each_backward(attention)
    assert census["moe_rows_to_tokens"] >= 4
    assert census[None] == 2 * (calls["gmm"] + calls["tgmm"]) == 2 * 4 * 12
    assert counts.keeps_forward(S, FULL[2]) == flash_mod.worth_keeping(
        S, FULL[2]) and counts.keeps_forward(WINDOW, WINDOWED[2]) \
        == flash_mod.worth_keeping(S, WINDOWED[2], WINDOW)


def test_the_cells_step_is_the_program_it_was(cell):
    assert cell[3] == LOWERED_STEP
