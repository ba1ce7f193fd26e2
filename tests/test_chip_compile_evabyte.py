"""Ask the TPU compiler, without a TPU, about EVA attention's kernels at
EvaByte's widths and the benchmark cell's length, and count the kernels the
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
from ray_tpu.ops import eva
from ray_tpu.parallel import MeshConfig, build_mesh
from ray_tpu.parallel.collectives import kernel_census

CELL = "evabyte-6.5b-1chip.steady"
# (B, S, H, D), window, chunk: EvaByte's attention at the cell's length.
SHAPE, WINDOW, CHUNK = (1, 32768, 32, 128), 2048, 16


def _operands(topo):
    one_chip = SingleDeviceSharding(topo.devices[0])
    B, S, H, D = SHAPE

    def bf16(*shape):
        return jax.ShapeDtypeStruct(shape, jnp.bfloat16, sharding=one_chip)
    return [bf16(*SHAPE)] * 3 + [bf16(H, D)] * 2


def _attend(q, k, v, phi, mu):
    kc, vc = eva.pool(k, v, phi, mu, CHUNK)
    out, mass = eva.eva_attention(q, k, v, kc, vc, WINDOW, CHUNK, 512, 512)
    return out.astype(jnp.float32).sum(), mass


@pytest.mark.parametrize("backward", [False, True], ids=["fwd", "fwd+bwd"])
def test_eva_kernels_compile_at_the_cells_shape(topo, backward):
    """One K and one V of 2048 + 32768 stacked rows, 304 tiles of 512 x 512
    a head, the one-compare mask (windows of whole tiles): ``eva_fwd`` and,
    with the gradient through the pooling, both backward kernels."""
    fn = jax.grad(_attend, argnums=(0, 1, 2, 3, 4), has_aux=True) \
        if backward else _attend
    text = jax.jit(fn).lower(*_operands(topo)).compile().as_text()
    want = {"eva_fwd": 1, "eva_bwd_dq": 1, "eva_bwd_dkv": 1} if backward \
        else {"eva_fwd": 1}
    assert kernel_census(text) == want


def test_the_cells_step_calls_the_eva_kernels_and_no_flash(topo):
    """The benchmark cell's own step, found as ``benchmark/rehearse.py``
    finds it and traced for the described chip: ``eva_fwd`` twice a layer
    (a query sees at most 2048 + 1920 keys and summaries, under
    ``worth_keeping``'s 32 x 128: the outputs are not kept across remat),
    each backward kernel once, and no ``flash_*``."""
    here = os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), "benchmark")
    sys.path.insert(0, here)
    try:
        import harness
        found = harness.load_cell(harness.load_spec(), CELL)
        layout, program = found.config["layout"], found.config["program"]
        family = harness.load_module("families", program["family"])
    finally:
        sys.path.remove(here)
    mesh = build_mesh(MeshConfig(**layout["mesh"]),
                      devices=list(topo.devices[:found.chips]))
    state, step = family.abstract_state_and_step(
        family.config(program), mesh, program)
    tokens = jax.ShapeDtypeStruct(
        (layout["batch"], layout["seq_len"]), jnp.int32,
        sharding=family.batch_sharding(mesh))
    census = kernel_census(jax.make_jaxpr(step.__wrapped__)(
        state, {"tokens": tokens, "targets": tokens}), a_step=True)
    layers = found.config["num_hidden_layers"]
    assert census == {"eva_fwd": 2 * layers, "eva_bwd_dq": layers,
                      "eva_bwd_dkv": layers}
