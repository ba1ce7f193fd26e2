"""Ask the TPU compiler, without a TPU: the main path's kernels and one
sharded step, compiled for a described ``v5e:2x2`` topology.

Interpret mode (every other test of the flash kernels) cannot see what
Mosaic refuses: a misaligned tile, too much VMEM, a kernel GSPMD cannot
partition. These compiles can, at no chip time (on-chip-measurement
guide §2.3). Nothing runs, so they say nothing about results or speed.

One file, one process: two processes describing a TPU topology at once
collide on libtpu's lock. The whole file skips where the topology cannot
be described (no libtpu).
"""

import os
import sys

os.environ.setdefault("TPU_LOG_DIR", "disabled")

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import pytest  # noqa: E402
from jax.sharding import NamedSharding, PartitionSpec, \
    SingleDeviceSharding  # noqa: E402

import ray_tpu.ops  # noqa: E402,F401 - loads ray_tpu.ops.flash_attention
from ray_tpu.models import gpt  # noqa: E402
from ray_tpu.parallel import MeshConfig, ShardingRules, \
    build_mesh  # noqa: E402
from ray_tpu.parallel.train_step import (abstract_train_state,  # noqa: E402
                                         make_train_step,
                                         memory_efficient_optimizer)

# ray_tpu.ops re-exports the *function* flash_attention under the module's
# own name, so `import ray_tpu.ops.flash_attention as m` binds the
# function; the module is reached through sys.modules.
flash_mod = sys.modules["ray_tpu.ops.flash_attention"]


def _describe_topology():
    try:
        from jax.experimental import topologies
        return topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2")
    except Exception as exc:  # noqa: BLE001 - any failure means "no libtpu"
        return exc


_TOPO = _describe_topology()
pytestmark = pytest.mark.skipif(
    isinstance(_TOPO, Exception),
    reason=f"v5e:2x2 topology cannot be described here: {_TOPO!r}")


@pytest.fixture(autouse=True)
def compile_for_tpu(monkeypatch):
    """The kernels ask ``jax.default_backend()`` whether to interpret, and
    that still says cpu here: steer it from the test. The persistent
    compile cache is off around these compiles: an executable built for a
    described chip is written but cannot be read back without one, and the
    next run would warn on every entry."""
    from jax.experimental.compilation_cache import compilation_cache
    monkeypatch.setattr(flash_mod, "_interpret", lambda: False)
    was_enabled = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", was_enabled)
    compilation_cache.reset_cache()


# (B, S, H, D) of every head width the dense presets use, at the recorded
# single-chip batch sizes.
PRESET_SHAPES = {
    "gpt-1.3b": (12, 1024, 16, 128),
    "gpt-410m": (18, 1024, 16, 64),
    "gpt-2.7b": (8, 1024, 32, 80),
    "gptj-6b": (1, 2048, 16, 256),
}


def _qkv(shape):
    one_chip = SingleDeviceSharding(_TOPO.devices[0])
    return [jax.ShapeDtypeStruct(shape, jnp.bfloat16, sharding=one_chip)
            for _ in range(3)]


def _attend(q, k, v):
    return flash_mod.flash_attention(q, k, v, True, 512, 512)


@pytest.mark.parametrize("preset", PRESET_SHAPES)
def test_flash_forward_compiles(preset):
    text = jax.jit(_attend).lower(
        *_qkv(PRESET_SHAPES[preset])).compile().as_text()
    assert text.count("tpu_custom_call") >= 1


@pytest.mark.parametrize("preset", PRESET_SHAPES)
def test_flash_backward_compiles(preset):
    """Forward + the dq and dk/dv kernels: three Mosaic calls."""
    def loss(q, k, v):
        return _attend(q, k, v).astype(jnp.float32).sum()

    text = jax.jit(jax.grad(loss, argnums=(0, 1, 2))).lower(
        *_qkv(PRESET_SHAPES[preset])).compile().as_text()
    assert text.count("tpu_custom_call") >= 3


def test_ragged_sequence_is_an_error_on_tpu():
    """No silent switch to the jnp blockwise path where a kernel exists."""
    with pytest.raises(ValueError, match="multiple of 128"):
        jax.jit(_attend).lower(*_qkv((2, 1000, 16, 128)))


def test_flash_step_compiles_on_four_chips():
    """The gpt-1.3b train step, attn_impl='flash', on an fsdp=2 x tp=2
    mesh. Before the kernels ran under shard_map this failed in under a
    second: "Mosaic kernels cannot be automatically partitioned"."""
    mesh = build_mesh(MeshConfig(dp=1, fsdp=2, tp=2),
                      devices=_TOPO.devices)
    cfg = gpt.config("gpt-1.3b", max_seq_len=1024, attn_impl="flash",
                     remat_policy="full", loss_chunk=4096,
                     param_dtype=jnp.bfloat16)
    rules = ShardingRules()
    optimizer = memory_efficient_optimizer(learning_rate=1e-4)
    state = abstract_train_state(cfg, mesh, rules, optimizer)
    tokens = jax.ShapeDtypeStruct(
        (8, 1024), jnp.int32,
        sharding=NamedSharding(mesh, PartitionSpec(("dp", "fsdp"), None)))
    compiled = make_train_step(cfg, mesh, rules, optimizer).lower(
        state, {"tokens": tokens, "targets": tokens}).compile()
    text = compiled.as_text()
    assert "tpu_custom_call" in text
    assert "all-gather" in text  # fsdp really shards the weights
    mem = compiled.memory_analysis()
    per_device = mem.argument_size_in_bytes + mem.temp_size_in_bytes
    assert per_device < 16 * 2 ** 30, per_device
