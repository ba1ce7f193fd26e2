"""What the files that ask the TPU compiler share (``test_chip_compile.py``,
``test_chip_compile_mesh.py``, ``test_chip_compile_kernels.py`` and a file a
family, ``test_chip_compile_<family>.py``): the described topology, the
steering of the kernels' dispatch, abstract q, k and v on one chip, the shapes
two of the files read, and the digest of a lowered step. pytest does not
collect this module; a file imports the two fixtures by name and they are
its own (``topo`` once a file, ``compile_for_tpu`` around each of its
cases). ``tests/test_chip_compile.py`` has why such compiles exist.
"""

import os
import re
import sys

os.environ.setdefault("TPU_LOG_DIR", "disabled")

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import pytest  # noqa: E402
from jax.sharding import SingleDeviceSharding  # noqa: E402

import ray_tpu.ops  # noqa: E402,F401 - loads ray_tpu.ops.flash_attention

# ray_tpu.ops re-exports the *function* flash_attention under the module's
# own name, so `import ray_tpu.ops.flash_attention as m` binds the
# function; the module is reached through sys.modules.
flash_mod = sys.modules["ray_tpu.ops.flash_attention"]


@pytest.fixture(scope="module")
def topo():
    """The described topology, made when the first test of this file runs
    and never while a module is imported: only the worker that is given
    this file loads the TPU's library (on-chip-measurement guide §2)."""
    try:
        from jax.experimental import topologies
        return topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2")
    except Exception as exc:  # noqa: BLE001 - any failure means "no libtpu"
        pytest.skip(f"v5e:2x2 topology cannot be described here: {exc!r}")


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


# Latent attention in training (models/deepseek.py): q/k of 192, v of 128,
# at the sequence length whose K/V no longer fit a kernel's VMEM whole.
MLA_SHAPE, MLA_V = (2, 8192, 16, 192), 128


def _qkv(topo, shape, v_dim=None):
    one_chip = SingleDeviceSharding(topo.devices[0])
    shapes = [shape, shape, shape[:-1] + (v_dim or shape[-1],)]
    return [jax.ShapeDtypeStruct(s, jnp.bfloat16, sharding=one_chip)
            for s in shapes]


def _attend(q, k, v):
    return flash_mod.flash_attention(q, k, v, True, 512, 512)


def _attend_loss(q, k, v):
    return _attend(q, k, v).astype(jnp.float32).sum()


# The flash kernels' operands at every benchmark cell's attention: (B, S, H,
# D), KV heads, Dv, window.
CELL_ATTENTION = {
    "gptj-6b": ((8, 2048, 16, 256), 16, 256, None),
    "gptj-6b, a shard of fsdp=2 x tp=2": ((8, 2048, 8, 256), 8, 256, None),
    "moonlight-16b-a3b": (MLA_SHAPE, 16, MLA_V, None),
    "granite-4.0-h-micro": ((1, 32768, 32, 64), 8, 64, None),
    "trinity-large-preview, full layer": ((1, 16384, 48, 128), 8, 128, None),
    "trinity-large-preview, window layer": ((1, 16384, 48, 128), 8, 128,
                                            4096),
    "kimi-linear-48b-a3b, latent layer": ((1, 16384, 32, 192), 32, 128,
                                          None),
    "lfm2-24b-a2b": ((4, 8192, 32, 64), 8, 64, None),
    "phi-4-mini-flash-reasoning, full and cross layers":
        ((1, 16384, 40, 64), 40, 128, None),
    "phi-4-mini-flash-reasoning, window layer":
        ((1, 16384, 40, 64), 40, 128, 512),
    "mellum2-12b-a2.5b, full layer": ((1, 16384, 32, 128), 4, 128, None),
    "mellum2-12b-a2.5b, window layer": ((1, 16384, 32, 128), 4, 128, 1024),
    "nemotron-3-nano-30b-a3b": ((1, 16384, 32, 128), 2, 128, None),
    "dots3-note-prev, window layer": ((1, 8192, 64, 256), 64, 128, 513),
    "granite-4.0-h-small": ((1, 16384, 32, 128), 8, 128, None),
    # No cell's: lane-dense statistics over an output of one and a half
    # lane tiles.
    "heads of 192": ((2, 4096, 8, 192), 8, 192, None),
}


def _cell_attention(cell, sharding=None):
    """(q, k, v, window): a ``CELL_ATTENTION`` entry's abstract operands
    and the forward's window."""
    shape, kv_heads, v_dim, window = CELL_ATTENTION[cell]
    q, k, v = (jax.ShapeDtypeStruct(s, jnp.bfloat16, sharding=sharding)
               for s in (shape, shape[:2] + (kv_heads, shape[3]),
                         shape[:2] + (kv_heads, v_dim)))
    return q, k, v, window


#: tokens, choices a token, width, rows of a buffer: what ``_to_tokens``
#: is given in the three cells that hold a share of the experts.
SHARE_SHAPES = {
    "lfm2-24b-a2b-1chip.steady": (32768, 4, 2048, 32768),
    "kimi-linear-48b-a3b-1chip.steady": (16384, 8, 2304, 32768),
    "trinity-large-preview-1chip.steady": (16384, 4, 3072, 4096),
}


def the_pair_for_each_backward(census):
    """A step's ``kernel_census`` under the names the benchmark's counts
    still carry (``benchmark/flops_*.py`` ``step_kernel_calls``, a
    ``benchmark`` PR's to rename): since PR 69 a layer's backward is one
    ``flash_bwd`` / ``flash_bwd_win`` call where the counts have a
    ``flash_bwd_dq`` and a ``flash_bwd_dkv`` (``_win``) each."""
    census = dict(census)
    for suffix in ("", "_win"):
        calls = census.pop("flash_bwd" + suffix, None)
        if calls:
            census["flash_bwd_dq" + suffix] = calls
            census["flash_bwd_dkv" + suffix] = calls
    return census


def _lowered_digest(step, args):
    """sha256 of ``step.lower(*args).as_text()`` (no locations), each
    ``tpu_custom_call``'s kernel taken out of its base64 bytecode and put
    back as the digest of its MLIR printed without debug info: the
    bytecode carries the checkout's path and the callers' line numbers."""
    import base64
    import hashlib
    from jax._src.interpreters import mlir
    from jax._src.lib import tpu
    from jax._src.lib.mlir import ir

    def kernel(match):
        context = mlir.make_ir_context()
        tpu.register_dialect(context)
        context.allow_unregistered_dialects = True
        with context:
            body = ir.Module.parse(base64.b64decode(match.group(1))
                                   ).operation.get_asm(enable_debug_info=False)
        return ('\\22body\\22: \\22'
                + hashlib.sha256(body.encode()).hexdigest() + '\\22')
    text = re.sub(r'\\22body\\22: \\22([A-Za-z0-9+/=]+)\\22', kernel,
                  step.lower(*args).as_text())
    return hashlib.sha256(text.encode()).hexdigest()[:12]
