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


#: tokens, choices a token, width, rows of a buffer: what ``_to_tokens``
#: is given in the three cells that hold a share of the experts.
SHARE_SHAPES = {
    "lfm2-24b-a2b-1chip.steady": (32768, 4, 2048, 32768),
    "kimi-linear-48b-a3b-1chip.steady": (16384, 8, 2304, 32768),
    "trinity-large-preview-1chip.steady": (16384, 4, 3072, 4096),
}


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
