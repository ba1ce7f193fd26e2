"""Ask the TPU compiler, without a TPU: the main path's kernels and one
sharded step, compiled for a described ``v5e:2x2`` topology.

Interpret mode (every other test of the flash kernels) cannot see what
Mosaic refuses: a misaligned tile, too much VMEM, a kernel GSPMD cannot
partition. These compiles can, at no chip time (on-chip-measurement
guide §2.3). Nothing runs, so they say nothing about results or speed.

One file, one process: two processes describing a TPU topology at once
collide on libtpu's lock, so the topology is described in a fixture, by the
worker that runs this file, and never at import. Every test skips where it
cannot be described (no libtpu).
"""

import json
import os
import re
import sys

os.environ.setdefault("TPU_LOG_DIR", "disabled")

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import pytest  # noqa: E402
from jax.sharding import NamedSharding, PartitionSpec, \
    SingleDeviceSharding  # noqa: E402

import ray_tpu.ops  # noqa: E402,F401 - loads ray_tpu.ops.flash_attention
from ray_tpu.models import gpt, lm  # noqa: E402
from ray_tpu.parallel import MeshConfig, ShardingRules, \
    build_mesh  # noqa: E402
from ray_tpu.parallel.train_step import (abstract_train_state,  # noqa: E402
                                         make_train_step,
                                         memory_efficient_optimizer)

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


# (B, S, H, D) of every head width the dense presets use, at the recorded
# single-chip batch sizes.
PRESET_SHAPES = {
    "gpt-1.3b": (12, 1024, 16, 128),
    "gpt-410m": (18, 1024, 16, 64),
    "gpt-2.7b": (8, 1024, 32, 80),
    "gptj-6b": (1, 2048, 16, 256),
}


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


@pytest.mark.parametrize("preset", PRESET_SHAPES)
def test_flash_forward_compiles(topo, preset):
    text = jax.jit(_attend).lower(
        *_qkv(topo, PRESET_SHAPES[preset])).compile().as_text()
    assert text.count("tpu_custom_call") >= 1


def _attend_loss(q, k, v):
    return _attend(q, k, v).astype(jnp.float32).sum()


@pytest.mark.parametrize("preset", PRESET_SHAPES)
def test_flash_backward_compiles(topo, preset):
    """Forward + the dq and dk/dv kernels: three Mosaic calls."""
    text = jax.jit(jax.grad(_attend_loss, argnums=(0, 1, 2))).lower(
        *_qkv(topo, PRESET_SHAPES[preset])).compile().as_text()
    assert text.count("tpu_custom_call") >= 3


@pytest.mark.parametrize("shape,v_dim", [
    (MLA_SHAPE, MLA_V), ((2, 8192, 16, 256), 256)])
def test_flash_compiles_at_8k_with_two_head_sizes(topo, shape, v_dim):
    """S = 8192: K and V (in the dk/dv kernel Q and dO) of a head are
    2-4 MB each and came whole into VMEM before they were streamed by the
    grid; q/k of 192 beside v of 128 is latent attention, 256 | 256 GPT-J
    at four times its context."""
    grads = jax.jit(jax.grad(_attend_loss, argnums=(0, 1, 2))).lower(
        *_qkv(topo, shape, v_dim)).compile()
    assert grads.as_text().count("tpu_custom_call") >= 3


def test_flash_compiles_at_8k_under_shard_map(topo):
    """The same kernels per shard of an fsdp=2 x tp=2 mesh, through the
    models' one attention dispatch."""
    from ray_tpu.models import deepseek
    from ray_tpu.parallel import mesh as mesh_mod
    mesh = build_mesh(MeshConfig(dp=1, fsdp=2, tp=2), devices=topo.devices)
    cfg = deepseek.config("moonlight-16b-a3b", attn_impl="flash")
    sharding = NamedSharding(mesh, PartitionSpec(("dp", "fsdp"), None, "tp",
                                                 None))
    q, k, v = (jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=sharding)
               for s in _qkv(topo, (4,) + MLA_SHAPE[1:], MLA_V))

    def loss(q, k, v):
        return lm.attention(q, k, v, cfg).astype(jnp.float32).sum()

    previous = mesh_mod.current_mesh()
    mesh_mod.set_current_mesh(mesh)
    try:
        text = jax.jit(jax.grad(loss, argnums=(0, 1, 2))).lower(
            q, k, v).compile().as_text()
    finally:
        mesh_mod.set_current_mesh(previous)
    assert text.count("tpu_custom_call") >= 3


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


#: {cell: (step, its abstract arguments, the jaxpr of the step)} and the
#: cells in the order they were loaded and traced: each once a run of this
#: file, which the file's last case holds it to.
_CELLS, _TRACES = {}, []


def _a_cells_step(topo, cell):
    """(step, its abstract arguments, the step's jaxpr) of a benchmark
    cell, found the way ``benchmark/rehearse.py`` finds it (the
    configuration's file, its family's ``config`` and
    ``abstract_state_and_step``) for the described chip, loaded and traced
    once for the module: every census reads that jaxpr, and ``step.lower``
    (the digests', the whole-step compiles') finds the same trace in the
    step's cache. A one-chip mesh: the trace asks it nothing
    (``lm._over_batch_shards``)."""
    if cell in _CELLS:
        return _CELLS[cell]
    here = os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), "benchmark")
    sys.path.insert(0, here)
    try:
        import harness
        found = harness.load_cell(harness.load_spec(), cell)
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
    args = (state, {"tokens": tokens, "targets": tokens})
    _TRACES.append(cell)
    _CELLS[cell] = step, args, jax.make_jaxpr(step.__wrapped__)(*args)
    if found.chips > 1:
        # Traced outside the step's mesh, which a census can read and a
        # lowering cannot use (no ``shard_map`` around the kernels):
        # ``step.lower`` must not find this trace.
        step.__wrapped__.clear_cache()
    return _CELLS[cell]


@pytest.mark.parametrize("cell,calls", [
    ("kimi-linear-48b-a3b-1chip.steady",
     {"conv_silu_fwd": 24, "conv_silu_bwd": 12, "kda_fwd": 8, "kda_bwd": 4,
      "gated_norm_fwd": 8, "gated_norm_bwd": 4}),
    ("granite-4.0-h-micro-1chip.steady",
     {"conv_silu_fwd": 36, "conv_silu_bwd": 18, "ssd_fwd": 36,
      "ssd_bwd": 18, "gated_norm_fwd": 36, "gated_norm_bwd": 18}),
    ("lfm2-24b-a2b-1chip.steady",
     {"short_conv_fwd": 26, "short_conv_bwd": 13}),
])
def test_a_cells_step_runs_the_convolutions_kernels(topo, cell, calls):
    """The benchmark cell's own step, traced for the described chip: the
    fused pass runs once a convolution in the forward scan, again where the
    backward scan rematerialises the block (its output feeds the
    recurrence's backward) and once backward: Kimi's 4 delta-rule layers x
    q, k, v, granite's 18 state-space layers, LFM2's 13 gated mixers. The
    gate and norm behind the recurrence (``lm.gated_norm``) run the same
    way, once a layer of Kimi's and of granite's."""
    from ray_tpu.parallel.collectives import kernel_census
    _, _, jaxpr = _a_cells_step(topo, cell)
    census = kernel_census(jaxpr, a_step=True)
    assert {name: census.get(name) for name in calls} == calls
    for name in ("conv_silu_fwd", "gated_norm_fwd"):
        if name not in calls:
            assert name not in census


@pytest.mark.parametrize("cell", [
    "gptj-6b-1chip.steady", "gptj-6b-4chip.steady",
    "moonlight-16b-a3b-1chip.steady", "trinity-large-preview-1chip.steady",
    "phi-4-mini-flash-reasoning-1chip.steady", "glm-5.2-1chip.steady"])
def test_no_other_cells_step_holds_the_gated_norm(topo, cell):
    """``lm.gated_norm`` has two callers, a granite state-space layer and a
    Kimi delta-rule layer: no other cell's traced step holds its kernels
    (LFM2's is the third row above; phi's Mamba-1 gate has no norm and
    stays XLA's)."""
    from ray_tpu.parallel.collectives import kernel_census
    _, _, jaxpr = _a_cells_step(topo, cell)
    census = kernel_census(jaxpr)
    # (megablox's grouped matmul gives its calls no name.)
    assert census and not [name for name in census
                           if "gated_norm" in str(name)]


def _wide_products_a_scan(jaxpr, width):
    """[``dot_general``s with a dimension of ``width`` in each ``scan``'s
    body] over the scans that hold any, sub-jaxprs looked through and a
    scan inside a scan counted as its own."""
    from ray_tpu.parallel.collectives import sub_jaxprs
    found = []

    def walk(jaxpr, counts):
        for eqn in jaxpr.eqns:
            if eqn.primitive.name == "dot_general" and any(
                    width in v.aval.shape for v in eqn.invars + eqn.outvars):
                counts.append(eqn)
            inside = [] if eqn.primitive.name == "scan" else counts
            for sub in sub_jaxprs(eqn):
                walk(sub, inside)
            if inside is not counts and inside:
                found.append(len(inside))

    outside = []
    walk(getattr(jaxpr, "jaxpr", jaxpr), outside)
    assert not outside, outside  # the whole logits exist nowhere
    return found


@pytest.mark.parametrize("cell", [
    "moonlight-16b-a3b-1chip.steady",
    "phi-4-mini-flash-reasoning-1chip.steady", "gptj-6b-1chip.steady"])
def test_a_cells_step_runs_three_products_a_chunk_of_the_head(topo, cell):
    """``lm.chunked_ce`` in the cell's own traced step: one scan over the
    chunks with the head's product and its two transposes (d x, d W) and no
    second scan with anything as wide as the vocabulary. Autodiff of the
    rematerialised scan it replaces traced two, of one and three: the head
    ran again in the backward scan."""
    _, args, jaxpr = _a_cells_step(topo, cell)
    vocab = args[0]["params"]["wte"].shape[0]
    assert _wide_products_a_scan(jaxpr, vocab) == [3]


def test_the_phi4flash_cells_step_runs_its_kernels_as_counted(topo):
    """``phi-4-mini-flash-reasoning-1chip.steady``'s own step, traced for the
    described chip, by the layers its configuration runs (``layers_run``):
    the scan's and the convolution's forward twice a Mamba layer (again
    where the backward scan rematerialises the pair) and their backward
    once (8 + 4 at three self pairs and the middle pair); the window layers'
    flash forward twice (63 tiles a head: its outputs are not worth
    keeping, ``flash_attention.worth_keeping``) and the full and the cross
    layers' once; every backward kernel once a layer."""
    from ray_tpu.parallel.collectives import kernel_census
    cell = "phi-4-mini-flash-reasoning-1chip.steady"
    _, _, jaxpr = _a_cells_step(topo, cell)
    here = os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), "benchmark", "configs")
    with open(os.path.join(here, cell.split(".")[0] + ".json")) as f:
        layers = json.load(f)["layers_run"]
    mamba = sum(i % 2 == 0 and i <= 16 for i in layers)
    window = sum(i % 2 == 1 and i < 16 for i in layers)
    causal = sum(i % 2 == 1 and i > 16 for i in layers)
    assert mamba >= 2 and window >= 1 and causal >= 2
    census = kernel_census(jaxpr, a_step=True)
    assert census == {
        "selective_scan_fwd": 2 * mamba, "selective_scan_bwd": mamba,
        "conv_silu_fwd": 2 * mamba, "conv_silu_bwd": mamba,
        "flash_fwd_win": 2 * window, "flash_bwd_dq_win": window,
        "flash_bwd_dkv_win": window, "flash_fwd": causal,
        "flash_bwd_dq": causal, "flash_bwd_dkv": causal}


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


def test_the_glm_cells_step_runs_its_kernels_as_counted(topo):
    """``glm-5.2-1chip.steady``'s own step, traced for the described chip,
    by the layers its configuration runs: the selection's forward kernel
    twice a layer (S = 4096 is under 32 x 256, its outputs are not worth
    keeping: ``flash_attention.worth_keeping``), the two backward kernels
    once a layer, the head-summed probabilities twice a layer that owns an
    indexer (its loss is part of the rematerialised block) and so the
    indexer's forward kernel, its backward kernel once, the share's way
    back to tokens in the expert layers, and no causal flash kernel."""
    from ray_tpu.parallel.collectives import kernel_census
    cell = "glm-5.2-1chip.steady"
    _, _, jaxpr = _a_cells_step(topo, cell)
    here = os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), "benchmark", "configs")
    with open(os.path.join(here, cell.rsplit(".", 1)[0] + ".json")) as f:
        config = json.load(f)
    layers = config["layers_run"]
    owners = sum(config["indexer_types"][l] == "full" for l in layers)
    assert len(layers) == 5 and owners == 2
    assert config["layout"]["seq_len"] < 32 * config["v_head_dim"]
    census = kernel_census(jaxpr, a_step=True)
    assert {name: n for name, n in census.items() if name and name.startswith(
        ("dsa_", "flash_"))} == {
        "dsa_fwd": 2 * len(layers), "dsa_bwd_dq": len(layers),
        "dsa_bwd_dkv": len(layers), "dsa_probs": 2 * owners,
        "dsa_index_fwd": 2 * owners, "dsa_index_bwd": owners}
    assert census["moe_rows_to_tokens"] >= 4


def test_the_phi4flash_cells_reference_check_holds_less_than_its_step(topo):
    """The program ``benchmark/runners/train.py`` ``_reference_check`` runs
    on ``phi-4-mini-flash-reasoning-1chip.steady`` before the first step
    (the family's logits of the whole sequence, 1024 positions of them
    kept), compiled for the described chip at the cell's size: with the
    family's head a block of the vocabulary at a time the compiler holds a
    piece of the [16384, 200064] logits at a time (2.55 GB of temporaries by
    ``memory_analysis``; the whole product, its copies for the gather and a
    second product were 12.28 GB, more than the chip has beside the state of
    fourteen layers). The chip's reading of the cell
    (``device.peak_hbm_gb``) takes the largest arena any program reserved:
    this one must stay under the step's 9 GB."""
    here = os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), "benchmark")
    sys.path.insert(0, here)
    try:
        import harness
        family = harness.load_module("families", "phi4flash")
    finally:
        sys.path.remove(here)
    _, (state, batch), _ = _a_cells_step(
        topo, "phi-4-mini-flash-reasoning-1chip.steady")
    with open(os.path.join(
            here, "configs", "phi-4-mini-flash-reasoning-1chip.json")) as f:
        cfg = family.config(json.load(f)["program"])
    tokens = batch["tokens"]
    where = jax.ShapeDtypeStruct((tokens.shape[0], 1024), jnp.int32,
                                 sharding=tokens.sharding)

    def program_forward(params, tokens, targets, positions):
        logits, losses = family.logits_and_losses(params, cfg, tokens,
                                                  targets)
        return jnp.take_along_axis(logits, positions[..., None], axis=1
                                   ).astype(jnp.float32), losses

    compiled = jax.jit(program_forward).lower(
        state["params"], tokens, tokens, where).compile()
    assert compiled.memory_analysis().temp_size_in_bytes < 4e9


#: GiB the compiled Kimi step reserves as ``preallocated-temp``: 8.30
#: before the delta rule's forward wrote its inverses (PR 38's tree: 8.17
#: and 128 MiB of another colour), 8.61 with them (268 MB a layer, alive
#: inside one block's backward under full remat), and 7.86 since the gate
#: and the norm behind the rule are ``lm.gated_norm`` (PR 48: no float32
#: gate, no float32 copy of the rule's output).
KIMI_TEMP_GIB = 7.86


# Marked slow by PR 51 (182 s and 139 s of the run's CPU-seconds, the two
# longest standalone cases: ROADMAP Queue 3 item 8); run them with -m slow
# when ops/kda.py, ops/moe.py or those cells' layouts change.
@pytest.mark.slow
def test_the_kimi_cells_compiled_step_holds_the_delta_rules_pair(
        topo, tmp_path):
    """``kimi-linear-48b-a3b-1chip.steady``'s step compiled for the described
    chip: ``kda_fwd`` 8 and ``kda_bwd`` 4 times a step (6 and 3 in the
    text: the two expert delta-rule layers in a row are one scan), and what
    the step reserves within 0.1 GiB of the recorded value, by the
    compiler's memory-usage report: a float32 copy of [16384, 4096] that
    comes back is 0.25."""
    import glob
    from ray_tpu.parallel.collectives import kernel_census
    step, args, jaxpr = _a_cells_step(
        topo, "kimi-linear-48b-a3b-1chip.steady")
    a_step = kernel_census(jaxpr, a_step=True)
    assert (a_step["kda_fwd"], a_step["kda_bwd"]) == (8, 4)
    compiled = step.lower(*args).compile(
        compiler_options={"xla_dump_to": str(tmp_path)})
    held = kernel_census(compiled.as_text())
    assert (held["kda_fwd"], held["kda_bwd"]) == (6, 3)
    (report,) = glob.glob(str(tmp_path / "*jit_step*memory-usage-report.txt"))
    with open(report) as f:
        found = re.findall(r"allocation (\d+): size ([0-9.]+)([KMG]?)i?B,"
                           r"[^\n]*preallocated-temp", f.read())
    scale = {"": 2.0 ** -30, "K": 2.0 ** -20, "M": 2.0 ** -10, "G": 1.0}
    reserved = sum(float(n) * scale[unit]
                   for n, unit in {i: (n, u) for i, n, u in found}.values())
    assert abs(reserved - KIMI_TEMP_GIB) < 0.1


#: tokens, choices a token, width, rows of a buffer: what ``_to_tokens``
#: is given in the three cells that hold a share of the experts.
SHARE_SHAPES = {
    "lfm2-24b-a2b-1chip.steady": (32768, 4, 2048, 32768),
    "kimi-linear-48b-a3b-1chip.steady": (16384, 8, 2304, 32768),
    "trinity-large-preview-1chip.steady": (16384, 4, 3072, 4096),
}


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


@pytest.mark.parametrize("cell,layers,passes", [
    ("lfm2-24b-a2b-1chip.steady", 16, 2),
    ("kimi-linear-48b-a3b-1chip.steady", 4, 2),
    ("trinity-large-preview-1chip.steady", 4, 3),
])
def test_a_share_cells_step_sums_rows_with_the_kernel(topo, cell, layers,
                                                      passes):
    """A share cell's own step, traced for the described chip: every way
    back to tokens is the kernel, in the forward scan (the weighted sum)
    and in the backward scan (``d x``), each the first buffer's call and
    the call in the loop over further buffers. Where the expert layer's sum
    is the block's output (LFM2, Kimi) the backward scan does not run the
    layer's forward again; Trinity norms the sum, and it does."""
    from ray_tpu.parallel.collectives import kernel_census
    _, _, jaxpr = _a_cells_step(topo, cell)
    census = kernel_census(jaxpr, a_step=True)
    assert census["moe_rows_to_tokens"] == layers * passes * 2


@pytest.mark.slow  # PR 51: as the Kimi cell's compiled step above
def test_the_lfm2_cells_compiled_step_gathers_no_slab_of_tokens(topo):
    """``lfm2-24b-a2b-1chip.steady``'s step compiled for the described chip
    holds the kernel wherever a buffer's rows go back to tokens, and no
    gather of [32768, 2048] rows for it: under ``moe_combine`` what is left
    is the cotangent's rows for the buffer (``g_rows``: one gather a
    backward call, so one for every two calls of the kernel, the forward's
    and the backward's ``d x``; the rematerialised forward of an expert
    layer is dead code, its output being the block's), under
    ``moe_dispatch`` the rows of ``x`` a call. With the ``top_k`` slabs a
    call the parent's step held 80 and 96 of them where this holds 16 and
    32."""
    from ray_tpu.parallel.collectives import kernel_census
    step, args, _ = _a_cells_step(topo, "lfm2-24b-a2b-1chip.steady")
    text = step.lower(*args).compile().as_text()
    calls = kernel_census(text)["moe_rows_to_tokens"]

    def gathers(scope):
        return len(re.findall(
            r"= bf16\[32768,2048\]\S* gather\([^\n]*"
            rf'op_name="{scope}/gather"', text))
    assert calls >= 4 and calls % 2 == 0
    assert (gathers("moe_combine"), gathers("moe_dispatch")) == (
        calls // 2, calls)


#: sha256 (first 12) of the lowered step of every cell without a share of
#: the experts: what a PR that says "these cells do not move" holds itself
#: to off the chip. Recorded from PR 44's tree, which meant to move all
#: five (``lm.chunked_ce`` forms its cotangents in the forward walk); from
#: PR 42's tree and equal on PR 43's they were 6609ff07ec2f, 5c05ed09a078,
#: cda3dc002fa5, e72cae811a53, df4cd8b6c9e8. A PR that means to change one
#: of these programs records the new value here (the failing assertion
#: prints it) and says so in CHANGES.md. PR 48 meant to change granite's
#: (``lm.gated_norm`` behind the scan; e0101a71d47b before it), PR 57
#: Moonlight's (the forward's statistics lane-dense at 192 | 128;
#: 030ce9c909a1 before it), PR 59 Moonlight's again and Mellum's, which it
#: lists from then on (the whole expert layer's row passes: gathers that
#: promise their indices, a sort and a comparison for the two scatters,
#: scalars permuted by a sort, a way back to tokens whose backward reads
#: the sorted rows; c02822046105 and 96a2c501bc69 before it), PR 61 both
#: again (``route`` picks its scores by a sum over E, so its transpose is a
#: sum and no scatter; 88ae7b5365ee and ebb20e464562 before it), PR 63
#: granite's (``ssd_bwd``'s head loop on operands a head wide; 9f52f929b5af
#: before it).
LOWERED_STEPS = {
    "gptj-6b-1chip.steady": "b470aa16aac6",
    "gptj-6b-4chip.steady": "42d82d54bed3",
    "moonlight-16b-a3b-1chip.steady": "7306fc08c9c0",
    "granite-4.0-h-micro-1chip.steady": "a72eac94c094",
    "phi-4-mini-flash-reasoning-1chip.steady": "b8326d36469b",
    "mellum2-12b-a2.5b-1chip.steady": "b0cda0859e19",
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


@pytest.mark.parametrize("cell", LOWERED_STEPS)
def test_a_step_without_a_share_is_the_program_it_was(topo, cell):
    """The six cells whose model holds every expert or none (the GPT-J
    cells, Moonlight's and Mellum's whole layers, granite, phi) lower to
    the text recorded above: nothing they run was touched since. PR 61
    moved Moonlight's and Mellum's by intent (``moe.route``, which every
    expert layer runs, picks its scores without a gather or a scatter); the
    two GPT-J cells', granite's and phi's hold as recorded, so nothing of a
    cell without an expert layer moved. PR 62 moved granite's by intent
    (6dff69cbb9be before it): ``ops/ssd.py`` takes B and C with a group
    axis, so the kernels find a head block's group by a ``%`` and a ``//``
    and granite hands its one group over as [batch, S, 1, N]; the five
    others hold, Moonlight's and Mellum's through the expert's form
    (``ops/moe.py`` ``activation``) too."""
    step, args, _ = _a_cells_step(topo, cell)
    assert _lowered_digest(step, args) == LOWERED_STEPS[cell]


def test_flash_compiles_at_4_x_8k_with_grouped_kv_heads(topo):
    """LFM2-24B-A2B's attention layer: 32 query heads over 8 KV heads of 64
    at 4 sequences of 8192, forward and both backward kernels."""
    from ray_tpu.parallel.collectives import kernel_census
    q, k, v = _qkv(topo, (4, 8192, 32, 64))
    k = v = jax.ShapeDtypeStruct((4, 8192, 8, 64), jnp.bfloat16,
                                 sharding=k.sharding)

    def loss(q, k, v):
        return (_attend(q, k, v).astype(jnp.float32) ** 2).sum()

    text = jax.jit(jax.grad(loss, argnums=(0, 1, 2))).lower(
        q, k, v).compile().as_text()
    assert kernel_census(text) == {
        "flash_fwd": 1, "flash_bwd_dq": 1, "flash_bwd_dkv": 1}


# The forward kernel at every benchmark cell's attention: (B, S, H, D),
# KV heads, Dv, window.
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


@pytest.mark.parametrize("cell", CELL_ATTENTION)
def test_flash_forward_compiles_at_every_cells_shape(topo, cell):
    """The forward alone, at tiles of 512 x 512, its statistics lane-dense
    at every head size: one Mosaic call under the kernel's name, inside the
    scoped VMEM the compiler grants by default (the call states no limit of
    its own)."""
    from ray_tpu.parallel.collectives import kernel_census
    q, k, v, window = _cell_attention(
        cell, SingleDeviceSharding(topo.devices[0]))
    text = jax.jit(lambda q, k, v: flash_mod.flash_attention(
        q, k, v, True, 512, 512, None, window)).lower(
            q, k, v).compile().as_text()
    name = "flash_fwd_win" if window else "flash_fwd"
    assert kernel_census(text) == {name: 1}
    assert "vmem_limit_bytes" not in text


@pytest.mark.parametrize("cell", CELL_ATTENTION)
def test_flash_forward_keeps_its_statistics_lane_dense(cell):
    """The forward traced (no chip described, nothing compiled) at every
    cell's head sizes: the kernel's first two scratch buffers, the running
    maximum and sum, are [blk_q, 128] float32 whatever D and Dv are."""
    q, k, v, window = _cell_attention(cell)
    jaxpr = jax.make_jaxpr(lambda q, k, v: flash_mod._flash_forward(
        q, k, v, True, 512, 512, None, window))(q, k, v)
    (call,) = [eqn for eqn in jaxpr.eqns
               if eqn.primitive.name == "pallas_call"]
    grid = call.params["grid_mapping"]
    scratch = call.params["jaxpr"].invars[-grid.num_scratch_operands:]
    assert [(a.aval.shape, a.aval.dtype) for a in scratch[:2]] == [
        ((512, 128), jnp.float32)] * 2


def test_flash_compiles_at_32k_with_grouped_kv_heads(topo):
    """granite-4.0-h-micro's attention layer: 32 query heads over 8 KV heads
    of 64 at S = 32768 (2,080 executed tiles a head), the model's own score
    scale, forward and both backward kernels."""
    shape = (1, 32768, 32, 64)
    q, k, v = _qkv(topo, shape)
    k = v = jax.ShapeDtypeStruct((1, 32768, 8, 64), jnp.bfloat16,
                                 sharding=k.sharding)

    def loss(q, k, v):
        return flash_mod.flash_attention(
            q, k, v, True, 512, 512, 1.0 / 64).astype(jnp.float32).sum()

    grads = jax.jit(jax.grad(loss, argnums=(0, 1, 2))).lower(
        q, k, v).compile()
    assert grads.as_text().count("tpu_custom_call") >= 3
    assert flash_mod.causal_tile_census(32768, 512, 512)["executed"] == 2080


@pytest.mark.parametrize("window,names", [
    (4096, ("flash_fwd_win", "flash_bwd_dq_win", "flash_bwd_dkv_win")),
    (None, ("flash_fwd", "flash_bwd_dq", "flash_bwd_dkv"))])
def test_flash_compiles_at_16k_with_and_without_a_window(topo, window, names):
    """Trinity-Large-Preview's attention layers: 48 query heads over 8 KV
    heads of 128 at S = 16384, a window layer (4096: 252 executed tiles a
    head, under the windowed kernels' own names) and a full layer (528),
    forward and both backward kernels."""
    q, k, v = _qkv(topo, (1, 16384, 48, 128))
    k = v = jax.ShapeDtypeStruct((1, 16384, 8, 128), jnp.bfloat16,
                                 sharding=k.sharding)

    def loss(q, k, v):
        return flash_mod.flash_attention(
            q, k, v, True, 512, 512, None, window).astype(jnp.float32).sum()

    text = jax.jit(jax.grad(loss, argnums=(0, 1, 2))).lower(
        q, k, v).compile().as_text()
    assert text.count("tpu_custom_call") >= 3
    for name in names:
        assert re.search(rf"\b{name}\b", text), name
    assert "flash_fwd_win" in text if window else "flash_fwd_win" not in text
    assert flash_mod.window_tile_census(16384, window, 512, 512)[
        "executed"] == (252 if window else 528)


@pytest.mark.parametrize("window,names", [
    (512, ("flash_fwd_win", "flash_bwd_dq_win", "flash_bwd_dkv_win")),
    (None, ("flash_fwd", "flash_bwd_dq", "flash_bwd_dkv"))])
def test_flash_compiles_at_16k_with_heads_of_64_and_values_of_128(
        topo, window, names):
    """Phi-4-mini-flash-reasoning's differential attention: 40 query heads
    of 64 against K of 64 and ``V_g`` of 128 laid out to the query heads,
    at S = 16384: a window no wider than a tile (512: 63 executed tiles a
    head, every one cut) and causal (528), forward and both backward
    kernels; the window's outputs are not worth keeping, Trinity's are."""
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


def test_flash_step_compiles_on_four_chips(topo):
    """The gpt-1.3b train step, attn_impl='flash', on an fsdp=2 x tp=2
    mesh. Before the kernels ran under shard_map this failed in under a
    second: "Mosaic kernels cannot be automatically partitioned"."""
    mesh = build_mesh(MeshConfig(dp=1, fsdp=2, tp=2),
                      devices=topo.devices)
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


@pytest.mark.parametrize("cell,layers,kept", [
    ("granite-4.0-h-micro-1chip.steady", 2, True),
    ("moonlight-16b-a3b-1chip.steady", 4, True),
    ("gptj-6b-1chip.steady", 10, False)])
def test_a_cells_traced_step_runs_the_flash_forward_by_what_remat_keeps(
        topo, cell, layers, kept):
    """The census the three whole-step compiles below read off the compiled
    text (``slow`` since PR 62: 79 CPU-seconds), read off the cells' own
    traced steps, which the digests' cases have traced already: the
    forward kernel once a layer that attends where remat keeps its outputs
    (granite's 2 attention layers of 20 at S / Dv = 512, Moonlight's 1 + 3
    at 64), twice where a kept byte buys too little (GPT-J's 10 at 8); each
    backward kernel once."""
    from ray_tpu.parallel.collectives import kernel_census
    census = kernel_census(_a_cells_step(topo, cell)[2], a_step=True)
    assert [census[name] for name in ("flash_fwd", "flash_bwd_dq",
                                      "flash_bwd_dkv")] == \
        [layers if kept else 2 * layers, layers, layers]


@pytest.mark.slow  # PR 62: the traced case above holds the census
@pytest.mark.parametrize("shaped_like", ["granite-4.0-h-micro",
                                         "moonlight-16b-a3b", "gptj-6b"])
def test_step_runs_the_flash_forward_once_a_layer(topo, shaped_like):
    """The benchmark's three models, every width, sequence and batch
    theirs, cut to one layer of each kind that attends (granite: its
    attention layer; Moonlight: the dense layer and one expert layer, a
    scan each; GPT-J: one block), in the whole train step under full remat.
    At granite's S / Dv = 512 and Moonlight's 64 the layer scan keeps the
    forward kernel's output and log-sum-exp
    (``flash_attention.RESIDUAL_NAMES``), so the compiled step holds
    ``flash_fwd`` once a layer beside the two backward kernels; at GPT-J's
    8 a kept byte buys too little (``worth_keeping``) and the step runs
    the kernel again, as every step does with the names taken out
    (``tests/test_remat_residuals.py``)."""
    from ray_tpu.models import deepseek, granite
    from ray_tpu.parallel.collectives import kernel_census
    common = dict(attn_impl="flash", remat_policy="full", loss_chunk=4096,
                  param_dtype=jnp.bfloat16)
    if shaped_like == "granite-4.0-h-micro":
        cfg = granite.config(shaped_like, num_hidden_layers=1,
                             layer_types=("attention",), **common)
        layers, shape, kept = 1, (1, 32768), True
    elif shaped_like == "moonlight-16b-a3b":
        cfg = deepseek.config(shaped_like, num_hidden_layers=2, **common)
        layers, shape, kept = 2, (2, 8192), True
    else:
        cfg = gpt.config(shaped_like, n_layers=1, **common)
        layers, shape, kept = 1, (8, 2048), False
    assert cfg.remat
    mesh = build_mesh(MeshConfig(dp=1, fsdp=1, tp=1),
                      devices=topo.devices[:1])
    rules = ShardingRules()
    optimizer = memory_efficient_optimizer(learning_rate=1e-4)
    state = abstract_train_state(cfg, mesh, rules, optimizer)
    tokens = jax.ShapeDtypeStruct(
        shape, jnp.int32,
        sharding=NamedSharding(mesh, PartitionSpec(("dp", "fsdp"), None)))

    calls = kernel_census(
        make_train_step(cfg, mesh, rules, optimizer).lower(
            state, {"tokens": tokens, "targets": tokens}).compile().as_text())
    assert [calls[name] for name in ("flash_fwd", "flash_bwd_dq",
                                     "flash_bwd_dkv")] == \
        [layers if kept else 2 * layers, layers, layers]


@pytest.mark.parametrize("parallel_block", [True, False],
                         ids=["parallel", "sequential"])
def test_step_sends_what_fsdp_x_tp_needs(topo, parallel_block):
    """The GPT-J step (every width as published, two layers: the scan's
    body is what depth repeats) on fsdp=2 x tp=2, read by census: the
    model states where its activations live, so the step sends the
    layout's own traffic and no more. Between blocks the residual stream
    is split over tp along S, and the block's sum over tp and the gather
    that undoes it cross as exchanges of slices [B / fsdp, S / tp, d]
    (``exchange.gathered_product``, ``exchange.scattered_product``):
    no all-reduce,
    all-gather or reduce-scatter of the hidden shape in a scan body, every
    exchange a start and a done with matmuls scheduled between, and in the
    forward body no other collective between the two (a synchronous one
    would wait for the transfer in flight). A parallel block reduces its
    two tp-partial products together: two exchanges forward, three
    backward beside the two recomputed. fsdp stays the partitioner's: a
    layer's twelve weight gathers (ten inside matmul fusions) and six
    gradient reductions as before. The head and loss run on each data
    shard's own tokens, so nothing as wide as the vocabulary crosses chips
    inside the chunk loop."""
    from ray_tpu.parallel.collectives import census
    mesh = build_mesh(MeshConfig(dp=1, fsdp=2, tp=2), devices=topo.devices)
    cfg = gpt.config("gptj-6b", n_layers=2, attn_impl="flash",
                     remat_policy="full", loss_chunk=4096,
                     param_dtype=jnp.bfloat16, parallel_block=parallel_block)
    batch, fsdp, tp = 16, 2, 2
    rules = ShardingRules()
    optimizer = memory_efficient_optimizer(learning_rate=1e-4)
    state = abstract_train_state(cfg, mesh, rules, optimizer)
    tokens = jax.ShapeDtypeStruct(
        (batch, cfg.max_seq_len), jnp.int32,
        sharding=NamedSharding(mesh, PartitionSpec(("dp", "fsdp"), None)))
    ops = census(make_train_step(cfg, mesh, rules, optimizer).lower(
        state, {"tokens": tokens, "targets": tokens}).compile().as_text())

    def dims(op):
        return [d for _, d in op["arrays"]]

    def named_collective(name):
        return name.startswith(("all-", "reduce-scatter", "collective-"))

    hidden = (batch // fsdp, cfg.max_seq_len, cfg.d_model)
    piece = (batch // fsdp, cfg.max_seq_len // tp, cfg.d_model)
    in_loop = [op for op in ops if op["in_loop"]]
    assert not [op for op in in_loop if op["kind"] in (
        "all-reduce", "all-gather", "reduce-scatter")
        and {hidden, piece} & set(dims(op))]
    exchanges = [op for op in in_loop if op["kind"] == "collective-permute"
                 and dims(op) == [piece]]
    forward = [op for op in exchanges if "transpose(" not in op["op_name"]]
    backward = [op for op in exchanges if "transpose(" in op["op_name"]]
    assert all(op["is_async"] for op in exchanges), exchanges
    if parallel_block:
        assert (len(forward), len(backward)) == (2, 3), exchanges
        assert all(op["matmuls_between"] >= 1 for op in exchanges), exchanges
    else:  # it needs x + attention before the second norm: two gathers and
        # two sums, and the backward's recomputation holds three of them
        assert (len(forward), len(backward)) == (4, 7), exchanges
    assert not [name for op in forward for name in op["between"]
                if named_collective(name)
                and not name.startswith("collective-permute")], forward

    # fsdp: the weights' gathers and their gradients' sums, in the two
    # bodies of the layer scan (where the exchanges are)
    bodies = {op["computation"] for op in exchanges}
    assert len(bodies) == 2, bodies
    weights = [op for op in in_loop if op["computation"] in bodies
               and op["bytes"] >= 16e6 and op["kind"] != "collective-permute"]
    gathered = [op for op in weights if op["kind"] == "all-gather"]
    if parallel_block:
        assert len(gathered) == 12, gathered
        assert sum(op["is_async"] for op in gathered) == 10, gathered
    else:  # 15 before the exchanges: its backward body now gathers wq, wk
        # and wv for the recomputation and again for their transposes
        assert len(gathered) == 18, gathered
    assert len([op for op in weights if op["kind"] == "all-reduce"]) == 6

    exchanged = [op for op in ops if op["kind"] == "all-to-all"]
    assert len(exchanged) <= 2, exchanged  # the wte lookup and its scatter
    assert all(dtype == "bf16" for op in exchanged
               for dtype, _ in op["arrays"]), exchanged

    vocab = cfg.vocab_size // tp

    def wide(op):
        return any(vocab in d for d in dims(op))

    assert not [op for op in ops if op["in_loop"] and wide(op)]
    head = (cfg.d_model, vocab)
    gathered = [op for op in ops if op["kind"] == "all-gather"
                and dims(op) == [head]]
    summed = [op for op in ops
              if op["kind"] in ("all-reduce", "reduce-scatter") and wide(op)
              and len(dims(op)[0]) == 2]
    assert len(gathered) == 1 and len(summed) == 1, (gathered, summed)


def test_every_cells_step_was_loaded_and_traced_once(topo):
    """The file's last case: the cases above name their cells 24 times, and
    each cell's step was built and traced the first time, and only then.
    Alone, it names the cells itself: the count holds in any selection."""
    named = sorted(set(LOWERED_STEPS) | set(SHARE_SHAPES))
    first = {cell: _a_cells_step(topo, cell) for cell in named}
    traced = list(_TRACES)
    assert all(_a_cells_step(topo, cell) is first[cell] for cell in named)
    assert _TRACES == traced and len(traced) == len(set(traced))
    assert set(named) <= set(traced) == set(_CELLS)
