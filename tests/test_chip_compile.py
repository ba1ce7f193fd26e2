"""Ask the TPU compiler, without a TPU: the benchmark cells' own steps,
traced for a described ``v5e:2x2`` topology, each cell once for the file.

Interpret mode (every other test of the flash kernels) cannot see what
Mosaic refuses: a misaligned tile, too much VMEM, a kernel GSPMD cannot
partition. These compiles can, at no chip time (on-chip-measurement
guide §2.3). Nothing runs, so they say nothing about results or speed.

Three files by what they compile, a worker each under ``--dist loadfile``:
this one (every case that reads a cell's step through ``_a_cells_step``:
the censuses of its jaxpr, the digests of its lowered text, what the
reference check's program reserves; its last case holds every cell to one
trace), ``tests/test_chip_compile_mesh.py`` (the programs over the four
chips, whole steps compiled, the flash forward at every cell's shape) and
``tests/test_chip_compile_kernels.py`` (the kernels at the published
widths); a family's own kernels and cell are
``tests/test_chip_compile_<family>.py``. ``tests/chip_compile.py`` holds
what they share.

Two processes describing a TPU topology at once collide on libtpu's lock
unless ``ALLOW_MULTIPLE_LIBTPU_LOAD=1`` lets the second in (tier-1's command
sets it), so the topology is described in a fixture, by the worker that runs
a file, and never at import. Every test skips where it cannot be described
(no libtpu).
"""

import json
import os
import re
import sys

import jax
import jax.numpy as jnp
import pytest

from chip_compile import (SHARE_SHAPES, _lowered_digest,  # noqa: F401
                          compile_for_tpu, topo)
from ray_tpu.parallel import MeshConfig, build_mesh


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
    layers' once; every backward kernel once a layer (attention's one,
    ``flash_bwd``, where ``flash_bwd_dq`` and ``flash_bwd_dkv`` were one
    each until PR 69)."""
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
        "flash_fwd_win": 2 * window, "flash_bwd_win": window,
        "flash_fwd": causal, "flash_bwd": causal}


def test_the_glm_cells_step_runs_its_kernels_as_counted(topo):
    """``glm-5.2-1chip.steady``'s own step, traced for the described chip,
    by the layers its configuration runs: the selection's forward kernel
    twice a layer (S = 4096 is under 32 x 256, its outputs are not worth
    keeping: ``flash_attention.worth_keeping``), the two backward kernels
    once a layer, the head-summed probabilities once a layer that owns an
    indexer (its loss is part of the rematerialised block, and ``"full"``
    keeps the one array the loss's backward pass reads:
    ``dsa.LOSS_GRADIENT_NAME``) and so the indexer's forward kernel, its
    backward kernel once, the share's way back to tokens in the expert
    layers, and no causal flash kernel."""
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
        "dsa_bwd_dkv": len(layers), "dsa_probs": owners,
        "dsa_index_fwd": owners, "dsa_index_bwd": owners}
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
    "gptj-6b-1chip.steady": "11f93ff03db3",
    "gptj-6b-4chip.steady": "367d2486e9be",
    "moonlight-16b-a3b-1chip.steady": "9ca9faa42afe",
    "granite-4.0-h-micro-1chip.steady": "8b588e948e41",
    "phi-4-mini-flash-reasoning-1chip.steady": "3766d4b30ed8",
    "mellum2-12b-a2.5b-1chip.steady": "3afaaacc42e1",
}


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
    (``ops/moe.py`` ``activation``) too. PR 69 moved all six by intent
    (b470aa16aac6, 42d82d54bed3, 7306fc08c9c0, a72eac94c094, b8326d36469b
    and b0cda0859e19 before it): every one attends through
    ``ops/flash_attention.py``, whose backward is one kernel where it was
    two."""
    step, args, _ = _a_cells_step(topo, cell)
    assert _lowered_digest(step, args) == LOWERED_STEPS[cell]


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
    at 64), twice where a kept byte buys too little (GPT-J's 10 at 8); the
    one backward kernel once, and nothing of the pair it replaced."""
    from ray_tpu.parallel.collectives import kernel_census
    census = kernel_census(_a_cells_step(topo, cell)[2], a_step=True)
    assert {name: calls for name, calls in census.items()
            if str(name).startswith("flash")} == {
        "flash_fwd": layers if kept else 2 * layers, "flash_bwd": layers}


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
