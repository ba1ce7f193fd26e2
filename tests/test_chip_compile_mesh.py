"""Ask the TPU compiler, without a TPU: the programs that exist only
across chips (the flash kernels under ``shard_map``, a flash step on fsdp=2
x tp=2, what the GPT-J step sends there), ``slow`` whole steps of one layer
compiled for one chip, and the flash forward alone at every benchmark
cell's attention shape, for a described ``v5e:2x2`` topology.
``tests/test_chip_compile.py`` has why such compiles exist and how they are
steered; this file is apart from it so that its three long compiles are
another worker's. The forward's twenty-two short cases are here and not
with the other kernels because pytest-xdist 3.8's ``--dist loadfile`` hands
out the files with the most cases first (``xdist/scheduler/loadscope.py``,
``loadscopereorder``): a file of four cases and 150 s starts among the last
and ends the run alone. Measured at PR 66: in a whole run of the tree that
had them with the kernels this file ran alone for the run's last 190 s, and
that hand-out replayed on the junit times of three whole runs of this tree
ends 35 to 46 s later with the cases there than here.
"""

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import NamedSharding, PartitionSpec, SingleDeviceSharding

from chip_compile import (CELL_ATTENTION, MLA_SHAPE, MLA_V,  # noqa: F401
                          _cell_attention, _qkv, compile_for_tpu, flash_mod,
                          topo)
from ray_tpu.models import gpt, lm
from ray_tpu.parallel import MeshConfig, ShardingRules, build_mesh
from ray_tpu.parallel.train_step import (abstract_train_state,
                                         make_train_step,
                                         memory_efficient_optimizer)


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
    from ray_tpu.parallel.collectives import kernel_census
    assert kernel_census(text) == {"flash_fwd": 1, "flash_bwd": 1}


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
    assert {name: n for name, n in calls.items()
            if str(name).startswith("flash")} == {
        "flash_fwd": layers if kept else 2 * layers, "flash_bwd": layers}


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
