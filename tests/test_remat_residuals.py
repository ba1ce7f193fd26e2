"""What the layer scan's rematerialisation keeps of the flash kernel
(``models/lm.py: scan_blocks``, ``ops/flash_attention.py: RESIDUAL_NAMES``):
the forward kernel's output and log-sum-exp are named residuals that both
remat policies keep, so the backward pass recomputes the block's XLA
operations and not the kernel, from the sequence length at which a kept
byte buys enough (``flash_attention.worth_keeping``: S / Dv >= 32). Read off
the gradient's jaxpr by ``parallel/collectives.py: kernel_census`` for every
family of model, on one device and under CPU meshes, and held against the
same program with the names taken out (the backward pass then runs
``flash_fwd`` again), whose loss and gradients are the same bits.

Every model here has heads of 16, so sequences of 512 (four tiles of 128)
are on the keeping side of the line and sequences of 128 (one tile) are
not; a sequence that is no multiple of 128 takes the blockwise path, which
has no kernel to count.
"""

import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import ray_tpu.ops  # noqa: F401 - loads ray_tpu.ops.flash_attention
from ray_tpu.models import deepseek, gpt, granite
from ray_tpu.parallel import MeshConfig, ShardingRules, build_mesh, \
    mesh as mesh_mod, shard_tree
from ray_tpu.parallel.collectives import kernel_census
from ray_tpu.parallel.train_step import make_eval_step

# ray_tpu.ops binds the function under the module's name.
flash_mod = sys.modules["ray_tpu.ops.flash_attention"]

SEQ, SHORT = 512, 128
# family -> (module, preset, layer scans that hold an attention layer, what
# makes its heads 16 wide where the preset does not)
FAMILIES = {
    "gpt": (gpt, "gpt-tiny", 1, {}),
    # the dense run and the experts'
    "deepseek": (deepseek, "deepseek-tiny", 2, {}),
    # mamba, attention, mamba x 2
    "granite": (granite, "granite-tiny", 1, {"num_attention_heads": 8}),
}
POLICIES = ("full", "selective")
MESHES = {"dp2": MeshConfig(dp=2, fsdp=1, tp=1),
          "fsdp2_tp2": MeshConfig(dp=1, fsdp=2, tp=2)}


def _config(family, **overrides):
    model, preset, scans, heads_of_16 = FAMILIES[family]
    return model, scans, model.config(
        preset, attn_impl="flash", attn_blk_q=128, attn_blk_k=128,
        **heads_of_16, **overrides)


def _batch(rows=2, seq=SEQ):
    rng = np.random.default_rng(7)
    return (jnp.asarray(rng.integers(0, 256, (rows, seq)), jnp.int32),
            jnp.asarray(rng.integers(0, 256, (rows, seq)), jnp.int32))


def _loss_and_grads(model, cfg, mesh_cfg=None, rows=2, run=True, seq=SEQ):
    """(kernel calls in the jaxpr of, what comes out of) the model's loss
    and gradients, traced under the mesh and its rules as a train step
    traces it; only traced, and None for what comes out, if not ``run``."""
    params = model.init(cfg, jax.random.PRNGKey(0))
    tokens, targets = _batch(rows, seq)
    mesh = rules = None
    if mesh_cfg is not None:
        mesh = build_mesh(mesh_cfg, devices=jax.devices()[:mesh_cfg.dp
                                                          * mesh_cfg.fsdp
                                                          * mesh_cfg.tp])
        rules = ShardingRules()
        params = shard_tree(params, mesh, model.param_specs(cfg, rules))
    fn = jax.value_and_grad(
        lambda p: model.loss_fn(p, cfg, tokens, targets, None)[0])
    previous = mesh_mod.current_mesh(), mesh_mod.current_rules()
    mesh_mod.set_current_mesh(mesh, rules)
    try:
        return (kernel_census(jax.make_jaxpr(fn)(params)),
                jax.device_get(jax.jit(fn)(params)) if run else None)
    finally:
        mesh_mod.set_current_mesh(*previous)


@pytest.fixture
def names_stripped(monkeypatch):
    """The custom VJP's residuals without their names: no policy can keep
    them, and ``"full"`` is ``nothing_saveable`` again."""
    def strip():
        monkeypatch.setattr(flash_mod, "checkpoint_name",
                            lambda value, name: value)
    return strip


def _assert_same_bits(got, want):
    assert np.array_equal(got[0], want[0]), (got[0], want[0])
    for (path, a), b in zip(jax.tree_util.tree_leaves_with_path(got[1]),
                            jax.tree.leaves(want[1])):
        np.testing.assert_array_equal(a, b,
                                      err_msg=jax.tree_util.keystr(path))


def test_the_census_reads_compiled_text_and_jaxprs():
    text = "\n".join([
        '  %a.1 = bf16[4,8] custom-call(%x), custom_call_target='
        '"tpu_custom_call", metadata={op_name="jit(step)/jvp(while)/body/'
        'checkpoint/block/flash_fwd/pallas_call" stack_frame_id=1}',
        '  %b.2 = bf16[4,8] custom-call(%x), custom_call_target='
        '"tpu_custom_call", metadata={op_name="jit(f)/transpose(jvp('
        'flash_bwd_dkv))/pallas_call"}',
        '  %c.3 = bf16[4,8] custom-call(%x), custom_call_target='
        '"tpu_custom_call", metadata={op_name="jit(f)/jvp(flash_fwd)/'
        'pallas_call"}',
        '  %d.4 = f32[4] custom-call(%x), custom_call_target="Sharding", '
        'metadata={op_name="jit(f)/flash_fwd/pallas_call"}',
    ])
    assert kernel_census(text) == {"flash_fwd": 2, "flash_bwd_dkv": 1}
    assert kernel_census("") == {}
    q = jnp.ones((1, SEQ, 1, 64))
    forward = jax.make_jaxpr(
        lambda q: flash_mod.flash_attention(q, q, q, True, 128, 128))(q)
    assert kernel_census(forward) == {"flash_fwd": 1}
    assert kernel_census(forward.jaxpr) == {"flash_fwd": 1}


@pytest.mark.parametrize("policy", POLICIES)
@pytest.mark.parametrize("family", FAMILIES)
def test_backward_runs_the_forward_kernel_once(family, policy,
                                               names_stripped):
    """One ``flash_fwd`` for every layer scan with attention in it, beside
    its one backward kernel; without the names the backward scan holds a
    second one (as it did before the names were there)."""
    model, scans, cfg = _config(family, remat=True, remat_policy=policy)
    kept, _ = _loss_and_grads(model, cfg, run=False)
    assert {name: calls for name, calls in kept.items()
            if str(name).startswith("flash")} == {
        "flash_fwd": scans, "flash_bwd": scans}
    names_stripped()
    rerun, _ = _loss_and_grads(model, cfg, run=False)
    assert rerun["flash_fwd"] == 2 * scans
    assert {k: n for k, n in rerun.items() if k != "flash_fwd"} == \
        {k: n for k, n in kept.items() if k != "flash_fwd"}


@pytest.mark.parametrize("seq_len,head,kept", [
    (32768, 64, True), (8192, 128, True), (2048, 256, False),
    (SEQ, 16, True), (SHORT, 16, False)])
def test_the_line_is_at_32_head_widths(seq_len, head, kept):
    """The three benchmark shapes the line was set by (PERF.md §6, PR 30)
    and the two of this file."""
    assert flash_mod.worth_keeping(seq_len, head) is kept


@pytest.mark.parametrize("family", FAMILIES)
def test_a_short_sequence_runs_the_kernel_again(family, names_stripped):
    """Below the line the outputs carry no name: the step is the one
    without them, ``flash_fwd`` twice a scan."""
    model, scans, cfg = _config(family, remat=True, remat_policy="full")
    named, _ = _loss_and_grads(model, cfg, run=False, seq=SHORT)
    assert named["flash_fwd"] == 2 * scans
    names_stripped()
    assert _loss_and_grads(model, cfg, run=False, seq=SHORT)[0] == named


@pytest.mark.parametrize("policy", POLICIES)
@pytest.mark.parametrize("family", FAMILIES)
def test_kept_residuals_are_the_recomputed_bits(family, policy,
                                                names_stripped):
    model, _, cfg = _config(family, remat=True, remat_policy=policy)
    _, kept = _loss_and_grads(model, cfg)
    names_stripped()
    _, rerun = _loss_and_grads(model, cfg)
    assert np.isfinite(kept[0])
    _assert_same_bits(kept, rerun)


@pytest.mark.parametrize("program", ["no_remat", "eval"])
@pytest.mark.parametrize("family", FAMILIES)
def test_names_change_nothing_without_remat(family, program, names_stripped):
    """``remat=False`` keeps every residual anyway, and a forward pass has
    none: a name is an identity there, and the kernels are counted as
    before."""
    model, scans, cfg = _config(family, remat=False)

    def census():
        if program == "no_remat":
            return _loss_and_grads(model, cfg, run=False)[0]
        mesh = build_mesh(MeshConfig(dp=1, fsdp=1, tp=1),
                          devices=jax.devices()[:1])
        tokens, targets = _batch()
        params = model.init(cfg, jax.random.PRNGKey(0))
        return kernel_census(jax.make_jaxpr(make_eval_step(cfg, mesh))(
            params, {"tokens": tokens, "targets": targets}))

    named = census()
    assert named["flash_fwd"] == scans
    assert ("flash_bwd" in named) == (program == "no_remat")
    names_stripped()
    assert census() == named


@pytest.mark.parametrize("mesh", MESHES)
@pytest.mark.parametrize("family", FAMILIES)
def test_kept_residuals_under_a_mesh(family, mesh, names_stripped):
    """The kernels run per shard under ``shard_map`` and their named
    outputs leave it as residuals: still one ``flash_fwd`` a scan, and the
    loss and gradients of the step that runs it twice."""
    model, scans, cfg = _config(family, remat=True, remat_policy="full")
    census, kept = _loss_and_grads(model, cfg, MESHES[mesh], rows=4)
    assert census["flash_fwd"] == scans
    names_stripped()
    census, rerun = _loss_and_grads(model, cfg, MESHES[mesh], rows=4)
    assert census["flash_fwd"] == 2 * scans
    _assert_same_bits(kept, rerun)
