"""``flops_evabyte.py``: the counts against the program's own shapes, the
issue's figures, the kernels' tables and brute force."""

import os

import jax
import numpy as np
import pytest

import flops_evabyte as flops_eva
import harness


def _config():
    for entry in harness.load_spec()["configs"]:
        held = harness.load_json(os.path.join(harness.ROOT, entry["file"]))
        if held["program"]["family"] == "evabyte":
            return held
    raise AssertionError("no evabyte configuration")


CONFIG = _config()
SEQ = CONFIG["layout"]["seq_len"]


def test_parameters_are_the_programs_and_the_rows():
    """202.39 M a layer; what the chip holds is what the program's init
    makes; 6.49 B for the 32 published layers against the row's "6.5B"."""
    family = harness.load_module("families", "evabyte")
    cfg = family.config(CONFIG["program"])
    from ray_tpu.models import evabyte
    shapes = jax.eval_shape(lambda key: evabyte.init(cfg, key),
                            jax.random.PRNGKey(0))
    assert flops_eva.layer_params(CONFIG) == 202_391_552
    assert flops_eva.held_params(CONFIG) == sum(
        int(np.prod(a.shape)) for a in jax.tree.leaves(shapes))
    eight = dict(CONFIG, num_hidden_layers=8)
    assert round(flops_eva.held_params(eight) / 1e9, 3) == 1.631
    assert CONFIG["reduced"]["num_hidden_layers"]["published"] == 32
    assert round(flops_eva.published_params(CONFIG) / 1e9, 2) == 6.49


def test_pairs_at_the_cells_length_and_by_brute_force():
    assert SEQ == 32768
    assert flops_eva.pairs(CONFIG, SEQ) == 65_028_096
    assert flops_eva.causal_pairs(SEQ) == 536_887_296
    assert round(flops_eva.pairs_share(CONFIG, SEQ), 5) == 0.12112
    for seq, window, chunk in [(256, 64, 8), (320, 128, 16), (100, 40, 4)]:
        small = {"window_size": window, "chunk_size": chunk}
        t = np.arange(seq)
        start = t - t % window
        assert flops_eva.pairs(small, seq) == int(
            (t - start + 1).sum() + (start // chunk).sum())


def test_pairs_are_the_kernels_tables():
    """The count the rooflines use is the count the program's gauge makes
    from its table and mask."""
    import sys

    import ray_tpu.ops  # noqa: F401
    flash = sys.modules["ray_tpu.ops.flash_attention"]
    census = flash.eva_tile_census(4096, 1024, 16, 512, 512)
    small = {"window_size": 1024, "chunk_size": 16}
    assert census["counted_pairs"] == census["pairs"] \
        == flops_eva.pairs(small, 4096)
    assert flops_eva.stacked_rows(small, 4096, 512) == 512 + 4096


def test_flops_a_token_by_part():
    """The issue's count at eight layers: 10.6 GFLOP a byte, attention 0.78
    of them (7.4 %), the head 0.6 %; this cell's depth scales the layers'
    parts."""
    eight = flops_eva.flops_by_part(dict(CONFIG, num_hidden_layers=8), SEQ)
    total = sum(eight.values())
    assert round(total / 1e9, 1) == 10.6
    assert round(eight["attention_over_pairs"] / 1e9, 2) == 0.78
    assert round(eight["attention_over_pairs"] / total, 3) == 0.074
    assert round(eight["head"] / total, 3) == 0.006
    assert eight["pooling"] / total < 1e-4
    here = flops_eva.flops_by_part(CONFIG, SEQ)
    layers = CONFIG["num_hidden_layers"]
    for part in ("attention_projections", "attention_over_pairs", "ffn"):
        assert here[part] * 8 == eight[part] * layers
    assert flops_eva.model_flops_per_token(CONFIG, SEQ) == sum(here.values())


@pytest.mark.parametrize("kernel,products", [
    ("eva_fwd", 2), ("eva_bwd_dq", 3), ("eva_bwd_dkv", 4)])
def test_a_kernels_call(kernel, products):
    call = flops_eva.attention_call(kernel, CONFIG, 1, SEQ)
    assert call["flops"] == 32 * 65_028_096 * 2.0 * 128 * products
    rows = 2048 + SEQ
    arrays = {"eva_fwd": (2, 2), "eva_bwd_dq": (2, 3),
              "eva_bwd_dkv": (4, 2)}[kernel]
    assert call["bytes"] == 32 * 128 * 2 * (arrays[0] * rows
                                            + arrays[1] * SEQ)
    # Bound by its products, not its bytes, on a v5e.
    assert call["flops"] / 197e12 > 3 * call["bytes"] / 819e9


def test_step_kernel_calls_and_the_rule_that_keeps_nothing():
    layers = CONFIG["num_hidden_layers"]
    assert not flops_eva.keeps_forward(CONFIG, SEQ)       # 2048 + 1920 keys
    assert flops_eva.keeps_forward(dict(CONFIG, chunk_size=8), SEQ)
    calls = flops_eva.step_kernel_calls(CONFIG, 1, SEQ, True)
    assert {k: v["calls"] for k, v in calls.items()} == {
        "eva_fwd": 2 * layers, "eva_bwd_dq": layers, "eva_bwd_dkv": layers}
    assert flops_eva.step_kernel_calls(CONFIG, 1, SEQ, False)[
        "eva_fwd"]["calls"] == layers


def test_the_poolings_bytes():
    call = flops_eva.pool_call(CONFIG, 1, SEQ)
    assert call["bytes"] == 2 * 32768 * 4096 * 2 * (1 + 1 / 16)
    assert call["bytes"] / 819e9 > call["flops"] / 197e12   # bound by bytes
