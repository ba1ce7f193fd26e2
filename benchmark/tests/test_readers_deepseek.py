"""The readers this family's cell adds, each on a record made by hand, and
None where its input is absent (another family's cell, an untraced run, a
program without the counters, a kernel outside the trace's ten longest)."""

import pytest

import flops_deepseek
import harness

CONFIG = {
    "model_type": "deepseek_v3", "hidden_size": 2048,
    "num_attention_heads": 16, "kv_lora_rank": 512, "qk_nope_head_dim": 128,
    "qk_rope_head_dim": 64, "v_head_dim": 128, "intermediate_size": 11264,
    "moe_intermediate_size": 1408, "n_routed_experts": 64,
    "num_experts_per_tok": 6, "n_shared_experts": 2,
    "first_k_dense_replace": 1, "vocab_size": 163840, "num_hidden_layers": 4,
    "program": {"family": "deepseek_v3", "preset": "moonlight-16b-a3b",
                "overrides": {"num_hidden_layers": 4}},
    "layout": {"batch": 2, "seq_len": 8192}}
STEPS = 8
RECORD = {
    "cell": {"name": "x", "chips": 1, "config": CONFIG},
    "device": {"kind": "TPU v5 lite"},
    "model": {"seq_len": 8192},
    "window": {"t0": 100.0, "unit_ends": [101.0, 102.0, 103.0, 104.0],
               "steps_per_unit": 1, "tokens_per_step": 16384},
    "trace": {"busy_s": 7.9, "mosaic_s": 4.0,
              "steps_device_s": [1.0] * STEPS,
              "device_ops": [["fusion.1", 2.0], ["flash_fwd.3", 0.48],
                             ["flash_fwd.1", 0.16], ["flash_bwd_dkv", 0.96],
                             ["gmm.45", 0.24], ["gmm.42", 0.12]]},
}
PEAK, BANDWIDTH = 197e12, 819e9


def read(name, record=RECORD):
    return harness.load_module("layer_metrics", name).read(record)


def without(*keys):
    record = dict(RECORD)
    for key in keys:
        record[key] = None
    return record


def test_active_mfu():
    want = 16384.0 * 5_013_504_000.0 / 197e12
    assert abs(read("moe.active_mfu") - want) < 1e-12
    other = dict(RECORD, cell=dict(RECORD["cell"], config={"n_layer": 2}))
    assert read("moe.active_mfu", other) is None


def test_kernel_rooflines_read_the_busiest_instruction():
    # flash_fwd.3 is the expert layers' scan: 3 layers x 8 steps calls.
    call = flops_deepseek.flash_call("flash_fwd", 32, 8192, 192, 128, 512,
                                     512)
    least = max(call["flops"] / PEAK, call["bytes"] / BANDWIDTH)
    assert least == call["flops"] / PEAK  # compute bound
    want = 100.0 * least / (0.48 / 24)
    assert abs(read("kernel.flash_fwd_roofline") - want) < 1e-9
    call = flops_deepseek.flash_call("flash_bwd_dkv", 32, 8192, 192, 128,
                                     512, 512)
    assert abs(read("kernel.flash_bwd_dkv_roofline")
               - 100.0 * call["flops"] / PEAK / (0.96 / 24)) < 1e-9
    # Not among the ten longest operations: nothing to read.
    assert read("kernel.flash_bwd_dq_roofline") is None
    one = 2.0 * 98304 * 2048 * 1408
    assert abs(read("kernel.gmm_roofline")
               - 100.0 * one / PEAK / (0.24 / 24)) < 1e-9


def test_mosaic_roofline():
    executed = sum(flops_deepseek.step_kernel_flops(
        CONFIG, 2, 8192, 512, 512, True).values())
    assert abs(read("kernel.mosaic_roofline")
               - 100.0 * executed * STEPS / (4.0 * PEAK)) < 1e-9


@pytest.mark.parametrize("name", [
    "kernel.mosaic_roofline", "kernel.flash_fwd_roofline",
    "kernel.flash_bwd_dq_roofline", "kernel.flash_bwd_dkv_roofline",
    "kernel.gmm_roofline"])
def test_kernel_readers_without_a_trace_or_on_another_family(name):
    assert read(name, without("trace")) is None
    other = dict(RECORD, cell=dict(RECORD["cell"], config={"n_layer": 2}))
    assert read(name, other) is None


def test_counters_read_the_program_s_registry():
    from ray_tpu._private import builtin_metrics
    from ray_tpu.util import metrics
    metrics.clear_registry()
    try:
        # A program that registered nothing: the parent of the PR.
        assert read("moe.assigned_share") is None
        assert read("moe.load_max_over_mean") is None
        builtin_metrics.train_moe_assignments().inc(3 * 294912.0)
        builtin_metrics.train_moe_tokens().inc(3 * 294912.0)
        builtin_metrics.train_moe_expert_load().set(2.04)
        assert read("moe.assigned_share") == 1.0
        assert read("moe.load_max_over_mean") == 2.04
        builtin_metrics.train_moe_tokens().inc(294912.0)
        assert read("moe.assigned_share") == 0.75  # a dropped step's worth
    finally:
        metrics.clear_registry()
