"""The readers this family's cell adds, each on a record made by hand, and
None where its input is absent (another family's cell, an untraced run, a
program without the counter)."""

import pytest

import flops_afmoe
import harness
import program_counters

PERIOD = ["sliding_attention"] * 3 + ["full_attention"]
CONFIG = {
    "model_type": "afmoe", "hidden_size": 3072, "head_dim": 128,
    "num_hidden_layers": 5, "num_dense_layers": 1,
    "layer_types": PERIOD * 15, "sliding_window": 4096,
    "num_attention_heads": 48, "num_key_value_heads": 8,
    "intermediate_size": 12288, "moe_intermediate_size": 3072,
    "num_experts": 8, "num_experts_per_tok": 4, "num_shared_experts": 1,
    "vocab_size": 25024,
    "deployment": {"experts_held": {"first": 0, "count": 8, "of": 256}},
    "program": {"family": "afmoe", "preset": "trinity-large-preview",
                "overrides": {"num_hidden_layers": 5, "num_dense_layers": 1,
                              "experts_held": [0, 8], "vocab_size": 25024}},
    "layout": {"batch": 1, "seq_len": 16384}}
STEPS = 8
RECORD = {
    "cell": {"name": "x", "chips": 1, "config": CONFIG},
    "device": {"kind": "TPU v5 lite"},
    "model": {"seq_len": 16384},
    "window": {"t0": 100.0, "unit_ends": [102.0, 104.0, 106.0, 108.0],
               "steps_per_unit": 1, "tokens_per_step": 16384},
    "trace": {"busy_s": 16.0, "mosaic_s": 6.0,
              "steps_device_s": [2.0] * STEPS,
              "device_ops": [["fusion.1", 2.0], ["flash_bwd_dkv_win", 0.9]]},
}
PEAK, BANDWIDTH = 197e12, 819e9


def read(name, record=RECORD):
    return harness.load_module("layer_metrics", name).read(record)


def without(*keys):
    record = dict(RECORD)
    for key in keys:
        record[key] = None
    return record


OTHER = dict(RECORD, cell=dict(RECORD["cell"], config={"n_layer": 2}))


@pytest.fixture
def counters(monkeypatch):
    """The program's registry as a dictionary the test fills."""
    held = {}
    monkeypatch.setattr(program_counters, "value", held.get)
    return held


def test_model_mfu():
    want = 16384.0 / 2.0 * 6_227_361_792.0 / PEAK
    assert abs(read("window.model_mfu") - want) < 1e-12
    assert read("window.model_mfu", OTHER) is None


def test_held_share(counters):
    assert read("moe.held_share") is None  # a parent without the counter
    counters["ray_tpu_train_moe_tokens_total"] = 9000.0
    assert read("moe.held_share") is None
    counters["ray_tpu_train_moe_routed_total"] = 262144.0
    assert read("moe.held_share") == 9000.0 / 262144.0


def expected_roofline(share):
    calls = flops_afmoe.step_kernel_calls(CONFIG, 1, 16384, 512, 512, True,
                                          share)
    least = sum(c["calls"] * max(c["flops"] / PEAK, c["bytes"] / BANDWIDTH)
                for c in calls.values())
    return 100.0 * least * STEPS / 6.0


def test_window_mosaic_roofline(counters):
    # Without the counters: the even share.
    assert abs(read("kernel.window_mosaic_roofline")
               - expected_roofline(None)) < 1e-9
    counters["ray_tpu_train_moe_tokens_total"] = 2 * 8192.0
    counters["ray_tpu_train_moe_routed_total"] = 262144.0
    got = read("kernel.window_mosaic_roofline")
    assert abs(got - expected_roofline(1 / 16)) < 1e-9
    assert expected_roofline(None) < got < 100.0


@pytest.mark.parametrize("record", [without("trace"), OTHER, dict(
    RECORD, trace=dict(RECORD["trace"], mosaic_s=0.0))],
    ids=["untraced", "another_family", "no_kernel_ran"])
def test_the_roofline_reader_finds_nothing_to_read(record, counters):
    assert read("kernel.window_mosaic_roofline", record) is None


@pytest.mark.parametrize("kernel,units,secs", [
    ("flash_fwd", 2, None), ("flash_bwd_dq", 3, None),
    ("flash_bwd_dkv", 4, 0.9)])
def test_window_flash_rooflines(kernel, units, secs):
    """One call's least time over the busiest instruction's time a call:
    the two expert window layers in a row are the longest run, so that
    instruction is called twice a step. None where the kernel is not among
    the trace's operations."""
    import window_rooflines
    assert window_rooflines.longest_window_run(CONFIG) == 2
    name = f"kernel.{kernel}_win_roofline"
    if secs is None:
        assert read(name) is None
        record = dict(RECORD, trace=dict(RECORD["trace"], device_ops=[
            [kernel + "_win.3", 0.2], [kernel + "_win.17", 0.5],
            [kernel + ".2", 0.7], [kernel + "_win", 0.1]]))
        secs = 0.5
    else:
        record = RECORD
    flops_ = 48 * 252 * units * 2 * 512 * 512 * 128
    want = 100.0 * (flops_ / PEAK) / (secs / (2 * STEPS))
    assert abs(read(name, record) - want) < 1e-9
    assert read(name, without("trace")) is None
    assert read(name, OTHER) is None
    # A sequence the window holds whole runs the causal kernels.
    short = dict(record, cell=dict(record["cell"], config=dict(
        CONFIG, layout={"batch": 1, "seq_len": 4096})))
    assert read(name, short) is None
