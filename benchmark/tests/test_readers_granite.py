"""The readers this family's cell adds, each on a record made by hand, and
None where its input is absent (another family's cell, an untraced run)."""

import pytest

import flops_granite
import harness

PERIOD = ["mamba"] * 5 + ["attention"] + ["mamba"] * 4
CONFIG = {
    "model_type": "granitemoehybrid", "hidden_size": 2048,
    "num_hidden_layers": 20, "layer_types": PERIOD * 4,
    "num_attention_heads": 32, "num_key_value_heads": 8,
    "shared_intermediate_size": 8192, "vocab_size": 100352,
    "mamba_n_heads": 64, "mamba_d_head": 64, "mamba_d_state": 128,
    "mamba_n_groups": 1, "mamba_chunk_size": 256,
    "program": {"family": "granitemoehybrid",
                "preset": "granite-4.0-h-micro",
                "overrides": {"num_hidden_layers": 20}},
    "layout": {"batch": 1, "seq_len": 32768}}
STEPS = 8
RECORD = {
    "cell": {"name": "x", "chips": 1, "config": CONFIG},
    "device": {"kind": "TPU v5 lite"},
    "model": {"seq_len": 32768},
    "window": {"t0": 100.0, "unit_ends": [104.0, 108.0, 112.0, 116.0],
               "steps_per_unit": 1, "tokens_per_step": 32768},
    "trace": {"busy_s": 32.0, "mosaic_s": 12.0,
              "steps_device_s": [4.0] * STEPS,
              "device_ops": [["fusion.1", 2.0], ["ssd_bwd.2", 0.5],
                             ["ssd_bwd", 0.3], ["ssd_bwd.1", 0.2],
                             ["flash_bwd_dkv", 0.96], ["ssd_fwd.3", 0.24]]},
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


def test_model_mfu():
    want = 32768.0 / 4.0 * 11_939_610_624.0 / PEAK
    assert abs(read("ssm.model_mfu") - want) < 1e-12
    assert read("ssm.model_mfu", OTHER) is None


def test_mosaic_share():
    assert read("kernel.mosaic_share") == 12.0 / 32.0
    assert read("kernel.mosaic_share", without("trace")) is None
    # A parent that ran no kernel reads zero, not nothing.
    none = dict(RECORD, trace=dict(RECORD["trace"], mosaic_s=0.0))
    assert read("kernel.mosaic_share", none) == 0.0


def least(call):
    return max(call["flops"] / PEAK, call["bytes"] / BANDWIDTH)


def test_hybrid_mosaic_roofline():
    calls = flops_granite.step_kernel_calls(CONFIG, 1, 32768, 512, 512, True)
    want = sum(c["calls"] * least(c) for c in calls.values())
    assert abs(read("kernel.hybrid_mosaic_roofline")
               - 100.0 * want * STEPS / 12.0) < 1e-9
    assert read("kernel.hybrid_mosaic_roofline") < 100.0


@pytest.mark.parametrize("name", ["kernel.hybrid_mosaic_roofline",
                                  "kernel.mosaic_share"])
def test_kernel_readers_without_a_trace(name):
    assert read(name, without("trace")) is None


def test_the_roofline_reader_on_another_family():
    assert read("kernel.hybrid_mosaic_roofline", OTHER) is None
