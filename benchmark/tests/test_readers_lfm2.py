"""The readers this family's cell adds, each on a record made by hand, and
None where its input is absent (another family's cell, an untraced run)."""

import os

import pytest

import flops_lfm2
import harness
import program_counters


def _config():
    for entry in harness.load_spec()["configs"]:
        held = harness.load_json(os.path.join(harness.ROOT, entry["file"]))
        if held["program"]["family"] == "lfm2_moe":
            return held
    raise AssertionError("no lfm2_moe configuration")


CONFIG = _config()
STEPS = 8
RECORD = {
    "cell": {"name": "x", "chips": 1, "config": CONFIG},
    "device": {"kind": "TPU v5 lite"},
    "model": {"seq_len": 8192},
    "window": {"t0": 100.0, "unit_ends": [102.0, 104.0, 106.0, 108.0],
               "steps_per_unit": 1, "tokens_per_step": 32768},
    "trace": {"busy_s": 16.0, "mosaic_s": 6.0,
              "steps_device_s": [2.0] * STEPS,
              "device_ops": [["fusion.1", 2.0], ["short_conv_bwd.5", 0.09],
                             ["short_conv_bwd", 0.03]]},
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
    want = 32768.0 / 2.0 * 3_366_408_192.0 / PEAK
    assert abs(read("lfm2.model_mfu") - want) < 1e-12
    assert read("lfm2.model_mfu", OTHER) is None


def expected_roofline(share):
    calls = flops_lfm2.step_kernel_calls(
        CONFIG, 4, 8192, 512, 512, True, True, share)
    least = sum(c["calls"] * max(c["flops"] / PEAK, c["bytes"] / BANDWIDTH)
                for c in calls.values())
    return 100.0 * least * STEPS / 6.0


def test_lfm2_mosaic_roofline(counters):
    # Without the counters: the even share.
    assert abs(read("kernel.lfm2_mosaic_roofline")
               - expected_roofline(None)) < 1e-9
    counters["ray_tpu_train_moe_tokens_total"] = 262144.0
    counters["ray_tpu_train_moe_routed_total"] = 4 * 262144.0
    got = read("kernel.lfm2_mosaic_roofline")
    assert abs(got - expected_roofline(1 / 4)) < 1e-9
    assert expected_roofline(None) < got < 100.0


@pytest.mark.parametrize("record", [without("trace"), OTHER, dict(
    RECORD, trace=dict(RECORD["trace"], mosaic_s=0.0))],
    ids=["untraced", "another_family", "no_kernel_ran"])
def test_the_roofline_reader_finds_nothing_to_read(record, counters):
    assert read("kernel.lfm2_mosaic_roofline", record) is None
