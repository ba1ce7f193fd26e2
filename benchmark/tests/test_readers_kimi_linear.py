"""The readers this family's cell adds, each on a record made by hand, and
None where its input is absent (another family's cell, an untraced run)."""

import os

import pytest

import flops_kimi_linear
import harness
import program_counters
from ray_tpu.ops.kda import CHUNK


def _config():
    for entry in harness.load_spec()["configs"]:
        held = harness.load_json(os.path.join(harness.ROOT, entry["file"]))
        if held["program"]["family"] == "kimi_linear":
            return held
    raise AssertionError("no kimi_linear configuration")


CONFIG = _config()
STEPS = 8
RECORD = {
    "cell": {"name": "x", "chips": 1, "config": CONFIG},
    "device": {"kind": "TPU v5 lite"},
    "model": {"seq_len": 16384},
    "window": {"t0": 100.0, "unit_ends": [102.0, 104.0, 106.0, 108.0],
               "steps_per_unit": 1, "tokens_per_step": 16384},
    "trace": {"busy_s": 16.0, "mosaic_s": 6.0,
              "steps_device_s": [2.0] * STEPS,
              "device_ops": [["fusion.1", 2.0], ["kda_bwd.5", 1.1],
                             ["kda_bwd", 0.6]]},
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
    want = 16384.0 / 2.0 * 3_192_815_616.0 / PEAK
    assert abs(read("kda.model_mfu") - want) < 1e-12
    assert read("kda.model_mfu", OTHER) is None


def expected_roofline(share):
    calls = flops_kimi_linear.step_kernel_calls(
        CONFIG, 1, 16384, CHUNK, 512, 512, True, share)
    least = sum(c["calls"] * max(c["flops"] / PEAK, c["bytes"] / BANDWIDTH)
                for c in calls.values())
    return 100.0 * least * STEPS / 6.0


def test_kda_mosaic_roofline(counters):
    # Without the counters: the even share.
    assert abs(read("kernel.kda_mosaic_roofline")
               - expected_roofline(None)) < 1e-9
    counters["ray_tpu_train_moe_tokens_total"] = 262144.0
    counters["ray_tpu_train_moe_routed_total"] = 4 * 262144.0
    got = read("kernel.kda_mosaic_roofline")
    assert abs(got - expected_roofline(1 / 4)) < 1e-9
    assert expected_roofline(None) < got < 100.0


@pytest.mark.parametrize("record", [without("trace"), OTHER, dict(
    RECORD, trace=dict(RECORD["trace"], mosaic_s=0.0))],
    ids=["untraced", "another_family", "no_kernel_ran"])
def test_the_roofline_reader_finds_nothing_to_read(record, counters):
    assert read("kernel.kda_mosaic_roofline", record) is None


@pytest.mark.parametrize("kernel,secs", [("kda_fwd", None),
                                         ("kda_bwd", 1.1)])
def test_kda_rooflines(kernel, secs):
    """One call's least time over the busiest instruction's time a call:
    the two expert KDA layers in a row are the longest run, so that
    instruction is called twice a step. None where the kernel is not among
    the trace's operations."""
    name = f"kernel.{kernel}_roofline"
    if secs is None:
        assert read(name) is None
        record = dict(RECORD, trace=dict(RECORD["trace"], device_ops=[
            [kernel + ".3", 0.2], [kernel + ".17", 0.5], ["kda_bwd", 0.9],
            [kernel, 0.1]]))
        secs = 0.5
    else:
        record = RECORD
    call = flops_kimi_linear.kda_call(kernel, CONFIG, 1, 16384, CHUNK)
    want = 100.0 * max(call["flops"] / PEAK, call["bytes"] / BANDWIDTH) \
        / (secs / (2 * STEPS))
    assert abs(read(name, record) - want) < 1e-9
    assert read(name, without("trace")) is None
    assert read(name, OTHER) is None
