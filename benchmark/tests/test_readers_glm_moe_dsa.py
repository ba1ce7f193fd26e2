"""The readers this family's cell adds, each on a record made by hand, and
None where its input is absent (another family's cell, an untraced run, a
program without the gauges)."""

import os

import pytest

import flops_glm_moe_dsa as flops_glm
import harness
import program_counters


def _config():
    for entry in harness.load_spec()["configs"]:
        held = harness.load_json(os.path.join(harness.ROOT, entry["file"]))
        if held["program"]["family"] == "glm_moe_dsa":
            return held
    raise AssertionError("no glm_moe_dsa configuration")


CONFIG = _config()
SEQ = CONFIG["layout"]["seq_len"]
STEPS = 8
RECORD = {
    "cell": {"name": "x", "chips": 1, "config": CONFIG},
    "device": {"kind": "TPU v5 lite"},
    "model": {"seq_len": SEQ},
    "window": {"t0": 100.0, "unit_ends": [100.5, 101.0, 101.5, 102.0],
               "steps_per_unit": 1, "tokens_per_step": SEQ},
    "trace": {"busy_s": 4.0, "mosaic_s": 1.3,
              "steps_device_s": [0.5] * STEPS,
              "device_ops": [["dsa_bwd_dkv.17", 0.18], ["fusion.1", 0.1],
                             ["dsa_bwd_dkv.15", 0.06], ["dsa_fwd.48", 0.116],
                             ["dsa_fwd.49", 0.115]]},
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
    want = SEQ / 0.5 * flops_glm.model_flops_per_token(CONFIG, SEQ) / PEAK
    assert abs(read("glm.model_mfu") - want) < 1e-12
    assert 0.3 < want < 0.5
    assert read("glm.model_mfu", OTHER) is None


def expected_roofline(share):
    calls = flops_glm.step_kernel_calls(CONFIG, 1, SEQ, True, share)
    least = sum(c["calls"] * max(c["flops"] / PEAK, c["bytes"] / BANDWIDTH)
                for c in calls.values())
    return 100.0 * least * STEPS / 1.3


def test_dsa_mosaic_roofline(counters):
    # Without the counters: the even share.
    assert abs(read("kernel.dsa_mosaic_roofline")
               - expected_roofline(None)) < 1e-9
    counters["ray_tpu_train_moe_tokens_total"] = 8192.0
    counters["ray_tpu_train_moe_routed_total"] = 16 * 8192.0
    got = read("kernel.dsa_mosaic_roofline")
    assert abs(got - expected_roofline(1 / 16)) < 1e-9
    assert expected_roofline(None) < got < 100.0


@pytest.mark.parametrize("record", [without("trace"), OTHER, dict(
    RECORD, trace=dict(RECORD["trace"], mosaic_s=0.0))],
    ids=["untraced", "another_family", "no_kernel_ran"])
def test_the_roofline_reader_finds_nothing_to_read(record, counters):
    assert read("kernel.dsa_mosaic_roofline", record) is None
    for kernel in ("dsa_fwd", "dsa_bwd_dq", "dsa_bwd_dkv"):
        if record.get("trace") and record is not OTHER:
            continue
        assert read(f"kernel.{kernel}_roofline", record) is None


@pytest.mark.parametrize("kernel,secs", [
    ("dsa_fwd", 0.116), ("dsa_bwd_dq", None), ("dsa_bwd_dkv", 0.18)])
def test_dsa_rooflines(kernel, secs):
    """One call's least time over the busiest instruction's time a call:
    the three expert layers that share a selection are the longest run, so
    that instruction is called three times a step. None where the kernel
    is not among the trace's operations."""
    name = f"kernel.{kernel}_roofline"
    if secs is None:
        assert read(name) is None
        record = dict(RECORD, trace=dict(RECORD["trace"], device_ops=[
            [kernel + ".3", 0.05], [kernel + ".17", 0.14],
            ["dsa_bwd_dkv", 0.9], [kernel, 0.01]]))
        secs = 0.14
    else:
        record = RECORD
    call = flops_glm.attention_call(kernel, CONFIG, 1, SEQ)
    want = 100.0 * max(call["flops"] / PEAK, call["bytes"] / BANDWIDTH) \
        / (secs / (3 * STEPS))
    assert abs(read(name, record) - want) < 1e-9
    assert 20.0 < want < 105.0
    assert read(name, without("trace")) is None
    assert read(name, OTHER) is None


def test_the_gauges_readers(counters):
    assert read("dsa.selected_share") is None
    assert read("dsa.index_loss") is None
    counters["ray_tpu_train_dsa_selected_share"] = \
        flops_glm.selected_share(SEQ, CONFIG["index_topk"])
    counters["ray_tpu_train_dsa_index_loss"] = 1.25
    assert abs(read("dsa.selected_share") - 0.74994) < 1e-5
    assert read("dsa.index_loss") == 1.25


def test_the_gauges_are_the_programs():
    """The names the readers ask the registry for are the ones
    ``models/glm_moe_dsa.py`` feeds."""
    from ray_tpu._private import builtin_metrics
    from ray_tpu.util import metrics
    builtin_metrics.train_dsa_selected_share().set(0.5)
    builtin_metrics.train_dsa_index_loss().set(2.0)
    names = {entry["name"] for entry in metrics.snapshot()}
    assert {"ray_tpu_train_dsa_selected_share",
            "ray_tpu_train_dsa_index_loss"} <= names
    assert read("dsa.selected_share") == 0.5
    assert read("dsa.index_loss") == 2.0
