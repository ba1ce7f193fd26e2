"""The readers this family's cell adds, each on a record made by hand, and
None where its input is absent (another family's cell, an untraced run, a
program without the gauges)."""

import os

import pytest

import flops_evabyte as flops_eva
import harness
import program_counters


def _config():
    for entry in harness.load_spec()["configs"]:
        held = harness.load_json(os.path.join(harness.ROOT, entry["file"]))
        if held["program"]["family"] == "evabyte":
            return held
    raise AssertionError("no evabyte configuration")


CONFIG = _config()
SEQ = CONFIG["layout"]["seq_len"]
LAYERS = CONFIG["num_hidden_layers"]
STEPS = 8
RECORD = {
    "cell": {"name": "x", "chips": 1, "config": CONFIG},
    "device": {"kind": "TPU v5 lite"},
    "model": {"seq_len": SEQ},
    "window": {"t0": 100.0, "unit_ends": [102.5, 105.0, 107.5, 110.0],
               "steps_per_unit": 1, "tokens_per_step": SEQ},
    "trace": {"busy_s": 19.9, "mosaic_s": 3.0,
              "steps_device_s": [2.5] * STEPS,
              "device_ops": [["fusion.1", 4.0], ["eva_bwd_dkv.3", 1.0],
                             ["eva_fwd.7", 0.55], ["eva_fwd.9", 0.56],
                             ["eva_bwd_dq.2", 0.8]]},
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
    want = SEQ / 2.5 * flops_eva.model_flops_per_token(CONFIG, SEQ) / PEAK
    assert abs(read("evabyte.model_mfu") - want) < 1e-12
    assert 0.3 < want < 0.7
    assert read("evabyte.model_mfu", OTHER) is None


def test_eva_mosaic_roofline():
    calls = flops_eva.step_kernel_calls(CONFIG, 1, SEQ, True)
    least = sum(c["calls"] * max(c["flops"] / PEAK, c["bytes"] / BANDWIDTH)
                for c in calls.values())
    want = 100.0 * least * STEPS / 3.0
    assert abs(read("kernel.eva_mosaic_roofline") - want) < 1e-9
    assert 20.0 < want < 105.0


@pytest.mark.parametrize("record", [without("trace"), OTHER, dict(
    RECORD, trace=dict(RECORD["trace"], mosaic_s=0.0))],
    ids=["untraced", "another_family", "no_kernel_ran"])
def test_the_roofline_readers_find_nothing_to_read(record):
    assert read("kernel.eva_mosaic_roofline", record) is None
    if not record.get("trace") or record is OTHER:
        assert read("kernel.eva_bwd_dkv_roofline", record) is None


def test_eva_bwd_dkv_roofline():
    """One call's least time over the busiest instruction's time a call:
    every layer is one scan, so an instruction is called once a layer and
    step. None where the kernel is not among the trace's operations. (The
    forward and the dq kernel have no reader: they are not among a trace's
    ten; ``eva_rooflines.kernel`` reads any of the three by name.)"""
    import eva_rooflines
    name = "kernel.eva_bwd_dkv_roofline"
    for kernel, secs in (("eva_fwd", 0.56), ("eva_bwd_dq", 0.8),
                         ("eva_bwd_dkv", 1.0)):
        call = flops_eva.attention_call(kernel, CONFIG, 1, SEQ)
        want = 100.0 * max(call["flops"] / PEAK, call["bytes"] / BANDWIDTH) \
            / (secs / (LAYERS * STEPS))
        assert abs(eva_rooflines.kernel(RECORD, kernel) - want) < 1e-9
        assert 20.0 < want < 105.0
    assert read(name) == eva_rooflines.kernel(RECORD, "eva_bwd_dkv")
    gone = dict(RECORD, trace=dict(RECORD["trace"], device_ops=[
        op for op in RECORD["trace"]["device_ops"]
        if not op[0].startswith("eva_bwd_dkv.")]))
    assert read(name, gone) is None
    assert read(name, without("trace")) is None
    assert read(name, OTHER) is None


def test_the_gauges_readers(counters):
    assert read("eva.pairs_share") is None
    assert read("eva.summary_mass") is None
    counters["ray_tpu_train_eva_pairs_share"] = \
        flops_eva.pairs_share(CONFIG, SEQ)
    counters["ray_tpu_train_eva_summary_mass"] = 0.25
    assert abs(read("eva.pairs_share") - 0.12112) < 1e-5
    assert read("eva.summary_mass") == 0.25


def test_the_gauges_are_the_programs():
    """The names the readers ask the registry for are the ones
    ``models/evabyte.py`` feeds."""
    from ray_tpu.models import evabyte
    from ray_tpu.util import metrics
    evabyte.RECORDED_METRICS["eva_pairs_share"](0.5)
    evabyte.RECORDED_METRICS["eva_summary_mass"](0.125)
    evabyte.RECORDED_METRICS["mbp_loss_3"](5.5)
    evabyte.RECORDED_METRICS["mbp_loss_4"](float("nan"))
    series = {entry["name"]: entry["series"]
              for entry in metrics.snapshot()}
    assert read("eva.pairs_share") == 0.5
    assert read("eva.summary_mass") == 0.125
    assert 5.5 in series["ray_tpu_train_mbp_loss"].values()
    assert all(v == v for v in series["ray_tpu_train_mbp_loss"].values())
