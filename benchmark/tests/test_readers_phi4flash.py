"""The readers this family's cell adds, each on a record made by hand, and
None where its input is absent (another family's cell, an untraced run)."""

import os

import pytest

import flops_phi4flash
import harness
import phi4flash_rooflines


def _config():
    for entry in harness.load_spec()["configs"]:
        held = harness.load_json(os.path.join(harness.ROOT, entry["file"]))
        if held["program"]["family"] == "phi4flash":
            return held
    raise AssertionError("no phi4flash configuration")


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
              "device_ops": [["fusion.1", 2.0],
                             ["selective_scan_bwd.5", 0.09]]},
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
    per_token = flops_phi4flash.model_flops_per_token(CONFIG, 16384)
    want = 16384.0 / 2.0 * per_token / PEAK
    assert abs(read("phi4flash.model_mfu") - want) < 1e-12
    assert 0.0 < want < 1.0
    assert read("phi4flash.model_mfu", OTHER) is None


def test_mosaic_roofline():
    # The window layers' forward runs twice (63 tiles a head are not worth
    # keeping), the full and the cross layers' once.
    calls = flops_phi4flash.step_kernel_calls(
        CONFIG, 1, 16384, 512, 512, True, {"window": False, "causal": True})
    assert calls["flash_fwd_win"]["calls"] == \
        2 * calls["flash_bwd_dq_win"]["calls"]
    assert calls["flash_fwd"]["calls"] == calls["flash_bwd_dq"]["calls"]
    least = sum(c["calls"] * max(c["flops"] / PEAK, c["bytes"] / BANDWIDTH)
                for c in calls.values())
    want = 100.0 * least * STEPS / 6.0
    got = read("kernel.phi4flash_mosaic_roofline")
    assert abs(got - want) < 1e-9 and 0.0 < got < 100.0


@pytest.mark.parametrize("record", [without("trace"), OTHER, dict(
    RECORD, trace=dict(RECORD["trace"], mosaic_s=0.0))],
    ids=["untraced", "another_family", "no_kernel_ran"])
def test_the_roofline_reader_finds_nothing_to_read(record):
    assert read("kernel.phi4flash_mosaic_roofline", record) is None


@pytest.mark.parametrize("kernel, wide_arrays", [
    ("selective_scan_fwd", 3), ("selective_scan_bwd", 5)])
def test_selective_scan_rooflines(kernel, wide_arrays):
    """One call's bytes (xs, delta, y | xs, delta, dy and two cotangents,
    bfloat16) over a call's time on the busiest instruction of the name:
    the self pairs' run, called once a Mamba layer of it a step. The
    backward's is a listed metric; the forward's instruction is not among
    the trace's ten in the cell, and PERF.md section 5 gives its share by
    the same function from a table of every instruction."""
    trace = dict(RECORD["trace"], device_ops=[
        ["fusion.1", 2.0], [kernel + ".5", 0.09], [kernel + ".2", 0.03]])
    layers = flops_phi4flash.longest_mamba_run(CONFIG)
    assert layers == sum(i % 2 == 0 and i < 16 for i in CONFIG["layers_run"])
    least = 16384 * 5120 * wide_arrays * 2 / BANDWIDTH
    want = 100.0 * least / (0.09 / (layers * STEPS))
    got = phi4flash_rooflines.kernel(dict(RECORD, trace=trace), kernel)
    assert abs(got - want) < 1e-9 and 0.0 < got < 100.0
    if kernel == "selective_scan_bwd":
        assert read("kernel.selective_scan_bwd_roofline",
                    dict(RECORD, trace=trace)) == got
    for record in (without("trace"), OTHER, dict(RECORD, trace=dict(
            trace, device_ops=[["fusion.1", 2.0]]))):
        assert phi4flash_rooflines.kernel(record, kernel) is None


def test_the_cells_listed_metrics_have_readers():
    spec = harness.load_spec()
    cell = next(w["name"] for w in spec["workloads"]
                if w["config"].startswith("phi-4-mini-flash"))
    listed = {m["name"] for m in harness.metrics_of(spec, "per_layer", cell)}
    assert {"phi4flash.model_mfu", "kernel.phi4flash_mosaic_roofline",
            "kernel.selective_scan_bwd_roofline", "kernel.mosaic_share", "step.device_ms", "device.idle_share",
            "device.peak_hbm_gb", "train.report_ms",
            "train.report_wait_ms"} <= listed
    for name in listed:
        assert callable(harness.load_module("layer_metrics", name).read)
