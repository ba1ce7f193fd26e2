"""The readers this family's cell adds, each on a record made by hand, and
None where its input is absent (another family's cell, an untraced run, a
program without the gauges)."""

import os

import pytest

import flops_minicpm_sala as counts
import harness
import program_counters
import sala_rooflines


def _config():
    for entry in harness.load_spec()["configs"]:
        held = harness.load_json(os.path.join(harness.ROOT, entry["file"]))
        if held["program"]["family"] == "minicpm_sala":
            return held
    raise AssertionError("no minicpm_sala configuration")


CONFIG = _config()
SEQ = CONFIG["layout"]["seq_len"]
STEPS = 8
RECORD = {
    "cell": {"name": "x", "chips": 1, "config": CONFIG},
    "device": {"kind": "TPU v5 lite"},
    "model": {"seq_len": SEQ},
    "window": {"t0": 100.0, "unit_ends": [101.25, 102.5, 103.75, 105.0],
               "steps_per_unit": 1, "tokens_per_step": SEQ},
    "trace": {"busy_s": 9.9, "mosaic_s": 0.75,
              "steps_device_s": [1.25] * STEPS,
              "device_ops": [["fusion.1", 4.0], ["sala_bwd_dkv.3", 0.2],
                             ["lightning_bwd.2", 0.08],
                             ["lightning_fwd.7", 0.03],
                             ["lightning_fwd.9", 0.04]]},
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
    want = SEQ / 1.25 * counts.model_flops_per_token(CONFIG, SEQ) / PEAK
    assert abs(read("sala.model_mfu") - want) < 1e-12
    assert 0.3 < want < 0.7
    assert read("sala.model_mfu", OTHER) is None


def test_sala_mosaic_roofline():
    calls = counts.step_kernel_calls(CONFIG, 1, SEQ, True)
    least = sum(c["calls"] * max(c["flops"] / PEAK, c["bytes"] / BANDWIDTH)
                for c in calls.values())
    want = 100.0 * least * STEPS / 0.75
    assert abs(read("kernel.sala_mosaic_roofline") - want) < 1e-9
    assert 20.0 < want < 105.0


@pytest.mark.parametrize("record", [without("trace"), OTHER, dict(
    RECORD, trace=dict(RECORD["trace"], mosaic_s=0.0)), dict(
    RECORD, cell=dict(RECORD["cell"], config=dict(CONFIG, layout=dict(
        CONFIG["layout"], seq_len=8192))))],
    ids=["untraced", "another_family", "no_kernel_ran", "dense"])
def test_the_roofline_reader_finds_nothing_to_read(record):
    assert read("kernel.sala_mosaic_roofline", record) is None


def test_one_kernels_share_by_name():
    """One call's least time over the busiest instruction's time a call: an
    instruction is called once a layer of its kind and step. None where the
    kernel is not among the trace's operations. (No kernel has a reader of
    its own: none is among a trace's ten in every run.)"""
    for kernel, secs, layers in (("sala_bwd_dkv", 0.2, 1),
                                 ("lightning_bwd", 0.08, 3),
                                 ("lightning_fwd", 0.04, 3)):
        call = counts.step_kernel_calls(CONFIG, 1, SEQ, True)[kernel]
        want = 100.0 * max(call["flops"] / PEAK, call["bytes"] / BANDWIDTH) \
            / (secs / (layers * STEPS))
        assert abs(sala_rooflines.kernel(RECORD, kernel) - want) < 1e-9
        assert 5.0 < want < 105.0
    assert sala_rooflines.kernel(RECORD, "sala_fwd") is None
    assert sala_rooflines.kernel(OTHER, "sala_bwd_dkv") is None


def test_the_gauges_readers(counters):
    for name in ("sala.selected_share", "sala.live_tile_share",
                 "sala.free_mass"):
        assert read(name) is None
    counters["ray_tpu_train_sala_selected_share"] = \
        counts.selected_share(CONFIG, SEQ)
    counters["ray_tpu_train_sala_live_tile_share"] = 1.0
    counters["ray_tpu_train_sala_free_mass"] = 0.25
    assert abs(read("sala.selected_share") - 0.43460) < 1e-5
    assert read("sala.live_tile_share") == 1.0
    assert read("sala.free_mass") == 0.25


def test_the_gauges_are_the_programs():
    """The names the readers ask the registry for are the ones
    ``models/minicpm_sala.py`` feeds; not a number (no selection ran) feeds
    nothing."""
    from ray_tpu.models import minicpm_sala
    from ray_tpu.util import metrics
    minicpm_sala.RECORDED_METRICS["sala_selected_share"](0.5)
    minicpm_sala.RECORDED_METRICS["sala_live_tile_share"](0.75)
    minicpm_sala.RECORDED_METRICS["sala_free_mass"](0.125)
    minicpm_sala.RECORDED_METRICS["sala_free_mass"](float("nan"))
    minicpm_sala.RECORDED_METRICS["lightning_decay_floor"](1e-9)
    assert read("sala.selected_share") == 0.5
    assert read("sala.live_tile_share") == 0.75
    assert read("sala.free_mass") == 0.125
    series = {entry["name"]: entry["series"]
              for entry in metrics.snapshot()}
    assert 1e-9 in series["ray_tpu_train_lightning_decay_floor"].values()
