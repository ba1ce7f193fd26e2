"""The readers this family's cell adds, each on a record made by hand, and
None where its input is absent (another family's cell, an untraced run, a
program without the gauge)."""

import pytest

import flops_mellum
import harness
import program_counters

PERIOD = ["sliding_attention"] * 3 + ["full_attention"]
CONFIG = {
    "model_type": "mellum", "hidden_size": 2304, "head_dim": 128,
    "num_hidden_layers": 4, "layer_types": PERIOD * 7,
    "sliding_window": 1024, "num_attention_heads": 32,
    "num_key_value_heads": 4, "moe_intermediate_size": 896,
    "num_experts": 64, "num_experts_per_tok": 8, "vocab_size": 98304,
    "program": {"family": "mellum", "preset": "mellum2-12b-a2.5b",
                "overrides": {"num_hidden_layers": 4}},
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
              "device_ops": [["fusion.1", 2.0], ["flash_bwd_dkv_win", 0.9],
                             ["gmm.4", 0.3], ["gmm.11", 0.7],
                             ["tgmm.2", 0.8]]},
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
AFMOE = dict(RECORD, cell=dict(RECORD["cell"], config=dict(
    CONFIG, model_type="afmoe")))


@pytest.fixture
def counters(monkeypatch):
    """The program's registry as a dictionary the test fills."""
    held = {}
    monkeypatch.setattr(program_counters, "value", held.get)
    return held


def test_model_mfu():
    want = 16384.0 / 2.0 * 4_017_487_872.0 / PEAK
    assert abs(read("mellum.model_mfu") - want) < 1e-12
    assert read("mellum.model_mfu", OTHER) is None
    assert read("mellum.model_mfu", AFMOE) is None


def test_mosaic_roofline():
    calls = flops_mellum.step_kernel_calls(CONFIG, 1, 16384, 512, 512, True)
    least = sum(c["calls"] * c["flops"] / PEAK for c in calls.values())
    got = read("kernel.mellum_mosaic_roofline")
    assert abs(got - 100.0 * least * STEPS / 6.0) < 1e-9
    assert 0.0 < got < 100.0
    # Tiles of 256: fewer executed pairs in the window layers.
    small = dict(RECORD, cell=dict(RECORD["cell"], config=dict(
        CONFIG, program=dict(CONFIG["program"], overrides={
            "num_hidden_layers": 4, "attn_blk_q": 256, "attn_blk_k": 256}))))
    assert read("kernel.mellum_mosaic_roofline", small) < got


@pytest.mark.parametrize("name", [
    "kernel.mellum_mosaic_roofline", "kernel.mellum_gmm_roofline",
    "kernel.mellum_flash_bwd_dkv_win_roofline"])
@pytest.mark.parametrize("record", [without("trace"), OTHER, AFMOE, dict(
    RECORD, trace=dict(RECORD["trace"], mosaic_s=0.0, device_ops=[]))],
    ids=["untraced", "another_family", "afmoe", "no_kernel_ran"])
def test_a_roofline_reader_finds_nothing_to_read(name, record):
    assert read(name, record) is None


def test_window_flash_roofline():
    """One call's least time over the busiest instruction's time a call:
    the three window layers are one run, so that instruction is called
    three times a step. Only the window layers' longest kernel has a
    reader: the two others never reach the trace's ten operations at tiles
    of 512 (``mellum_rooflines.flash`` would read them the same way)."""
    import mellum_rooflines
    name = "kernel.mellum_flash_bwd_dkv_win_roofline"
    flops_ = 32 * 93 * 4 * 2 * 512 * 512 * 128
    want = 100.0 * (flops_ / PEAK) / (0.9 / (3 * STEPS))
    assert abs(read(name) - want) < 1e-9
    assert mellum_rooflines.flash(RECORD, "flash_fwd") is None
    busier = dict(RECORD, trace=dict(RECORD["trace"], device_ops=[
        ["flash_fwd_win.3", 0.2], ["flash_fwd_win.17", 0.5],
        ["flash_fwd.2", 0.7], ["flash_fwd_win", 0.1]]))
    assert abs(mellum_rooflines.flash(busier, "flash_fwd") - 100.0 * (
        flops_ / 2 / PEAK) / (0.5 / (3 * STEPS))) < 1e-9
    # A sequence the window holds whole runs the causal kernels.
    short = dict(RECORD, cell=dict(RECORD["cell"], config=dict(
        CONFIG, layout={"batch": 1, "seq_len": 1024})))
    assert read(name, short) is None


def test_gmm_roofline():
    """The busiest ``gmm`` instruction (not ``tgmm``'s), called once a layer
    of the longest run, three, and step."""
    flops_ = 2 * 16384 * 8 * 2304 * 896
    want = 100.0 * (flops_ / PEAK) / (0.7 / (3 * STEPS))
    assert abs(read("kernel.mellum_gmm_roofline") - want) < 1e-9


def test_the_programs_gauges(counters):
    for name in ("window.tile_fill", "moe.picked_mass"):
        assert read(name) is None  # a parent without the gauge
    counters["ray_tpu_train_attn_window_tile_fill"] = 0.6667
    counters["ray_tpu_train_moe_picked_mass"] = 0.44
    assert read("window.tile_fill") == 0.6667
    assert read("moe.picked_mass") == 0.44


def test_the_gauges_come_from_the_programs_registry():
    from ray_tpu.models import mellum
    mellum.RECORDED_METRICS["moe_picked_mass"](0.375)
    mellum.RECORDED_METRICS["attn_window_tile_fill"](0.75)
    mellum.RECORDED_METRICS["attn_window_tile_fill"](float("nan"))
    assert read("moe.picked_mass") == 0.375
    assert read("window.tile_fill") == 0.75
