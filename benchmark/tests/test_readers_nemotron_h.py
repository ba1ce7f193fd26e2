"""The readers this family's cell adds, each on a record made by hand, and
None where its input is absent (another family's cell, an untraced run, a
program without the gauge)."""

import pytest

import flops_nemotron_h as counts
import harness
import program_counters

CONFIG = {
    "model_type": "nemotron_h", "hidden_size": 2688, "head_dim": 128,
    "num_hidden_layers": 9,
    "hybrid_override_pattern":
        "MEMEM*EMEMEM*EMEMEM*EMEMEM*EMEMEM*EMEMEMEM*EMEMEMEME",
    "num_attention_heads": 32, "num_key_value_heads": 2,
    "mamba_num_heads": 64, "mamba_head_dim": 64, "ssm_state_size": 128,
    "n_groups": 8, "conv_kernel": 4, "moe_intermediate_size": 1856,
    "moe_shared_expert_intermediate_size": 3712, "n_shared_experts": 1,
    "n_routed_experts": 32, "num_experts_per_tok": 6, "vocab_size": 32768,
    "deployment": {"experts_held": {"first": 0, "count": 32, "of": 128},
                   "vocab_slice": {"first": 0, "count": 32768,
                                   "of": 131072},
                   "layers_run": {"first": 34, "count": 9}},
    "program": {"family": "nemotron_h", "preset": "nemotron-3-nano-30b-a3b",
                "overrides": {"num_hidden_layers": 9, "first_layer": 34,
                              "experts_held": [0, 32],
                              "vocab_size": 32768}},
    "layout": {"batch": 2, "seq_len": 16384}}
STEPS = 8
RECORD = {
    "cell": {"name": "x", "chips": 1, "config": CONFIG},
    "device": {"kind": "TPU v5 lite"},
    "model": {"seq_len": 16384},
    "window": {"t0": 100.0, "unit_ends": [102.0, 104.0, 106.0, 108.0],
               "steps_per_unit": 1, "tokens_per_step": 32768},
    "trace": {"busy_s": 16.0, "mosaic_s": 6.0,
              "steps_device_s": [2.0] * STEPS,
              "device_ops": [["fusion.1", 2.0], ["ssd_bwd", 0.9],
                             ["ssd_fwd.1", 0.3], ["ssd_fwd", 0.25],
                             ["gmm.4", 0.3], ["gmm.11", 0.7],
                             ["tgmm.2", 0.8]]},
}
PEAK, BANDWIDTH = 197e12, 819e9
ROOFLINES = ["kernel.nemotron_mosaic_roofline",
             "kernel.nemotron_ssd_bwd_roofline"]


def read(name, record=RECORD):
    return harness.load_module("layer_metrics", name).read(record)


def without(*keys):
    record = dict(RECORD)
    for key in keys:
        record[key] = None
    return record


OTHER = dict(RECORD, cell=dict(RECORD["cell"], config={"n_layer": 2}))
GRANITE = dict(RECORD, cell=dict(RECORD["cell"], config=dict(
    CONFIG, model_type="granitemoehybrid")))


@pytest.fixture
def counters(monkeypatch):
    """The program's registry as a dictionary the test fills."""
    held = {}
    monkeypatch.setattr(program_counters, "value", held.get)
    return held


def calls(share=None):
    return counts.step_kernel_calls(CONFIG, 2, 16384, 512, 512, True, 128,
                                    share)


def least(call):
    return max(call["flops"] / PEAK, call["bytes"] / BANDWIDTH)


def test_model_mfu():
    want = 32768.0 / 2.0 * 3_280_994_304.0 / PEAK
    assert abs(read("nemotron.model_mfu") - want) < 1e-12
    assert read("nemotron.model_mfu", OTHER) is None
    assert read("nemotron.model_mfu", GRANITE) is None


def test_mosaic_roofline(counters):
    # No counters (a parent without the share's): even routing, a quarter.
    want = sum(c["calls"] * least(c) for c in calls().values())
    got = read("kernel.nemotron_mosaic_roofline")
    assert abs(got - 100.0 * want * STEPS / 6.0) < 1e-9
    assert 0.0 < got < 100.0
    # The expert layers' rows at what the counters measured.
    counters["ray_tpu_train_moe_tokens_total"] = 30.0
    counters["ray_tpu_train_moe_routed_total"] = 100.0
    more = sum(c["calls"] * least(c) for c in calls(0.3).values())
    assert abs(read("kernel.nemotron_mosaic_roofline")
               - 100.0 * more * STEPS / 6.0) < 1e-9
    assert more > want


@pytest.mark.parametrize("name", ROOFLINES)
@pytest.mark.parametrize("record", [without("trace"), OTHER, GRANITE, dict(
    RECORD, trace=dict(RECORD["trace"], mosaic_s=0.0, device_ops=[]))],
    ids=["untraced", "another_family", "granite", "no_kernel_ran"])
def test_a_roofline_reader_finds_nothing_to_read(name, record):
    assert read(name, record) is None


def test_ssd_bwd_roofline():
    """One call's least time over the busiest instruction's time a call:
    the four units are one scan, so that instruction is called four times a
    step. ``ssd_fwd`` is read the same way where it is among the ten (the
    scan's instruction and the rematerialised one's, four times each), and
    has no reader because in the cell it is not."""
    import nemotron_rooflines
    want = 100.0 * least(calls()["ssd_bwd"]) / (0.9 / (4 * STEPS))
    assert abs(read("kernel.nemotron_ssd_bwd_roofline") - want) < 1e-9
    assert abs(nemotron_rooflines.ssd(RECORD, "ssd_fwd") - 100.0 * least(
        calls()["ssd_fwd"]) / (0.3 / (4 * STEPS))) < 1e-9
    assert nemotron_rooflines.longest_run(CONFIG, "mamba") == 4
    whole = dict(CONFIG, num_hidden_layers=52, deployment={})
    assert nemotron_rooflines.longest_run(whole, "mamba") == 4
    assert nemotron_rooflines.longest_run(whole, "attention") == 0


def test_the_programs_gauge(counters):
    assert read("moe.relu2_zero_share") is None  # a parent without it
    counters["ray_tpu_train_moe_relu2_zero_share"] = 0.5
    assert read("moe.relu2_zero_share") == 0.5


def test_the_gauge_comes_from_the_programs_registry():
    from ray_tpu.models import nemotron_h
    nemotron_h.RECORDED_METRICS["moe_relu2_zero_share"](0.48)
    assert read("moe.relu2_zero_share") == 0.48
