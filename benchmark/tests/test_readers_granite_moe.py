"""The readers this family's cell adds, each on a record made by hand, and
None where its input is absent (another family's cell, the family without
experts, an untraced run)."""

import pytest

import flops_granite_moe as counts
import harness
import program_counters

PERIOD = ["mamba"] * 5 + ["attention"] + ["mamba"] * 4
CONFIG = {
    "model_type": "granitemoehybrid", "hidden_size": 4096,
    "num_hidden_layers": 10, "layer_types": PERIOD * 4,
    "num_attention_heads": 32, "num_key_value_heads": 8,
    "mamba_n_heads": 128, "mamba_d_head": 64, "mamba_d_state": 128,
    "mamba_n_groups": 1, "mamba_d_conv": 4, "mamba_chunk_size": 256,
    "intermediate_size": 768, "shared_intermediate_size": 1536,
    "num_local_experts": 9, "num_experts_per_tok": 10, "vocab_size": 12544,
    "deployment": {"experts_held": {"first": 0, "count": 9, "of": 72},
                   "vocab_slice": {"first": 0, "count": 12544,
                                   "of": 100352}},
    "program": {"family": "granitemoehybrid_moe",
                "preset": "granite-4.0-h-small",
                "overrides": {"num_hidden_layers": 10,
                              "experts_held": [0, 9], "vocab_size": 12544}},
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
              "device_ops": [["fusion.1", 2.0], ["ssd_bwd", 0.9]]},
}
PEAK, BANDWIDTH = 197e12, 819e9
OTHER = dict(RECORD, cell=dict(RECORD["cell"], config={"n_layer": 2}))
MICRO = dict(RECORD, cell=dict(RECORD["cell"], config=dict(
    CONFIG, program=dict(CONFIG["program"], family="granitemoehybrid"))))


def read(name, record=RECORD):
    return harness.load_module("layer_metrics", name).read(record)


@pytest.fixture
def counters(monkeypatch):
    """The program's registry as a dictionary the test fills."""
    held = {}
    monkeypatch.setattr(program_counters, "value", held.get)
    return held


def calls(share=None):
    return counts.step_kernel_calls(CONFIG, 1, 16384, 512, 512, True, share)


def least(call):
    return max(call["flops"] / PEAK, call["bytes"] / BANDWIDTH)


def test_model_mfu():
    want = 16384.0 / 2.0 * counts.model_flops_per_token(CONFIG, 16384) / PEAK
    assert abs(read("granite_moe.model_mfu") - want) < 1e-12
    assert 0.0 < want < 1.0
    assert read("granite_moe.model_mfu", OTHER) is None
    assert read("granite_moe.model_mfu", MICRO) is None


def test_mosaic_roofline(counters):
    # No counters (a parent without the share's): even routing, an eighth.
    want = sum(c["calls"] * least(c) for c in calls().values())
    got = read("kernel.granite_moe_mosaic_roofline")
    assert abs(got - 100.0 * want * STEPS / 6.0) < 1e-9
    assert 0.0 < got < 100.0
    # The expert layers' rows at what the counters measured.
    counters["ray_tpu_train_moe_tokens_total"] = 30.0
    counters["ray_tpu_train_moe_routed_total"] = 100.0
    more = sum(c["calls"] * least(c) for c in calls(0.3).values())
    assert abs(read("kernel.granite_moe_mosaic_roofline")
               - 100.0 * more * STEPS / 6.0) < 1e-9
    assert more > want


@pytest.mark.parametrize("record", [dict(RECORD, trace=None), OTHER, MICRO,
                                    dict(RECORD, trace=dict(
                                        RECORD["trace"], mosaic_s=0.0))],
                         ids=["untraced", "another_family", "no_experts",
                              "no_kernel_ran"])
def test_the_roofline_reader_finds_nothing_to_read(record):
    assert read("kernel.granite_moe_mosaic_roofline", record) is None


def test_the_picked_mass_comes_from_the_programs_registry():
    from ray_tpu.models import granite
    granite.RECORDED_METRICS["moe_picked_mass"](0.41)
    assert read("moe.picked_mass") == 0.41
