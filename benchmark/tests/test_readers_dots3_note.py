"""The readers this family's cell adds, each on a record made by hand, and
None where its input is absent (another family's cell, an untraced run, a
program without the gauges)."""

import os

import pytest

import dots3_rooflines
import flops_dots3_note as counts
import harness
import program_counters


def _config():
    for entry in harness.load_spec()["configs"]:
        held = harness.load_json(os.path.join(harness.ROOT, entry["file"]))
        if held["program"]["family"] == "dots3_note":
            return held
    raise AssertionError("no dots3_note configuration")


CONFIG = _config()
SEQ = CONFIG["layout"]["seq_len"]
STEPS = 8
RECORD = {
    "cell": {"name": "x", "chips": 1, "config": CONFIG},
    "device": {"kind": "TPU v5 lite"},
    "model": {"seq_len": SEQ},
    "window": {"t0": 100.0, "unit_ends": [101.0, 102.0, 103.0, 104.0],
               "steps_per_unit": 1, "tokens_per_step": SEQ},
    "trace": {"busy_s": 8.0, "mosaic_s": 3.0,
              "steps_device_s": [1.0] * STEPS,
              "device_ops": [["dsa_bwd_dkv.17", 0.40], ["fusion.1", 0.1],
                             ["dsa_bwd_dkv.15", 0.39], ["dsa_fwd.48", 0.2],
                             ["flash_bwd_dkv_win.3", 0.12]]},
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
    want = SEQ / 1.0 * counts.model_flops_per_token(CONFIG, SEQ) / PEAK
    assert abs(read("dots3.model_mfu") - want) < 1e-12
    assert 0.2 < want < 0.5
    assert read("dots3.model_mfu", OTHER) is None


def expected_roofline(share):
    calls = counts.step_kernel_calls(CONFIG, 1, SEQ, True, share)
    least = sum(c["calls"] * max(c["flops"] / PEAK, c["bytes"] / BANDWIDTH)
                for c in calls.values())
    return 100.0 * least * STEPS / 3.0


def test_mosaic_roofline(counters):
    # Without the counters: the even share.
    assert abs(read("kernel.dots3_mosaic_roofline")
               - expected_roofline(None)) < 1e-9
    counters["ray_tpu_train_moe_tokens_total"] = 8192.0
    counters["ray_tpu_train_moe_routed_total"] = 16 * 8192.0
    got = read("kernel.dots3_mosaic_roofline")
    assert abs(got - expected_roofline(1 / 16)) < 1e-9
    assert expected_roofline(None) < got < 100.0


@pytest.mark.parametrize("record", [without("trace"), OTHER, dict(
    RECORD, trace=dict(RECORD["trace"], mosaic_s=0.0))],
    ids=["untraced", "another_family", "no_kernel_ran"])
def test_the_roofline_reader_finds_nothing_to_read(record, counters):
    assert read("kernel.dots3_mosaic_roofline", record) is None
    for kernel in ("dsa_fwd", "dsa_bwd_dq", "dsa_bwd_dkv"):
        if record.get("trace") and record is not OTHER:
            continue
        assert read(f"kernel.dots3_{kernel}_roofline", record) is None


@pytest.mark.parametrize("kernel,secs", [
    ("dsa_fwd", 0.2), ("dsa_bwd_dq", None), ("dsa_bwd_dkv", 0.40)])
def test_dsa_rooflines(kernel, secs):
    """One call's least time over the busiest instruction's time a call:
    the two full layers are runs of their own (``dense_full``,
    ``moe_full``), so an instruction is called once a step. None where the
    kernel is not among the trace's operations."""
    name = f"kernel.dots3_{kernel}_roofline"
    if secs is None:
        assert read(name) is None
        record = dict(RECORD, trace=dict(RECORD["trace"], device_ops=[
            [kernel + ".3", 0.05], [kernel + ".17", 0.3],
            ["dsa_bwd_dkv", 0.9], [kernel, 0.01]]))
        secs = 0.3
    else:
        record = RECORD
    call = counts.attention_call(kernel, CONFIG, 1, SEQ)
    want = 100.0 * max(call["flops"] / PEAK, call["bytes"] / BANDWIDTH) \
        / (secs / STEPS)
    assert abs(read(name, record) - want) < 1e-9
    assert 15.0 < want < 105.0
    assert read(name, without("trace")) is None
    assert read(name, OTHER) is None


def test_a_window_kernels_share_reads_the_longest_window_run():
    """No listed reader (the window's kernels are not among the ten in the
    cell's traces), but ``dots3_rooflines.kernel`` reads them for PERF.md's
    tables: three window layers a run."""
    call = counts.attention_call("flash_bwd_dkv_win", CONFIG, 1, SEQ)
    want = 100.0 * max(call["flops"] / PEAK, call["bytes"] / BANDWIDTH) \
        / (0.12 / (3 * STEPS))
    assert abs(dots3_rooflines.kernel(RECORD, "flash_bwd_dkv_win")
               - want) < 1e-9
    assert dots3_rooflines.kernel(RECORD, "flash_fwd_win") is None
    assert dots3_rooflines.kernel(RECORD, "gmm") is None


def test_the_gates_readers_read_the_programs_gauge_by_its_tag():
    """The series the readers ask the registry for are the ones
    ``models/dots3_note.py`` feeds, one a kind of layer; the older gauges
    this cell reports are fed under the names their readers ask for."""
    from ray_tpu.models import dots3_note
    from ray_tpu.util import metrics
    recorded = dots3_note.RECORDED_METRICS
    recorded["attn_gate_mean_full"](0.52)
    assert read("attn.gate_mean_full") == 0.52
    recorded["attn_gate_mean_window"](0.47)
    recorded["attn_gate_mean_full"](float("nan"))
    assert read("attn.gate_mean_full") == 0.52
    assert read("attn.gate_mean_window") == 0.47
    recorded["dsa_selected_share"](counts.selected_share(8192, 2048))
    recorded["dsa_index_loss"](1.25)
    recorded["attn_window_tile_fill"](0.501)
    assert abs(read("dsa.selected_share") - 0.43748) < 1e-5
    assert read("dsa.index_loss") == 1.25
    assert read("window.tile_fill") == 0.501
    assert dots3_rooflines.GATE_GAUGE in {
        entry["name"] for entry in metrics.snapshot()}


def test_a_program_without_the_gauge_leaves_the_readers_with_nothing(
        monkeypatch):
    from ray_tpu.util import metrics
    monkeypatch.setattr(metrics, "snapshot", lambda: [])
    assert read("attn.gate_mean_full") is None
    assert read("attn.gate_mean_window") is None
