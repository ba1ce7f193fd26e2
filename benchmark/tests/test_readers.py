"""The metric readers on a record made by hand."""

import harness

RECORD = {
    "cell": {"name": "x", "chips": 4,
             "config": {"n_layer": 28, "n_embd": 4096, "n_head": 16,
                        "n_inner": None, "vocab_size": 50400},
             "traffic": {"data": "dataset"}},
    "device": {"kind": "TPU v5 lite", "memory_peak_bytes": 12_000_000_000},
    "model": {"seq_len": 2048},
    "setup": {"setup_s": 30.0, "init_s": 0.5, "first_step_s": 4.0},
    "window": {"t0": 100.0, "unit_ends": [110.0, 120.0, 131.0],
               "steps_per_unit": 4, "tokens_per_step": 1000},
    "spans": {"save": [[0, 5.0], [10, 17.0], [20, 26.0]],
              "report": [[0, 0.001], [1, 1.003]], "data": [[0, 0.004]]},
    "save_seconds": {"sum": 6.0, "count": 3},
    "trace": {"window_s": 10.0, "busy_s": 4.0, "collective_s": 1.0,
              "mosaic_s": 0.4, "steps_device_s": [1.0, 1.2, 1.1]},
}


def read(kind, name, record=RECORD):
    return harness.load_module(kind, name).read(record)


def test_end_to_end():
    # Three whole units of four steps of 1000 tokens in 31 s.
    assert read("end_to_end", "tokens_per_s") == 12000 / 31.0
    assert abs(read("end_to_end", "mfu")
               - (12000 / 31.0) * 37_880_070_144 / (4 * 197e12)) < 1e-12
    # Median of the three saves' spans (5, 7 and 6 s).
    assert read("layer_metrics", "ckpt.stall_s") == 6.0
    assert read("end_to_end", "setup_s") == 30.0


def test_per_layer():
    assert read("layer_metrics", "core.init_s") == 0.5
    assert read("layer_metrics", "step.compile_s") == 4.0
    assert abs(read("layer_metrics", "train.report_ms") - 2.0) < 1e-9
    assert read("layer_metrics", "train.data_wait_ms") == 4.0
    assert read("layer_metrics", "ckpt.write_s") == 2.0
    assert read("layer_metrics", "step.device_ms") == 1100.0
    assert read("layer_metrics", "collective.share") == 0.1
    assert read("layer_metrics", "kernel.custom_call_share") == 0.1
    assert read("layer_metrics", "device.idle_share") == 0.6
    assert read("layer_metrics", "device.peak_hbm_gb") == 12.0
    # The whole-unit rate under the name it has in a cell with saves: a
    # stalled unit (the third, 11 s) counts in full.
    assert read("end_to_end", "job_tokens_per_s") == 12000 / 31.0
    stalled = dict(RECORD, spans={"step": [[0, 1.9], [2, 16.6], [17, 18.9]]})
    assert abs(read("layer_metrics", "train.step_max_ms", stalled)
               - 14600.0) < 1e-6


def test_nothing_to_read_is_none():
    empty = dict(RECORD, trace=None, spans={}, save_seconds={
        "sum": 0.0, "count": 0}, window=dict(RECORD["window"], unit_ends=[]))
    for name in ("step.device_ms", "collective.share", "device.idle_share",
                 "kernel.custom_call_share", "train.report_ms",
                 "ckpt.write_s"):
        assert read("layer_metrics", name, empty) is None
    assert read("end_to_end", "tokens_per_s", empty) is None
    assert read("layer_metrics", "ckpt.stall_s", empty) is None
    assert read("end_to_end", "job_tokens_per_s", empty) is None
    assert read("layer_metrics", "train.step_max_ms", empty) is None
    one_chip = dict(RECORD, cell=dict(RECORD["cell"], chips=1))
    assert read("layer_metrics", "collective.share", one_chip) is None


def test_a_stalled_unit_counts_in_full():
    """The rate is over the clock to the end of the last whole unit: a save
    twice as long takes its seconds out of the rate, and steps that end
    after the last whole unit are no part of it."""
    window = dict(RECORD["window"], unit_ends=[110.0, 120.0, 142.0])
    slow = dict(RECORD, window=window)
    assert read("end_to_end", "job_tokens_per_s", slow) == 12000 / 42.0
    one = dict(RECORD, window=dict(window, unit_ends=[110.0]))
    assert read("end_to_end", "job_tokens_per_s", one) == 4000 / 10.0
    assert read("end_to_end", "tokens_per_s", one) == 4000 / 10.0


def test_a_cell_reports_only_its_metrics():
    spec = harness.load_spec()
    for cell in spec["workloads"]:
        named = {m["name"] for m in harness.metrics_of(
            spec, "per_layer", cell["name"])}
        listed = {m["name"] for m in spec["per_layer"]
                  if cell["name"] in m.get("workloads", [cell["name"]])}
        assert named == listed
