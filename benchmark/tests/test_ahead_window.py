"""The window of a mix with ``ahead_s`` (``runners/train.py``), driven here
on the CPU at the tiny size: steps are sent ahead of the loss that is
waited for, every step sent is waited for before the clock is read, and the
same cell with ``ahead_s`` 0 runs the loop that reads each loss before the
next step is sent."""

import time
from dataclasses import replace

import harness
import pytest

CELL = "gptj-6b-1chip.steady"


def run(ahead_s, trace=False, seconds=3.0):
    spec = harness.load_spec()
    cell = harness.load_cell(spec, CELL)
    runner = harness.load_module("runners", cell.traffic["runner"])
    cell = runner.shrink(cell)
    cell = replace(cell, traffic=dict(cell.traffic, ahead_s=ahead_s))
    return runner.run(cell, harness.RunArgs(
        seed=1, seconds=seconds, trace=trace, t_start=time.perf_counter(),
        rehearsal=True))


def test_the_steady_mix_sends_ahead():
    spec = harness.load_spec()
    assert harness.load_cell(spec, CELL).traffic["ahead_s"] > 0


@pytest.mark.parametrize("ahead_s", [0.0, 1.5])
def test_every_step_sent_is_waited_for_and_counted(ahead_s):
    record = run(ahead_s)
    window, spans = record["window"], record["spans"]
    assert record["correct"], record["checks"]
    steps = window["steps"]
    assert steps >= 3 and len(window["unit_ends"]) == steps
    assert len(spans["step"]) == len(spans["report"]) == steps
    # The clock is read after the last wait: no step ends after the last
    # unit, and the window's span closes on it.
    last = window["unit_ends"][-1]
    assert all(t1 <= last for _, t1 in spans["step"])
    assert window["t1"] >= last
    rate = harness.load_module("end_to_end", "tokens_per_s").read(record)
    assert rate == pytest.approx(
        steps * window["tokens_per_step"] / (last - window["t0"]))
    if ahead_s:
        assert len(spans["wait"]) == steps
        assert all(t1 <= last for _, t1 in spans["wait"])
        assert window["ahead"]["max_pending"] >= 2
        assert window["ahead"]["step_s"] > 0
        # A step is sent before the loss of the one before it is read.
        sends = sorted(t0 for t0, _ in spans["step"])
        reads = sorted(t1 for _, t1 in spans["wait"])
        assert any(s < r for s, r in zip(sends[2:], reads[1:]))
    else:
        assert "wait" not in spans
        assert window["ahead"]["max_pending"] == 0


def test_a_traced_window_stops_after_its_units():
    record = run(1.5, trace=True, seconds=30.0)
    runner = harness.load_module("runners", "train")
    assert record["window"]["steps"] == runner.TRACE_UNITS
    assert len(record["window"]["unit_ends"]) == runner.TRACE_UNITS


def test_a_mix_with_saves_may_not_send_ahead():
    spec = harness.load_spec()
    cell = harness.load_cell(spec, "gptj-6b-1chip.job")
    runner = harness.load_module("runners", cell.traffic["runner"])
    cell = runner.shrink(cell)
    cell = replace(cell, traffic=dict(cell.traffic, ahead_s=1.0))
    with pytest.raises(Exception, match="ahead_s"):
        runner.run(cell, harness.RunArgs(
            seed=1, seconds=1.0, trace=False, t_start=time.perf_counter(),
            rehearsal=True))
