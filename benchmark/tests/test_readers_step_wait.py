"""The four readers of the step's call site, the sampler's tick and the
batch's span, on a span buffer made by hand (``ray_tpu.util.tracing
.get_spans`` swapped for a list): spans outside the window are ignored, an
empty buffer (the parent of the PR that added the spans, or a ``--trace 0``
run) reads None."""

import os

import harness
import pytest

from ray_tpu.util import tracing

WINDOW = {"t0": 100.0, "t1": 200.0}
RECORD = {"window": WINDOW}
NAMES = ("step.dispatch_ms", "step.interval_max_over_median",
         "host.late_tick_max_ms", "data.next_batch_ms")


def span(name, start, seconds, thread="train-rank-0", **attrs):
    return tracing.Span(name=name, trace_id="t", span_id=f"{name}@{start}",
                        parent_id=None, start_time=1e9 + start,
                        duration=seconds, end_time=1e9 + start + seconds,
                        attributes=attrs, perf_start=start, thread=thread)


@pytest.fixture
def buffer(monkeypatch):
    spans = []
    monkeypatch.setattr(tracing, "get_spans", lambda: list(spans))
    return spans


def read(name, record=RECORD):
    return harness.load_module("layer_metrics", name).read(record)


@pytest.mark.parametrize("name", NAMES)
def test_an_empty_buffer_reads_none(buffer, name):
    assert read(name) is None
    # Other spans, and the right ones outside the window, are nothing too.
    buffer += [span("train::report", 110.0, 1.0),
               span("train::step", 90.0, 0.004), span("train::step", 201.0, 1),
               span("host::tick", 99.9, 3.0, thread="ray_tpu-profiler-x"),
               span("data::next_batch", 250.0, 0.5)]
    assert read(name) is None


def test_dispatch_is_the_median_call_in_the_window(buffer):
    buffer += [span("train::step", 99.0, 9.0)]  # the warm-up's
    buffer += [span("train::step", 101.0 + i, ms / 1e3, n=i + 2)
               for i, ms in enumerate((4.0, 3.0, 50.0, 5.0, 6.0))]
    assert read("step.dispatch_ms") == pytest.approx(5.0)
    # A span still open (no duration yet) is not in the window.
    buffer += [tracing.Span(name="train::step", trace_id="t", span_id="open",
                            parent_id=None, start_time=1e9 + 150.0,
                            perf_start=150.0)]
    assert read("step.dispatch_ms") == pytest.approx(5.0)


def test_the_ratio_is_over_seven_intervals_of_eight_spans(buffer):
    entries = [101.0, 102.0, 103.0, 104.0, 105.5, 106.5, 107.5, 108.5]
    buffer += [span("train::step", t, 0.004) for t in entries]
    buffer += [span("train::step", 60.0, 0.004),  # outside: no interval
               span("step::first_call", 100.5, 30.0)]
    assert read("step.interval_max_over_median") == pytest.approx(1.5)
    del buffer[2:]  # two spans are one interval: no median to hold it to
    assert read("step.interval_max_over_median") is None
    buffer += [span("train::step", 104.0, 0.004)]
    assert read("step.interval_max_over_median") == pytest.approx(2 / 1.5)


def test_where_steps_are_sent_ahead_the_ratio_is_over_the_losses_reads(
        buffer):
    """The queue fills in a burst of ``train::step`` entries; the reads of
    the losses end a step apart, but where the host stood still: then one
    ends late and those behind it at once."""
    buffer += [span("train::step", t, 0.004)
               for t in (101.0, 102.0, 102.01, 102.02, 102.03)]
    waits = {"wait": [[101.0, 102.0], [102.0, 103.0], [103.0, 104.0],
                      [104.0, 106.5], [106.5, 106.5], [106.5, 107.0]]}
    assert read("step.interval_max_over_median",
                dict(RECORD, spans=waits)) == pytest.approx(2.5)
    assert read("step.interval_max_over_median", dict(
        RECORD, spans={"wait": waits["wait"][:2]})) is None


def test_the_latest_tick_of_the_window(buffer):
    buffer += [span("host::tick", 100.0 + i / 10, late,
                    thread="ray_tpu-profiler-driver")
               for i, late in enumerate((2e-4, 3e-4, 0.116, 1e-4))]
    buffer += [span("host::tick", 99.0, 3.4), span("host::tick", 200.5, 2.0)]
    assert read("host.late_tick_max_ms") == pytest.approx(116.0)


def test_a_batch_is_the_median_of_the_window_s(buffer):
    buffer += [span("data::next_batch", 101.0 + i, ms / 1e3, bytes=65568)
               for i, ms in enumerate((2.0, 9.0, 3.0))]
    buffer += [span("data::to_device", 101.0, 0.001),
               span("data::next_batch", 10.0, 7.0)]
    assert read("data.next_batch_ms") == pytest.approx(3.0)


@pytest.mark.parametrize("name,layer,moves,unit,cells", [
    ("step.dispatch_ms", "step", "tokens_per_s", "ms", ".steady"),
    ("step.interval_max_over_median", "step", "tokens_per_s", "ratio",
     ".steady"),
    ("host.late_tick_max_ms", "core runtime", "tokens_per_s", "ms",
     ".steady"),
    ("data.next_batch_ms", "Data ingest", "job_tokens_per_s", "ms", ".job"),
])
def test_each_is_listed_for_its_cells_with_a_reader(name, layer, moves,
                                                    unit, cells):
    """Wherever the entry stands in ``per_layer``: later PRs append."""
    spec = harness.load_spec()
    [entry] = [m for m in spec["per_layer"] if m["name"] == name]
    want = [w["name"] for w in spec["workloads"]
            if w["name"].endswith(cells)]
    assert want and entry == {
        "name": name, "unit": unit, "better": "lower",
        "source": "program_span", "layer": layer, "moves": moves,
        "workloads": want}
    assert os.path.isfile(os.path.join(harness.HERE, "layer_metrics",
                                       name + ".py"))
    # Every cell that lists it reports the metric it moves.
    reporting = {w for m in spec["end_to_end"] if m["name"] == moves
                 for w in m["workloads"]}
    assert set(want) <= reporting
