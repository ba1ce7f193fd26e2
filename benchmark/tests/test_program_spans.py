"""The ``program_span`` readers on a span buffer made by hand: the program's
buffer (``ray_tpu.util.tracing.get_spans``) is swapped for a list of spans
with chosen starts, durations, parents and threads."""

import harness
import pytest

from ray_tpu.util import tracing

WINDOW = {"t0": 100.0, "t1": 200.0}
RECORD = {"window": WINDOW}
NAMES = ["ckpt.gather_s", "ckpt.copy_s", "ckpt.checksum_s", "ckpt.io_s",
         "ckpt.ack_s", "ckpt.unattributed_s", "ckpt.commit_s",
         "train.report_wait_ms"]


def span(name, start, seconds, parent=None, thread="train-rank-0", **attrs):
    s = tracing.Span(name=name, trace_id="t", span_id=f"{name}@{start}",
                     parent_id=parent.span_id if parent else None,
                     start_time=1e9 + start, duration=seconds,
                     end_time=1e9 + start + seconds, attributes=attrs,
                     perf_start=start, thread=thread)
    return s


def save(start, seq, gather, copy, checksum, write, ack, slack):
    """One save: two leaves, each phase split over them, ``slack`` seconds
    that no child covers, and its commit on the driver's thread."""
    phases = [("ckpt::meta", 0.5), ("ckpt::gather", gather / 2),
              ("ckpt::copy", copy / 4), ("ckpt::gather", gather / 2),
              ("ckpt::copy", copy / 4), ("ckpt::copy", copy / 4),
              ("ckpt::checksum", checksum / 2), ("ckpt::copy", copy / 4),
              ("ckpt::checksum", checksum / 2), ("ckpt::write", write),
              ("train::report", ack)]
    whole = span("train::report_sharded", start,
                 sum(d for _, d in phases) + slack, seq=seq)
    out, t = [whole], start
    for name, seconds in phases:
        out.append(span(name, t, seconds, parent=whole))
        t += seconds
    wait = span("train::report_wait", t - ack / 2, ack / 2, parent=out[-1])
    commit = span("ckpt::commit", t - ack / 2, 0.75, thread="MainThread",
                  seq=seq)
    prune = span("ckpt::prune", t, 0.25, parent=commit, thread="MainThread")
    return out + [wait, commit, prune]


@pytest.fixture
def buffer(monkeypatch):
    spans = []
    monkeypatch.setattr(tracing, "get_spans", lambda: list(spans))
    return spans


def read(name, record=RECORD):
    return harness.load_module("layer_metrics", name).read(record)


def test_one_save_reads_each_phase(buffer):
    buffer += save(110.0, seq=1, gather=4.0, copy=3.0, checksum=8.0,
                   write=4.5, ack=0.25, slack=0.125)
    assert read("ckpt.gather_s") == 4.0
    assert read("ckpt.copy_s") == 3.0
    assert read("ckpt.checksum_s") == 8.0
    assert read("ckpt.io_s") == 4.5
    assert read("ckpt.ack_s") == 0.25
    assert abs(read("ckpt.unattributed_s") - 0.125) < 1e-9
    assert read("ckpt.commit_s") == 0.75
    # The six loop-side metrics and the metadata add up to the save.
    whole = buffer[0].duration
    parts = sum(read(n) for n in NAMES[:6])
    assert abs(parts + 0.5 - whole) < 1e-9


def test_median_over_saves_and_the_window(buffer):
    for start, seq, gather in ((110.0, 1, 2.0), (140.0, 2, 6.0),
                               (170.0, 3, 3.0)):
        buffer += save(start, seq=seq, gather=gather, copy=1.0,
                       checksum=gather * 2, write=1.0, ack=0.5, slack=0.0)
    # Before the window (a warm-up save) and after it: not counted.
    buffer += save(50.0, seq=0, gather=50.0, copy=1.0, checksum=1.0,
                   write=1.0, ack=0.5, slack=9.0)
    buffer += save(201.0, seq=4, gather=60.0, copy=1.0, checksum=1.0,
                   write=1.0, ack=0.5, slack=9.0)
    assert read("ckpt.gather_s") == 3.0
    assert read("ckpt.checksum_s") == 6.0
    assert abs(read("ckpt.unattributed_s")) < 1e-9
    assert read("ckpt.commit_s") == 0.75
    # A commit that carries no save of the window is no save's cost.
    buffer.append(span("ckpt::commit", 150.0, 30.0, thread="MainThread",
                       seq=99))
    assert read("ckpt.commit_s") == 0.75


def test_report_wait_is_a_median_in_milliseconds(buffer):
    for i, seconds in enumerate((0.001, 0.002, 0.009)):
        report = span("train::report", 110.0 + i, seconds + 0.001)
        buffer += [report, span("train::report_wait", 110.0 + i, seconds,
                                parent=report)]
    buffer.append(span("train::report_wait", 99.0, 5.0))
    assert abs(read("train.report_wait_ms") - 2.0) < 1e-9


def test_an_open_span_is_not_read(buffer):
    whole = span("train::report_sharded", 110.0, 1.0, seq=1)
    child = span("ckpt::gather", 110.0, 1.0, parent=whole)
    child.duration = child.end_time = None
    buffer += [whole, child]
    assert read("ckpt.gather_s") is None


@pytest.mark.parametrize("name", NAMES)
def test_nothing_recorded_is_none(buffer, name):
    assert read(name) is None
    # Spans of other layers, and spans outside the window, change nothing.
    buffer.append(span("task::f", 150.0, 1.0))
    buffer += save(10.0, seq=1, gather=1.0, copy=1.0, checksum=1.0,
                   write=1.0, ack=1.0, slack=1.0)
    assert read(name) is None


def test_a_program_without_the_clock_is_none(buffer):
    """The parent's spans carry no ``perf_start``: nothing is selected."""

    class Old:
        name, duration, parent_id, span_id = "train::report_wait", 1.0, None, 1
        attributes = {}

    buffer.append(Old())
    assert read("train.report_wait_ms") is None


def test_the_entries_are_program_spans_of_their_cells():
    spec = harness.load_spec()
    entries = {m["name"]: m for m in spec["per_layer"]}
    job = [w["name"] for w in spec["workloads"] if w["traffic"] == "job"]
    steady = [w["name"] for w in spec["workloads"]
              if w["traffic"] == "steady"]
    for name in NAMES:
        assert entries[name]["source"] == "program_span"
        assert entries[name]["workloads"] == (
            steady if name == "train.report_wait_ms" else job)
        assert entries[name]["moves"] == (
            "tokens_per_s" if name == "train.report_wait_ms"
            else "ckpt_stall_s")
    # Appended: what the benchmark had keeps its place.
    assert [m["name"] for m in spec["per_layer"]][-len(NAMES):] == NAMES
