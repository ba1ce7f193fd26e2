"""The ``program_span`` readers on a span buffer made by hand: the program's
buffer (``ray_tpu.util.tracing.get_spans``) is swapped for a list of spans
with chosen starts, durations, parents and threads. Two programs' saves:
the older one, whose phases all run inside the stall on the loop's thread
(``save``), and the one that writes behind the loop (``save_behind``): the
copy, checksum and write are still the save's direct children, but run on
the writer's thread, begin where it hands over and end after it."""

import harness
import pytest

from ray_tpu.util import tracing

WINDOW = {"t0": 100.0, "t1": 200.0}
RECORD = {"window": WINDOW}
LOOP, WRITER, DRIVER = "train-rank-0", "ckpt-writer-0", "MainThread"
NAMES = ["ckpt.gather_s", "ckpt.copy_s", "ckpt.checksum_s", "ckpt.io_s",
         "ckpt.ack_s", "ckpt.unattributed_s", "ckpt.commit_s",
         "train.report_wait_ms"]
BEHIND = ["ckpt.drain_wait_s", "ckpt.durable_lag_s"]


def span(name, start, seconds, parent=None, thread=LOOP, **attrs):
    return tracing.Span(
        name=name, trace_id="t", span_id=f"{name}@{start}",
        parent_id=parent.span_id if parent else None,
        start_time=1e9 + start, duration=seconds,
        end_time=1e9 + start + seconds, attributes=attrs,
        perf_start=start, thread=thread)


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
    commit = span("ckpt::commit", t - ack / 2, 0.75, thread=DRIVER,
                  seq=seq)
    prune = span("ckpt::prune", t, 0.25, parent=commit, thread=DRIVER)
    return out + [wait, commit, prune]


def save_behind(start, seq, drain, gather, checksum, write, commit_at,
                marked=True, slack=0.0):
    """One save: the stall (drain wait, metadata, two gathers, the ack) on
    the loop's thread; one copy, two checksums and the write on the
    writer's, from the ack on; the commit at ``commit_at`` on the
    driver's. ``marked`` False is the older program: no drain wait, and
    the writer's phases inside the stall. ``slack`` seconds of the stall no
    child covers."""
    stall = [("ckpt::drain_wait", drain)] if marked else []
    stall += [("ckpt::meta", 0.5), ("ckpt::gather", gather / 2),
              ("ckpt::gather", gather / 2)]
    behind = [("ckpt::copy", 0.25), ("ckpt::checksum", checksum / 2),
              ("ckpt::checksum", checksum / 2), ("ckpt::write", write)]
    if not marked:
        stall, behind = stall + behind, []
    stall.append(("train::report", 0.125))
    whole = span("train::report_sharded", start,
                 sum(d for _, d in stall) + slack, seq=seq)
    out, t = [whole], start
    for name, seconds in stall:
        out.append(span(name, t, seconds, parent=whole))
        t += seconds
    t -= 0.125  # handed over before the ack
    for name, seconds in behind:
        out.append(span(name, t, seconds, parent=whole, thread=WRITER))
        t += seconds
    if commit_at is not None:
        out.append(span("ckpt::commit", commit_at, 0.75, thread=DRIVER,
                        seq=seq))
    return out


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


def test_a_save_with_late_children_reads_each_phase(buffer):
    buffer += save_behind(110.0, seq=1, drain=0.0, gather=4.0, checksum=1.5,
                          write=4.5, commit_at=121.0)
    end = buffer[0].perf_start + buffer[0].duration
    assert buffer[0].duration == 4.625  # the stall: no checksum, no write
    assert max(s.perf_start + s.duration for s in buffer
               if s.thread == WRITER) > end
    assert read("ckpt.drain_wait_s") == 0.0
    assert read("ckpt.gather_s") == 4.0
    assert read("ckpt.copy_s") == 0.25
    assert read("ckpt.checksum_s") == 1.5
    assert read("ckpt.io_s") == 4.5
    assert read("ckpt.ack_s") == 0.125
    assert read("ckpt.commit_s") == 0.75
    assert read("ckpt.durable_lag_s") == 121.75 - end
    # The writer's 6.25 s are no part of a stall of 4.625 s: nothing is
    # left over, where a sum across both threads would read -6.25.
    assert read("ckpt.unattributed_s") == 0.0


def test_children_on_another_thread_after_the_save_are_not_its_time(buffer):
    """The stall's own seconds are what the loop's thread leaves uncovered,
    whatever the writer does once the save has ended; the stall is the
    drain wait, the metadata, the gathers, the ack and that rest."""
    for start, seq, slack in ((110.0, 1, 0.0625), (140.0, 2, 0.125),
                              (170.0, 3, 0.25)):
        buffer += save_behind(start, seq=seq, drain=0.0, gather=4.0,
                              checksum=1.5, write=40.0, commit_at=None,
                              slack=slack)
    assert abs(read("ckpt.unattributed_s") - 0.125) < 1e-9
    whole = sorted(s.duration for s in buffer
                   if s.name == "train::report_sharded")[1]
    parts = [read(n) for n in ("ckpt.gather_s", "ckpt.ack_s",
                               "ckpt.unattributed_s")]
    assert abs(sum(parts) + 0.5 - whole) < 1e-9  # 0.5: ckpt::meta
    # A child that carries no thread (an older program's span) counts as
    # on the save's own, as before.
    for s in buffer:
        s.thread = ""
    assert read("ckpt.unattributed_s") < -40.0


def test_medians_over_the_window_s_saves(buffer):
    for start, seq, drain, commit_at in ((110.0, 1, 0.0, 118.0),
                                         (140.0, 2, 2.0, 152.0),
                                         (170.0, 3, 0.5, 181.0)):
        buffer += save_behind(start, seq=seq, drain=drain, gather=2.0,
                              checksum=1.0, write=3.0, commit_at=commit_at)
    assert read("ckpt.drain_wait_s") == 0.5
    ends = [s.perf_start + s.duration for s in buffer
            if s.name == "train::report_sharded"]
    lags = sorted(c + 0.75 - e
                  for c, e in zip((118.0, 152.0, 181.0), ends))
    assert read("ckpt.durable_lag_s") == lags[1]
    # Saves outside the window are no part of either.
    buffer += save_behind(50.0, seq=0, drain=30.0, gather=1.0, checksum=1.0,
                          write=1.0, commit_at=99.0)
    buffer += save_behind(201.0, seq=4, drain=30.0, gather=1.0, checksum=1.0,
                          write=1.0, commit_at=260.0)
    assert read("ckpt.drain_wait_s") == 0.5
    assert read("ckpt.durable_lag_s") == lags[1]


def test_a_commit_after_the_window_counts_and_a_missing_one_does_not(buffer):
    """The window's last save commits after the window has ended: its lag
    is read if the commit was recorded, and the save is left out if it was
    not (the profile had stopped)."""
    buffer += save_behind(110.0, seq=1, drain=0.0, gather=2.0, checksum=1.0,
                          write=3.0, commit_at=118.0)
    buffer += save_behind(190.0, seq=2, drain=0.0, gather=2.0, checksum=1.0,
                          write=3.0, commit_at=205.0)
    ends = [s.perf_start + s.duration for s in buffer
            if s.name == "train::report_sharded"]
    first, second = 118.75 - ends[0], 205.75 - ends[1]
    assert read("ckpt.durable_lag_s") == (first + second) / 2
    # The late save's writer ran past the window: what began in it counts.
    assert read("ckpt.io_s") == 3.0
    buffer[:] = [s for s in buffer if not (
        s.name == "ckpt::commit" and s.attributes["seq"] == 2)]
    assert read("ckpt.durable_lag_s") == first
    # An open commit is no commit yet.
    late = span("ckpt::commit", 205.0, 0.75, thread=DRIVER, seq=2)
    late.duration = late.end_time = None
    buffer.append(late)
    assert read("ckpt.durable_lag_s") == first


@pytest.mark.parametrize("name", BEHIND)
def test_a_program_that_writes_inside_the_stall_is_none(buffer, name):
    assert read(name) is None
    buffer += save_behind(110.0, seq=1, drain=0.0, gather=4.0, checksum=1.5,
                          write=4.5, commit_at=120.0, marked=False)
    buffer.append(span("task::f", 150.0, 1.0))
    assert read(name) is None
    assert read("ckpt.commit_s") == 0.75  # the older readers still read
    # Spans without the in-process clock select nothing.
    buffer[:] = [s for s in buffer if s.name != "ckpt::commit"]

    class Old:
        name, duration, parent_id, span_id = "ckpt::commit", 1.0, None, 1
        attributes = {"seq": 1}

    buffer.append(Old())
    assert read(name) is None


def test_the_entries_are_program_spans_of_their_cells():
    """Names and cells, not places: every new cell or metric moves the
    places."""
    spec = harness.load_spec()
    entries = {m["name"]: m for m in spec["per_layer"]}
    job = [w["name"] for w in spec["workloads"] if w["traffic"] == "job"]
    steady = [w["name"] for w in spec["workloads"]
              if w["traffic"] == "steady"]
    for name in NAMES + BEHIND:
        assert entries[name]["source"] == "program_span"
        assert entries[name]["unit"] == name.rsplit("_", 1)[1]
        assert entries[name]["better"] == "lower"
        assert entries[name]["workloads"] == (
            steady if name == "train.report_wait_ms" else job)
        assert entries[name]["moves"] == (
            "tokens_per_s" if name == "train.report_wait_ms"
            else "job_tokens_per_s")
        if name.startswith("ckpt.") and name != "ckpt.ack_s":
            assert entries[name]["layer"] == "checkpoint writer"
