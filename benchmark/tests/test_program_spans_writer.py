"""The readers of the save written behind the loop (``ckpt.drain_wait_s``,
``ckpt.durable_lag_s``), and the older phase readers on its spans, on a
span buffer made by hand: the phases that moved to the writer's thread are
still the save's direct children, begin where it hands over and end after
it."""

import harness
import pytest

from ray_tpu.util import tracing

RECORD = {"window": {"t0": 100.0, "t1": 200.0}}
LOOP, WRITER, DRIVER = "train-rank-0", "ckpt-writer-0", "MainThread"


def span(name, start, seconds, parent=None, thread=LOOP, **attrs):
    return tracing.Span(
        name=name, trace_id="t", span_id=f"{name}@{start}",
        parent_id=parent.span_id if parent else None,
        start_time=1e9 + start, duration=seconds,
        end_time=1e9 + start + seconds, attributes=attrs,
        perf_start=start, thread=thread)


def save(start, seq, drain, gather, checksum, write, commit_at,
         marked=True):
    """One save: the stall (drain wait, metadata, two gathers, the ack) on
    the loop's thread; one copy, two checksums and the write on the
    writer's, from the ack on; the commit at ``commit_at`` on the
    driver's. ``marked`` False is the older program: no drain wait, and
    the writer's phases inside the stall."""
    stall = [("ckpt::drain_wait", drain)] if marked else []
    stall += [("ckpt::meta", 0.5), ("ckpt::gather", gather / 2),
              ("ckpt::gather", gather / 2)]
    behind = [("ckpt::copy", 0.25), ("ckpt::checksum", checksum / 2),
              ("ckpt::checksum", checksum / 2), ("ckpt::write", write)]
    if not marked:
        stall, behind = stall + behind, []
    stall.append(("train::report", 0.125))
    whole = span("train::report_sharded", start, sum(d for _, d in stall),
                 seq=seq)
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


def test_a_save_with_late_children_reads_each_phase(buffer):
    buffer += save(110.0, seq=1, drain=0.0, gather=4.0, checksum=1.5,
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


def test_medians_over_the_window_s_saves(buffer):
    for start, seq, drain, commit_at in ((110.0, 1, 0.0, 118.0),
                                         (140.0, 2, 2.0, 152.0),
                                         (170.0, 3, 0.5, 181.0)):
        buffer += save(start, seq=seq, drain=drain, gather=2.0,
                       checksum=1.0, write=3.0, commit_at=commit_at)
    assert read("ckpt.drain_wait_s") == 0.5
    ends = [s.perf_start + s.duration for s in buffer
            if s.name == "train::report_sharded"]
    lags = sorted(c + 0.75 - e
                  for c, e in zip((118.0, 152.0, 181.0), ends))
    assert read("ckpt.durable_lag_s") == lags[1]
    # Saves outside the window are no part of either.
    buffer += save(50.0, seq=0, drain=30.0, gather=1.0, checksum=1.0,
                   write=1.0, commit_at=99.0)
    buffer += save(201.0, seq=4, drain=30.0, gather=1.0, checksum=1.0,
                   write=1.0, commit_at=260.0)
    assert read("ckpt.drain_wait_s") == 0.5
    assert read("ckpt.durable_lag_s") == lags[1]


def test_a_commit_after_the_window_counts_and_a_missing_one_does_not(buffer):
    """The window's last save commits after the window has ended: its lag
    is read if the commit was recorded, and the save is left out if it was
    not (the profile had stopped)."""
    buffer += save(110.0, seq=1, drain=0.0, gather=2.0, checksum=1.0,
                   write=3.0, commit_at=118.0)
    buffer += save(190.0, seq=2, drain=0.0, gather=2.0, checksum=1.0,
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


@pytest.mark.parametrize("name", ["ckpt.drain_wait_s", "ckpt.durable_lag_s"])
def test_a_program_that_writes_inside_the_stall_is_none(buffer, name):
    assert read(name) is None
    buffer += save(110.0, seq=1, drain=0.0, gather=4.0, checksum=1.5,
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


def test_the_entries_are_the_job_cell_s_program_spans():
    spec = harness.load_spec()
    entries = {m["name"]: m for m in spec["per_layer"]}
    job = [w["name"] for w in spec["workloads"] if w["traffic"] == "job"]
    names = ["ckpt.drain_wait_s", "ckpt.durable_lag_s"]
    for name in names:
        assert entries[name] == {
            "name": name, "unit": "s", "better": "lower",
            "source": "program_span", "layer": "checkpoint writer",
            "moves": "ckpt_stall_s", "workloads": job}
    # Appended behind what the benchmark had, which keeps its place
    # (later entries may follow).
    order = [m["name"] for m in spec["per_layer"]]
    assert order.index("kernel.eva_bwd_dkv_roofline") \
        < order.index(names[0]) < order.index(names[1])
