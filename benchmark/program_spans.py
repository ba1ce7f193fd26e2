"""The program's own spans (``ray_tpu.util.tracing``), for the per-layer
metrics whose ``source`` is ``program_span``.

A ``--trace 1`` run records the window under ``jax.profiler``, and while a
profile is recorded the program's span sites record: each span goes into the
profile as a ``TraceAnnotation`` and into the program's in-memory buffer,
which outlives ``ray_tpu.shutdown()``. The runner keeps neither the profile
nor any span but its own, so the readers take the buffer, in this process,
and select what started inside the window the runner timed
(``record["window"]``, on ``time.perf_counter``, the clock of a span's
``perf_start``). A program that records no such spans (the parent of the PR
that added them, or a ``--trace 0`` run) leaves every reader with None.

A save is one ``train::report_sharded`` span on the loop's thread; its
phases (``ckpt::drain_wait``, ``ckpt::meta``, ``ckpt::gather``,
``ckpt::copy``, ``ckpt::checksum``, ``ckpt::write``, and the ack, a nested
``train::report``) are its direct children. Since the program writes behind
the loop, the copy, checksum and write run on a writer's thread, begin
where the save hands over and end after it: still its children by
``parent_id``, but no part of its duration. A metric "per save" is summed
inside each save and the median is taken over the window's saves.
"""

from __future__ import annotations

from typing import Any, Dict, List, Optional, Tuple

import harness

SAVE = "train::report_sharded"


def in_window(record: Dict[str, Any]) -> List[Any]:
    """The buffer's ended spans that started inside the record's window."""
    from ray_tpu.util import tracing
    t0, t1 = record["window"]["t0"], record["window"]["t1"]
    return [s for s in tracing.get_spans()
            if s.duration is not None
            and t0 <= getattr(s, "perf_start", t0 - 1.0) <= t1]


def median_seconds(record: Dict[str, Any], name: str) -> Optional[float]:
    """Median duration of the window's spans called ``name``."""
    return harness.median(s.duration for s in in_window(record)
                          if s.name == name)


def saves(record: Dict[str, Any]) -> List[Tuple[Any, List[Any]]]:
    """``[(save span, its direct children)]`` of the window's saves."""
    spans = in_window(record)
    return [(save, [s for s in spans if s.parent_id == save.span_id])
            for save in spans if save.name == SAVE]


def phase_seconds(record: Dict[str, Any], name: str) -> Optional[float]:
    """Seconds a save spends in its children called ``name``: summed per
    save, median over the saves that have any."""
    sums = []
    for _, children in saves(record):
        phase = [s.duration for s in children if s.name == name]
        if phase:
            sums.append(sum(phase))
    return harness.median(sums)


def self_seconds(record: Dict[str, Any]) -> Optional[float]:
    """Seconds of a save that none of its children covers, median over the
    saves that have children. Only the children on the save's own thread
    count: those on another run beside it or after it has ended, and a sum
    across threads is no time."""
    return harness.median(
        save.duration - sum(
            s.duration for s in children
            if getattr(s, "thread", "") == getattr(save, "thread", ""))
        for save, children in saves(record) if children)


def commit_seconds(record: Dict[str, Any]) -> Optional[float]:
    """Seconds of the ``ckpt::commit`` (manifest, index, and the pruning
    nested in it) that carries a save's ``seq``, median over the saves."""
    seqs = {save.attributes.get("seq") for save, _ in saves(record)}
    return harness.median(
        s.duration for s in in_window(record)
        if s.name == "ckpt::commit" and s.attributes.get("seq") in seqs)
