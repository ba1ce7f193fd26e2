"""What JAX says of the programs it makes, as this repo's metrics and spans.

JAX times three phases of making a program and reports each on its way out
(``jax.monitoring``; ``dispatch.py``'s ``log_elapsed_time`` in the
installed 0.9): tracing the function to a jaxpr, lowering the jaxpr to an
MLIR module (where every Pallas kernel goes through Mosaic), and the
backend's part: XLA's compile on a miss of the persistent cache; the key,
the retrieval and the deserialising on a hit. ``install()`` registers, once
a process, listeners that turn each into

* an observation of ``ray_tpu_jax_compile_seconds{phase, within}``:
  ``phase`` is ``trace``, ``lower`` or ``backend``, and ``within`` the
  set-up stage open on the thread (``state_init``, ``first_call``, ...;
  ``none`` outside any: ``builtin_metrics.setup_stage``). The phases nest (a
  jitted function traced inside another; a constant computed eagerly, so
  compiled, in the middle of a trace), so each is observed **less what it
  enclosed** on its thread: the observations tile, and their sum over a
  stage is the time the stage spent making programs, each second once.
  ``phase="cache_load"`` is the retrieval alone; it lies inside ``backend``
  and is not to be added to it;
* a count in ``ray_tpu_jax_programs_total{outcome=compiled|cache_hit}``;
* where spans record (``tracing.finished_span_context``), a span
  ``compile::trace`` / ``compile::lower`` / ``compile::backend`` /
  ``compile::cache_load`` of the phase's whole length (from 5 ms: a step's
  trace holds hundreds of inner functions' own) with the function's name
  as ``program`` (an attribute, not a label: unbounded), on
  ``compile::backend`` also ``cache``: ``hit``, ``miss`` or ``off``;
  parented to the thread's active span, so a step's hang under
  ``step::first_call`` and ``init``'s under ``setup::state_init``. The
  interval is over when JAX reports it, so these spans are **not**
  ``TraceAnnotation``s and do not appear in a ``jax.profiler`` trace, which
  takes no event after the fact; JAX's stamps are ``time.time()``, and
  ``perf_start`` is that start moved by the two clocks' offset read at
  arrival.

``first_call`` is the stage around a call of a jitted step: it is observed,
and is a span ``step::first_call``, only when the function's cache grew
during the call. A warm loop reaches no listener: JAX reports nothing for a
call that finds its program.

Such a call is timed where it is made, by the same two clock reads. Always
on: ``ray_tpu_train_step_dispatch_seconds{program}`` (entry to return) and
``ray_tpu_train_step_interval_seconds{program}`` (entry to entry of the same
wrapped step, ``StepClock``), the interval divided by the thread's running
totals of the loop's waits (``builtin_metrics.loop_wait``) into its save,
its report, its batch and the rest; a rest far above the median of the last
ones is a stalled step: a journal row (``event=step_stall``) and seconds in
``ray_tpu_train_step_stalled_seconds_total{program, cause}``, the cause from
the continuous profiler's late ticks inside the interval
(``_private/profiling.py``). While something records, the call is a span
``train::step`` (``program``, ``n``, and of the interval that ended at its
entry ``interval_s``, ``save_s``, ``report_s``, ``data_s``), the recorder of
the model's scalars its child ``step::record``.
"""

from __future__ import annotations

import collections
import statistics
import threading
import time
import uuid
from typing import Any, Callable, Dict, Optional

from ray_tpu._private import builtin_metrics, events, profiling
from ray_tpu.util import tracing

_PHASE_BY_EVENT = {
    "/jax/core/compile/jaxpr_trace_duration": "trace",
    "/jax/core/compile/jaxpr_to_mlir_module_duration": "lower",
    "/jax/core/compile/backend_compile_duration": "backend",
}
_CACHE_BY_EVENT = {"/jax/compilation_cache/cache_hits": "hit",
                   "/jax/compilation_cache/cache_misses": "miss"}
_CACHE_LOAD_EVENT = "/jax/compilation_cache/cache_retrieval_time_sec"
FIRST_CALL = "step::first_call"
TRAIN_STEP = "train::step"
RECORD = "step::record"
# Two stamps of one instant may differ by the clock's grain.
_GRAIN = 1e-6
# A step's trace holds hundreds of inner jitted functions' own (``add``,
# ``_where``): a phase shorter than this is observed and leaves no span.
_SPAN_FLOOR = 5e-3
# A trace reports thousands of inner ones before itself: none of those may
# be forgotten until it has.
_SEEN_KEPT = 65536

_install_lock = threading.Lock()
_installed = False
_local = threading.local()
#: Calls of any of the three listeners, for the test that a warm loop makes
#: none.
listener_calls = 0


def install() -> None:
    """Register the listeners with ``jax.monitoring``, once a process (JAX
    keeps a listener for good). Called from the program's first JAX entry
    points; ``ray_tpu.init()`` never gets here."""
    global _installed
    if _installed:
        return
    with _install_lock:
        if _installed:
            return
        import jax
        jax.monitoring.register_event_time_span_listener(_on_time_span)
        jax.monitoring.register_event_duration_secs_listener(_on_duration)
        jax.monitoring.register_event_listener(_on_event)
        _installed = True


def _span_context() -> Optional[Dict[str, Any]]:
    """Under what a ``compile::*`` span records now, or None: a retracing
    call's ``step::first_call``, which is itself recorded only when the call
    is over, else the thread's active span."""
    call = getattr(_local, "call", None)
    return call.child_context() if call is not None \
        else tracing.finished_span_context()


def _record(phase: str, start: float, end: float,
            attributes: Dict[str, Any]) -> None:
    ctx = _span_context() if end - start >= _SPAN_FLOOR else None
    if ctx is not None:
        tracing.record_complete_span(
            "compile::" + phase, ctx, wall_start=start,
            duration=end - start, attributes=attributes,
            perf_start=time.perf_counter() - (time.time() - start))


def _on_time_span(event: str, start_time: float, end_time: float,
                  **kwargs) -> None:
    global listener_calls
    listener_calls += 1
    phase = _PHASE_BY_EVENT.get(event)
    if phase is None:
        return
    # Intervals arrive as they end, so what this one enclosed on this
    # thread is the tail of the ones seen that began no earlier.
    seen = getattr(_local, "seen", None)
    if seen is None:
        seen = _local.seen = []
    seconds = end_time - start_time
    while seen and seen[-1][0] >= start_time - _GRAIN:
        inner = seen.pop()
        seconds -= inner[1] - inner[0]
    if len(seen) >= 2 * _SEEN_KEPT:  # outermost intervals, never enclosed
        del seen[:_SEEN_KEPT]
    seen.append((start_time, end_time))
    builtin_metrics.jax_compile_seconds().observe(
        max(0.0, seconds),
        tags={"phase": phase,
              "within": builtin_metrics.setup_stage_open()})
    attributes = {"program": kwargs.get("fun_name", "")}
    if phase == "backend":
        cache = getattr(_local, "cache", None)
        _local.cache = None
        attributes["cache"] = cache or "off"
        builtin_metrics.jax_programs().inc(tags={
            "outcome": "cache_hit" if cache == "hit" else "compiled"})
    _record(phase, start_time, end_time, attributes)


def _on_duration(event: str, duration_secs: float, **kwargs) -> None:
    global listener_calls
    listener_calls += 1
    if event != _CACHE_LOAD_EVENT:
        return
    builtin_metrics.jax_compile_seconds().observe(
        duration_secs, tags={"phase": "cache_load",
                             "within": builtin_metrics.setup_stage_open()})
    now = time.time()
    _record("cache_load", now - duration_secs, now, {})


def _on_event(event: str, **kwargs) -> None:
    global listener_calls
    listener_calls += 1
    cache = _CACHE_BY_EVENT.get(event)
    if cache is not None:
        # Inside the backend's interval, which is reported after it.
        _local.cache = cache


class StepClock:
    """What one wrapped step keeps between its calls: two trainers, or a
    train step and an eval step, do not mix their intervals."""

    __slots__ = ("program", "calls", "entry", "waits", "rests")

    def __init__(self, program: str) -> None:
        self.program = program
        self.calls = 0
        #: The last call's entry (``time.perf_counter``), None where the
        #: next interval would span a call that made a program.
        self.entry: Optional[float] = None
        #: ``builtin_metrics.loop_waits()`` as that entry found them.
        self.waits = (0.0, 0.0, 0.0)
        self.rests: collections.deque = collections.deque(
            maxlen=profiling.STALL_HISTORY)

    def enter(self, t0: float, span) -> Optional[tuple]:
        """A call's entry at ``t0``: the interval that ends here, observed
        and, with its parts, the span's attributes. Returns what ``judge``
        takes, or None where no interval ends here."""
        self.calls += 1
        previous, self.entry = self.entry, t0
        before, self.waits = self.waits, builtin_metrics.loop_waits()
        if span is not None:
            span.attributes.update(program=self.program, n=self.calls)
        if previous is None:
            return None
        interval = t0 - previous
        parts = tuple(now - was for now, was in zip(self.waits, before))
        builtin_metrics.train_step_interval_seconds().observe(
            interval, tags={"program": self.program})
        if span is not None:
            span.attributes.update(
                interval_s=interval,
                **{f"{what}_s": part for what, part in
                   zip(builtin_metrics.LOOP_WAITS, parts)})
        return previous, interval, parts

    def judge(self, previous: float, interval: float, parts: tuple) -> None:
        """Hold the interval's rest (a save every fourth step is no stall)
        against the median of the last ones; far above it is a stalled
        step, put down to the late ticks inside the interval."""
        rest = interval - sum(parts)
        rests = self.rests
        if len(rests) >= profiling.STALL_MIN_HISTORY:
            median = statistics.median(rests)
            excess = rest - median
            if excess > max(profiling.STALL_FLOOR_S,
                            profiling.STALL_SHARE * median):
                self._stalled(previous, interval, parts, median, excess)
        rests.append(rest)

    def _stalled(self, previous: float, interval: float, parts: tuple,
                 median: float, excess: float) -> None:
        agent = profiling.global_profiler()
        late, cause = (0.0, "unwatched") if agent is None else \
            agent.late_between(previous, previous + interval)
        builtin_metrics.train_step_stalled_seconds().inc(
            excess, tags={"program": self.program, "cause": cause})
        waits = ", ".join(f"{what} {part:.3f}" for what, part in
                          zip(builtin_metrics.LOOP_WAITS, parts))
        events.emit(
            "train",
            f"step stalled: {self.program} call {self.calls - 1} took "
            f"{interval:.3f}s to the next ({waits}; the rest "
            f"{excess:.3f}s over its median {median:.3f}s); the process "
            f"woke late by {late:.3f}s inside it, cause {cause}",
            severity="warning",
            labels={"event": "step_stall", "program": self.program,
                    "cause": cause})


class first_call:
    """The stage around one call of the jitted ``step``. The first call of
    a function is a span like any other, so in a profile it is a
    ``TraceAnnotation`` too. A later call cannot know that it will retrace:
    it is a span ``train::step`` like every call that finds its program,
    and its ``step::first_call`` is recorded inside that when it is over
    (``recompile=True``, with a journal row: the stall an operator most
    wants named) under an id made when the first ``compile::*`` span inside
    it asked for a parent. A call that found its program observes its
    dispatch and the interval that ended at its entry (``StepClock``); one
    that made a program is set-up's, and starts the interval's clock anew."""

    __slots__ = ("_jitted", "_clock", "_size", "_scope", "_outer", "_t0",
                 "_returned", "_interval", "_ctx", "_span_id")

    def __init__(self, jitted, clock: StepClock):
        self._jitted = jitted
        self._clock = clock

    def child_context(self) -> Optional[Dict[str, Any]]:
        if self._span_id is None:
            self._ctx = tracing.finished_span_context()
            self._span_id = uuid.uuid4().hex[:8]
        return self._ctx and dict(self._ctx, parent_id=self._span_id)

    def __enter__(self) -> "first_call":
        self._size = self._jitted._cache_size()
        self._ctx = self._span_id = self._returned = None
        first = self._size == 0
        self._scope = tracing.start_span(FIRST_CALL if first else TRAIN_STEP)
        span = self._scope.__enter__()
        if first:
            if span is not None:
                span.attributes.update(program=self._clock.program,
                                       recompile=False)
        else:
            _local.call = self
        self._outer = builtin_metrics.enter_setup_stage("first_call")
        self._t0 = time.perf_counter() if span is None else span.perf_start
        self._interval = self._clock.enter(self._t0,
                                           None if first else span)
        return self

    def after(self, after_call: Callable[[Any], None], out: Any) -> None:
        """What the step's wrapper does with the call's result, inside the
        call's span and dispatch and outside the set-up stage."""
        self._returned = time.perf_counter()
        with tracing.child_span(RECORD):
            after_call(out)

    def __exit__(self, *exc) -> bool:
        now = time.perf_counter()
        program = self._clock.program
        seconds = (self._returned or now) - self._t0
        grew = self._jitted._cache_size() > self._size
        made = grew or self._size == 0
        builtin_metrics.leave_setup_stage(
            "first_call", self._outer, seconds if made else None)
        _local.call = None
        if grew and self._size:
            if self._span_id is None:
                self.child_context()
            tracing.record_complete_span(
                FIRST_CALL, self._ctx, wall_start=time.time() - seconds,
                duration=seconds, perf_start=self._t0,
                span_id=self._span_id,
                attributes={"program": program, "recompile": True})
            events.emit(
                "train",
                f"step recompiled: {program} retraced on call with "
                f"new argument shapes or types ({seconds:.2f}s)",
                severity="warning",
                labels={"event": "step_recompile", "program": program})
        self._scope.__exit__(*exc)
        if made:
            self._clock.entry = None
        elif exc[0] is None:
            builtin_metrics.train_step_dispatch_seconds().observe(
                now - self._t0, tags={"program": program})
            if self._interval is not None:
                self._clock.judge(*self._interval)
        return False
