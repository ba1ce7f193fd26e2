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
"""

from __future__ import annotations

import threading
import time
import uuid
from typing import Any, Dict, Optional

from ray_tpu._private import builtin_metrics, events
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


class first_call:
    """The stage around one call of the jitted ``step``. The first call of
    a function is a span like any other, so in a profile it is a
    ``TraceAnnotation`` too. A later call cannot know that it will retrace:
    its span is recorded when it is over (``recompile=True``, with a journal
    row: the stall an operator most wants named) under an id made when the
    first ``compile::*`` span inside it asked for a parent. A call that
    found its program observes and records nothing."""

    __slots__ = ("_jitted", "_program", "_size", "_scope", "_outer",
                 "_t0", "_ctx", "_span_id")

    def __init__(self, jitted, program: str):
        self._jitted = jitted
        self._program = program

    def child_context(self) -> Optional[Dict[str, Any]]:
        if self._span_id is None:
            self._ctx = tracing.finished_span_context()
            self._span_id = uuid.uuid4().hex[:8]
        return self._ctx and dict(self._ctx, parent_id=self._span_id)

    def __enter__(self) -> None:
        self._size = self._jitted._cache_size()
        self._ctx = self._span_id = self._scope = None
        if self._size == 0:
            self._scope = tracing.start_span(FIRST_CALL)
            span = self._scope.__enter__()
            if span is not None:
                span.attributes.update(program=self._program,
                                       recompile=False)
        else:
            _local.call = self
        self._outer = builtin_metrics.enter_setup_stage("first_call")
        self._t0 = time.perf_counter()

    def __exit__(self, *exc) -> bool:
        seconds = time.perf_counter() - self._t0
        grew = self._jitted._cache_size() > self._size
        builtin_metrics.leave_setup_stage(
            "first_call", self._outer,
            seconds if grew or self._size == 0 else None)
        if self._scope is not None:
            self._scope.__exit__(*exc)
            return False
        _local.call = None
        if grew:
            if self._span_id is None:
                self.child_context()
            tracing.record_complete_span(
                FIRST_CALL, self._ctx, wall_start=time.time() - seconds,
                duration=seconds, perf_start=self._t0,
                span_id=self._span_id,
                attributes={"program": self._program, "recompile": True})
            events.emit(
                "train",
                f"step recompiled: {self._program} retraced on call with "
                f"new argument shapes or types ({seconds:.2f}s)",
                severity="warning",
                labels={"event": "step_recompile",
                        "program": self._program})
        return False
