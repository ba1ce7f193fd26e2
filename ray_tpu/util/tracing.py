"""Distributed tracing: spans propagated through task submission.

Analog of the reference's util/tracing/tracing_helper.py (OpenTelemetry
spans wrapping every .remote() with the context carried inside task specs,
_DictPropagator :160): an OTel-compatible-shaped but dependency-free span
recorder. Enable with ``enable_tracing()``; every task/actor call then
records a span parented to the caller's active span, and ``get_spans()`` /
``export_chrome_trace()`` expose the tree.

Cross-process model (Dapper-style): the driver makes the sampling
decision ONCE per trace (``RAY_TPU_TRACE_SAMPLE_RATE``, head-of-trace
sampling) and serializes ``{trace_id, parent_id, sampled}`` into the
task spec / request metadata; every downstream hop parents its spans to
the carried context. Unsampled requests carry no context at all, so the
remote side's cost is a single attribute read. Finished spans ride
``metrics_batch`` frames to the head, where the trace assembler
(_private/trace_assembler.py) merges them per trace_id.

Timing: ``start_time`` is a wall-clock ANCHOR (for cross-process
alignment on one timeline); ``duration`` is measured monotonically so an
NTP step mid-span cannot corrupt it. ``end_time`` is derived
(anchor + duration), never a second wall-clock read.

Device profiles: in a process that has imported JAX, spans also record
while ``jax.profiler`` is recording a trace (between ``start_trace`` and
``stop_trace``), with no call to ``enable_tracing()``, and each recorded
span is then also a ``jax.profiler.TraceAnnotation`` of the same name: an
event on its thread's line of ``/host:CPU`` in the ``.xplane.pb``, on the
device trace's clock. This module never imports JAX itself
(``ray_tpu.init()`` runs without it).
"""

from __future__ import annotations

import os
import random
import sys
import threading
import time
import uuid
from dataclasses import dataclass, field
from typing import Any, Dict, List, Optional

_state = threading.local()
_lock = threading.Lock()
_spans: List["Span"] = []
_enabled = False
_MAX_SPANS = 100_000
#: Resolved sample rate; None = not yet resolved (lazy: env/config may
#: not be final at import time).
_sample_rate: Optional[float] = None


@dataclass
class Span:
    name: str
    trace_id: str
    span_id: str
    parent_id: Optional[str]
    start_time: float  # wall-clock anchor (cross-process alignment only)
    end_time: Optional[float] = None  # derived: start_time + duration
    duration: Optional[float] = None  # monotonic, NTP-step-proof
    attributes: Dict[str, Any] = field(default_factory=dict)
    # Set once the span has been drained into a metrics_batch frame, so a
    # long-open span ahead of it in the buffer cannot cause re-shipping.
    shipped: bool = field(default=False, repr=False, compare=False)
    # In-process only, never serialized (meaningless across processes):
    # the start on ``time.perf_counter()``, which is monotonic and is the
    # clock an in-process reader times its own windows on (0.0 for a span
    # recorded after the fact by a caller that did not know it), and the
    # name of the recording thread.
    perf_start: float = field(default=0.0, repr=False, compare=False)
    thread: str = field(default="", repr=False, compare=False)

    def end(self) -> None:
        if self.end_time is None:
            self.duration = time.perf_counter() - self.perf_start
            self.end_time = self.start_time + self.duration

    def to_dict(self) -> Dict[str, Any]:
        return {
            "name": self.name,
            "trace_id": self.trace_id,
            "span_id": self.span_id,
            "parent_id": self.parent_id,
            "start_time": self.start_time,
            "end_time": self.end_time,
            "duration": self.duration,
            "attributes": dict(self.attributes),
        }


class _Unsampled:
    """Thread-local sentinel: the active trace drew NOT-sampled. Keeps
    the head-of-trace decision sticky for nested local spans (a child of
    an unsampled root must not re-draw and start recording mid-trace)."""

    __slots__ = ()


_UNSAMPLED = _Unsampled()


def enable_tracing() -> None:
    """Turn span recording on (reference: ray.init(_tracing_startup_hook))."""
    global _enabled
    _enabled = True


def disable_tracing() -> None:
    global _enabled
    _enabled = False


def is_tracing_enabled() -> bool:
    return _enabled


def clear_spans() -> None:
    with _lock:
        _spans.clear()


def set_sample_rate(rate: Optional[float]) -> None:
    """Override the head-of-trace sampling rate (None = re-resolve from
    env/config on next use). Tests and the overhead bench use this."""
    global _sample_rate
    _sample_rate = None if rate is None else max(0.0, min(1.0, float(rate)))


def sample_rate() -> float:
    """The head-of-trace sampling probability (``RAY_TPU_TRACE_SAMPLE_RATE``
    env var / ``trace_sample_rate`` config flag; default 1.0 — every
    trace records once tracing is enabled). Resolved lazily and cached."""
    global _sample_rate
    rate = _sample_rate
    if rate is None:
        raw = os.environ.get("RAY_TPU_TRACE_SAMPLE_RATE")
        if raw is None:
            raw = os.environ.get("RAY_TPU_trace_sample_rate")
        try:
            rate = float(raw) if raw is not None else 1.0
        except ValueError:
            rate = 1.0
        rate = max(0.0, min(1.0, rate))
        _sample_rate = rate
    return rate


def _draw_sampled() -> bool:
    rate = sample_rate()
    if rate >= 1.0:
        return True
    if rate <= 0.0:
        return False
    return random.random() < rate


def current_span() -> Optional[Span]:
    span = getattr(_state, "span", None)
    return None if span is _UNSAMPLED else span


def _record(span: Span) -> None:
    with _lock:
        if len(_spans) < _MAX_SPANS:
            _spans.append(span)


def _new_span(name: str, trace_id: str, parent_id: Optional[str],
              attributes: Optional[Dict[str, Any]] = None) -> Span:
    return Span(
        name=name,
        trace_id=trace_id,
        span_id=uuid.uuid4().hex[:8],
        parent_id=parent_id,
        start_time=time.time(),
        attributes=dict(attributes or {}),
        perf_start=time.perf_counter(),
        thread=threading.current_thread().name,
    )


#: ``jax.profiler.TraceAnnotation`` once this process has imported JAX.
_annotation: Any = None


def _profiling() -> bool:
    """Is ``jax.profiler`` recording a trace right now (true exactly between
    ``start_trace`` and ``stop_trace``)? JAX is found in ``sys.modules``,
    never imported from here."""
    global _annotation
    if _annotation is None:
        try:
            _annotation = sys.modules["jax"].profiler.TraceAnnotation
        except (KeyError, AttributeError):  # not imported, or half-way
            return False
    return _annotation.is_enabled()


class _NoSpan:
    """What a span site gets when nothing records: one shared object, so
    the off path allocates nothing."""

    __slots__ = ()

    def __enter__(self) -> None:
        return None

    def __exit__(self, *exc) -> bool:
        return False


_NO_SPAN = _NoSpan()


class _UnsampledScope:
    """A root that drew not-sampled: marks the thread for its lifetime."""

    __slots__ = ()

    def __enter__(self) -> None:
        _state.span = _UNSAMPLED
        return None

    def __exit__(self, *exc) -> bool:
        _state.span = None
        return False


_UNSAMPLED_SCOPE = _UnsampledScope()


class _SpanScope:
    """One recorded span as the thread's active context for a ``with``
    block. While a device profile is recorded the span is also a
    ``TraceAnnotation`` of the same name, so it lies on the profile's
    clock, on this thread's line of the host plane."""

    __slots__ = ("_args", "_span", "_prev", "_annotated")

    def __init__(self, name: str, trace_id: str, parent_id: Optional[str],
                 attributes: Optional[Dict[str, Any]]):
        self._args = (name, trace_id, parent_id, attributes)

    def __enter__(self) -> Span:
        self._span = span = _new_span(*self._args)
        _record(span)
        self._prev = getattr(_state, "span", None)
        _state.span = span
        self._annotated = None
        if _profiling():
            self._annotated = _annotation(span.name)
            self._annotated.__enter__()
        return span

    def __exit__(self, *exc) -> bool:
        self._span.end()
        if self._annotated is not None:
            self._annotated.__exit__(*exc)
        _state.span = self._prev
        return False


def start_span(name: str, attributes: Optional[Dict[str, Any]] = None):
    """Open a span as the thread's active context; nested spans (and remote
    tasks submitted inside) are parented to it. A ROOT span (no active
    parent) makes the head-of-trace sampling decision; the verdict sticks
    for everything nested under it.

    Records after ``enable_tracing()`` or while ``jax.profiler`` records a
    device profile (every root is sampled then: the profile is the
    operator's request). Otherwise the cost is two flag reads, and the
    caller gets a shared no-op: set attributes on the yielded span, if it
    is not None, not through a dict built at the call site."""
    if not _enabled and not _profiling():
        return _NO_SPAN
    prev = getattr(_state, "span", None)
    if prev is _UNSAMPLED:
        return _NO_SPAN
    if prev is None and not _draw_sampled() and not _profiling():
        return _UNSAMPLED_SCOPE
    return _SpanScope(
        name,
        trace_id=prev.trace_id if prev else uuid.uuid4().hex[:16],
        parent_id=prev.span_id if prev else None,
        attributes=attributes)


def inject_context() -> Optional[Dict[str, Any]]:
    """Serialize the active span context for a task spec (the reference's
    _DictPropagator.inject_current_context). With no active span this IS
    the head of a trace: the sampling decision is made here, once, and an
    unsampled draw returns None — remote hops then pay one attribute read
    and nothing else."""
    if not _enabled:
        return None
    span = getattr(_state, "span", None)
    if span is _UNSAMPLED:
        return None
    if span is not None:
        return {"trace_id": span.trace_id, "parent_id": span.span_id,
                "sampled": True}
    if not _draw_sampled():
        return None
    return {"trace_id": uuid.uuid4().hex[:16], "parent_id": None,
            "sampled": True}


def span_context(span: Optional[Span]) -> Optional[Dict[str, Any]]:
    """A propagation context parented to ``span`` (for threading a
    specific span — e.g. the driver-submit span — into a wire message
    without touching thread-local state)."""
    if span is None:
        return None
    return {"trace_id": span.trace_id, "parent_id": span.span_id,
            "sampled": True}


def _ctx_sampled(ctx: Optional[Dict[str, Any]]) -> bool:
    # Contexts from pre-sampling peers carry no flag: treat as sampled
    # (they were only injected when tracing was on).
    return bool(ctx) and bool(ctx.get("sampled", True))


def continue_context(ctx: Optional[Dict[str, Any]], name: str,
                     attributes: Optional[Dict[str, Any]] = None):
    """Worker-side: run a task under the caller's trace context.

    Deliberately NOT gated on the local ``_enabled`` flag: a carried
    sampled context IS the enablement signal — the driver made the
    decision, and daemons/workers (where enable_tracing was never
    called) record purely because the request asked them to."""
    if not _ctx_sampled(ctx):
        return _NO_SPAN
    return _SpanScope(name, trace_id=ctx["trace_id"],
                      parent_id=ctx.get("parent_id"), attributes=attributes)


def record_complete_span(name: str, ctx: Optional[Dict[str, Any]], *,
                         wall_start: float, duration: float,
                         attributes: Optional[Dict[str, Any]] = None,
                         perf_start: float = 0.0,
                         span_id: Optional[str] = None) -> Optional[Span]:
    """Record an already-finished span under ``ctx`` retroactively —
    for stages measured across callbacks (queue wait, result store)
    where no ``with`` block brackets the interval. ``wall_start`` is the
    anchor; ``duration`` must come from monotonic deltas. Like
    continue_context, gated on the context alone, not ``_enabled``.
    ``perf_start`` places it for in-process readers where the caller knows
    it; ``span_id`` is for a parent recorded after children that already
    name it."""
    if not _ctx_sampled(ctx):
        return None
    duration = max(0.0, float(duration))
    span = Span(
        name=name,
        trace_id=ctx["trace_id"],
        span_id=span_id or uuid.uuid4().hex[:8],
        parent_id=ctx.get("parent_id"),
        start_time=wall_start,
        end_time=wall_start + duration,
        duration=duration,
        attributes=dict(attributes or {}),
        perf_start=perf_start,
        thread=threading.current_thread().name,
    )
    _record(span)
    return span


def finished_span_context() -> Optional[Dict[str, Any]]:
    """``start_span``'s rule for a span that is over when its caller learns
    of it (an interval JAX reports on exit, a stage that crosses threads):
    the context to hand ``record_complete_span``, parented to the thread's
    active span, or None where nothing records. Such a span cannot be a
    ``TraceAnnotation``: the profile takes no event after the fact."""
    if not _enabled and not _profiling():
        return None
    prev = getattr(_state, "span", None)
    if prev is _UNSAMPLED:
        return None
    if prev is not None:
        return span_context(prev)
    if not _draw_sampled() and not _profiling():
        return None
    return {"trace_id": uuid.uuid4().hex[:16], "parent_id": None,
            "sampled": True}


def child_span(name: str, attributes: Optional[Dict[str, Any]] = None):
    """A span recorded ONLY under an active sampled parent (data-plane
    helpers like object pulls: traced when a traced task triggers them,
    free when nothing is tracing this request). The parent — not the
    local ``_enabled`` flag — is the gate, so pulls inside a propagated
    remote span record too."""
    parent = current_span()
    if parent is None:
        return _NO_SPAN
    return _SpanScope(name, trace_id=parent.trace_id,
                      parent_id=parent.span_id, attributes=attributes)


class _AdoptedScope:
    """Another thread's span as this thread's active context."""

    __slots__ = ("_span", "_prev")

    def __init__(self, span: Span):
        self._span = span

    def __enter__(self) -> Span:
        self._prev = getattr(_state, "span", None)
        _state.span = self._span
        return self._span

    def __exit__(self, *exc) -> bool:
        _state.span = self._prev
        return False


def adopt_span(span: Optional[Span]):
    """Work handed from one thread to another stays under the span that
    began it: inside the ``with`` block ``span`` (open or ended, recorded
    on any thread) is this thread's active span, so ``child_span`` sites
    record as its direct children, each on the thread it ran on. With None
    (nothing recorded where the work began) nothing records here."""
    return _NO_SPAN if span is None else _AdoptedScope(span)


def drain_finished_spans(cursor: int = 0) -> tuple:
    """Ended, not-yet-shipped spans at or after ``cursor``, as plain
    dicts, plus the new cursor (the metrics agent's incremental export:
    spans ride ``metrics_batch`` frames to the head so /api/timeline can
    render cross-process task spans). Open spans are left in place and
    revisited on the next drain; the cursor only advances past the prefix
    whose spans are all shipped."""
    out: List[Dict[str, Any]] = []
    with _lock:
        cursor = max(0, min(cursor, len(_spans)))
        new_cursor = cursor
        advancing = True
        for i in range(cursor, len(_spans)):
            span = _spans[i]
            if span.end_time is None:
                advancing = False
            elif not span.shipped:
                span.shipped = True
                out.append(span.to_dict())
            if advancing:
                new_cursor = i + 1
    return out, new_cursor


def get_spans(trace_id: Optional[str] = None) -> List[Span]:
    with _lock:
        spans = list(_spans)
    if trace_id is not None:
        spans = [s for s in spans if s.trace_id == trace_id]
    return spans


def export_chrome_trace() -> List[Dict[str, Any]]:
    """Spans as chrome://tracing complete events (merges into the timeline
    the state API already emits)."""
    out = []
    for s in get_spans():
        dur = s.duration if s.duration is not None else 0.0
        out.append({
            "name": s.name,
            "cat": "trace",
            "ph": "X",
            "ts": s.start_time * 1e6,
            "dur": dur * 1e6,
            "pid": s.trace_id,
            "tid": s.span_id,
            "args": s.attributes,
        })
    return out
