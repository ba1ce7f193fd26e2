"""Head-side trace assembly: per-origin spans -> complete traces.

Every process's finished spans ride ``metrics_batch`` frames to the head
(_private/metrics_agent.py); :class:`ClusterMetrics.update` stamps each
with its origin (node_id, pid, component) and feeds it here. The
assembler groups spans by trace_id into bounded-retention traces
(``RAY_TPU_TRACE_RETENTION`` newest traces; oldest evicted), attributes
every span to a pipeline stage (submit/queue/lease/pull/execute/store/
serve_dispatch/serve_handle), and serves three read surfaces:

* ``list_traces()`` / ``get_trace(id)`` — the ``/api/traces`` dashboard
  routes and ``ray-tpu trace``: full span trees with per-stage breakdown.
* ``summary()`` — cluster-level critical-path attribution: where does
  request time go, by stage (count / total / share / p50 / p95). Also
  exported continuously as the ``ray_tpu_trace_stage_seconds`` histogram.
* ``perfetto()`` / ``flow_events()`` — Chrome-trace JSON with ``s``/``f``
  flow events linking parent→child spans across process boundaries, so
  daemon-hop causality renders as arrows in Perfetto / chrome://tracing.
"""

from __future__ import annotations

import os
import threading
from collections import OrderedDict
from typing import Any, Dict, List, Optional

DEFAULT_RETENTION = 1000

#: Canonical span-name prefix -> pipeline stage (the glossary in the
#: README's tracing section). Spans may also carry an explicit
#: ``attributes["stage"]``, which wins.
_STAGE_BY_PREFIX = (
    ("driver::submit", "submit"),
    ("sched::queue_wait", "queue"),
    ("sched::lease", "lease"),
    ("data::pull", "pull"),
    ("task::store_result", "store"),
    ("serve::router_dispatch", "serve_dispatch"),
    ("serve::replica_handler", "serve_handle"),
    ("task::", "execute"),
    ("actor_task::", "execute"),
    # Train's loop: a save with its phases, a report, a batch.
    ("train::report_sharded", "train_save"),
    ("train::report", "train_report"),
    # A call of the jitted step with the recorder of its scalars inside,
    # and the continuous profiler's ticks (a tick's length is its lateness).
    ("train::step", "train_step"),
    ("step::record", "train_step"),
    ("host::tick", "host_late"),
    ("ckpt::", "ckpt"),
    ("data::next_batch", "train_ingest"),
    ("data::to_device", "train_ingest"),
    # Train's set-up, stage by stage, and what JAX reports of the programs
    # made on the way (parallel/compile_events.py).
    ("setup::", "train_setup"),
    ("step::first_call", "train_setup"),
    ("step::lower", "train_setup"),
    ("compile::", "compile"),
)


def trace_retention() -> int:
    """Retained trace count: ``RAY_TPU_TRACE_RETENTION`` env /
    ``trace_retention`` config flag (default 1000)."""
    raw = os.environ.get("RAY_TPU_TRACE_RETENTION")
    if raw is not None:
        try:
            return max(1, int(float(raw)))
        except ValueError:
            pass
    try:
        from ray_tpu._private.ray_config import runtime_config_value
        return max(1, int(runtime_config_value("trace_retention",
                                               DEFAULT_RETENTION)))
    except Exception:  # noqa: BLE001 - config table unavailable
        return DEFAULT_RETENTION


def span_stage(span: Dict[str, Any]) -> str:
    attrs = span.get("attributes") or {}
    stage = attrs.get("stage")
    if stage:
        return str(stage)
    name = span.get("name", "")
    for prefix, stage in _STAGE_BY_PREFIX:
        if name.startswith(prefix):
            return stage
    return "other"


def _span_duration(span: Dict[str, Any]) -> float:
    dur = span.get("duration")
    if dur is None:
        # Pre-monotonic peers: fall back to the wall-clock pair.
        end = span.get("end_time")
        start = span.get("start_time", 0.0)
        dur = (end - start) if end is not None else 0.0
    return max(0.0, float(dur))


def _origin_label(span: Dict[str, Any]) -> str:
    """The Chrome-trace pid label; matches ClusterMetrics.chrome_spans so
    flow events land on the same tracks as the complete events."""
    return (f"node:{(span.get('node_id') or 'head')[:12]}"
            f"/{span.get('component', '')}-{span.get('pid', 0)}")


def _percentile(sorted_vals: List[float], q: float) -> float:
    if not sorted_vals:
        return 0.0
    idx = min(len(sorted_vals) - 1, int(q * len(sorted_vals)))
    return sorted_vals[idx]


def _stage_breakdown(spans: List[Dict[str, Any]]) -> Dict[str, Any]:
    totals: Dict[str, float] = {}
    counts: Dict[str, int] = {}
    for s in spans:
        stage = span_stage(s)
        totals[stage] = totals.get(stage, 0.0) + _span_duration(s)
        counts[stage] = counts.get(stage, 0) + 1
    grand = sum(totals.values()) or 1.0
    return {stage: {"count": counts[stage],
                    "total_s": round(totals[stage], 6),
                    "share": round(totals[stage] / grand, 4)}
            for stage in sorted(totals)}


class TraceAssembler:
    """Bounded trace_id -> spans registry with stage attribution."""

    def __init__(self, retention: Optional[int] = None):
        self._lock = threading.Lock()
        # Insertion-ordered: oldest trace evicted first once over
        # retention. Values are span-dict lists in arrival order.
        self._traces: "OrderedDict[str, List[Dict[str, Any]]]" = \
            OrderedDict()
        self._retention = retention
        self._histogram = None

    @property
    def retention(self) -> int:
        if self._retention is None:
            self._retention = trace_retention()
        return self._retention

    def _observe_stage(self, stage: str, duration: float) -> None:
        if self._histogram is None:
            try:
                from ray_tpu._private import builtin_metrics
                self._histogram = builtin_metrics.trace_stage_seconds()
            except Exception:  # noqa: BLE001 - metrics must not break ingest
                self._histogram = False
        if self._histogram:
            self._histogram.observe(duration, {"stage": stage})

    def add_span(self, span: Dict[str, Any]) -> None:
        """Ingest one origin-stamped span dict (from a metrics batch)."""
        trace_id = span.get("trace_id")
        if not trace_id:
            return
        self._observe_stage(span_stage(span), _span_duration(span))
        with self._lock:
            spans = self._traces.get(trace_id)
            if spans is None:
                spans = self._traces[trace_id] = []
                while len(self._traces) > self.retention:
                    self._traces.popitem(last=False)
            spans.append(dict(span))

    def __len__(self) -> int:
        with self._lock:
            return len(self._traces)

    def _snapshot(self, trace_id: Optional[str] = None
                  ) -> "OrderedDict[str, List[Dict[str, Any]]]":
        with self._lock:
            if trace_id is not None:
                spans = self._traces.get(trace_id)
                return OrderedDict(
                    [(trace_id, list(spans))] if spans else [])
            return OrderedDict((tid, list(sp))
                               for tid, sp in self._traces.items())

    def list_traces(self, limit: Optional[int] = None
                    ) -> List[Dict[str, Any]]:
        """Newest-first trace summaries for ``GET /api/traces``."""
        traces = self._snapshot()
        out = []
        for trace_id in reversed(traces):
            spans = traces[trace_id]
            starts = [s.get("start_time", 0.0) for s in spans]
            ends = [s.get("end_time") or s.get("start_time", 0.0)
                    for s in spans]
            roots = [s for s in spans if not s.get("parent_id")]
            root = min(roots or spans,
                       key=lambda s: s.get("start_time", 0.0))
            out.append({
                "trace_id": trace_id,
                "root": root.get("name", ""),
                "span_count": len(spans),
                "start_time": min(starts) if starts else 0.0,
                "duration_s": round(max(ends) - min(starts), 6)
                              if starts else 0.0,
                "origins": sorted({_origin_label(s) for s in spans}),
            })
            if limit is not None and len(out) >= limit:
                break
        return out

    def get_trace(self, trace_id: str) -> Optional[Dict[str, Any]]:
        """One assembled trace: spans sorted by wall anchor, the
        per-stage critical-path breakdown, and cross-process count."""
        traces = self._snapshot(trace_id)
        spans = traces.get(trace_id)
        if not spans:
            return None
        spans = sorted(spans, key=lambda s: s.get("start_time", 0.0))
        starts = [s.get("start_time", 0.0) for s in spans]
        ends = [s.get("end_time") or s.get("start_time", 0.0)
                for s in spans]
        return {
            "trace_id": trace_id,
            "span_count": len(spans),
            "start_time": min(starts),
            "duration_s": round(max(ends) - min(starts), 6),
            "origins": sorted({_origin_label(s) for s in spans}),
            "stages": _stage_breakdown(spans),
            "spans": spans,
        }

    def summary(self) -> Dict[str, Any]:
        """Cluster-level critical-path attribution across every retained
        trace: per-stage count / total seconds / share / p50 / p95."""
        traces = self._snapshot()
        durations: Dict[str, List[float]] = {}
        transfer_s = 0.0
        transfer_bytes = 0
        transfer_pulls = 0
        for spans in traces.values():
            for s in spans:
                durations.setdefault(span_stage(s), []).append(
                    _span_duration(s))
                # data::pull spans carry the flow plane's enrichment
                # (bytes/chunks/failovers) — roll them up so the
                # summary answers "how much of the critical path is
                # object transfer, and how many bytes was that".
                if s.get("name") == "data::pull":
                    transfer_s += _span_duration(s)
                    transfer_pulls += 1
                    attrs = s.get("attributes") or {}
                    try:
                        transfer_bytes += int(attrs.get("bytes") or 0)
                    except (TypeError, ValueError):
                        pass
        grand = sum(sum(v) for v in durations.values()) or 1.0
        stages = {}
        for stage in sorted(durations):
            vals = sorted(durations[stage])
            total = sum(vals)
            stages[stage] = {
                "count": len(vals),
                "total_s": round(total, 6),
                "share": round(total / grand, 4),
                "p50_s": round(_percentile(vals, 0.50), 6),
                "p95_s": round(_percentile(vals, 0.95), 6),
            }
        return {
            "traces": len(traces),
            "stages": stages,
            "transfer": {
                "pulls": transfer_pulls,
                "total_s": round(transfer_s, 6),
                "share": round(transfer_s / grand, 4),
                "bytes": transfer_bytes,
            },
        }

    def _flow_events_for(self, spans: List[Dict[str, Any]]
                         ) -> List[Dict[str, Any]]:
        by_id = {s.get("span_id"): s for s in spans}
        out = []
        for child in spans:
            parent = by_id.get(child.get("parent_id"))
            if parent is None:
                continue
            if (parent.get("node_id"), parent.get("pid")) == \
                    (child.get("node_id"), child.get("pid")):
                continue  # same process: nesting already shows causality
            # Flow id must be unique per arrow; the child span_id is.
            flow_id = child.get("span_id", "")
            common = {"cat": "trace_flow", "name": "trace",
                      "id": flow_id}
            out.append(dict(common, ph="s",
                            pid=_origin_label(parent),
                            tid=parent.get("span_id", ""),
                            ts=parent.get("start_time", 0.0) * 1e6))
            # bp:"e" binds the finish to the enclosing child slice.
            out.append(dict(common, ph="f", bp="e",
                            pid=_origin_label(child),
                            tid=child.get("span_id", ""),
                            ts=child.get("start_time", 0.0) * 1e6))
        return out

    def flow_events(self) -> List[Dict[str, Any]]:
        """Cross-process flow arrows for every retained trace — merged
        into ``/api/timeline`` next to the complete events."""
        out = []
        for spans in self._snapshot().values():
            out.extend(self._flow_events_for(spans))
        return out

    def perfetto(self, trace_id: Optional[str] = None
                 ) -> List[Dict[str, Any]]:
        """Chrome-trace/Perfetto JSON: complete (``X``) events per span
        plus ``s``/``f`` flow events for every cross-process edge."""
        events = []
        for tid, spans in self._snapshot(trace_id).items():
            for s in spans:
                events.append({
                    "name": s.get("name", ""),
                    "cat": "trace",
                    "ph": "X",
                    "ts": s.get("start_time", 0.0) * 1e6,
                    "dur": _span_duration(s) * 1e6,
                    "pid": _origin_label(s),
                    "tid": s.get("span_id", ""),
                    "args": dict(s.get("attributes") or {},
                                 trace_id=tid,
                                 parent_id=s.get("parent_id"),
                                 stage=span_stage(s)),
                })
            events.extend(self._flow_events_for(spans))
        return events
