"""Grafana dashboard factory.

Analog of the reference's
dashboard/modules/metrics/grafana_dashboard_factory.py: generates
importable Grafana dashboard JSON whose panels query THIS cluster's
Prometheus metrics (`/metrics` on the dashboard). Default panels cover
the core serving/scheduling surface; live registry metrics not covered
by a default panel get an auto-generated one, so custom
``util.metrics`` Counters/Gauges show up without configuration.
"""

from __future__ import annotations

import re
from typing import Any, Dict, List, Optional

# (title, promql expr, unit) — the curated core panels (reference:
# grafana_dashboard_factory.py's default dashboard rows).
_DEFAULT_PANELS = [
    ("Tasks finished / s", "rate(ray_tpu_tasks_finished_total[1m])",
     "ops"),
    ("Tasks failed / s", "rate(ray_tpu_tasks_failed_total[1m])", "ops"),
    ("Scheduler queue depth", "ray_tpu_scheduler_pending_tasks", "short"),
    ("Object store bytes", "ray_tpu_object_store_bytes", "bytes"),
    ("Object spilled bytes / s",
     "rate(ray_tpu_object_spilled_bytes_total[1m])", "Bps"),
    ("Object restores / s (by recovery tier)",
     "sum by (source) (rate(ray_tpu_object_restores_total[5m]))", "ops"),
    ("Object spill failures / s (by op)",
     "sum by (op) (rate(ray_tpu_object_spill_failures_total[5m]))",
     "ops"),
    ("Object store hit rate",
     "rate(ray_tpu_object_store_hits_total[5m]) / "
     "(rate(ray_tpu_object_store_hits_total[5m]) + "
     "rate(ray_tpu_object_store_misses_total[5m]))", "percentunit"),
    ("Node count", "ray_tpu_alive_nodes", "short"),
    ("Actor count", "ray_tpu_actors", "short"),
    ("Actor restarts / s", "rate(ray_tpu_actor_restarts_total[5m])",
     "ops"),
    ("Channel reconnects / s",
     "rate(ray_tpu_channel_reconnects_total[5m])", "ops"),
    ("Channel frames resent / s",
     "rate(ray_tpu_channel_frames_resent_total[5m])", "ops"),
    ("Channel send retries / s",
     "rate(ray_tpu_channel_send_retries_total[5m])", "ops"),
    ("Channel bytes sent / s",
     "rate(ray_tpu_channel_bytes_sent_total[1m])", "Bps"),
    ("Channel pure acks / s",
     "rate(ray_tpu_channel_acks_sent_total[1m])", "ops"),
    ("Alert transitions / s (by state)",
     "sum by (state) (rate(ray_tpu_alerts_transitions_total[5m]))",
     "ops"),
    ("Cluster events / s (by severity)",
     "sum by (severity) (rate(ray_tpu_cluster_events_total[5m]))",
     "ops"),
    ("Profile samples / s (by component)",
     "sum by (component) (rate(ray_tpu_profile_samples_total[1m]))",
     "ops"),
    ("Profile batches dropped / s",
     "rate(ray_tpu_profile_batches_dropped_total[5m])", "ops"),
    ("Head recoveries", "ray_tpu_head_recoveries_total", "short"),
    ("Head recovery records replayed (by kind)",
     "sum by (kind) (ray_tpu_head_recovery_replayed_total)", "short"),
    ("Daemon re-dials / s (by outcome)",
     "sum by (outcome) (rate(ray_tpu_daemon_redials_total[5m]))", "ops"),
    ("GCS corrupt records skipped",
     "ray_tpu_gcs_corrupt_records_total", "short"),
    ("Serve failovers / s", "rate(ray_tpu_serve_failovers_total[5m])",
     "ops"),
    ("Serve replicas drained / s (by outcome)",
     "sum by (outcome) (rate(ray_tpu_serve_drained_total[5m]))", "ops"),
    ("Serve health-check failures / s",
     "rate(ray_tpu_serve_health_check_failures_total[5m])", "ops"),
    ("Serve requests shed / s", "rate(ray_tpu_serve_shed_total[1m])",
     "ops"),
    ("Serve qps (by deployment)",
     "sum by (deployment) (rate(ray_tpu_serve_requests_total[1m]))",
     "ops"),
    ("Serve p95 latency (by deployment)",
     "histogram_quantile(0.95, sum by (le, deployment) "
     "(rate(ray_tpu_serve_request_latency_seconds_bucket[5m])))", "s"),
    ("Serve queue depth (by deployment)",
     "sum by (deployment) (ray_tpu_serve_queue_depth)", "short"),
    ("Serve replicas (by deployment)",
     "max by (deployment) (ray_tpu_serve_replicas)", "short"),
    ("Serve target replicas (by deployment)",
     "max by (deployment) (ray_tpu_serve_target_replicas)", "short"),
    ("Serve autoscale decisions / min (by direction)",
     "sum by (direction) "
     "(rate(ray_tpu_serve_autoscale_decisions_total[5m])) * 60", "ops"),
    ("Serve batch size (by fn)",
     "max by (fn) (ray_tpu_serve_batch_size)", "short"),
    ("Head loop lag (by loop)",
     "max by (loop) (ray_tpu_loop_lag_seconds)", "s"),
    ("Train gang restarts / s (by cause)",
     "sum by (cause) (rate(ray_tpu_train_gang_restarts_total[5m]))",
     "ops"),
    ("Train checkpoints persisted / s",
     "rate(ray_tpu_train_checkpoints_persisted_total[5m])", "ops"),
    ("Train ckpt shard write bytes / s (by rank)",
     "sum by (rank) (rate(ray_tpu_train_ckpt_shard_bytes_total[5m]))",
     "Bps"),
    ("Train reshards / s (by direction)",
     "sum by (direction) (rate(ray_tpu_train_reshards_total[5m]))",
     "ops"),
    ("Train set-up seconds, mean (by stage)",
     "sum by (stage) (ray_tpu_train_setup_seconds_sum{within=\"none\"}) / "
     "sum by (stage) (ray_tpu_train_setup_seconds_count{within=\"none\"})",
     "s"),
    ("Worker pool size", "ray_tpu_worker_pool_size", "short"),
    ("Worker lease wait p95 (s)",
     "histogram_quantile(0.95, "
     "rate(ray_tpu_worker_lease_wait_seconds_bucket[5m]))", "s"),
    ("Log lines / s", "rate(ray_tpu_log_monitor_lines_total[1m])",
     "ops"),
    ("Trace stage p95 latency (s)",
     "histogram_quantile(0.95, sum by (le, stage) "
     "(rate(ray_tpu_trace_stage_seconds_bucket[5m])))", "s"),
    ("Trace stage time share",
     "sum by (stage) (rate(ray_tpu_trace_stage_seconds_sum[5m])) / "
     "ignoring (stage) group_left sum "
     "(rate(ray_tpu_trace_stage_seconds_sum[5m]))", "percentunit"),
    ("Data-plane pulled bytes / s",
     "rate(ray_tpu_dataplane_pulled_bytes_total[1m])", "Bps"),
    ("Object transfer bytes / s (by direction)",
     "sum by (direction) (rate(ray_tpu_object_transfer_bytes_total[1m]))",
     "Bps"),
    ("Pull chunks / s", "rate(ray_tpu_pull_chunks_total[1m])", "ops"),
    # Dataplane flow plane (flow.py): the head-synthesized per-link
    # series — a heatmap-able bytes rate per (src,dst) cell, the
    # windowed per-link MB/s gauge, and the top fan-out objects that
    # mark broadcast amplification.
    ("Transfer link bytes / s (src->dst heatmap)",
     "sum by (src, dst) (rate(ray_tpu_transfer_link_bytes_total[1m]))",
     "Bps"),
    ("Per-link transfer MB/s",
     "max by (link) (ray_tpu_transfer_link_mbps)", "MBs"),
    ("Top fan-out objects (nodes pulling one object)",
     "topk(10, max by (key) (ray_tpu_object_fanout_nodes))", "short"),
    # Collective dataplane: spanning-tree broadcasts launched, bytes
    # moved over the push tier, and how often locality placement lands
    # a task next to its argument bytes vs spilling it elsewhere.
    ("Broadcast trees / s", "rate(ray_tpu_broadcast_trees_total[5m])",
     "ops"),
    ("Broadcast push bytes / s",
     "rate(ray_tpu_push_bytes_total[1m])", "Bps"),
    ("Lease locality outcomes / s",
     "sum by (outcome) (rate(ray_tpu_lease_locality_total[5m]))",
     "ops"),
]


def _panel(panel_id: int, title: str, expr: str, unit: str,
           x: int, y: int) -> Dict[str, Any]:
    return {
        "id": panel_id,
        "title": title,
        "type": "timeseries",
        "datasource": {"type": "prometheus", "uid": "${datasource}"},
        "fieldConfig": {"defaults": {"unit": unit}, "overrides": []},
        "gridPos": {"h": 8, "w": 12, "x": x, "y": y},
        "targets": [{"expr": expr, "refId": "A",
                     "legendFormat": "__auto"}],
    }


def generate_dashboard(extra_metrics: Optional[List[str]] = None
                       ) -> Dict[str, Any]:
    """A complete importable Grafana dashboard document."""
    panels = []
    covered = set()
    pid = 1
    for i, (title, expr, unit) in enumerate(_DEFAULT_PANELS):
        panels.append(_panel(pid, title, expr, unit,
                             x=(i % 2) * 12, y=(i // 2) * 8))
        # Every metric family a curated expr touches counts as covered
        # (hit-rate/quantile exprs reference several; suffixes like
        # _bucket reduce to the registry's family name).
        for ref in re.findall(r"ray_tpu[a-zA-Z0-9_]*", expr):
            covered.add(ref)
            for suffix in ("_bucket", "_total"):
                if ref.endswith(suffix):
                    covered.add(ref[:-len(suffix)])
        pid += 1
    # Auto-panels for live registry metrics without a curated panel.
    names = list(extra_metrics or [])
    try:
        from ray_tpu.util.metrics import Counter, registry
        for name, metric in sorted(registry().items()):
            prom = name if name.startswith("ray_tpu") else \
                f"ray_tpu_{name}"
            if prom in covered or f"{prom}_total" in covered:
                continue
            if isinstance(metric, Counter):
                names.append(f"rate({prom}_total[1m])")
            else:
                names.append(prom)
    except Exception:  # noqa: BLE001 - registry optional in tools context
        pass
    base_y = (len(_DEFAULT_PANELS) // 2 + 1) * 8
    for i, expr in enumerate(names):
        title = expr.replace("rate(", "").split("[")[0].rstrip(")")
        panels.append(_panel(pid, title, expr, "short",
                             x=(i % 2) * 12, y=base_y + (i // 2) * 8))
        pid += 1
    return {
        "title": "ray_tpu cluster",
        "uid": "ray-tpu-core",
        "schemaVersion": 38,
        "version": 1,
        "refresh": "10s",
        "time": {"from": "now-30m", "to": "now"},
        "templating": {"list": [{
            "name": "datasource",
            "type": "datasource",
            "query": "prometheus",
        }]},
        # The event journal doubles as the annotation source: the
        # dashboard head serves Grafana-shaped rows ({time: epoch-ms,
        # text, tags}) at GET /api/events?fmt=annotations for a JSON
        # datasource; severity/source/node ride along as tags.
        "annotations": {"list": [{
            "name": "cluster events",
            "enable": True,
            "iconColor": "red",
            "hide": False,
            "target": {"type": "tags", "tags": ["error", "critical"]},
        }]},
        "panels": panels,
    }


def write_dashboards(out_dir: str) -> List[str]:
    """Write dashboard JSON files for Grafana provisioning; returns the
    written paths (the CLI face: ray-tpu grafana-dashboards)."""
    import json
    import os
    os.makedirs(out_dir, exist_ok=True)
    path = os.path.join(out_dir, "ray_tpu_core_dashboard.json")
    with open(path, "w") as f:
        json.dump(generate_dashboard(), f, indent=2)
    return [path]
