"""Built-in ``ray_tpu_*`` runtime metrics.

Analog of the reference's core-runtime stats (stats/metric_defs.h:
tasks, scheduler, object store, and worker-pool series every Ray
process emits). Each accessor lazily (re-)binds the metric through the
registry so ``ray_tpu.util.metrics.clear_registry()`` in tests cannot
orphan the instrumentation: the next event simply re-registers.

Counters are incremented at the runtime's choke points (task state
transitions, spills, restarts, log batches); level-style gauges are
refreshed by per-agent collector callbacks right before each snapshot
(``MetricsAgent.add_collector``) so hot paths stay untouched.

Hot-path events (per-task state transitions, store hits, transfer
bytes, lease waits that were serviced immediately) do NOT touch the
registry inline: a ``Counter.inc`` takes a lock and a tag-dict merge,
which showed up as a double-digit tasks_per_sec regression. They bump
plain-dict integer cells instead — a single int add under the GIL —
and ``flush_fast_counters`` (registered as a default MetricsAgent
collector) folds the cells into the real metrics right before each
snapshot. Increments racing a flush survive because the flush
decrements by the amount it read rather than zeroing the cell.
"""

from __future__ import annotations

import threading
import time
from typing import Optional

# ray_tpu.util.metrics is imported inside each accessor: importing it at
# module scope would execute ray_tpu.util/__init__ (which pulls
# placement_group -> _private.worker) while _private modules that
# instrument themselves are still initializing - a circular import.

# -- fast cells (hot-path increments, folded by flush_fast_counters) ------

_fast_task_events = {"SUBMITTED": 0, "RUNNING": 0, "FINISHED": 0,
                     "FAILED": 0}
# (node_id_hex, status) -> count: per-node task transitions, the series
# behind `ray-tpu top`'s per-node submit/finish rates. Unbounded only by
# node count x 4 statuses.
_fast_node_task_events: dict = {}
_fast_store = {"hit": 0, "miss": 0}
_fast_transfer = {"in": 0, "out": 0}
_fast_chunks = {"n": 0}
_fast_lease_immediate = {"n": 0}
_fast_channel = {"bytes": 0, "acks": 0}
# Continuous-profiler stack walks: bumped every sampler tick (hz rate),
# folded into ray_tpu_profile_samples_total at each snapshot.
_fast_profile = {"samples": 0}
# component -> the sampler's worst lateness since the last flush (a max,
# not a sum: the tick writes it without a lock, the flush takes it whole).
_fast_sampler_lag: dict = {}
# Alerting plane cells: state -> transition count and severity -> event
# count. Transitions happen inside ClusterMetrics.update's merge path
# and journal appends can ride task/spill hot paths, so both stay
# dict adds until flush.
_fast_alert_transitions: dict = {}
_fast_cluster_events: dict = {}


def record_alert_transition(state: str) -> None:
    _fast_alert_transitions[state] = \
        _fast_alert_transitions.get(state, 0) + 1


def record_cluster_event(severity: str) -> None:
    _fast_cluster_events[severity] = \
        _fast_cluster_events.get(severity, 0) + 1


def record_store_hit() -> None:
    _fast_store["hit"] += 1


def record_store_miss() -> None:
    _fast_store["miss"] += 1


def record_transfer_in(nbytes: int) -> None:
    _fast_transfer["in"] += nbytes


def record_transfer_out(nbytes: int) -> None:
    _fast_transfer["out"] += nbytes


def record_pull_chunks(n: int) -> None:
    _fast_chunks["n"] += n


def record_channel_bytes_sent(nbytes: int) -> None:
    """Every ResilientChannel write (header + payload bytes): one dict
    int add on the frame send path, folded at flush."""
    _fast_channel["bytes"] += nbytes


def record_channel_ack_sent() -> None:
    _fast_channel["acks"] += 1


def record_profile_samples(n: int) -> None:
    """Stacks walked by one ProfilerAgent tick: a dict int add on the
    sampler thread, folded at flush."""
    _fast_profile["samples"] += n


def record_sampler_lag(component: str, late_s: float) -> None:
    """How late one ProfilerAgent tick woke: kept where it is the worst
    since the last flush, which sets it as
    ``ray_tpu_loop_lag_seconds{loop="sampler.<component>"}``. A tick that
    races the flush may show in two flushes, never in none."""
    if late_s > _fast_sampler_lag.get(component, -1.0):
        _fast_sampler_lag[component] = late_s


def record_lease_immediate() -> None:
    """A lease request satisfied without waiting: lands in the lease-wait
    histogram's smallest bucket at flush time, skipping two monotonic
    clock reads and a locked observe on the lease fast path."""
    _fast_lease_immediate["n"] += 1


def flush_fast_counters() -> None:
    """Fold the fast cells into the registry metrics. Runs as a
    MetricsAgent collector before each snapshot (and may be called
    directly in tests). Decrements each cell by the value it read so
    increments racing the flush are kept for the next one."""
    for status, n in list(_fast_task_events.items()):
        if n:
            _fast_task_events[status] -= n
            _TASK_STATUS_COUNTERS[status]().inc(n)
    for (node_hex, status), n in list(_fast_node_task_events.items()):
        if n:
            _fast_node_task_events[(node_hex, status)] -= n
            node_task_events().inc(
                n, tags={"node_id": node_hex, "status": status})
    for kind, n in list(_fast_store.items()):
        if n:
            _fast_store[kind] -= n
            acc = object_store_hits if kind == "hit" else object_store_misses
            acc().inc(n)
    for direction, n in list(_fast_transfer.items()):
        if n:
            _fast_transfer[direction] -= n
            object_transfer_bytes().inc(n, tags={"direction": direction})
    n = _fast_chunks["n"]
    if n:
        _fast_chunks["n"] -= n
        pull_chunks().inc(n)
    n = _fast_channel["bytes"]
    if n:
        _fast_channel["bytes"] -= n
        channel_bytes_sent().inc(n)
    n = _fast_channel["acks"]
    if n:
        _fast_channel["acks"] -= n
        channel_acks_sent().inc(n)
    n = _fast_profile["samples"]
    if n:
        _fast_profile["samples"] -= n
        profile_samples().inc(n)
    for component in list(_fast_sampler_lag):
        loop_lag().set(_fast_sampler_lag.pop(component),
                       tags={"loop": f"sampler.{component}"})
    for state, n in list(_fast_alert_transitions.items()):
        if n:
            _fast_alert_transitions[state] -= n
            alerts_transitions().inc(n, tags={"state": state})
    for severity, n in list(_fast_cluster_events.items()):
        if n:
            _fast_cluster_events[severity] -= n
            cluster_events().inc(n, tags={"severity": severity})
    n = _fast_lease_immediate["n"]
    if n:
        _fast_lease_immediate["n"] -= n
        h = worker_lease_wait()
        key = h._key(None)
        with h._lock:
            buckets = h._buckets.setdefault(
                key, [0] * (len(h.boundaries) + 1))
            buckets[0] += n
            h._counts[key] = h._counts.get(key, 0) + n
            h._sums[key] = h._sums.get(key, 0.0) + 0.0
            h._series[key] = 0.0


# -- tasks / scheduler ----------------------------------------------------


def tasks_submitted() -> Counter:
    from ray_tpu.util.metrics import Counter
    return Counter("ray_tpu_tasks_submitted_total",
                   "Tasks submitted to the runtime.")


def tasks_started() -> Counter:
    from ray_tpu.util.metrics import Counter
    return Counter("ray_tpu_tasks_started_total",
                   "Tasks that began executing.")


def tasks_finished() -> Counter:
    from ray_tpu.util.metrics import Counter
    return Counter("ray_tpu_tasks_finished_total",
                   "Tasks that finished successfully.")


def tasks_failed() -> Counter:
    from ray_tpu.util.metrics import Counter
    return Counter("ray_tpu_tasks_failed_total",
                   "Tasks that failed (after retries).")


_TASK_STATUS_COUNTERS = {
    "SUBMITTED": tasks_submitted,
    "RUNNING": tasks_started,
    "FINISHED": tasks_finished,
    "FAILED": tasks_failed,
}


def node_task_events() -> Counter:
    from ray_tpu.util.metrics import Counter
    return Counter(
        "ray_tpu_node_task_events_total",
        "Task state transitions attributed to the executing node; the "
        "windowed rate per (node_id, status) feeds `ray-tpu top`'s "
        "per-node submit/finish columns.",
        tag_keys=("node_id", "status"))


def record_task_event(status: str,
                      node_hex: Optional[str] = None) -> None:
    """Map a task state transition onto its counter (no-op for statuses
    that are not terminal/throughput signals, e.g. OOM_RETRY). This is
    on the per-task submit/execute fast path: one dict int add (two
    when the executing node is known), folded into the real counters by
    ``flush_fast_counters``."""
    if status in _fast_task_events:
        _fast_task_events[status] += 1
        if node_hex:
            key = (node_hex, status)
            _fast_node_task_events[key] = \
                _fast_node_task_events.get(key, 0) + 1


def scheduler_pending_tasks() -> Gauge:
    from ray_tpu.util.metrics import Gauge
    return Gauge("ray_tpu_scheduler_pending_tasks",
                 "Tasks queued waiting for resources or leases.")


def alive_nodes() -> Gauge:
    from ray_tpu.util.metrics import Gauge
    return Gauge("ray_tpu_alive_nodes", "Nodes currently alive.")


def actors_gauge() -> Gauge:
    from ray_tpu.util.metrics import Gauge
    return Gauge("ray_tpu_actors", "Live actors registered at the head.")


def actor_restarts() -> Counter:
    from ray_tpu.util.metrics import Counter
    return Counter(
        "ray_tpu_actor_restarts_total",
        "Actor restarts, including detached-actor rebinds after a head "
        "restart.", tag_keys=("kind",))


# -- object store ---------------------------------------------------------


def object_store_bytes() -> Gauge:
    from ray_tpu.util.metrics import Gauge
    return Gauge("ray_tpu_object_store_bytes",
                 "Bytes resident in the local object store.")


def object_spilled_bytes() -> Counter:
    from ray_tpu.util.metrics import Counter
    return Counter("ray_tpu_object_spilled_bytes_total",
                   "Bytes spilled from the object store to disk.")


def object_restores() -> Counter:
    from ray_tpu.util.metrics import Counter
    return Counter(
        "ray_tpu_object_restores_total",
        "Lost-object recoveries by the tier that paid for them: "
        "replica = re-pointed at another in-memory holder, spill = "
        "payload read back from a surviving spill URI, lineage = "
        "producer task re-executed (the most expensive tier).",
        tag_keys=("source",))


def object_spill_failures() -> Counter:
    from ray_tpu.util.metrics import Counter
    return Counter(
        "ray_tpu_object_spill_failures_total",
        "Spill-backend IO failures by op (write = spill kept the "
        "in-memory copy instead; restore = tier miss, recovery fell "
        "down a tier). Includes chaos-injected io_oserror faults.",
        tag_keys=("op",))


def object_store_hits() -> Counter:
    from ray_tpu.util.metrics import Counter
    return Counter("ray_tpu_object_store_hits_total",
                   "Object reads served from memory (plasma-analog hit).")


def object_store_misses() -> Counter:
    from ray_tpu.util.metrics import Counter
    return Counter(
        "ray_tpu_object_store_misses_total",
        "Object reads that had to restore a spilled payload from disk.")


# -- data plane -----------------------------------------------------------


def object_transfer_bytes() -> Counter:
    from ray_tpu.util.metrics import Counter
    return Counter(
        "ray_tpu_object_transfer_bytes_total",
        "Bytes moved over the node-to-node data plane, by direction "
        "(in = pulled to this node, out = served to peers).",
        tag_keys=("direction",))


def pull_chunks() -> Counter:
    from ray_tpu.util.metrics import Counter
    return Counter(
        "ray_tpu_pull_chunks_total",
        "Ranged chunks fetched by the chunked parallel pull path.")


def broadcast_trees() -> Counter:
    from ray_tpu.util.metrics import Counter
    return Counter(
        "ray_tpu_broadcast_trees_total",
        "Spanning-tree push broadcasts issued by the head (explicit "
        "ray_tpu.broadcast hints + auto-triggered hot-object fan-out).")


def push_bytes() -> Counter:
    from ray_tpu.util.metrics import Counter
    return Counter(
        "ray_tpu_push_bytes_total",
        "Bytes replicated through push_object broadcast directives "
        "(head seed sends + tree-edge forwards), as acknowledged by "
        "completing nodes.")


def lease_locality() -> Counter:
    from ray_tpu.util.metrics import Counter
    return Counter(
        "ray_tpu_lease_locality_total",
        "Locality-aware placement outcomes for tasks with remote "
        "argument bytes: local = landed on the node holding the "
        "largest share, spillback = preferred node was over the "
        "spillback threshold or lost the acquire, remote = no usable "
        "preference (holders dead or sizes unknown).",
        tag_keys=("outcome",))


# -- worker pool ----------------------------------------------------------


def worker_pool_size() -> Gauge:
    from ray_tpu.util.metrics import Gauge
    return Gauge("ray_tpu_worker_pool_size",
                 "Live worker subprocesses in this process's pool.")


def worker_lease_wait() -> Histogram:
    from ray_tpu.util.metrics import Histogram
    return Histogram(
        "ray_tpu_worker_lease_wait_seconds",
        "Seconds a lease request waited for a worker subprocess.",
        boundaries=[0.001, 0.01, 0.05, 0.25, 1, 5, 30])


# -- distributed tracing ---------------------------------------------------


def trace_stage_seconds() -> Histogram:
    from ray_tpu.util.metrics import Histogram
    return Histogram(
        "ray_tpu_trace_stage_seconds",
        "Span durations by pipeline stage (submit/queue/lease/pull/"
        "execute/store/serve_dispatch/serve_handle), observed by the "
        "head's trace assembler as sampled spans arrive — the "
        "critical-path attribution behind `ray-tpu trace --summary`.",
        boundaries=[0.0001, 0.001, 0.01, 0.1, 1, 10, 100],
        tag_keys=("stage",))


# -- log subsystem --------------------------------------------------------


def log_lines() -> Counter:
    from ray_tpu.util.metrics import Counter
    return Counter("ray_tpu_log_monitor_lines_total",
                   "Log lines published by this node's log monitor.")


def log_lines_dropped() -> Counter:
    from ray_tpu.util.metrics import Counter
    return Counter(
        "ray_tpu_log_monitor_lines_dropped_total",
        "Log lines dropped by backpressure (publish returned False).")


# -- channel resilience ----------------------------------------------------
# Rare-path events (a reconnect is news, not load): plain lazy
# accessors, no fast cells. Incremented from channel.py attach/send
# paths and the dataplane's pooled-socket retry classification.


def channel_reconnects() -> Counter:
    from ray_tpu.util.metrics import Counter
    return Counter(
        "ray_tpu_channel_reconnects_total",
        "Successful session-channel resumes (socket re-dialed and "
        "re-attached without node death).")


def channel_frames_resent() -> Counter:
    from ray_tpu.util.metrics import Counter
    return Counter(
        "ray_tpu_channel_frames_resent_total",
        "Unacked frames replayed from the resend ring after a channel "
        "resume.")


def channel_send_retries() -> Counter:
    from ray_tpu.util.metrics import Counter
    return Counter(
        "ray_tpu_channel_send_retries_total",
        "Transient transport errors classified as retryable (channel "
        "send breaks, stale pooled-socket retries) instead of "
        "escalating to node death or pull failure.")


# -- membership fencing (wire v9) ------------------------------------------


def frames_fenced() -> Counter:
    from ray_tpu.util.metrics import Counter
    return Counter(
        "ray_tpu_frames_fenced_total",
        "Frames and handshakes rejected because they carried a dead "
        "incarnation's epoch (or came from a session the head no "
        "longer knows): stale-envelope drops, fenced resume attempts, "
        "and unknown-node health-channel announces. Counted, never "
        "applied — and never per-frame log spam.")


def node_deaths() -> Counter:
    from ray_tpu.util.metrics import Counter
    return Counter(
        "ray_tpu_node_deaths_total",
        "Node incarnations declared dead, by how the detector decided "
        "(hard = process-gone evidence; suspicion = accrual phi over "
        "threshold; lease = hard silence bound).",
        tag_keys=("kind",))


# -- head failover ---------------------------------------------------------
# Rare-path events (a head recovery is news): plain lazy accessors.
# Incremented from gcs_store load, the runtime's recovery path, and the
# node daemon's re-dial loop.


def gcs_corrupt_records() -> Counter:
    from ray_tpu.util.metrics import Counter
    return Counter(
        "ray_tpu_gcs_corrupt_records_total",
        "gcs_store records skipped at load because they were truncated "
        "or failed their CRC (torn write through kill -9, disk "
        "corruption). Skipped with a warning, never fatal: the rest of "
        "the snapshot still restores.")


def head_recoveries() -> Counter:
    from ray_tpu.util.metrics import Counter
    return Counter(
        "ray_tpu_head_recoveries_total",
        "Head processes that started against a gcs_store with prior "
        "state and rehydrated the control plane from it (membership "
        "epochs, actor/serve/job records, object spill URIs).")


def head_recovery_replayed() -> Counter:
    from ray_tpu.util.metrics import Counter
    return Counter(
        "ray_tpu_head_recovery_replayed_total",
        "Records replayed from the gcs_store during a head recovery, "
        "by table (kv, actors, jobs, node_epochs, serve_deployments, "
        "spill_uris, object_replicas).",
        tag_keys=("kind",))


def daemon_redials() -> Counter:
    from ray_tpu.util.metrics import Counter
    return Counter(
        "ray_tpu_daemon_redials_total",
        "Daemon re-dial attempts against a lost head session, by how "
        "they ended: resumed (same head, channel re-attached), "
        "reregistered (full re-register — head restarted or resume "
        "rejected), gave_up (head_failover_window_s exhausted; the "
        "daemon exits).",
        tag_keys=("outcome",))


# -- serve resilience ------------------------------------------------------
# Control-plane events (a failover or a drain is news, not load): plain
# lazy accessors, no fast cells. Incremented from the serve router's
# completion callbacks and the controller's lifecycle loop.


def serve_failovers() -> Counter:
    from ray_tpu.util.metrics import Counter
    return Counter(
        "ray_tpu_serve_failovers_total",
        "Serve requests transparently re-assigned to another replica "
        "after a system failure (actor death / object loss) — never "
        "application exceptions.")


def serve_drained() -> Counter:
    from ray_tpu.util.metrics import Counter
    return Counter(
        "ray_tpu_serve_drained_total",
        "Replicas retired through the DRAINING state, by outcome "
        "(clean = in-flight requests reached zero; timeout = killed "
        "with requests still running after the drain window).",
        tag_keys=("outcome",))


def serve_health_check_failures() -> Counter:
    from ray_tpu.util.metrics import Counter
    return Counter(
        "ray_tpu_serve_health_check_failures_total",
        "Failed replica health probes (check_health raised or timed "
        "out); a replica is replaced after the consecutive-failure "
        "threshold.")


def serve_shed() -> Counter:
    from ray_tpu.util.metrics import Counter
    return Counter(
        "ray_tpu_serve_shed_total",
        "Serve requests fast-failed with BackPressureError because the "
        "deployment's max_queued_requests cap was hit (HTTP 503 via "
        "the proxy).")


# -- serve signal plane ----------------------------------------------------
# Per-deployment traffic series the autoscaler reads from the head's
# time-series store (qps, p95, queue depth, replica count). Incremented
# from the router's assign/settle path — serve settles are not the task
# hot path, so these touch the registry directly.


def serve_requests() -> Counter:
    from ray_tpu.util.metrics import Counter
    return Counter(
        "ray_tpu_serve_requests_total",
        "Serve requests settled (completed or raised), per deployment; "
        "the windowed rate of this series is the deployment's qps.",
        tag_keys=("deployment",))


def serve_request_latency() -> "Histogram":
    from ray_tpu.util.metrics import Histogram
    return Histogram(
        "ray_tpu_serve_request_latency_seconds",
        "End-to-end serve request latency at the router (assign to "
        "settle, including queueing and retries).",
        boundaries=(0.001, 0.005, 0.01, 0.05, 0.1, 0.25, 0.5, 1.0,
                    2.5, 5.0, 10.0, 30.0),
        tag_keys=("deployment",))


def serve_queue_depth() -> Gauge:
    from ray_tpu.util.metrics import Gauge
    return Gauge(
        "ray_tpu_serve_queue_depth",
        "Outstanding (assigned, unsettled) serve requests at a router, "
        "per deployment.",
        tag_keys=("deployment",))


def serve_replicas() -> Gauge:
    from ray_tpu.util.metrics import Gauge
    return Gauge(
        "ray_tpu_serve_replicas",
        "Replica count in the router's current routing table, per "
        "deployment (refreshed on every controller long-poll).",
        tag_keys=("deployment",))


# -- serve autoscaler + batching engines -----------------------------------
# Actuation-plane series: the controller's autoscale pass sets the
# target gauge every pass (so target-vs-actual graphs exist at steady
# state) and counts actuated decisions; the batching engines gauge
# their live operating point.


def serve_target_replicas() -> Gauge:
    from ray_tpu.util.metrics import Gauge
    return Gauge(
        "ray_tpu_serve_target_replicas",
        "Autoscaler's desired replica count per deployment (compare "
        "with ray_tpu_serve_replicas for target-vs-actual).",
        tag_keys=("deployment",))


def serve_autoscale_decisions() -> Counter:
    from ray_tpu.util.metrics import Counter
    return Counter(
        "ray_tpu_serve_autoscale_decisions_total",
        "Actuated autoscaling decisions (replica target changed), per "
        "deployment and direction.",
        tag_keys=("deployment", "direction"))


def serve_batch_size() -> Gauge:
    from ray_tpu.util.metrics import Gauge
    return Gauge(
        "ray_tpu_serve_batch_size",
        "Size of the last executed @serve.batch batch, per batched "
        "function (adaptive batching moves this with load).",
        tag_keys=("fn",))


def serve_batch_size_limit() -> Gauge:
    from ray_tpu.util.metrics import Gauge
    return Gauge(
        "ray_tpu_serve_batch_size_limit",
        "Current adaptive max-batch-size operating point of a "
        "@serve.batch queue (AIMD-tuned against the latency budget).",
        tag_keys=("fn",))


def serve_decode_active_slots() -> Gauge:
    from ray_tpu.util.metrics import Gauge
    return Gauge(
        "ray_tpu_serve_decode_active_slots",
        "Occupied slots in a continuous-batching decode loop, per "
        "engine (fixed-shape pjit batch; free slots admit new "
        "sequences at iteration boundaries).",
        tag_keys=("engine",))


def serve_decode_admitted() -> Counter:
    from ray_tpu.util.metrics import Counter
    return Counter(
        "ray_tpu_serve_decode_admitted_total",
        "Sequences admitted into a continuous-batching decode loop, "
        "by admission kind (fresh = loop was idle, running = joined a "
        "live decode batch at an iteration boundary).",
        tag_keys=("engine", "kind"))


# -- control-loop saturation -----------------------------------------------


def loop_lag() -> Gauge:
    from ray_tpu.util.metrics import Gauge
    return Gauge(
        "ray_tpu_loop_lag_seconds",
        "Scheduling lag of a control loop: how far past its intended "
        "period/deadline the loop actually woke (head membership sweep, "
        "dashboard asyncio loop, metrics agent ticks; sampler.<component>: "
        "the continuous profiler's 10 Hz tick, worst since the last "
        "flush).",
        tag_keys=("loop",))


# -- continuous profiling --------------------------------------------------


def profile_samples() -> Counter:
    from ray_tpu.util.metrics import Counter
    return Counter(
        "ray_tpu_profile_samples_total",
        "Thread stacks sampled by this process's continuous "
        "ProfilerAgent (profiling.py; RAY_TPU_PROFILE_HZ ticks x "
        "threads walked).")


def profile_batches_dropped() -> Counter:
    from ray_tpu.util.metrics import Counter
    return Counter(
        "ray_tpu_profile_batches_dropped_total",
        "profile_batch publishes that failed (no live head session / "
        "full sender); the samples are refunded into the accumulator "
        "and ride the next tick.")


# -- dataplane flow observability ------------------------------------------


def flow_batches_dropped() -> Counter:
    from ray_tpu.util.metrics import Counter
    return Counter(
        "ray_tpu_flow_batches_dropped_total",
        "flow_batch publishes that failed (no live head session / full "
        "sender); the transfer records are refunded into the "
        "FlowRecorder and ride the next tick.")


def transfer_inflight_bytes() -> Gauge:
    from ray_tpu.util.metrics import Gauge
    return Gauge(
        "ray_tpu_transfer_inflight_bytes",
        "Object payload bytes currently mid-pull in this process "
        "(admission granted, body not yet landed) — the FlowRecorder's "
        "in-flight gauge.")


# -- alerting plane / cluster events ---------------------------------------


def alerts_transitions() -> Counter:
    from ray_tpu.util.metrics import Counter
    return Counter(
        "ray_tpu_alerts_transitions_total",
        "Alert state-machine transitions by the state entered (firing = "
        "a rule breached past its hold; resolved = the breach cleared).",
        tag_keys=("state",))


def cluster_events() -> Counter:
    from ray_tpu.util.metrics import Counter
    return Counter(
        "ray_tpu_cluster_events_total",
        "Events appended to the head's cluster event journal "
        "(_private/events.py), by severity.",
        tag_keys=("severity",))


# -- train fault tolerance -------------------------------------------------
# Gang lifecycle events (a restart or a persisted checkpoint is news,
# not load): plain lazy accessors, no fast cells. Incremented from the
# BackendExecutor restart loop and the durable CheckpointManager.


def train_gang_restarts() -> Counter:
    from ray_tpu.util.metrics import Counter
    return Counter(
        "ray_tpu_train_gang_restarts_total",
        "Whole-gang train restarts from the latest checkpoint, by cause "
        "(system = worker/daemon death or failed liveness probe; app = "
        "the train loop raised).",
        tag_keys=("cause",))


def train_checkpoints_persisted() -> Counter:
    from ray_tpu.util.metrics import Counter
    return Counter(
        "ray_tpu_train_checkpoints_persisted_total",
        "Reported train checkpoints persisted durably through the "
        "storage_path spill backend (what a gang restart resumes from).")


def train_checkpoint_persist_failures() -> Counter:
    from ray_tpu.util.metrics import Counter
    return Counter(
        "ray_tpu_train_checkpoint_persist_failures_total",
        "Reported checkpoints whose durable persist raised SpillFailure "
        "(training continues on the in-memory copy; a gang restart "
        "would resume from an older checkpoint). Watched by the "
        "checkpoint_persist_failures alert rule.")


# -- sharded checkpoints ---------------------------------------------------
# Per-rank sharded saves (train/_internal/sharded_checkpoint.py): every
# rank writes only its local shard, the manifest commit is driver-side.


def train_ckpt_shard_bytes() -> Counter:
    from ray_tpu.util.metrics import Counter
    return Counter(
        "ray_tpu_train_ckpt_shard_bytes_total",
        "Bytes of checkpoint shard files written, by rank — N live rank "
        "labels per save is the signature of the parallel sharded path "
        "(a single-writer monolithic save only moves rank 0).",
        tag_keys=("rank",))


def train_ckpt_save_seconds() -> Histogram:
    from ray_tpu.util.metrics import Histogram
    return Histogram(
        "ray_tpu_train_ckpt_save_seconds",
        "End-to-end sharded save wall time: slowest rank's shard write "
        "plus the manifest commit.",
        boundaries=(0.01, 0.05, 0.25, 1.0, 5.0, 30.0, 120.0, 600.0))


def train_reshards() -> Counter:
    from ray_tpu.util.metrics import Counter
    return Counter(
        "ray_tpu_train_reshards_total",
        "Sharded-checkpoint resumes by mesh-change direction: shrink "
        "(elastic gang came back smaller), grow, or same (plain "
        "restart).",
        tag_keys=("direction",))


def train_ckpt_orphans_gc() -> Counter:
    from ray_tpu.util.metrics import Counter
    return Counter(
        "ray_tpu_train_ckpt_orphans_gc_total",
        "Orphaned checkpoint files garbage-collected at index load: "
        "shard files no committed manifest references (mid-save crash "
        "debris) and manifests with missing/corrupt shards.")


# -- expert layers ---------------------------------------------------------
# Fed by parallel/train_step.py from the scalars a step of an expert model
# returns (models/deepseek.py, models/afmoe.py), one call late at most,
# never by a sync.


def train_moe_assignments() -> Counter:
    from ray_tpu.util.metrics import Counter
    return Counter(
        "ray_tpu_train_moe_assignments_total",
        "Token-to-expert assignments the expert layers computed: the rows "
        "their grouped matmuls were given (on a share of the experts, the "
        "rows its buffers placed; ops/moe.py). Equal to "
        "ray_tpu_train_moe_tokens_total, or an assignment was dropped.")


def train_moe_tokens() -> Counter:
    from ray_tpu.util.metrics import Counter
    return Counter(
        "ray_tpu_train_moe_tokens_total",
        "Assignments the routing gave to experts held here. With every "
        "expert held: tokens x experts per token x expert layers, per "
        "step.")


def train_moe_routed() -> Counter:
    from ray_tpu.util.metrics import Counter
    return Counter(
        "ray_tpu_train_moe_routed_total",
        "Every assignment the routing made, to experts held here or not: "
        "tokens x experts per token x expert layers, per step. "
        "ray_tpu_train_moe_tokens_total over it is this chip's share of "
        "the routing's work.")


def train_moe_rows_summed() -> Counter:
    from ray_tpu.util.metrics import Counter
    return Counter(
        "ray_tpu_train_moe_rows_summed_total",
        "Rows the expert layers' way back to tokens read in the forward "
        "pass: the rows their buffers hold where the kernel "
        "moe_rows_to_tokens ran (then equal to "
        "ray_tpu_train_moe_tokens_total), one a routed assignment and "
        "buffer where the gathers did (ops/moe.py). Over "
        "ray_tpu_train_moe_routed_total it says whether the kernel "
        "engaged: the held share where it did, 1.0 where it did not.")


def train_moe_calls() -> Counter:
    from ray_tpu.util.metrics import Counter
    return Counter(
        "ray_tpu_train_moe_calls_total",
        "Expert-layer calls: expert layers x microbatches, per step.")


def train_moe_calls_within_bound() -> Counter:
    from ray_tpu.util.metrics import Counter
    return Counter(
        "ray_tpu_train_moe_calls_within_bound_total",
        "Expert-layer calls whose assignments to the experts held here fit "
        "one buffer of the static bound (ops/moe.py: twice the even share), "
        "so that the layer moved that many rows once. Under "
        "ray_tpu_train_moe_calls_total, the routing is more uneven than "
        "the bound allows for and the other calls took further buffers.")


def train_moe_expert_load() -> Gauge:
    from ray_tpu.util.metrics import Gauge
    return Gauge(
        "ray_tpu_train_moe_expert_load_max_over_mean",
        "Busiest expert's assignments over the mean of the experts held "
        "here, worst expert layer of the last recorded step (1.0 = "
        "balanced).")


def train_moe_picked_mass() -> Gauge:
    from ray_tpu.util.metrics import Gauge
    return Gauge(
        "ray_tpu_train_moe_picked_mass",
        "Of a token's router probability (a softmax over all the experts), "
        "the share its picked experts hold before the weights are "
        "renormalised: the mean over tokens and expert layers of the last "
        "recorded step (ops/moe.py route, score='softmax'). top_k / experts "
        "is a flat router, 1.0 one whose picks hold everything.")


def train_moe_relu2_zero_share() -> Gauge:
    from ray_tpu.util.metrics import Gauge
    return Gauge(
        "ray_tpu_train_moe_relu2_zero_share",
        "Of the hidden activations the held experts computed in the last "
        "recorded step (squared-ReLU experts: ops/moe.py, "
        "activation='relu2'), the share the ReLU zeroed, the expert layers' "
        "mean: about a half at random weights, 0 if the ReLU were missing, "
        "and what a product that skips zeros could save.")


def train_attn_window_tile_fill() -> Gauge:
    from ray_tpu.util.metrics import Gauge
    return Gauge(
        "ray_tpu_train_attn_window_tile_fill",
        "Of the (query, key) pairs in the tiles the flash kernels execute "
        "for a sliding-window layer, the share the mask keeps, from "
        "ops/flash_attention.py window_tile_census of the pair table the "
        "last recorded step was built with (models/mellum.py "
        "window_tile_fill): 0.667 at 16384 tokens, a window of 1024 and "
        "tiles of 512, 0.800 at tiles of 256.")


def train_attn_gate_mean() -> Gauge:
    from ray_tpu.util.metrics import Gauge
    return Gauge(
        "ray_tpu_train_attn_gate_mean",
        "Mean over heads, tokens and layers of the sigmoid gate a head on "
        "the attention's output in the last recorded step, a kind of layer "
        "(models/dots3_note.py: 'full', latent attention over a learned "
        "selection; 'window', latent attention in a sliding window). About "
        "0.5 at random weights; stuck at exactly 0.5 or at 1 the gate is "
        "dropped or dead.",
        tag_keys=("kind",))


# -- delta-rule layers -----------------------------------------------------
# Fed as the expert layers' scalars are (models/kimi_linear.py).


def train_kda_decay_floor() -> Gauge:
    from ray_tpu.util.metrics import Gauge
    return Gauge(
        "ray_tpu_train_kda_decay_floor",
        "Most negative running sum of log-decays any chunk of any Kimi "
        "Delta Attention layer reached in the last recorded step "
        "(ops/kda.py forms exp of differences of it): float32's exp "
        "underflows to 0 below -103.")


def train_selective_scan_decay_floor() -> Gauge:
    from ray_tpu.util.metrics import Gauge
    return Gauge(
        "ray_tpu_train_selective_scan_decay_floor",
        "Most negative delta_t A any (token, channel, state) of any Mamba-1 "
        "layer saw in the last recorded step (ops/selective_scan.py takes "
        "exp of it): where a state forgets within a token.")


def train_diff_attention_lambda_max() -> Gauge:
    from ray_tpu.util.metrics import Gauge
    return Gauge(
        "ray_tpu_train_diff_attention_lambda_max",
        "Largest lambda of any differential-attention layer in the last "
        "recorded step (models/phi4flash.py): above 1 the subtracted "
        "softmax map outweighs the first.")


def train_dsa_selected_share() -> Gauge:
    from ray_tpu.util.metrics import Gauge
    return Gauge(
        "ray_tpu_train_dsa_selected_share",
        "Pairs the learned sparse attention's selections kept over the "
        "causal pairs, counted from the selections the last recorded step "
        "made (ops/dsa.py select; models/glm_moe_dsa.py): sum_t min(t + 1, "
        "index_topk) over S (S + 1) / 2, or a row kept another count.")


def train_dsa_index_loss() -> Gauge:
    from ray_tpu.util.metrics import Gauge
    return Gauge(
        "ray_tpu_train_dsa_index_loss",
        "The indexers' own loss in the last recorded step "
        "(models/glm_moe_dsa.py): KL of the main attention's head-summed "
        "probabilities over the selection against the softmax of the "
        "indexer's scores there, mean over rows, summed over the layers "
        "that own an indexer.")


# -- EVA attention and several prediction heads; block-sparse and linear ------
# attention. Fed as the expert layers' scalars are (models/evabyte.py,
# models/minicpm_sala.py).


def train_eva_pairs_share() -> Gauge:
    from ray_tpu.util.metrics import Gauge
    return Gauge(
        "ray_tpu_train_eva_pairs_share",
        "(Query, key or summary) pairs EVA attention's mask allows over the "
        "causal pairs, counted from the table the last recorded step's "
        "kernels were traced with (ops/flash_attention.py eva_tile_census): "
        "0.12112 at 32768 tokens, a window of 2048 and chunks of 16.")


def train_eva_summary_mass() -> Gauge:
    from ray_tpu.util.metrics import Gauge
    return Gauge(
        "ray_tpu_train_eva_summary_mass",
        "Mean share of a query's softmax sum that lay on chunk summaries "
        "in the last recorded step, over queries, heads and layers "
        "(ops/eva.py: the forward kernel keeps the summary tiles' part of "
        "its running sum): 0 where attention never looks past its window.")


def train_sala_selected_share() -> Gauge:
    from ray_tpu.util.metrics import Gauge
    return Gauge(
        "ray_tpu_train_sala_selected_share",
        "(Query, key) pairs block-sparse attention attended over the causal "
        "pairs in the last recorded step, counted from the selection itself "
        "(ops/infllm.py selected_pairs_share), the mean over sparse layers: "
        "0.4346 at 16384 tokens with the top 64 blocks of 64.")


def train_sala_live_tile_share() -> Gauge:
    from ray_tpu.util.metrics import Gauge
    return Gauge(
        "ray_tpu_train_sala_live_tile_share",
        "Tile pairs of the sparse attention kernels' causal table in which "
        "any query of any KV group selected a key, over the table, in the "
        "last recorded step (ops/infllm.py live_tiles): what a kernel that "
        "skipped empty tiles' steps and fetches could leave out is 1 - it.")


def train_sala_free_mass() -> Gauge:
    from ray_tpu.util.metrics import Gauge
    return Gauge(
        "ray_tpu_train_sala_free_mass",
        "Mean share of a query's softmax sum that lay on blocks chosen by "
        "score and not forced (the first block, the local window), over a "
        "stride of query rows, every head and sparse layer of the last "
        "recorded step (ops/infllm.py free_mass): 0 would say the selection "
        "does nothing.")


def train_lightning_decay_floor() -> Gauge:
    from ray_tpu.util.metrics import Gauge
    return Gauge(
        "ray_tpu_train_lightning_decay_floor",
        "The least lambda^chunk of the linear-attention layers of the last "
        "recorded step: what the steepest head keeps of its state over one "
        "chunk of ops/lightning.py's kernels.")


def train_mbp_loss() -> Gauge:
    from ray_tpu.util.metrics import Gauge
    return Gauge(
        "ray_tpu_train_mbp_loss",
        "Cross-entropy of each prediction head in the last recorded step "
        "(models/lm.py multi_token_loss): head i predicts token t + 1 + i.",
        tag_keys=("head",))


# -- train set-up ----------------------------------------------------------
# A few dozen events a process (and again at every gang restart), so their
# durations are observed whether or not anybody traces; the span beside each
# observation records under tracing's own rule. ``within`` is the stage that
# was open on the thread when this one was observed ("none": the outermost),
# so a reader sums ``within="none"`` and counts no second twice.

_SETUP_BOUNDARIES = (0.01, 0.05, 0.25, 1.0, 5.0, 15.0, 60.0, 300.0)
_setup = threading.local()


def train_setup_seconds() -> Histogram:
    from ray_tpu.util.metrics import Histogram
    return Histogram(
        "ray_tpu_train_setup_seconds",
        "Seconds of one stage between process start and the first timed "
        "train step (init, native_build, worker_group, backend, "
        "loop_start, mesh, state_init, first_call, aot_lower); observed "
        "again at every gang restart. within = the stage it ran inside, "
        "or none.",
        boundaries=_SETUP_BOUNDARIES, tag_keys=("stage", "within"))


def jax_compile_seconds() -> Histogram:
    from ray_tpu.util.metrics import Histogram
    return Histogram(
        "ray_tpu_jax_compile_seconds",
        "Seconds JAX reports for making one program, by phase: trace, "
        "lower, backend (compile on a miss; key, retrieval and "
        "deserialise on a hit), each less what it enclosed, so the three "
        "tile; cache_load (the retrieval alone) lies inside backend. "
        "within = the set-up stage it fell inside, or none.",
        boundaries=_SETUP_BOUNDARIES, tag_keys=("phase", "within"))


def jax_programs() -> Counter:
    from ray_tpu.util.metrics import Counter
    return Counter(
        "ray_tpu_jax_programs_total",
        "Programs JAX made in this process: compiled, or loaded from the "
        "persistent compilation cache.",
        tag_keys=("outcome",))


def setup_stage_open() -> str:
    """The innermost set-up stage open on this thread, or "none"."""
    return getattr(_setup, "stage", "none")


def enter_setup_stage(stage: str) -> str:
    """Make ``stage`` the thread's open stage; returns the one it was."""
    outer = getattr(_setup, "stage", "none")
    _setup.stage = stage
    return outer


def leave_setup_stage(stage: str, outer: str,
                      seconds: Optional[float]) -> None:
    """Give the thread back to ``outer``; observe ``seconds`` of ``stage``
    unless None (a call that turned out to be no stage)."""
    _setup.stage = outer
    if seconds is not None:
        train_setup_seconds().observe(
            seconds, tags={"stage": stage, "within": outer})


class setup_stage:
    """One stage of set-up as a ``with`` block: its seconds go to
    ``ray_tpu_train_setup_seconds{stage, within}`` always, and the block is
    a ``tracing.start_span(span_name)`` site (yields the span, or None
    where nothing records: the shared no-op plus one ``observe``)."""

    __slots__ = ("_stage", "_scope", "_outer", "_t0")

    def __init__(self, stage: str, span_name: str):
        from ray_tpu.util import tracing
        self._stage = stage
        self._scope = tracing.start_span(span_name)

    def __enter__(self):
        self._outer = enter_setup_stage(self._stage)
        self._t0 = time.perf_counter()
        return self._scope.__enter__()

    def __exit__(self, *exc) -> bool:
        seconds = time.perf_counter() - self._t0
        self._scope.__exit__(*exc)
        leave_setup_stage(self._stage, self._outer, seconds)
        return False


# -- train loop: a step's call, the waits between steps, a process off the CPU
# Always on: two observations a call of a step, two clock reads at each of
# the loop's waits between steps. The spans beside them (``train::step``,
# ``step::record``, ``host::tick``) record under tracing's own rule.

_loop = threading.local()
#: The loop's waits between steps, in the order ``loop_waits`` gives them.
LOOP_WAITS = ("save", "report", "data")


def train_step_interval_seconds() -> Histogram:
    from ray_tpu.util.metrics import Histogram
    return Histogram(
        "ray_tpu_train_step_interval_seconds",
        "Seconds from one call of a jitted train or eval step to the next "
        "call of the same step (entry to entry): the step as the loop "
        "lives it. A call that made a program starts the clock anew.",
        boundaries=(0.05, 0.1, 0.25, 0.5, 1.0, 2.0, 4.0, 8.0, 30.0),
        tag_keys=("program",))


def train_step_dispatch_seconds() -> Histogram:
    from ray_tpu.util.metrics import Histogram
    return Histogram(
        "ray_tpu_train_step_dispatch_seconds",
        "Seconds a call of a jitted step that found its program holds the "
        "loop's thread: entry to return, the recorder of the model's "
        "scalars included; the device runs the step meanwhile and after.",
        boundaries=(0.001, 0.002, 0.005, 0.01, 0.02, 0.05, 0.1, 0.5, 2.0),
        tag_keys=("program",))


def train_loop_wait_seconds() -> Counter:
    from ray_tpu.util.metrics import Counter
    return Counter(
        "ray_tpu_train_loop_wait_seconds_total",
        "Seconds the train loop's thread spent between steps in a report "
        "(session.report), a save (report_sharded: until the state is in "
        "host memory, the wait for the save before it included) or the "
        "wait for a batch (iter_jax_batches).",
        tag_keys=("what",))


def train_step_stalled_seconds() -> Counter:
    from ray_tpu.util.metrics import Counter
    return Counter(
        "ray_tpu_train_step_stalled_seconds_total",
        "Seconds by which a step's interval, less its save, report and "
        "batch, passed the median of that step's last intervals, by what "
        "the sampler's late ticks inside it say kept the process off the "
        "CPU (none: it ran all along).",
        tag_keys=("program", "cause"))


def process_late_seconds() -> Counter:
    from ray_tpu.util.metrics import Counter
    return Counter(
        "ray_tpu_process_late_seconds_total",
        "Seconds by which the continuous profiler's ticks woke late in "
        "this process, counted from 0.02 s a tick, by cause: runqueue, "
        "throttled, steal, pressure_cpu / _io / _memory, gc, gil, "
        "unknown.",
        tag_keys=("cause",))


def loop_waits() -> tuple:
    """This thread's running totals of ``LOOP_WAITS``, in that order."""
    return getattr(_loop, "totals", (0.0, 0.0, 0.0))


class loop_wait:
    """One of the loop's waits between steps as a ``with`` block: a
    ``tracing.start_span(span_name)`` site (yields the span, or None where
    nothing records) whose seconds go, always, to
    ``ray_tpu_train_loop_wait_seconds_total{what}`` and to the thread's
    running total, which a step's call site reads at its next entry. A wait
    inside another (a save's ack is a report) is the outer one's."""

    __slots__ = ("_what", "_scope", "_outermost", "_t0")

    def __init__(self, what: str, span_name: str):
        from ray_tpu.util import tracing
        self._what = LOOP_WAITS.index(what)
        self._scope = tracing.start_span(span_name)

    def __enter__(self):
        self._outermost = not getattr(_loop, "waiting", False)
        _loop.waiting = True
        span = self._scope.__enter__()
        self._t0 = time.perf_counter()
        return span

    def __exit__(self, *exc) -> bool:
        seconds = time.perf_counter() - self._t0
        self._scope.__exit__(*exc)
        if self._outermost:
            _loop.waiting = False
            totals = list(loop_waits())
            totals[self._what] += seconds
            _loop.totals = tuple(totals)
            train_loop_wait_seconds().inc(
                seconds, tags={"what": LOOP_WAITS[self._what]})
        return False


def channel_bytes_sent() -> Counter:
    from ray_tpu.util.metrics import Counter
    return Counter(
        "ray_tpu_channel_bytes_sent_total",
        "Bytes written to session channels (seq envelope + payload), "
        "fed by the per-frame fast cell.")


def channel_acks_sent() -> Counter:
    from ray_tpu.util.metrics import Counter
    return Counter(
        "ray_tpu_channel_acks_sent_total",
        "Pure ack frames (seq 0) flushed by the deferred-ack timer — "
        "acks piggybacked on regular traffic are not counted here.")
