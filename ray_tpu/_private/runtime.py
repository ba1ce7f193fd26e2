"""The single-node runtime: task submission, dispatch, execution, actors.

This is the round-1 analog of the reference's CoreWorker + raylet pair
(src/ray/core_worker/core_worker.cc SubmitTask/ExecuteTask;
src/ray/raylet/local_task_manager.cc DispatchScheduledTasksToWorkers):

* ``submit_task`` registers return objects, resolves ObjectRef dependencies
  (callback-driven, like the reference's LocalDependencyResolver), then hands
  the task to the dispatcher.
* The dispatcher acquires resources from the ResourceScheduler and assigns an
  idle executor (worker), growing the pool on demand the way the reference's
  WorkerPool pops/starts workers.
* Actors are executors pinned for the actor's lifetime; actor tasks bypass
  resource accounting and are ordered per submission (serial / threadpool /
  asyncio modes, the analog of the reference's ActorSchedulingQueue +
  ConcurrencyGroupManager fibers).
* Failed tasks retry per ``max_retries``/``retry_exceptions``
  (reference: src/ray/core_worker/task_manager.cc retry path).

Execution backends plug in beneath the executor interface. The default
backend runs tasks on threads in the driver process (JAX/XLA releases the
GIL during compute, so single-host TPU orchestration loses little).
Multi-node runs through the head server + node daemons
(_private/multinode.py).
"""

from __future__ import annotations

import hashlib
import logging
import os
import queue
import threading
import time
import traceback
from typing import Any, Callable, Dict, List, Optional, Tuple

from ray_tpu._private import builtin_metrics, serialization
from ray_tpu._private.cluster_scheduler import (ClusterResourceScheduler,
                                                make_cluster_scheduler)
from ray_tpu._private.ids import (ActorID, JobID, NodeID, ObjectID,
                                  PlacementGroupID, TaskID, WorkerID)
from ray_tpu._private.object_ref import ObjectRef
from ray_tpu._private.object_store import ObjectStore
from ray_tpu._private.resource_spec import NodeResources
from ray_tpu._private.task_spec import TaskKind, TaskSpec
from ray_tpu.exceptions import (ActorDiedError, GetTimeoutError,
                                NodeDiedError, ObjectLostError,
                                TaskCancelledError, TaskError)

logger = logging.getLogger("ray_tpu")

_STOP = object()

# Per-thread execution context: which task (if any) this thread is running.
# Used to release the task's resources while it blocks in a nested ``get``
# (the analog of the reference worker's NotifyDirectCallTaskBlocked →
# raylet releases CPU, core_worker.cc).
_task_context = threading.local()


def current_task_spec():
    return getattr(_task_context, "spec", None)


class FunctionTable:
    """Function export table — analog of the reference's FunctionActorManager
    export to GCS KV (python/ray/_private/function_manager.py). Functions are
    pickled once; executors memoize the unpickled callable by id."""

    def __init__(self):
        self._by_id: Dict[bytes, bytes] = {}
        self._loaded: Dict[bytes, Callable] = {}
        self._lock = threading.Lock()

    def export(self, fn: Callable) -> bytes:
        try:
            payload = serialization.dumps_function(fn)
        except Exception:  # noqa: BLE001
            # Unpicklable closure (locks, events, ...): legal on the
            # in-process thread backend where the live object is shared;
            # the process backend would reject this at spawn time.
            payload = None
        if payload is not None:
            fn_id = hashlib.sha1(payload).digest()
        else:
            import os as _os
            fn_id = _os.urandom(20)
        with self._lock:
            if fn_id not in self._by_id:
                if payload is not None:
                    self._by_id[fn_id] = payload
                self._loaded[fn_id] = fn
        return fn_id

    def export_bytes(self, payload: bytes) -> bytes:
        fn_id = hashlib.sha1(payload).digest()
        with self._lock:
            self._by_id.setdefault(fn_id, payload)
        return fn_id

    def get_bytes(self, fn_id: bytes) -> bytes:
        with self._lock:
            return self._by_id[fn_id]

    def load(self, fn_id: bytes) -> Callable:
        with self._lock:
            fn = self._loaded.get(fn_id)
            if fn is not None:
                return fn
            payload = self._by_id[fn_id]
        fn = serialization.loads_function(payload)
        with self._lock:
            self._loaded[fn_id] = fn
        return fn


class _PendingTask:
    __slots__ = ("spec", "unresolved", "cancelled")

    def __init__(self, spec: TaskSpec, unresolved: int):
        self.spec = spec
        self.unresolved = unresolved
        self.cancelled = False


class Executor:
    """A worker: executes submitted thunks. Subclasses define the threading
    model. ``submit`` must preserve submission order for serial executors."""

    def __init__(self, worker_id: WorkerID):
        self.worker_id = worker_id
        self.actor_id: Optional[ActorID] = None
        self.dead = False

    def submit(self, thunk: Callable[[], None]) -> None:
        raise NotImplementedError

    def stop(self, wait: bool = False) -> None:
        raise NotImplementedError


class SerialThreadExecutor(Executor):
    def __init__(self, worker_id: WorkerID, name: str):
        super().__init__(worker_id)
        self._queue: "queue.Queue" = queue.Queue()
        self._thread = threading.Thread(target=self._loop, name=name, daemon=True)
        self._thread.start()

    def _loop(self):
        while True:
            item = self._queue.get()
            if item is _STOP:
                break
            try:
                item()
            except BaseException:  # noqa: BLE001 - executor must survive
                logger.exception("Uncaught error in worker loop")
            # Drop the completed thunk NOW: an idle worker must not keep the
            # last task's spec (and its ObjectRef args) alive until the next
            # task arrives — that pins freed objects' refcounts.
            del item

    def submit(self, thunk):
        self._queue.put(thunk)

    def stop(self, wait: bool = False):
        self.dead = True
        self._queue.put(_STOP)
        if wait:
            self._thread.join(timeout=5)


class ThreadPoolActorExecutor(Executor):
    """Actor executor with max_concurrency > 1 (sync methods)."""

    def __init__(self, worker_id: WorkerID, name: str, max_concurrency: int):
        super().__init__(worker_id)
        import concurrent.futures
        self._pool = concurrent.futures.ThreadPoolExecutor(
            max_workers=max_concurrency, thread_name_prefix=name)

    def submit(self, thunk):
        self._pool.submit(thunk)

    def stop(self, wait: bool = False):
        self.dead = True
        self._pool.shutdown(wait=wait, cancel_futures=True)


class ConcurrencyGroupExecutor(Executor):
    """Named concurrency groups for sync actors (reference:
    core_worker/transport/concurrency_group_manager.h): each group gets
    its own sub-executor with its own limit — "io" calls never eat
    "compute" slots — and per-group FIFO ordering holds (serial groups
    are strictly ordered; pooled groups bound concurrency). Untagged
    methods run on the default group (max_concurrency)."""

    def __init__(self, worker_id: WorkerID, name: str,
                 groups: Dict[str, int], max_concurrency: int):
        super().__init__(worker_id)

        def make(limit: int, suffix: str) -> Executor:
            if limit <= 1:
                return SerialThreadExecutor(worker_id, f"{name}-{suffix}")
            return ThreadPoolActorExecutor(worker_id, f"{name}-{suffix}",
                                           limit)

        self._default = make(max(max_concurrency, 1), "default")
        self._groups: Dict[str, Executor] = {
            g: make(int(n), g) for g, n in groups.items()}

    def submit(self, thunk):
        self._default.submit(thunk)

    def submit_group(self, group: Optional[str], thunk):
        self._groups.get(group, self._default).submit(thunk)

    def group_names(self):
        return set(self._groups)

    def stop(self, wait: bool = False):
        self.dead = True
        self._default.stop(wait)
        for ex in self._groups.values():
            ex.stop(wait)


class AsyncioActorExecutor(Executor):
    """Actor executor for async actors: a dedicated event loop thread; each
    task runs as an asyncio task, so ``await`` interleaves calls the way the
    reference's fiber-based async actors do
    (src/ray/core_worker/transport/fiber.h). Named concurrency groups map
    to per-group semaphores on the same loop."""

    def __init__(self, worker_id: WorkerID, name: str, max_concurrency: int,
                 groups: Optional[Dict[str, int]] = None):
        super().__init__(worker_id)
        import asyncio
        self._loop = asyncio.new_event_loop()
        self._sem = asyncio.Semaphore(max_concurrency)
        self._group_sems = {g: asyncio.Semaphore(int(n))
                            for g, n in (groups or {}).items()}
        self._thread = threading.Thread(
            target=self._loop.run_forever, name=name, daemon=True)
        self._thread.start()

    @property
    def loop(self):
        return self._loop

    def submit(self, thunk):
        self.submit_group(None, thunk)

    def submit_group(self, group: Optional[str], thunk):
        import asyncio
        sem = self._group_sems.get(group, self._sem)

        async def _run():
            async with sem:
                result = thunk()
                if asyncio.iscoroutine(result):
                    await result

        asyncio.run_coroutine_threadsafe(_run(), self._loop)

    def stop(self, wait: bool = False):
        import asyncio
        self.dead = True

        def _cancel_then_stop():
            # Cancel parked tasks ON the loop so their cleanup runs here,
            # now, while the runtime is alive — never later in a random
            # thread's garbage collector (long-poll actor methods park
            # for tens of seconds; see _acall's GeneratorExit guard).
            for task in asyncio.all_tasks(self._loop):
                task.cancel()
            self._loop.call_later(0.1, self._loop.stop)

        try:
            self._loop.call_soon_threadsafe(_cancel_then_stop)
        except RuntimeError:
            pass  # loop already closed
        if wait:
            self._thread.join(timeout=5)


class ActorState:
    def __init__(self, actor_id: ActorID, creation_spec: TaskSpec,
                 max_restarts: int, max_concurrency: int, name: str = "",
                 namespace: str = "",
                 concurrency_groups: Optional[Dict[str, int]] = None,
                 lifetime: Optional[str] = None):
        self.actor_id = actor_id
        self.creation_spec = creation_spec
        # Human-readable class name ("Cls" from the creation task's
        # "Cls.__init__") — travels over the actor_info client op so
        # client-session handles can name tasks without loading the class.
        self.class_name = (creation_spec.name or "").rsplit(".", 1)[0]
        self.max_restarts = max_restarts
        self.num_restarts = 0
        self.max_concurrency = max_concurrency
        self.concurrency_groups = dict(concurrency_groups or {})
        self.name = name
        self.namespace = namespace
        # GCS-owned lifetime (reference: gcs_actor_manager detached
        # actors): "detached" actors are NOT reaped on driver exit or
        # client disconnect; only kill(no_restart=True) removes them.
        self.lifetime = lifetime
        self.detached = lifetime == "detached"
        self.executor: Optional[Executor] = None
        self.instance: Any = None  # thread backend: the live instance
        self.dead = False
        self.death_cause: Optional[BaseException] = None
        self.created = threading.Event()
        self.lock = threading.RLock()
        # Per-handle sequencing (the analog of the reference's
        # ActorSchedulingQueue ordering by sequence_no): tasks execute in each
        # handle's submission order even if their deps resolve out of order.
        self.seq_state: Dict[str, dict] = {}
        # Tasks submitted but not yet sealed; killed actors seal these with
        # ActorDiedError so gets never hang.
        self.unfinished: Dict[TaskID, TaskSpec] = {}
        # Dep-resolved tasks that arrived before __init__ finished, in order.
        self.pre_creation_queue: List[TaskSpec] = []
        self.resources_released = False


class _WorkerLease:
    """One worker lease (reference: direct_task_transport.cc:174
    OnWorkerIdle + lease_policy.cc): a single resource acquisition on a
    remote daemon that a stream of same-scheduling-class tasks pipelines
    onto. The daemon runs leased tasks serially on a dedicated executor
    (with a worker subprocess pinned for the lease's lifetime), so one
    acquisition still means one task *running* at a time — the up-to-
    ``max_tasks_in_flight_per_worker`` extras ride the wire early instead
    of paying a head dispatch round-trip each."""

    __slots__ = ("lease_id", "class_key", "node_id", "resources", "pg_id",
                 "bidx", "tpu_ids", "inflight", "dropped", "blocked")

    def __init__(self, lease_id: str, class_key, node_id, resources,
                 pg_id, bidx, tpu_ids):
        self.lease_id = lease_id
        self.class_key = class_key
        self.node_id = node_id
        self.resources = resources
        self.pg_id = pg_id
        self.bidx = bidx
        self.tpu_ids = tpu_ids
        self.inflight = 1  # the creating task
        self.dropped = False
        # COUNT of this lease's tasks blocked in nested gets (the serial
        # task plus any bypass-thread tasks may block simultaneously):
        # while nonzero, skip new attaches and spill the daemon-side
        # queue (deadlock safety — a child queued behind its blocked
        # parent could never run). A boolean cleared on the FIRST
        # unblock re-enabled attaches behind a still-blocked executor.
        # Falsy when 0, so `not lease.blocked` reads stay correct.
        self.blocked = 0


class Runtime:
    def __init__(self, node_resources: NodeResources, job_id: JobID,
                 max_workers: Optional[int] = None,
                 system_config: Optional[Dict[str, Any]] = None,
                 log_to_driver: bool = True):
        import uuid
        self.session_id = uuid.uuid4().hex
        self.job_id = job_id
        self.node_resources = node_resources
        # Typed flag table (reference: RayConfig / ray_config_def.h):
        # native C++ defaults overridable via RAY_TPU_<flag> env vars and
        # the _system_config dict handed to init().
        from ray_tpu._private.ray_config import make_ray_config
        self.config = make_ray_config(system_config)
        # Shared-memory arena sized like the reference's object store
        # (30% of memory, services.py object_store_memory default).
        import tempfile
        spill_dir = (self.config.object_spilling_directory
                     or os.path.join(tempfile.gettempdir(), "ray_tpu_spill",
                                     self.session_id))
        # Durable spill tier (reference: external storage behind the
        # raylet's LocalObjectManager): object_spill_uri routes spill
        # writes through a pluggable backend — session:// / mock-s3://
        # records survive process death and feed tiered recovery. An
        # unset/invalid URI keeps the plain per-session directory.
        spill_backend = None
        _spill_uri = str(self.config.object_spill_uri or "")
        if _spill_uri:
            from ray_tpu._private.spill import backend_for_uri
            try:
                spill_backend = backend_for_uri(
                    _spill_uri, session_id=self.session_id,
                    fallback_dir=spill_dir)
            except (ValueError, OSError):
                logger.exception(
                    "invalid object_spill_uri %r; using the local "
                    "spill directory", _spill_uri)
        self.store = ObjectStore(
            deserializer=serialization.deserialize,
            native_capacity=int(node_resources.memory_bytes *
                                self.config.object_store_memory_fraction),
            use_native=self.config.use_native_object_store,
            spill_threshold_bytes=int(
                self.config.object_spilling_threshold_bytes),
            spill_directory=spill_dir,
            spill_backend=spill_backend)
        # A head-local spilled entry whose file vanished (chaos, scrubbed
        # tmpdir) falls down to the lineage tier instead of surfacing an
        # IO error from get().
        self.store.restore_miss_hook = self._restore_from_lineage
        # Housekeeping: arenas/spill of SIGKILLed predecessors never
        # unlink themselves — a day of test churn measured 118GB of
        # dead /dev/shm mappings starving live runs.
        def _reap_stale():
            from ray_tpu._private.native_store import reap_stale_arenas
            reap_stale_arenas()

        threading.Thread(target=_reap_stale, name="ray_tpu-arena-reaper",
                         daemon=True).start()
        self.scheduler = make_cluster_scheduler(
            use_native=self.config.use_native_scheduler)
        self.head_node_id = self.scheduler.add_node(
            node_resources.to_resource_map(), is_head=True)
        self.functions = FunctionTable()
        self._lock = threading.RLock()
        self._idle_workers: List[Executor] = []
        self._all_workers: List[Executor] = []
        self._ready: List[TaskSpec] = []
        # Leasable NORMAL tasks queue per scheduling class (reference:
        # cluster_task_manager tasks_to_schedule_ by SchedulingClass):
        # same-class tasks are placement-interchangeable, so dispatch
        # probes ONE representative per class instead of scanning every
        # queued task — O(#classes), not O(#tasks), when saturated.
        from collections import deque as _deque
        self._ready_by_class: Dict[Any, Any] = {}
        self._deque = _deque
        self._pending_by_oid: Dict[ObjectID, List[_PendingTask]] = {}
        self._inflight: Dict[TaskID, TaskSpec] = {}
        self._actors: Dict[ActorID, ActorState] = {}
        self._named_actors: Dict[Tuple[str, str], ActorID] = {}
        self._dep_waiters: Dict[ObjectID, threading.Thread] = {}
        self._pg_counter = 0
        self._put_index = 0
        self._shutdown = False
        # Worker cap: thread executors are cheap; cap well above CPU count so
        # blocking tasks (e.g. sleeping) don't starve the pool.
        self._max_workers = max_workers or max(
            int(self.config.worker_cap_min),
            int(node_resources.num_cpus) *
            int(self.config.worker_cap_multiplier))
        self._task_events: List[dict] = []  # lightweight task-event buffer
        self._infeasible_warned: set = set()
        # Real remote node daemons (multi-process cluster, _private/
        # multinode.py): NodeID → NodeConnection. Virtual sim nodes
        # (cluster_utils) never appear here.
        self._remote_nodes: Dict[NodeID, Any] = {}
        self._head_server = None
        # Worker leases (reference: direct_task_transport.cc OnWorkerIdle):
        # class_key -> live leases. Guarded by self._lock.
        self._leases: Dict[Any, List[_WorkerLease]] = {}
        # Attachability index: class_key -> {lease_id: lease} holding
        # only leases with pipeline room (the envelope workload opens
        # THOUSANDS of leases per class — a linear scan per attach was
        # O(leases) on the submit hot path). Maintained by
        # _lease_avail_update at every inflight/blocked/drop mutation;
        # _find_lease double-checks before trusting an entry.
        self._lease_avail: Dict[Any, Dict[str, _WorkerLease]] = {}
        self._lease_counter = 0
        # Compact wire names for scheduling classes (shipped with each
        # leased task so the daemon can group its LOCAL dispatch queues
        # by class; the full class_key is a rich tuple).
        self._class_wire_ids: Dict[Any, str] = {}
        # Class keys dispatch saw feasible-but-capacity-blocked in its
        # last full scan: a draining lease releases early iff a class
        # OTHER than its own is starved (lease fairness without churn).
        self._lease_contended: set = set()
        self.lease_stats = {"created": 0, "attached": 0, "released": 0,
                            "reclaimed": 0}
        self._lease_window = max(
            1, int(self.config.max_tasks_in_flight_per_worker))
        self._lease_enabled = bool(self.config.worker_lease_enabled)
        # Submit/completion hot-path flags, read once: config.get is a
        # native ctypes round-trip — 5 per task adds up at 10k tasks/s.
        self._cfg_inline_limit = int(
            self.config.remote_object_inline_limit_bytes)
        self._cfg_max_task_events = int(self.config.max_task_events)
        self._cfg_lineage_max = int(self.config.lineage_max_entries)
        self._cfg_obj_loc_max = int(
            self.config.object_locations_max_entries)
        self._cfg_locality_spillback = float(
            self.config.locality_spillback_threshold)
        # ObjectID → (NodeID, daemon object key) for results resident on
        # node daemons (fetched lazily; see ObjectStore.put_remote).
        self._remote_values: Dict[ObjectID, Tuple[NodeID, str]] = {}
        # Lineage: creating TaskSpec per return object, for reconstruction
        # after node loss (reference: task_manager.h TaskResubmissionInterface
        # + object_recovery_manager.h). Bounded; puts are not reconstructable.
        self._lineage: Dict[ObjectID, TaskSpec] = {}
        self._object_locations: Dict[ObjectID, NodeID] = {}
        # Tiered-recovery location data (reference: the ownership-based
        # object directory tracking ALL holders, not just the primary):
        # _object_replicas — other daemons known to hold an in-memory
        # copy (learned when a task's marker arg was pulled there);
        # _spill_uris_by_key — durable spill URIs announced by daemons
        # (object_spilled frames), keyed by the daemon object key;
        # _remote_keys — key → ObjectID reverse map for those frames.
        # Node death walks replica → spill → lineage, cheapest first.
        self._object_replicas: Dict[ObjectID, Dict[NodeID, None]] = {}
        self._spill_uris_by_key: Dict[str, Tuple[str, int]] = {}
        self._remote_keys: Dict[str, ObjectID] = {}
        # Collective dataplane (tree broadcast): objects already pushed
        # through a spanning tree (head-resident ones keep materialized
        # values yet still ship as replica markers — see _resolve_args),
        # distinct consumer nodes seen per object (the auto-broadcast
        # demand signal), and the in-flight guard so demand spikes fire
        # one tree, not one per queued pull.
        self._broadcasted: Dict[ObjectID, None] = {}
        self._pull_demand: Dict[ObjectID, Dict[NodeID, None]] = {}
        self._broadcast_inflight: Dict[ObjectID, None] = {}
        # Ownership/reference counting (reference: reference_count.h):
        # ObjectRef handles hold local refs, pending tasks hold dependency
        # refs; when an owned object's counts hit zero its value is freed
        # and lineage pruned. Native C++ engine with a Python twin.
        from ray_tpu._private.refcount import make_reference_counter
        self.refs = make_reference_counter(
            use_native=self.config.use_native_refcount)
        # Long-poll pubsub hub (reference: src/ray/pubsub/): task-state
        # events publish here; consumers subscribe + poll.
        from ray_tpu._private.pubsub import make_pubsub
        self.pubsub = make_pubsub()
        self._chaos_us = {
            flag: int(self.config.get(flag))
            for flag in ("testing_submit_delay_us",
                         "testing_dispatch_delay_us",
                         "testing_store_delay_us")
        }
        # OOM protection (reference: MemoryMonitor + worker-killing policy):
        # poll memory pressure; above the threshold, fail the newest
        # retriable running task.
        self.memory_monitor = None
        threshold = float(self.config.memory_usage_threshold)
        refresh_ms = int(self.config.memory_monitor_refresh_ms)
        if 0 < threshold < 1.0 and refresh_ms > 0:
            from ray_tpu._private.memory_monitor import MemoryMonitor
            self.memory_monitor = MemoryMonitor(
                threshold, refresh_ms,
                get_running_tasks=self._running_normal_tasks,
                kill_fn=self._oom_kill_task)
            self.memory_monitor.start()
        # Process worker pool (reference: raylet WorkerPool — real worker
        # subprocesses). Lazily created: tasks/actors opt in via
        # runtime_env {"worker_process": True} (or pip/venv envs); TPU
        # tasks always run in this chip-owning process.
        self._process_pool = None
        self._proc_tasks: Dict[TaskID, Any] = {}  # task_id → WorkerHandle
        # GCS persistence (reference: gcs_server.cc:523 Redis-backed
        # storage): with _system_config={"gcs_store_path": ...}, the
        # internal KV + named-actor + job tables survive head death; a
        # restarted head restores them and rebinds daemon-resident
        # actors as their daemons reconnect.
        self.gcs_store = None
        self._kv_mem: Dict[str, Dict[bytes, bytes]] = {}
        gcs_path = str(self.config.gcs_store_path or "")
        if gcs_path:
            from ray_tpu._private.gcs_store import GcsStore
            self.gcs_store = GcsStore(gcs_path)
            # Job table (reference: GcsJobManager): the driver's job
            # record survives head death, so a post-restart head can
            # answer "what ran here". Keyed process-uniquely: JobID is a
            # per-process counter, so two driver processes sharing a
            # store would otherwise clobber each other's records.
            import uuid as _uuid
            self._gcs_job_key = f"{job_id.hex()}-{_uuid.uuid4().hex[:8]}"
            self.gcs_store.record_job(self._gcs_job_key, {
                "job_id": job_id.hex(),
                "pid": os.getpid(),
                "status": "RUNNING",
                "start_time": time.time(),
            })
        # Fenced membership (wire v9, _private/membership.py): every
        # daemon registration mints an incarnation epoch here; the
        # HeadServer's suspicion loop and death paths declare through
        # this table (exactly-once per incarnation), and join/death
        # events fan out to in-process subscribers (serve controller,
        # train executor) plus the "membership" pubsub channel.
        from ray_tpu._private.membership import MembershipTable
        self.membership = MembershipTable(self.gcs_store)
        self.membership.subscribe(self._membership_event)
        # Head failover (reference: GCS server restart replaying its
        # persistent store before serving): when the store carries a
        # previous head life's state, rehydrate the control plane NOW —
        # before the head server accepts any daemon traffic. Membership
        # already floored its epoch counter above every prior epoch;
        # here the object directory's durable tiers come back, dead
        # serve-generation actor records are retired (the fresh
        # controller redeploys from the serve table instead), and the
        # head incarnation counter + recovery summary land back in the
        # store for status surfaces.
        self._head_incarnation = 0
        self._head_recovery: Optional[Dict[str, Any]] = None
        self._recovered_object_replicas: Dict[str, list] = {}
        self._serve_rehydrate_started = False
        if self.gcs_store is not None:
            self._recover_from_store()
        # Deferred-free queue: ObjectRef.__del__ can fire at any point —
        # including inside the store's non-reentrant lock when a freed value
        # drops the last handle to another object — so handle-death frees
        # are drained by a dedicated GC thread instead of inline.
        import collections
        self._gc_queue: "collections.deque[ObjectID]" = collections.deque()
        self._gc_event = threading.Event()
        self._gc_thread = threading.Thread(
            target=self._gc_loop, name="ray_tpu-refgc", daemon=True)
        self._gc_thread.start()
        # Log subsystem (reference: _private/log_monitor.py + worker.py
        # print_logs): head-spawned worker output is captured to session
        # files and tailed by a head-local LogMonitor; daemons push
        # log_batch frames for theirs; everything fans out on the "logs"
        # pubsub channel, where a printer thread echoes it to the
        # driver's console unless init(log_to_driver=False).
        self.log_to_driver = log_to_driver
        self._log_monitor = None
        self._log_printer = None
        from ray_tpu._private import ray_logging
        try:
            ray_logging.setup_session(self.session_id, "head")
        except OSError:
            logger.exception("could not create the session log dir; "
                             "worker output will inherit this console")
        else:
            from ray_tpu._private.log_monitor import LogMonitor
            self._log_monitor = LogMonitor(self._publish_log_batch)
            ray_logging.register_capture_callback(
                self._log_monitor.add_file)
            if log_to_driver:
                self._log_printer = ray_logging.DriverLogPrinter(
                    self.pubsub)
        # Cluster metrics pipeline (reference: dashboard/agent.py + the
        # core's metric_exporter, collapsed to ONE scrape): the head
        # holds the cluster registry; its own agent publishes this
        # process's series straight into it, daemons and workers arrive
        # as metrics_batch frames / reply piggybacks.
        from ray_tpu._private.metrics_agent import (ClusterMetrics,
                                                    MetricsAgent)
        self._cluster_metrics = ClusterMetrics()
        self._journal_head_recovery()
        self._metrics_agent = MetricsAgent(
            self._publish_head_metrics, component="driver",
            publish_profile=self._publish_head_profile,
            publish_flow=self._publish_head_flow)
        self._metrics_agent.add_collector(self._collect_head_metrics)

    # ------------------------------------------------------------------
    # Head failover recovery
    # ------------------------------------------------------------------

    def _recover_from_store(self) -> None:
        """Rehydrate head state from the gcs_store before serving.

        Runs in __init__, before start_head_server can accept a single
        daemon — so everything a re-registering daemon's handshake
        touches (epoch floor, actor records, object directory) is
        already in its recovered shape. Replayed tiers:

        * spill URIs — durable by definition (the bytes live in the
          spill dir, not in any process), so they go straight back into
          the live ``_spill_uris_by_key`` table and tiered recovery can
          restore from them immediately.
        * replica holders — node ids are re-minted when daemons
          re-register, so the recorded NodeID hexes are stale; they are
          kept in a side table for status/debugging only, never in the
          live ``_object_replicas`` map.
        * serve actor records — controller/replica actors belong to the
          dead head's serve generation; their records are dropped so
          re-registering daemons don't rebind zombies (the daemon
          destroys them instead) and the fresh controller redeploys
          from the durable serve table.
        """
        store = self.gcs_store
        counts = store.counts()
        recovery: Optional[Dict[str, Any]] = None
        if store.had_prior_state:
            # Spill URIs: live again immediately.
            spills = dict(store.spill_uris)
            self._spill_uris_by_key.update(spills)
            # Replica holders: stale node identities → side table only.
            self._recovered_object_replicas = {
                k: list(v) for k, v in store.object_replicas.items()}
            # Serve-generation actors died with the old head; retire
            # their records (detached *user* actors keep theirs — that
            # is the exactly-once incarnation guarantee).
            purged = [aid for aid, rec in list(store.actors.items())
                      if str(rec.get("name") or "").startswith(
                          ("_serve_controller", "_serve_replica::"))]
            for aid in purged:
                store.remove_actor(aid)
            recovery = {
                "at": time.time(),
                "epoch_floor": self.membership.recovered_epoch_floor,
                "corrupt_records": store.corrupt_records,
                "replayed": {
                    "kv": counts["kv"],
                    "actors": counts["actors"] - len(purged),
                    "jobs": counts["jobs"],
                    "node_epochs": counts["node_epochs"],
                    "serve_deployments": counts["serve_deployments"],
                    "spill_uris": len(spills),
                    "object_replicas": len(
                        self._recovered_object_replicas),
                },
            }
        else:
            self._recovered_object_replicas = {}
        self._head_incarnation = store.begin_head_incarnation(recovery)
        self._head_recovery = recovery
        if recovery is not None:
            try:
                from ray_tpu._private import builtin_metrics
                builtin_metrics.head_recoveries().inc()
                for kind, n in recovery["replayed"].items():
                    if n:
                        builtin_metrics.head_recovery_replayed().inc(
                            n, tags={"kind": kind})
            except Exception:  # noqa: BLE001 - metrics must not block boot
                logger.exception("head recovery metrics failed")
            logger.warning(
                "head recovered from gcs_store %s: incarnation %d, "
                "epoch floor %d, replayed %s (%d corrupt records "
                "skipped)", store.path, self._head_incarnation,
                recovery["epoch_floor"], recovery["replayed"],
                recovery["corrupt_records"])

    def head_recovery_info(self) -> Dict[str, Any]:
        """Status surface: head incarnation + last recovery summary."""
        info: Dict[str, Any] = {
            "incarnation": self._head_incarnation,
            "recovered": self._head_recovery is not None,
            "last_recovery": self._head_recovery,
            "prior_node_count": getattr(
                self.membership, "prior_node_count", 0),
        }
        return info

    def _journal_head_recovery(self) -> None:
        """Emit the ``head_recovered`` journal event. Called from
        __init__ right after the cluster journal exists (the recovery
        itself ran earlier, before any daemon traffic)."""
        rec = self._head_recovery
        if rec is None:
            return
        labels = {"incarnation": str(self._head_incarnation),
                  "epoch_floor": str(rec["epoch_floor"])}
        labels.update({f"replayed_{k}": str(v)
                       for k, v in rec["replayed"].items() if v})
        try:
            self._cluster_metrics.events.record(
                "head", "head_recovered", severity="warning",
                labels=labels)
        except Exception:  # noqa: BLE001 - journal is best-effort
            logger.exception("could not journal head recovery")

    def maybe_rehydrate_serve_async(self) -> None:
        """Redeploy persisted serve applications in the background.

        Triggered once per runtime, after the worker wiring is attached
        (deploys go through the normal actor API). The controller's
        deploy retry budget absorbs daemons that re-register after us:
        a replica needing a daemon's resources just stays pending until
        that daemon's resources come back."""
        if self.gcs_store is None or self._serve_rehydrate_started:
            return
        if not self.gcs_store.serve_deployments:
            return
        self._serve_rehydrate_started = True
        t = threading.Thread(target=self._rehydrate_serve,
                             name="ray_tpu-serve-rehydrate", daemon=True)
        t.start()

    def _rehydrate_serve(self) -> None:
        try:
            from ray_tpu.serve import _redeploy_from_records
            records = dict(self.gcs_store.serve_deployments)
            n = _redeploy_from_records(records)
            if n:
                logger.warning(
                    "serve rehydrated %d deployment(s) from gcs_store",
                    n)
                try:
                    self._cluster_metrics.events.record(
                        "serve", "serve_rehydrated", severity="info",
                        labels={"deployments": str(n)})
                except Exception:  # noqa: BLE001
                    pass
        except Exception:  # noqa: BLE001 - rehydration is best-effort;
            # the deployments stay in the store for the next attempt.
            logger.exception("serve rehydration failed")

    # ------------------------------------------------------------------
    # Object API
    # ------------------------------------------------------------------

    def free_objects(self, oids: List[ObjectID]) -> None:
        """Explicitly free object values (``ray.free`` analog) regardless of
        outstanding references, cascading to objects contained in them."""
        cascade: Dict[ObjectID, None] = dict.fromkeys(oids)
        for oid in oids:
            # force_free returns the oid itself (when tracked) plus any
            # contained objects it cascaded to; dedupe against the explicit
            # list so nothing reaches store.free twice.
            cascade.update(dict.fromkeys(self.refs.force_free(oid)))
        self._free_now(list(cascade))

    def _free_now(self, oids: List[ObjectID]) -> None:
        """Drop freed objects' values and lineage/location bookkeeping (the
        reference prunes lineage when refs go out of scope)."""
        if not oids:
            return
        self.store.free(oids)
        remote_frees = []
        had_spill_uri = []
        with self._lock:
            all_conns = list(self._remote_nodes.values())
            for oid in oids:
                self._lineage.pop(oid, None)
                self._object_locations.pop(oid, None)
                self._object_replicas.pop(oid, None)
                self._broadcasted.pop(oid, None)
                self._pull_demand.pop(oid, None)
                self._broadcast_inflight.pop(oid, None)
                rv = self._remote_values.pop(oid, None)
                if rv is not None:
                    remote_frees.append(rv[1])
                    self._remote_keys.pop(rv[1], None)
                    if self._spill_uris_by_key.pop(rv[1], None) \
                            is not None:
                        had_spill_uri.append(rv[1])
        # Retract the durable object-directory mirror (throttled saves
        # inside the store: a mass free coalesces to one fsync).
        if self.gcs_store is not None:
            try:
                for key in had_spill_uri:
                    self.gcs_store.remove_spill_uri(key)
                for oid in oids:
                    self.gcs_store.remove_object_replicas(oid.hex())
            except OSError:
                pass
        # Broadcast: peer daemons may hold PULLED copies of the object
        # beyond the primary (the data plane caches pulls locally), so
        # every node gets the eviction notice (reference: object pubsub
        # eviction notifications).
        for key in remote_frees:
            for conn in all_conns:
                try:
                    conn.free_object(key)
                except Exception:  # noqa: BLE001 - best effort
                    pass

    def on_ref_deleted(self, oid: ObjectID) -> None:
        """An ObjectRef handle was garbage collected. Runs inside __del__,
        which can fire at ANY allocation (cyclic GC) — including while this
        very thread holds the store lock, the reference counter's lock, or
        even the GC event's internal (non-reentrant) condition lock. So:
        strictly lock-free here — deque.append only; the GC thread's timed
        poll (gc_sweep_interval_ms) picks the oid up."""
        self._gc_queue.append(oid)

    def _gc_loop(self) -> None:
        while True:
            self._gc_event.wait(
                timeout=self.config.gc_sweep_interval_ms / 1000.0)
            self._gc_event.clear()
            batch: List[ObjectID] = []
            while self._gc_queue:
                try:
                    batch.append(self._gc_queue.popleft())
                except IndexError:
                    break
            if batch:
                try:
                    freed: List[ObjectID] = []
                    for oid in batch:
                        freed.extend(self.refs.remove_local(oid))
                    self._free_now(freed)
                except Exception:  # noqa: BLE001 - GC must never die
                    logger.exception("refcount GC sweep failed")
            if self._shutdown:
                # Exit promptly (don't wait for the queue to drain): the
                # whole store is being torn down, and shutdown() joins this
                # thread before unmapping the native arena.
                return

    def _register_task_refs(self, spec: TaskSpec) -> None:
        """Owner-side bookkeeping at submission: own the return objects and
        pin the argument objects until the task completes."""
        if spec.num_returns != 0:
            for oid in spec.return_ids:
                self.refs.add_owned(oid)
        deps = self._find_dependencies(spec)
        spec._dep_oids = deps  # type: ignore[attr-defined]
        self.refs.add_task_deps(deps)

    def _release_task_deps(self, spec: TaskSpec) -> None:
        """Task reached a terminal state: drop its dependency pins.
        Atomic: a completing worker and a killer (OOM / node death) may
        race here; exactly one release happens."""
        with self._lock:
            deps = getattr(spec, "_dep_oids", None)
            spec._dep_oids = None  # type: ignore[attr-defined]
        if deps:
            self._free_now(self.refs.remove_task_deps(deps))

    def put(self, value: Any) -> ObjectRef:
        with self._lock:
            self._put_index += 1
            idx = self._put_index
        oid = ObjectID.for_put(TaskID.for_normal_task(self.job_id), idx)
        self._chaos_delay("testing_store_delay_us")
        self.store.put_inline(oid, value)
        self.refs.add_owned(oid)
        return ObjectRef(oid)

    def create_promise(self) -> ObjectRef:
        """Mint an owned but UNSEALED object (a promise): ``get`` blocks
        until someone settles it via :meth:`fulfill_promise`. The serve
        router hands these to callers so the caller-visible ref survives
        replica failover — the ref's identity is decoupled from any one
        actor-task attempt (reference: serve router replica_result
        wrappers over retried assignments)."""
        with self._lock:
            self._put_index += 1
            idx = self._put_index
        oid = ObjectID.for_put(TaskID.for_normal_task(self.job_id), idx)
        self.store._entry(oid)  # create the unsealed entry now
        self.refs.add_owned(oid)
        return ObjectRef(oid)

    def fulfill_promise(self, ref: ObjectRef, value: Any = None,
                        exception: Optional[BaseException] = None,
                        alias: Optional[ObjectRef] = None) -> None:
        """Settle a promise minted by :meth:`create_promise`.

        Exactly one of ``value`` / ``exception`` / ``alias`` semantics
        applies; the store's first-write-wins seal makes racing settles
        (e.g. a deadline expiry vs. a completing replica) safe. With
        ``alias`` the promise resolves to whatever the alias ref holds,
        materialized lazily through the store's remote-fetch hook: the
        closure pins the alias ref until the value (or error) is read."""
        oid = ref.object_id()
        if alias is not None:
            inner = alias  # closure keeps the aliased ref (and oid) alive

            def _fetch(timeout=None):
                return self.store.get(inner.object_id(), timeout=timeout)

            self.store.put_remote(oid, _fetch, 0)
        elif exception is not None:
            self.store.put_inline(oid, exception, is_exception=True)
        else:
            self.store.put_inline(oid, value)

    def register_remote_put(self, node_id: NodeID, key: str,
                            size: int, adopt: bool) -> ObjectRef:
        """Distributed-ownership put: the VALUE already sits in
        ``node_id``'s object table (written by daemon- or worker-side
        user code); the head records only the DIRECTORY entry and mints
        the ref (reference: owner-is-creator, reference_count.h:61 —
        the creating node serves the bytes; losing that node loses the
        object, exactly the reference's owner-failure model). ``adopt``
        asks the daemon to take bookkeeping ownership first (worker-
        process writers bypass the daemon's table accounting)."""
        conn = self._remote_nodes.get(node_id)
        if conn is None:
            raise KeyError(f"node {node_id.hex()[:12]} is not connected")
        if adopt and not conn.adopt_object(key, size):
            raise KeyError(
                f"object {key} no longer resident on "
                f"{node_id.hex()[:12]} (evicted before adoption)")
        with self._lock:
            self._put_index += 1
            idx = self._put_index
        oid = ObjectID.for_put(TaskID.for_normal_task(self.job_id), idx)
        from ray_tpu._private.multinode import RemoteValueStub
        stub = RemoteValueStub(conn, key, size)
        with self._lock:
            self._remote_values[oid] = (node_id, key)
        self.store.put_remote(oid, stub.fetch, size)
        self.refs.add_owned(oid)
        return ObjectRef(oid)

    def get(self, refs: List[ObjectRef], timeout: Optional[float]) -> List[Any]:
        import time as _time
        deadline = None if timeout is None else _time.monotonic() + timeout
        # If a worker thread blocks here on objects that aren't ready yet,
        # release its task's resources so dependent/nested tasks can run
        # (otherwise a parent holding the only CPU deadlocks on its child).
        blocking = any(not self.store.contains(r.object_id()) for r in refs)
        spec = current_task_spec() if blocking else None
        released = False
        if spec is not None and spec.resources:
            pg_id, _ = self._pg_key(spec)
            node_id = getattr(spec, "_node_id", None)
            bidx = getattr(spec, "_acquired_bundle", -1)
            self.scheduler.release(spec.resources, node_id, pg_id, bidx)
            released = True
            self._dispatch()
        try:
            results = []
            for ref in refs:
                remaining = None
                if deadline is not None:
                    remaining = max(0.0, deadline - _time.monotonic())
                results.append(self.store.get(ref.object_id(), timeout=remaining))
            return results
        finally:
            if released:
                self.scheduler.force_acquire(
                    spec.resources, node_id, pg_id, bidx)

    def wait(self, refs: List[ObjectRef], num_returns: int,
             timeout: Optional[float], fetch_local: bool = True):
        import time as _time
        deadline = None if timeout is None else _time.monotonic() + timeout
        pending = list(refs)
        ready: List[ObjectRef] = []
        # Fast path scan, then block on the first pending ref repeatedly.
        while len(ready) < num_returns and pending:
            progressed = False
            for ref in list(pending):
                if self.store.contains(ref.object_id()):
                    ready.append(ref)
                    pending.remove(ref)
                    progressed = True
                    if len(ready) >= num_returns:
                        break
            if len(ready) >= num_returns or not pending:
                break
            if not progressed:
                remaining = 0.05
                if deadline is not None:
                    remaining = min(remaining,
                                    max(0.0, deadline - _time.monotonic()))
                    if remaining == 0.0:
                        break
                self.store.wait_ready(pending[0].object_id(), remaining)
                if deadline is not None and _time.monotonic() >= deadline:
                    # final scan before giving up
                    for ref in list(pending):
                        if self.store.contains(ref.object_id()):
                            ready.append(ref)
                            pending.remove(ref)
                            if len(ready) >= num_returns:
                                break
                    break
        return ready, pending

    # ------------------------------------------------------------------
    # Task submission
    # ------------------------------------------------------------------

    def register_function(self, fn: Callable) -> bytes:
        return self.functions.export(fn)

    def _chaos_delay(self, flag: str) -> None:
        """Fault-injection hook (reference: asio_chaos.cc +
        RAY_testing_asio_delay_us): sleep testing_*_delay_us microseconds
        when the flag is nonzero, to surface ordering races in tests.
        Values are snapshotted at init — submit/dispatch are hot paths, and
        a per-call native config probe there is not free."""
        us = self._chaos_us.get(flag, 0)
        if us:
            import time as _time
            _time.sleep(us / 1e6)

    def submit_task(self, spec: TaskSpec) -> List[ObjectRef]:
        """Submit a normal task. Returns refs for its return objects."""
        self._chaos_delay("testing_submit_delay_us")
        from ray_tpu.util import tracing
        if tracing.is_tracing_enabled():
            # Propagate the caller's span context inside the spec
            # (reference: tracing_helper.py _DictPropagator). With no
            # active caller span this is the HEAD of a trace:
            # inject_context makes the sampling decision once, and an
            # unsampled submit carries no context at all.
            ctx = tracing.inject_context()
            if ctx is not None:
                import time as _time
                with tracing.continue_context(
                        ctx, "driver::submit",
                        {"stage": "submit", "task": spec.name}) as span:
                    spec.trace_ctx = tracing.span_context(span)
                    spec._trace_submit_mono = _time.monotonic()  # type: ignore[attr-defined]
                    spec._trace_submit_wall = span.start_time  # type: ignore[attr-defined]
                    return self._submit_task_inner(spec)
        return self._submit_task_inner(spec)

    def _submit_task_inner(self, spec: TaskSpec) -> List[ObjectRef]:
        n = 1 if spec.num_returns == "dynamic" else spec.num_returns
        spec.return_ids = [
            ObjectID.for_return(spec.task_id, i + 1) for i in range(max(n, 1))]
        refs = [ObjectRef(oid) for oid in spec.return_ids]
        if spec.num_returns == 0:
            refs = []
        with self._lock:
            if len(self._lineage) < self._cfg_lineage_max:
                for oid in spec.return_ids:
                    self._lineage[oid] = spec
        self._register_task_refs(spec)
        self._record_event(spec, "SUBMITTED")
        self._resolve_dependencies(spec)
        return refs

    def _find_dependencies(self, spec: TaskSpec) -> List[ObjectID]:
        deps = []
        for a in spec.args:
            if isinstance(a, ObjectRef):
                deps.append(a.object_id())
        for v in spec.kwargs.values():
            if isinstance(v, ObjectRef):
                deps.append(v.object_id())
        return deps

    def _resolve_dependencies(self, spec: TaskSpec) -> None:
        # _register_task_refs already walked the args; reuse its list.
        deps = getattr(spec, "_dep_oids", None)
        if deps is None:
            deps = self._find_dependencies(spec)
        spec.dependencies = deps
        unresolved = [d for d in deps if not self.store.contains(d)]
        if not unresolved:
            self._on_dependencies_ready(spec)
            return
        pending = _PendingTask(spec, 0)
        to_watch = []
        with self._lock:
            # Count + registration both under the lock: a concurrent seal's
            # waiter can only decrement entries registered here, so the
            # zero-check below cannot race with a waiter's decrement.
            for d in unresolved:
                if self.store.contains(d):
                    continue
                pending.unresolved += 1
                self._pending_by_oid.setdefault(d, []).append(pending)
                to_watch.append(d)
            ready_now = pending.unresolved == 0
        if ready_now:
            self._on_dependencies_ready(spec)
            return
        # Watch each unresolved dep from a waiter thread; cheap enough at
        # round-1 scale, replaced by store callbacks with the native store.
        for d in to_watch:
            self._spawn_dep_waiter(d)

    def _spawn_dep_waiter(self, oid: ObjectID) -> None:
        with self._lock:
            if oid in self._dep_waiters:
                return
            t = threading.Thread(
                target=self._dep_wait_loop, args=(oid,), daemon=True)
            self._dep_waiters[oid] = t
        t.start()

    def _dep_wait_loop(self, oid: ObjectID) -> None:
        self.store.wait_ready(oid, None)
        ready = []
        with self._lock:
            self._dep_waiters.pop(oid, None)
            waiters = self._pending_by_oid.pop(oid, [])
            for pending in waiters:
                pending.unresolved -= 1
                if pending.unresolved == 0 and not pending.cancelled:
                    ready.append(pending.spec)
        for spec in ready:
            try:
                self._on_dependencies_ready(spec)
            except BaseException as e:  # noqa: BLE001 - keep waiter alive
                self._store_error(spec, e)

    def _on_dependencies_ready(self, spec: TaskSpec) -> None:
        # Propagate dependency failures without running the task
        # (reference behavior: dependent tasks fail with the same error).
        for d in spec.dependencies:
            exc = self.store.get_if_exception(d)
            if exc is not None:
                self._store_error(spec, exc)
                if spec.kind == TaskKind.ACTOR_TASK:
                    # The handle's sequence must still advance, or every
                    # later call on this handle would wait forever.
                    self._abort_actor_task_seq(spec)
                return
        if spec.kind == TaskKind.ACTOR_TASK:
            self._dispatch_actor_task(spec)
        else:
            self._dispatch_single(spec)

    # ------------------------------------------------------------------
    # Dispatch
    # ------------------------------------------------------------------

    def _pg_key(self, spec: TaskSpec):
        strategy = spec.scheduling_strategy
        pg_id = None
        bundle = -1
        if strategy is not None and hasattr(strategy, "placement_group") and \
                strategy.placement_group is not None:
            pg_id = strategy.placement_group.id
            bundle = strategy.placement_group_bundle_index
            if bundle is None:
                bundle = -1
        return pg_id, bundle

    # ------------------------------------------------------------------
    # Worker leases (reference: direct_task_transport.cc + lease_policy)
    # ------------------------------------------------------------------

    def _lease_class(self, spec: TaskSpec):
        """Scheduling class for worker leasing (reference:
        scheduling_class_util): tasks sharing one are placement-
        interchangeable and may pipeline onto one lease. None means the
        task is not leasable (actors, affinity/spread strategies — those
        carry per-task placement intent)."""
        key = getattr(spec, "_lease_key", False)
        if key is not False:
            return key
        key = None
        if self._lease_enabled and spec.kind == TaskKind.NORMAL:
            strategy = spec.scheduling_strategy
            pg_id, bundle = self._pg_key(spec)
            if strategy is None or strategy == "DEFAULT" or pg_id is not None:
                try:
                    renv = repr(sorted((spec.runtime_env or {}).items()))
                    res = tuple(sorted((spec.resources or {}).items()))
                    key = (spec.function_id, res, renv, pg_id, bundle)
                except TypeError:
                    key = None
        spec._lease_key = key  # type: ignore[attr-defined]
        return key

    def _lease_attachable(self, lease: _WorkerLease) -> bool:
        return (not lease.dropped and not lease.blocked
                and lease.inflight < self._lease_window
                and lease.node_id in self._remote_nodes)

    def _lease_avail_update(self, lease: _WorkerLease) -> None:
        """Re-index one lease's attachability (caller holds _lock)."""
        bucket = self._lease_avail.get(lease.class_key)
        if self._lease_attachable(lease):
            if bucket is None:
                bucket = self._lease_avail[lease.class_key] = {}
            bucket[lease.lease_id] = lease
        elif bucket is not None:
            bucket.pop(lease.lease_id, None)
            if not bucket:
                del self._lease_avail[lease.class_key]

    def _find_lease(self, class_key) -> Optional[_WorkerLease]:
        """An attachable live lease for this class (caller holds _lock).
        O(1) amortized via the availability index: peek the head entry,
        pop it if stale (safe — every indexed mutation re-adds through
        _lease_avail_update). No bucket copy: materializing thousands
        of entries per attach would re-create the linear scan this
        index removed."""
        bucket = self._lease_avail.get(class_key)
        while bucket:
            lease_id, lease = next(iter(bucket.items()))
            if self._lease_attachable(lease):
                return lease
            bucket.pop(lease_id, None)
        if bucket is not None and not bucket:
            del self._lease_avail[class_key]
        return None

    def _lease_task_done(self, spec: TaskSpec, lease: _WorkerLease) -> None:
        """Completion bookkeeping for a leased task. A lease that drains
        either TAKES the next queued same-class task right here (so a
        kept-alive lease always has a completion coming to re-evaluate
        it — a passively "kept" idle lease would leak its resources if
        the queued work later launched elsewhere or was cancelled) or
        drops and releases. Contention from OTHER classes forces the
        drop, so starved classes get the scheduler's arbitration."""
        drop = False
        next_spec = None
        with self._lock:
            lease.inflight -= 1
            self._lease_avail_update(lease)
            if lease.dropped:
                return  # node death already tore it down
            if lease.inflight <= 0:
                starved_other = any(k != lease.class_key
                                    for k in self._lease_contended)
                dq = self._ready_by_class.get(lease.class_key)
                if dq and not starved_other and not lease.blocked and \
                        lease.node_id in self._remote_nodes:
                    next_spec = dq.popleft()
                    if not dq:
                        del self._ready_by_class[lease.class_key]
                    self._inflight[next_spec.task_id] = next_spec
                    next_spec._node_id = lease.node_id
                    next_spec._acquired_bundle = lease.bidx
                    next_spec._lease = lease  # type: ignore[attr-defined]
                    next_spec._tpu_ids = lease.tpu_ids
                    lease.inflight += 1
                    self._lease_avail_update(lease)
                    next_spec.invalidated = False
                    next_spec._finalized = False
                    self.lease_stats["attached"] += 1
                else:
                    lease.dropped = True
                    self._lease_avail_update(lease)
                    lst = self._leases.get(lease.class_key)
                    if lst is not None:
                        try:
                            lst.remove(lease)
                        except ValueError:
                            pass
                        if not lst:
                            del self._leases[lease.class_key]
                    drop = True
        if next_spec is not None:
            self._launch(next_spec, None)
            return
        if drop:
            self.scheduler.release(lease.resources, lease.node_id,
                                   lease.pg_id, lease.bidx)
            if lease.tpu_ids:
                self.scheduler.return_tpu_ids(lease.node_id, lease.tpu_ids)
            self.lease_stats["released"] += 1
            conn = self._remote_nodes.get(lease.node_id)
            if conn is not None:
                conn.drop_lease(lease.lease_id)
            # Freed capacity + empty head-side queues: pull misplaced
            # work back from overloaded daemon queues.
            self._maybe_spillback()

    def _class_wire_id(self, class_key) -> str:
        """Compact stable name for a scheduling class, shipped on the
        wire so daemons key their local dispatch queues by it."""
        with self._lock:
            cid = self._class_wire_ids.get(class_key)
            if cid is None:
                cid = f"k{len(self._class_wire_ids)}"
                self._class_wire_ids[class_key] = cid
            return cid

    def _maybe_spillback(self) -> None:
        """Misplaced-work correction (reference: cluster_task_manager.cc
        ScheduleAndDispatchTasks spillback): capacity just freed
        somewhere, the head has nothing queued for a class, yet tasks
        already pipelined to one node sit in its LOCAL queue behind busy
        slots. Reclaim the tail; the re-dispatch takes the idle
        capacity. Only non-PG, non-TPU (shared-queue) classes — serial
        leases keep strict ownership of their pipelined tasks."""
        target = None
        with self._lock:
            for ck, leases in self._leases.items():
                if self._ready_by_class.get(ck):
                    continue  # new capacity will be fed head-side
                for lease in leases:
                    if (lease.pg_id is not None or lease.tpu_ids
                            or lease.blocked or lease.dropped):
                        continue
                    extra = lease.inflight - 1
                    if extra >= 2 and (target is None
                                       or extra > target[2]):
                        target = (ck, lease, extra)
        if target is None:
            return
        ck, lease, extra = target
        # Probe: does idle capacity for this class actually exist? (The
        # probe acquisition is returned immediately — the reclaimed
        # tasks re-acquire through the normal dispatch path.)
        acq = self.scheduler.try_acquire(lease.resources, None, -1)
        if acq is None:
            return
        self.scheduler.release(lease.resources, acq[0], None, acq[1])
        conn = self._remote_nodes.get(lease.node_id)
        if conn is not None:
            conn.reclaim_tasks(self._class_wire_id(ck),
                               max_n=min(extra, 16))

    def _drop_node_leases(self, node_id: NodeID) -> None:
        """Node death: its leases vanish with it — the scheduler already
        dropped the node's resources wholesale, so no release here."""
        with self._lock:
            for key in list(self._leases):
                lst = self._leases[key]
                for lease in lst[:]:
                    if lease.node_id == node_id:
                        lease.dropped = True
                        self._lease_avail_update(lease)
                        lst.remove(lease)
                if not lst:
                    del self._leases[key]

    def _try_launch_locked(self, spec: TaskSpec, blocked: list):
        """Attempt to launch ONE ready spec (caller holds _lock; the spec
        is NOT in self._ready from this method's point of view — callers
        pop/skip-queue on non-None). Returns:

        * ``(spec, worker)`` — launched; caller runs the launch tail
          outside the lock (worker None = async remote send).
        * ``"error"`` — failed fast (error stored); drop it.
        * ``None`` — not launchable now; leave/put it in the queue.

        Capacity-blocked class keys append to ``blocked`` (lease-fairness
        signal)."""
        class_key = self._lease_class(spec)
        pg_id, bundle = self._pg_key(spec)
        if not self.scheduler.is_feasible(
                spec.resources, pg_id, bundle,
                spec.scheduling_strategy):
            # Hard node-affinity to a dead/unknown node can never
            # succeed: fail fast (reference behavior). Anything
            # else stays queued as autoscaler demand — the
            # reference warns and waits for the cluster to grow.
            from ray_tpu.util.scheduling_strategies import (
                NodeAffinitySchedulingStrategy)
            strategy = spec.scheduling_strategy
            if pg_id is not None:
                # PG-targeted infeasibility can never be fixed by
                # cluster growth: either the PG was removed, or
                # the bundle's fixed capacity is exceeded.
                if self.scheduler.placement_group_exists(pg_id):
                    msg = (f"Task {spec.name} requires "
                           f"{spec.resources} which exceeds the "
                           "capacity of its placement group "
                           "bundle.")
                else:
                    msg = (f"Task {spec.name} was scheduled into "
                           "a placement group that does not "
                           "exist (removed or never created).")
                self._store_error(spec, ValueError(msg))
                return "error"
            if isinstance(strategy,
                          NodeAffinitySchedulingStrategy) and \
                    not strategy.soft:
                self._store_error(spec, ValueError(
                    f"Task {spec.name} has hard node affinity to "
                    f"node {strategy.node_id}, which is not alive "
                    "or lacks the required resources."))
                return "error"
            if spec.task_id not in self._infeasible_warned:
                self._infeasible_warned.add(spec.task_id)
                logger.warning(
                    "Task %s requires %s which no alive node "
                    "satisfies (cluster total: %s). It will stay "
                    "pending until the cluster grows (autoscaler "
                    "demand).", spec.name, spec.resources,
                    self.scheduler.total)
            return None
        # Locality-aware placement: with no explicit strategy, prefer
        # (softly) the node already holding the largest share of this
        # task's argument bytes — the args become local table reads
        # instead of cross-node pulls. An overloaded preferred node
        # spills the task back to the hybrid order.
        launch_strategy = spec.scheduling_strategy
        locality_node = None
        if pg_id is None and launch_strategy is None:
            locality_node = self._locality_preference(spec)
            if locality_node is not None:
                state = self.scheduler.node(locality_node)
                if state is None or not state.alive:
                    self._count_locality("remote")
                    locality_node = None
                elif state.utilization() >= self._cfg_locality_spillback:
                    self._count_locality("spillback")
                    locality_node = None
                else:
                    from ray_tpu.util.scheduling_strategies import (
                        NodeAffinitySchedulingStrategy)
                    launch_strategy = NodeAffinitySchedulingStrategy(
                        node_id=locality_node.hex(), soft=True)
        acquired = self.scheduler.try_acquire(
            spec.resources, pg_id, bundle,
            strategy=launch_strategy)
        if locality_node is not None and acquired is not None:
            self._count_locality(
                "local" if acquired[0] == locality_node
                else "spillback")
        if acquired is None:
            # No idle capacity: fall back to pipelining onto a live lease
            # of this class (reference: pipelining SUPPLEMENTS additional
            # lease requests, it never replaces them — idle CPUs always
            # win over queueing behind a busy worker).
            if class_key is not None:
                lease = self._find_lease(class_key)
                if lease is not None:
                    if locality_node is not None:
                        self._count_locality(
                            "local" if lease.node_id == locality_node
                            else "spillback")
                    self._inflight[spec.task_id] = spec
                    spec._node_id = lease.node_id
                    spec._acquired_bundle = lease.bidx
                    spec._lease = lease  # type: ignore[attr-defined]
                    spec._tpu_ids = lease.tpu_ids
                    lease.inflight += 1
                    self._lease_avail_update(lease)
                    spec.invalidated = False
                    spec._finalized = False
                    self.lease_stats["attached"] += 1
                    return (spec, None)
            blocked.append(class_key)
            return None
        node_id, bidx = acquired
        # Normal tasks on a remote daemon take the ASYNC path:
        # no head worker thread is parked for them (reference:
        # callback-driven direct task transport) — head thread
        # count stays flat as the cluster widens.
        conn = self._remote_nodes.get(node_id)
        if conn is not None and spec.kind == TaskKind.NORMAL:
            worker = None
        else:
            worker = self._pop_worker()
            if worker is None:
                self.scheduler.release(spec.resources, node_id,
                                       pg_id, bidx)
                return None
        self._inflight[spec.task_id] = spec
        spec._node_id = node_id  # type: ignore[attr-defined]
        spec._acquired_bundle = bidx  # type: ignore[attr-defined]
        spec.invalidated = False
        # App-level retries redispatch the same spec: re-arm the
        # exactly-once finalize claim for the new attempt.
        spec._finalized = False  # type: ignore[attr-defined]
        n_tpus = int(spec.resources.get("TPU", 0))
        if n_tpus >= 1:
            spec._tpu_ids = (  # type: ignore[attr-defined]
                self.scheduler.take_tpu_ids(node_id, n_tpus))
        spec._lease = None  # type: ignore[attr-defined]
        if worker is None and class_key is not None:
            # First task of its class on this node: open a
            # lease — followers pipeline onto it above.
            self._lease_counter += 1
            lease = _WorkerLease(
                f"ls-{self._lease_counter}", class_key,
                node_id, dict(spec.resources or {}), pg_id,
                bidx, getattr(spec, "_tpu_ids", None))
            self._leases.setdefault(class_key,
                                    []).append(lease)
            self._lease_avail_update(lease)
            spec._lease = lease  # type: ignore[attr-defined]
            self.lease_stats["created"] += 1
        return (spec, worker)

    def _locality_preference(self, spec: TaskSpec) -> Optional[NodeID]:
        """The node holding the largest share of the task's ObjectRef
        argument bytes (primary holders + broadcast/pull replicas), or
        None when no argument lives on a daemon. Caller holds _lock."""
        per_node: Dict[NodeID, int] = {}
        for a in list(spec.args) + list(spec.kwargs.values()):
            if not isinstance(a, ObjectRef):
                continue
            oid = a.object_id()
            rv = self._remote_values.get(oid)
            if rv is None:
                continue
            size = self.store.size_of(oid)
            if size <= 0:
                continue
            per_node[rv[0]] = per_node.get(rv[0], 0) + size
            for nid in (self._object_replicas.get(oid) or ()):
                if nid != rv[0]:
                    per_node[nid] = per_node.get(nid, 0) + size
        if not per_node:
            return None
        return max(per_node.items(), key=lambda kv: kv[1])[0]

    @staticmethod
    def _count_locality(outcome: str) -> None:
        try:
            builtin_metrics.lease_locality().inc(
                tags={"outcome": outcome})
        except Exception:  # noqa: BLE001 - accounting only
            pass

    def _launch(self, spec: TaskSpec, worker) -> None:
        """Launch tail (outside the lock) for a _try_launch_locked hit."""
        import time as _time
        spec._start_time = _time.monotonic()  # type: ignore[attr-defined]
        ctx = getattr(spec, "trace_ctx", None)
        if ctx is not None:
            self._record_trace_sched_spans(spec, ctx)
        self._record_event(spec, "RUNNING")
        if worker is None:
            self._submit_remote_async(spec)
        elif spec.kind == TaskKind.ACTOR_CREATION:
            worker.submit(lambda s=spec, w=worker: self._run_actor_creation(s, w))
        else:
            worker.submit(lambda s=spec, w=worker: self._run_normal_task(s, w))

    def _record_trace_sched_spans(self, spec: TaskSpec, ctx: dict) -> None:
        """Retroactive scheduler spans for a traced task at launch:
        ``sched::queue_wait`` covering submit -> launch (monotonic
        duration anchored at the submit span's wall time) and a
        zero-length ``sched::lease_grant`` marker carrying the lease
        identity (the grant itself is an instant in this scheduler — the
        waiting shows up in queue_wait)."""
        mono0 = getattr(spec, "_trace_submit_mono", None)
        if mono0 is None:
            return
        from ray_tpu.util import tracing
        wait = spec._start_time - mono0
        wall0 = getattr(spec, "_trace_submit_wall", 0.0)
        tracing.record_complete_span(
            "sched::queue_wait", ctx, wall_start=wall0, duration=wait,
            attributes={"stage": "queue", "task": spec.name})
        lease = getattr(spec, "_lease", None)
        if lease is not None:
            tracing.record_complete_span(
                "sched::lease_grant", ctx, wall_start=wall0 + wait,
                duration=0.0,
                attributes={"stage": "lease", "task": spec.name,
                            "lease_id": lease.lease_id})

    def _queue_ready_locked(self, spec: TaskSpec) -> None:
        ck = self._lease_class(spec)
        if ck is None:
            self._ready.append(spec)
        else:
            dq = self._ready_by_class.get(ck)
            if dq is None:
                dq = self._ready_by_class[ck] = self._deque()
            dq.append(spec)

    def _ready_specs_locked(self):
        """All queued-ready specs, class buckets first (caller holds
        _lock; iteration order is the dispatch probe order)."""
        for dq in self._ready_by_class.values():
            yield from dq
        yield from self._ready

    def _dispatch_single(self, spec: TaskSpec) -> None:
        """O(1) dispatch for one just-ready task — the submit hot path:
        try a lease attach or a direct acquisition for THIS spec only and
        queue it otherwise. Full _dispatch() scans remain the capacity-
        freed path (completions, node joins)."""
        self._chaos_delay("testing_dispatch_delay_us")
        with self._lock:
            if self._shutdown:
                return
            ck = self._lease_class(spec)
            if ck is not None and self._ready_by_class.get(ck):
                # FIFO within a class: earlier same-class submits go first.
                self._ready_by_class[ck].append(spec)
                return
            res = self._try_launch_locked(spec, [])
            if res is None:
                self._queue_ready_locked(spec)
                return
            if res == "error":
                return
        self._launch(*res)

    def _dispatch(self) -> None:
        self._chaos_delay("testing_dispatch_delay_us")
        while True:
            launched = None
            with self._lock:
                if self._shutdown:
                    return
                blocked: list = []
                # Class buckets: probe ONE representative per class —
                # same-class tasks are interchangeable, so its verdict
                # (launch / error / blocked) covers the whole bucket.
                for ck, dq in self._ready_by_class.items():
                    if not dq:
                        continue
                    res = self._try_launch_locked(dq[0], blocked)
                    if res is None:
                        continue
                    dq.popleft()
                    if not dq:
                        del self._ready_by_class[ck]
                    launched = True if res == "error" else res
                    break
                if launched is None:
                    # Unleasable tasks: FIFO scan (original semantics).
                    for i, spec in enumerate(self._ready):
                        res = self._try_launch_locked(spec, blocked)
                        if res is None:
                            continue
                        self._ready.pop(i)
                        launched = True if res == "error" else res
                        break
                if launched is None:
                    # Full scan completed: remember which classes were
                    # capacity-blocked (lease fairness: a draining lease
                    # releases early iff a DIFFERENT class is starved).
                    self._lease_contended = set(blocked)
            if launched is None or launched is True:
                if launched is None:
                    return
                continue
            self._launch(*launched)

    def _pop_worker(self) -> Optional[Executor]:
        if self._idle_workers:
            return self._idle_workers.pop()
        if len(self._all_workers) >= self._max_workers:
            return None
        wid = WorkerID.from_random()
        worker = SerialThreadExecutor(wid, name=f"ray_tpu-worker-{wid.hex()[:8]}")
        self._all_workers.append(worker)
        return worker

    def _return_worker(self, worker: Optional[Executor]) -> None:
        if worker is None:
            return  # async remote task: no head thread was consumed
        with self._lock:
            if not worker.dead and worker.actor_id is None:
                self._idle_workers.append(worker)

    # ------------------------------------------------------------------
    # Execution (thread backend: runs in executor threads)
    # ------------------------------------------------------------------

    def _resolve_args(self, spec: TaskSpec, conn=None,
                      to_process: bool = False):
        """Materialize ObjectRef args. With a target daemon connection,
        arguments whose payload lives in a node object table travel as
        tiny markers: payload on THAT daemon → local read; payload on a
        PEER daemon → the executing daemon pulls it directly from the
        peer's object server (zero bytes through the head — reference:
        object_manager.h node-to-node chunked pulls). For a local worker
        PROCESS target, arena-resident arrays travel as ArenaArrayRef
        markers the worker resolves to zero-copy shm views (plasma's
        cross-process mission: no copy between store and worker)."""
        from ray_tpu._private.dataplane import ObjectMarker

        def resolve(a):
            if not isinstance(a, ObjectRef):
                return a
            oid = a.object_id()
            if conn is not None:
                with self._lock:
                    rv = self._remote_values.get(oid)
                    owner_conn = (self._remote_nodes.get(rv[0])
                                  if rv is not None else None)
                    alt_addrs = ()
                    spill_uri = None
                    if rv is not None:
                        # Every OTHER live holder rides the marker as a
                        # failover candidate, and a durable spill URI as
                        # the last data-plane resort — a mid-pull holder
                        # death resumes instead of erroring into
                        # reconstruction.
                        reps = self._object_replicas.get(oid)
                        if reps:
                            alt_addrs = tuple(
                                c.object_addr
                                for nid in reps
                                if nid != rv[0] and nid != conn.node_id
                                and (c := self._remote_nodes.get(nid))
                                is not None and c.object_addr is not None)
                        rec = self._spill_uris_by_key.get(rv[1])
                        if rec is not None:
                            spill_uri = rec[0]
                # Broadcasted head-resident objects stay materialized at
                # the head AND ship as markers: the consumer daemon's
                # local table (tree push already landed a replica) or a
                # nearby holder serves the bytes, never the head again.
                if rv is not None and \
                        (oid in self._broadcasted or
                         not self.store.is_materialized(oid)):
                    if rv[0] == conn.node_id:
                        return ObjectMarker(rv[1])
                    if owner_conn is not None and \
                            owner_conn.object_addr is not None:
                        # The executing daemon will pull a copy: note the
                        # (oid, key) so task completion can register it
                        # as an in-memory replica holder.
                        self._note_pull_demand(oid, conn.node_id)
                        pulls = getattr(spec, "_marker_pulls", None)
                        if pulls is None:
                            pulls = spec._marker_pulls = []
                        pulls.append((oid, rv[1]))
                        return ObjectMarker(rv[1],
                                            owner_addr=owner_conn.object_addr,
                                            alt_addrs=alt_addrs,
                                            spill_uri=spill_uri)
            if conn is not None and \
                    self.store.size_of(oid) >= self._cfg_inline_limit:
                # Head-resident payload about to ship inline to a
                # daemon: head egress. Enough distinct consumer nodes
                # flips the object to a broadcast tree.
                self._note_pull_demand(oid, conn.node_id)
            if to_process and self.store.native_array_key(oid) is not None:
                from ray_tpu._private.worker_process import ArenaArrayRef
                # The task's dependency pin keeps the entry alive until
                # the task finishes, so the worker's read cannot race a
                # free.
                return ArenaArrayRef(oid.hex())
            return self.store.get(oid)

        args = [resolve(a) for a in spec.args]
        kwargs = {k: resolve(v) for k, v in spec.kwargs.items()}
        return args, kwargs

    #: distinct consumer nodes before an object auto-upgrades from
    #: point-to-point pulls to one spanning-tree broadcast.
    _AUTO_BROADCAST_MIN_CONSUMERS = 4

    def _note_pull_demand(self, oid: ObjectID, node_id: NodeID) -> None:
        """Auto-broadcast trigger: the same object heading to its Nth
        distinct node is a fan-out workload — O(N) transfers out of one
        source become one bounded-fanout tree (O(log N) depth, source
        egress capped at fanout x size)."""
        with self._lock:
            nodes = self._pull_demand.setdefault(oid, {})
            nodes[node_id] = None
            if len(nodes) < self._AUTO_BROADCAST_MIN_CONSUMERS or \
                    oid in self._broadcasted or \
                    oid in self._broadcast_inflight:
                return
            self._broadcast_inflight[oid] = None
        threading.Thread(target=self._broadcast_bg, args=(oid,),
                         daemon=True, name="auto-broadcast").start()

    def _broadcast_bg(self, oid: ObjectID) -> None:
        try:
            self._broadcast_object(oid)
        except Exception:  # noqa: BLE001 - broadcast is an optimization
            logger.exception("auto-broadcast of %s failed; consumers "
                             "fall back to point-to-point pulls",
                             oid.hex()[:12])
        finally:
            with self._lock:
                self._broadcast_inflight.pop(oid, None)

    def _store_results(self, spec: TaskSpec, result: Any) -> None:
        ctx = getattr(spec, "trace_ctx", None)
        if ctx is None:
            return self._store_results_inner(spec, result)
        import time as _time
        from ray_tpu.util import tracing
        wall = _time.time()
        mono0 = _time.monotonic()
        try:
            return self._store_results_inner(spec, result)
        finally:
            tracing.record_complete_span(
                "task::store_result", ctx, wall_start=wall,
                duration=_time.monotonic() - mono0,
                attributes={"stage": "store", "task": spec.name})

    def _store_results_inner(self, spec: TaskSpec, result: Any) -> None:
        if getattr(spec, "invalidated", False):
            # The task's node died while it ran; a retry owns the return
            # objects now (reference: a worker on a dead node can't deliver).
            return
        self._release_task_deps(spec)
        node_id = getattr(spec, "_node_id", None)
        if node_id is not None:
            with self._lock:
                # Same bound as _lineage: past it, objects are simply not
                # reconstructable (the maps must not grow without limit in
                # long-running drivers).
                # Remote-daemon results return inline and live in the
                # HEAD's store — recording the daemon as their location
                # would make its death discard values we still hold.
                if node_id not in self._remote_nodes and \
                        len(self._object_locations) < \
                        self._cfg_obj_loc_max:
                    for oid in spec.return_ids:
                        self._object_locations[oid] = node_id
                # Marker args the daemon pulled are now in-memory
                # REPLICAS there (the data plane caches pulls): register
                # the extra holder so node death can re-point the fetch
                # instead of re-executing (bounded like the location
                # table; replicas are an optimization, never required).
                pulls = getattr(spec, "_marker_pulls", None)
                if pulls and node_id in self._remote_nodes:
                    for oid, _key in pulls:
                        if oid in self._remote_values and \
                                self._remote_values[oid][0] != node_id \
                                and len(self._object_replicas) < \
                                self._cfg_obj_loc_max:
                            self._object_replicas.setdefault(
                                oid, {})[node_id] = None
                            # Throttled durable mirror (head failover
                            # accounting; holders are advisory after a
                            # head restart since node ids re-mint).
                            if self.gcs_store is not None:
                                try:
                                    self.gcs_store.record_object_replica(
                                        oid.hex(), node_id.hex())
                                except OSError:
                                    pass
        n = spec.num_returns
        if n == 0:
            return
        if n == "dynamic":
            # Dynamic generator returns (reference: _raylet.pyx:624): each
            # yielded value becomes its own object; the declared return object
            # holds the list of refs.
            if not self.refs.has(spec.return_ids[0]):
                return  # every handle dropped while the task ran
            item_refs = []
            for i, item in enumerate(result):
                oid = ObjectID.for_return(spec.task_id, i + 2)
                self.store.put_inline(oid, item)
                self.refs.add_owned(oid)
                item_refs.append(ObjectRef(oid))
            self._store_if_referenced(spec.return_ids[0], item_refs)
            return
        if n == 1:
            from ray_tpu._private.multinode import RemoteValueStub
            if isinstance(result, RemoteValueStub):
                self._store_remote_result(spec, spec.return_ids[0], result)
            else:
                self._store_if_referenced(spec.return_ids[0], result)
            return
        if not isinstance(result, (tuple, list)) or len(result) != n:
            from ray_tpu._private.multinode import (MismatchedReturn,
                                                    RemoteValueStub,
                                                    describe_value)
            if isinstance(result, MismatchedReturn):
                # Daemon detected the shape mismatch and described the
                # real value instead of storing it (nothing to free).
                desc = result.desc
            elif isinstance(result, RemoteValueStub):
                # Defensive: an oversized mismatched single-return stub.
                # Describe by size (never ship the payload to the head
                # just for an error string) and free the daemon copy —
                # it must not sit in the node's table until session end.
                desc = (f"a single daemon-resident value "
                        f"({result.size} bytes)")
                try:
                    result.conn.free_object(result.key)
                except Exception:  # noqa: BLE001 - best effort
                    pass
            else:
                desc = describe_value(result)
            self._store_error(spec, ValueError(
                f"Task {spec.name} declared num_returns={n} but returned "
                f"{desc}"))
            return
        from ray_tpu._private.multinode import RemoteValueStub
        for oid, value in zip(spec.return_ids, result):
            if isinstance(value, RemoteValueStub):
                # Multi-return daemon task: big elements stay daemon-
                # resident individually (shuffle partials ride the data
                # plane, never the head).
                self._store_remote_result(spec, oid, value)
            else:
                self._store_if_referenced(oid, value)

    def _store_remote_result(self, spec: TaskSpec, oid: ObjectID,
                             stub) -> None:
        """Seal a daemon-resident result as a lazily-fetched store entry
        (mirrors _store_if_referenced's dropped-handle handling: if nobody
        can ever read it, free the daemon-side payload instead)."""
        def drop():
            try:
                stub.conn.free_object(stub.key)
            except Exception:  # noqa: BLE001 - best effort
                pass

        if not self.refs.has(oid):
            drop()
            return
        with self._lock:
            # Atomic with remove_node's dooming (same lock): either the
            # node death already invalidated this spec (the retry owns the
            # object — never seal a fetch against a dead connection), or
            # the seal lands first and node-death recovery reconstructs
            # the daemon-resident value.
            if getattr(spec, "invalidated", False):
                return
            self._remote_values[oid] = (stub.conn.node_id, stub.key)
            self._remote_keys[stub.key] = oid
            self.store.put_remote(oid, stub.fetch, stub.size)
        if not self.refs.has(oid):
            with self._lock:
                self._remote_values.pop(oid, None)
                self._remote_keys.pop(stub.key, None)
            self.store.free([oid])
            drop()

    def _store_if_referenced(self, oid: ObjectID, value: Any,
                             is_exception: bool = False) -> None:
        """Store a task result unless every handle was already dropped.

        The recheck AFTER the store closes the race with a handle dying
        between the check and the seal: either the death happened before the
        recheck (we free inline) or after it (the counter still tracked the
        object, so remove_local returns it and the GC thread frees it)."""
        if not self.refs.has(oid):
            return
        self.store.put_inline(oid, value, is_exception=is_exception)
        if not self.refs.has(oid):
            self.store.free([oid])

    def _store_error(self, spec: TaskSpec, exc: BaseException) -> None:
        self._release_task_deps(spec)
        if not isinstance(exc, (TaskError, ActorDiedError, TaskCancelledError,
                                GetTimeoutError, NodeDiedError,
                                ObjectLostError)):
            exc = TaskError.from_exception(exc, spec.name)
        for oid in spec.return_ids:
            self._store_if_referenced(oid, exc, is_exception=True)
        self._record_event(spec, "FAILED")

    def _should_retry(self, spec: TaskSpec, exc: BaseException) -> bool:
        if spec.attempt_number >= spec.max_retries:
            return False
        retry_on = spec.retry_exceptions
        if isinstance(exc, TaskError):
            # Application error: retry only if retry_exceptions allows.
            if retry_on is True:
                return True
            if isinstance(retry_on, (list, tuple)):
                return isinstance(exc.cause, tuple(retry_on))
            return False
        # System error (worker died): always retriable within budget.
        return True

    def _run_normal_task(self, spec: TaskSpec, worker: Executor) -> None:
        try:
            fn = self.functions.load(spec.function_id)
            args, kwargs = self._resolve_args(
                spec, self._remote_conn(spec),
                to_process=self._use_process_worker(spec))
            _task_context.spec = spec
            try:
                from ray_tpu.util import tracing
                with tracing.continue_context(
                        getattr(spec, "trace_ctx", None),
                        f"task::{spec.name}", {"stage": "execute"}):
                    # Remote tasks apply runtime_env daemon-side (the
                    # request carries it) and process-worker tasks apply
                    # it worker-side (where a pip venv is active); only
                    # thread-local runs apply it here.
                    if spec.runtime_env and self._remote_conn(spec) is None \
                            and not self._use_process_worker(spec):
                        from ray_tpu._private import runtime_env as _renv
                        _renv.setup(spec.runtime_env)
                        with _renv.applied(spec.runtime_env):
                            result = self._invoke_user(spec, fn, args,
                                                       kwargs)
                    else:
                        result = self._invoke_user(spec, fn, args, kwargs)
            finally:
                _task_context.spec = None
            self._store_results(spec, result)
            self._record_event(spec, "FINISHED")
        except BaseException as e:  # noqa: BLE001
            if getattr(spec, "invalidated", False):
                self._return_worker(worker)
                self._dispatch()
                return
            if isinstance(e, TaskCancelledError):
                # Force-cancel killed the worker process: terminal, never
                # retried (reference: cancelled tasks are not retried).
                self._store_error(spec, e)
                self._finish_task(spec, worker)
                return
            err = e if isinstance(e, TaskError) else TaskError(
                e, traceback.format_exc(), spec.name)
            # A dropped node connection is a SYSTEM failure (node death),
            # not an application error — probe retry with the raw
            # exception so the always-retriable path applies even when the
            # death handler hasn't invalidated this spec yet. Likewise a
            # failed node-to-node object pull (the arg's owner died): the
            # retry waits on reconstruction, not the user's code. A died
            # worker PROCESS (crash/kill) is the reference's
            # WorkerCrashedError — system-retriable too.
            from ray_tpu._private.dataplane import ObjectPullError
            from ray_tpu._private.multinode import RemoteNodeDiedError
            from ray_tpu._private.worker_process import WorkerCrashedError
            probe = e if isinstance(e, (RemoteNodeDiedError,
                                        WorkerCrashedError)) else err
            if isinstance(err, TaskError) and \
                    isinstance(err.cause, (ObjectPullError,
                                           WorkerCrashedError)):
                probe = err.cause
            if self._should_retry(spec, probe):
                spec.attempt_number += 1
                self._finish_task(spec, worker, retried=True)
                logger.warning("Retrying task %s (attempt %d/%d)", spec.name,
                               spec.attempt_number, spec.max_retries)
                self._resolve_dependencies(spec)
                return
            self._store_error(spec, err)
        self._finish_task(spec, worker)

    def _submit_remote_async(self, spec: TaskSpec) -> None:
        """Ship a normal task to its remote daemon without parking a head
        thread: the send runs on the completion pool, the reply arrives as
        a callback (reference: direct_task_transport.cc — client-side
        submission is fully callback-driven)."""
        conn = self._remote_conn(spec)

        def send():
            if getattr(spec, "invalidated", False):
                self._dispatch()  # node died between dispatch and send
                return
            try:
                if conn is None:
                    from ray_tpu._private.multinode import \
                        RemoteNodeDiedError
                    raise RemoteNodeDiedError(
                        "task's node vanished before the send")
                args, kwargs = self._resolve_args(spec, conn)
                lease = getattr(spec, "_lease", None)
                conn.execute_task_async(
                    spec, self.functions, args, kwargs,
                    self._result_store_limit(spec),
                    lambda reply: self._complete_remote_task(spec, conn,
                                                             reply),
                    lease_id=lease.lease_id if lease is not None else None,
                    class_id=(self._class_wire_id(lease.class_key)
                              if lease is not None else None))
            except BaseException as e:  # noqa: BLE001
                self._remote_task_error(spec, e)

        # Inline send: the frame write is microseconds (args were already
        # resolved to values/markers when the task became ready), and a
        # pool hop per task costs more than it hides at 5k+ tasks/s. The
        # REPLY is still callback-driven — no head thread parks while the
        # daemon works.
        send()

    def _complete_remote_task(self, spec: TaskSpec, conn, reply: dict
                              ) -> None:
        """Continuation for an async remote task (runs on the completion
        pool): unpack, store, finish — mirroring _run_normal_task's
        terminal handling without a dedicated thread."""
        if reply.get("reclaimed"):
            # Spillback: the daemon handed this queued-not-started task
            # back (capacity freed elsewhere). Release its lease ride
            # and re-dispatch — same accounting as a retry, without
            # consuming a retry attempt.
            if getattr(spec, "invalidated", False):
                self._dispatch()
                return
            with self._lock:
                self.lease_stats["reclaimed"] += 1
            self._finish_task(spec, None, retried=True)
            self._resolve_dependencies(spec)
            return
        try:
            if reply.get("type") == "died":
                from ray_tpu._private.multinode import RemoteNodeDiedError
                raise RemoteNodeDiedError(
                    f"node {conn.address} died (or chaos fired) while the "
                    "task was in flight")
            result = conn._unpack(reply, spec.name)
            self._store_results(spec, result)
            self._record_event(spec, "FINISHED")
        except BaseException as e:  # noqa: BLE001
            self._remote_task_error(spec, e)
            return
        self._finish_task(spec, None)

    def _remote_task_error(self, spec: TaskSpec, e: BaseException) -> None:
        """Shared error/retry terminal for the async remote path. By the
        time a 'died' completion is delivered, the connection's close()
        has already run the node-death bookkeeping (on_death fires before
        callbacks), so spec.invalidated is authoritative here — no wait
        loop needed."""
        if getattr(spec, "invalidated", False):
            self._dispatch()
            return
        err = e if isinstance(e, TaskError) else TaskError(
            e, traceback.format_exc(), spec.name)
        from ray_tpu._private.dataplane import ObjectPullError
        from ray_tpu._private.multinode import RemoteNodeDiedError
        from ray_tpu._private.worker_process import WorkerCrashedError
        probe = e if isinstance(e, RemoteNodeDiedError) else err
        if isinstance(err, TaskError) and \
                isinstance(err.cause, (ObjectPullError, WorkerCrashedError)):
            probe = err.cause
        if self._should_retry(spec, probe):
            spec.attempt_number += 1
            self._finish_task(spec, None, retried=True)
            logger.warning("Retrying task %s (attempt %d/%d)", spec.name,
                           spec.attempt_number, spec.max_retries)
            self._resolve_dependencies(spec)
            return
        self._store_error(spec, err)
        self._finish_task(spec, None)

    def _running_normal_tasks(self) -> List[TaskSpec]:
        with self._lock:
            return [s for s in self._inflight.values()
                    if s.kind == TaskKind.NORMAL]

    def _oom_kill_task(self, spec: TaskSpec) -> None:
        """Memory-monitor victim: discard the task's (still running) work
        like a node-death zombie, release its resources, and retry within
        budget or seal OutOfMemoryError (reference: raylet worker killing
        + task OOM retry)."""
        from ray_tpu.exceptions import OutOfMemoryError
        with self._lock:
            if spec.task_id not in self._inflight:
                return
        if spec.return_ids and all(
                self.store.contains(oid) for oid in spec.return_ids):
            return  # effectively completed; nothing to reclaim by killing
        if not self._try_claim_finalize(spec):
            return  # the worker finalized first
        with self._lock:  # atomic vs. _store_remote_result's seal
            spec.invalidated = True
            handle = self._proc_tasks.get(spec.task_id)
            if handle is not None:
                # Process-backed victim: a REAL kill — the worker's RSS
                # goes back to the OS (reference: raylet worker killing
                # actually reclaims memory; threads can only discard).
                # Under the lock: the release path pops _proc_tasks under
                # this lock, so the kill can't hit a re-leased worker.
                handle.kill(wait=False)
        self._release_task_resources(spec)
        if spec.attempt_number < spec.max_retries:
            retry = spec.clone_for_retry()
            with self._lock:
                for oid in retry.return_ids:
                    if oid in self._lineage:
                        self._lineage[oid] = retry
            self._register_task_refs(retry)
            self._release_task_deps(spec)
            self._record_event(spec, "OOM_RETRY")
            self._resolve_dependencies(retry)
        else:
            err = OutOfMemoryError(
                f"Task {spec.name} was killed by the memory monitor: node "
                "memory usage exceeded the configured threshold "
                "(memory_usage_threshold) and its retry budget is spent.")
            self._release_task_deps(spec)
            for oid in spec.return_ids:
                self._store_if_referenced(oid, err, is_exception=True)
            self._record_event(spec, "FAILED")
        self._dispatch()

    def _try_claim_finalize(self, spec: TaskSpec) -> bool:
        """Exactly-once claim on a task's resource release: the finishing
        worker and an asynchronous killer (OOM monitor, node death) race to
        finalize; only the winner releases resources."""
        with self._lock:
            if getattr(spec, "_finalized", False):
                return False
            spec._finalized = True  # type: ignore[attr-defined]
            self._inflight.pop(spec.task_id, None)
            return True

    def _release_task_resources(self, spec: TaskSpec) -> None:
        lease = getattr(spec, "_lease", None)
        if lease is not None:
            # The LEASE owns the acquisition; this task only rode it.
            spec._lease = None  # type: ignore[attr-defined]
            with self._lock:
                blocked = getattr(spec, "_blocked_release", False)
                spec._blocked_release = False  # type: ignore[attr-defined]
            if blocked:
                gate = self._unblock_lease_gated(lease)
                if not lease.dropped:
                    # Finalized while blocked in a nested get (lease
                    # capacity was lent out): re-take it so the lease's
                    # eventual drop releases exactly once.
                    self.scheduler.force_acquire(
                        lease.resources, lease.node_id,
                        lease.pg_id, lease.bidx)
                if gate:
                    self._send_unspill_and_open(lease)
            self._lease_task_done(spec, lease)
            return
        with self._lock:
            # A blocked client get (client_get_release) already gave the
            # resources back; consuming the flag here makes release
            # exactly-once when the task finalizes mid-block.
            blocked = getattr(spec, "_blocked_release", False)
            spec._blocked_release = False  # type: ignore[attr-defined]
        pg_id, _ = self._pg_key(spec)
        node_id = getattr(spec, "_node_id", None)
        bidx = getattr(spec, "_acquired_bundle", -1)
        if not blocked:
            self.scheduler.release(spec.resources, node_id, pg_id, bidx)
        tpu_ids = getattr(spec, "_tpu_ids", None)
        if tpu_ids and node_id is not None:
            self.scheduler.return_tpu_ids(node_id, tpu_ids)
            spec._tpu_ids = None  # type: ignore[attr-defined]

    def _unblock_lease_gated(self, lease) -> bool:
        """One task's blocked get returned: decrement the blocked count.
        The LAST unblocker must hold the gate (blocked stays >=1, so no
        _dispatch can attach) until the unspill frame is ON THE WIRE —
        decrement-then-send would let an attach frame overtake the
        unspill and execute on a still-spilled daemon executor. Returns
        True iff the caller owns the gate and must follow with
        _send_unspill_and_open."""
        with self._lock:
            lease.blocked -= 1
            if lease.blocked == 0:
                lease.blocked = 1  # gate: attaches stay closed
                return True
            self._lease_avail_update(lease)
        return False

    def _send_unspill_and_open(self, lease) -> None:
        """Second half of the gated unblock: ship the unspill frame,
        then open attaches (arithmetic decrement — a NEW blocked get
        during the send may have incremented, and its spill frame
        travels after ours, which the daemon applies in order)."""
        if not lease.dropped:
            conn = self._remote_nodes.get(lease.node_id)
            if conn is not None:
                conn.unspill_lease(lease.lease_id)
        with self._lock:
            lease.blocked -= 1
            self._lease_avail_update(lease)
        self._dispatch()

    def client_get_release(self, task_id_hex: str) -> Optional[TaskSpec]:
        """A client runtime's get blocked inside this running task:
        release the task's resources so nested/dependent work can run
        (the client-side analog of Runtime.get's own blocked-worker
        release; reference: NotifyDirectCallTaskBlocked). Returns the
        spec iff released — pass it to client_get_reacquire after."""
        try:
            task_id = TaskID(bytes.fromhex(task_id_hex))
        except (ValueError, TypeError):
            return None
        with self._lock:
            spec = self._inflight.get(task_id)
            if spec is None or spec.kind != TaskKind.NORMAL or \
                    not spec.resources:
                return None
            if getattr(spec, "_finalized", False) or \
                    getattr(spec, "_blocked_release", False):
                return None
            lease = getattr(spec, "_lease", None)
            if lease is not None and lease.dropped:
                return None
            spec._blocked_release = True  # type: ignore[attr-defined]
            if lease is not None:
                # INSIDE the lock: _find_lease/_lease_task_done read
                # blocked under it — set-after-release would let a
                # dispatch attach a same-class child to this lease in
                # the window, landing it behind its blocked parent.
                lease.blocked += 1
                self._lease_avail_update(lease)
        if lease is not None:
            # A leased task blocks its lease's serial executor, so lending
            # out the LEASE's acquisition is safe: nothing else can run on
            # it until this task's get unblocks (composition: nested work
            # must be schedulable while the parent waits). Tasks already
            # pipelined BEHIND the blocked one daemon-side could include
            # the very child being waited on — spill them to free threads
            # and stop attaching until the get returns.
            self.scheduler.release(lease.resources, lease.node_id,
                                   lease.pg_id, lease.bidx)
            conn = self._remote_nodes.get(lease.node_id)
            if conn is not None:
                conn.spill_lease(lease.lease_id)
        else:
            pg_id, _ = self._pg_key(spec)
            self.scheduler.release(spec.resources,
                                   getattr(spec, "_node_id", None), pg_id,
                                   getattr(spec, "_acquired_bundle", -1))
        self._dispatch()
        return spec

    def client_get_reacquire(self, spec: TaskSpec) -> None:
        """Re-take the blocked task's resources once its get unblocked.
        If the task finalized meanwhile, _release_task_resources consumed
        the flag (and skipped its release) — nothing to re-take."""
        with self._lock:
            if not getattr(spec, "_blocked_release", False):
                return
            spec._blocked_release = False  # type: ignore[attr-defined]
            lease = getattr(spec, "_lease", None)
        if lease is not None:
            gate = self._unblock_lease_gated(lease)
            if not lease.dropped:
                self.scheduler.force_acquire(lease.resources, lease.node_id,
                                             lease.pg_id, lease.bidx)
            if gate:
                self._send_unspill_and_open(lease)
            return
        pg_id, _ = self._pg_key(spec)
        self.scheduler.force_acquire(
            spec.resources, getattr(spec, "_node_id", None), pg_id,
            getattr(spec, "_acquired_bundle", -1))

    def _finish_task(self, spec: TaskSpec, worker: Executor,
                     retried: bool = False) -> None:
        if self._try_claim_finalize(spec) and not getattr(
                spec, "invalidated", False):
            # (invalidated + claimed: node death released the node's
            # resources wholesale — nothing to give back here.)
            self._release_task_resources(spec)
        self._return_worker(worker)
        self._dispatch()

    # ------------------------------------------------------------------
    # Actors
    # ------------------------------------------------------------------

    def create_actor(self, spec: TaskSpec, *, max_restarts: int,
                     max_concurrency: int, name: str = "",
                     namespace: str = "default",
                     get_if_exists: bool = False,
                     concurrency_groups: Optional[Dict[str, int]] = None,
                     lifetime: Optional[str] = None) -> ActorID:
        actor_id = spec.actor_id
        if lifetime == "detached" and not name:
            # A detached actor is reachable ONLY through the named-actor
            # registry once its creator exits — an anonymous one would
            # be an unkillable orphan.
            raise ValueError(
                "detached actors must be created with a name "
                "(.options(name=..., lifetime='detached'))")
        state = ActorState(actor_id, spec, max_restarts, max_concurrency,
                           name, namespace,
                           concurrency_groups=concurrency_groups,
                           lifetime=lifetime)
        with self._lock:
            # Uniqueness check + registration atomically, so concurrent
            # creates with the same name cannot both succeed.
            if name:
                existing = self._named_actors.get((namespace, name))
                if existing is not None:
                    if get_if_exists:
                        return existing
                    raise ValueError(
                        f"Actor name {name!r} already taken in namespace "
                        f"{namespace!r}")
                self._named_actors[(namespace, name)] = actor_id
            self._actors[actor_id] = state
        if name and self.gcs_store is not None:
            # Persist OUTSIDE the runtime lock — the store fsyncs a file
            # per mutation; dispatch must not stall on disk I/O.
            try:
                cls_bytes = self.functions.get_bytes(spec.function_id)
            except KeyError:
                cls_bytes = None  # unpicklable: cannot survive restarts
            creation_payload = None
            if lifetime == "detached":
                # Detached actors must be restartable AFTER a head
                # restart — persist the __init__ args so the rebound
                # creation spec is re-runnable (best effort: unpicklable
                # args degrade to rebind-without-restart).
                try:
                    creation_payload = serialization.serialize(
                        (spec.args, spec.kwargs))
                except Exception:  # noqa: BLE001
                    creation_payload = None
            self.gcs_store.record_actor(
                actor_id.hex(), name, namespace, max_restarts,
                max_concurrency, cls_bytes=cls_bytes,
                resources=dict(spec.resources or {}),
                concurrency_groups=concurrency_groups,
                lifetime=lifetime,
                creation_payload=creation_payload)
        spec.return_ids = [ObjectID.for_return(spec.task_id, 1)]
        self._register_task_refs(spec)
        self._record_event(spec, "SUBMITTED")
        self._resolve_dependencies(spec)
        return actor_id

    def _make_actor_executor(self, state: ActorState) -> Executor:
        import asyncio
        wid = WorkerID.from_random()
        name = f"ray_tpu-actor-{state.name or state.actor_id.hex()[:8]}"
        cls = self.functions.load(state.creation_spec.function_id)
        is_async = any(
            asyncio.iscoroutinefunction(getattr(cls, m, None))
            for m in dir(cls) if not m.startswith("__"))
        if is_async:
            ex: Executor = AsyncioActorExecutor(
                wid, name, max(state.max_concurrency, 1000 if
                               state.max_concurrency <= 1 else
                               state.max_concurrency),
                groups=state.concurrency_groups)
        elif state.concurrency_groups:
            ex = ConcurrencyGroupExecutor(wid, name,
                                          state.concurrency_groups,
                                          state.max_concurrency)
        elif state.max_concurrency > 1:
            ex = ThreadPoolActorExecutor(wid, name, state.max_concurrency)
        else:
            ex = SerialThreadExecutor(wid, name)
        ex.actor_id = state.actor_id
        return ex

    def _release_actor_resources(self, state: ActorState) -> None:
        """Release the creation-time resources exactly once, and only if they
        were actually acquired (the spec carries _acquired_bundle iff the
        dispatcher acquired them)."""
        spec = state.creation_spec
        with state.lock:
            if state.resources_released:
                return
            if not hasattr(spec, "_acquired_bundle"):
                state.resources_released = True
                return
            state.resources_released = True
        pg_id, _ = self._pg_key(spec)
        node_id = getattr(spec, "_node_id", None)
        bidx = getattr(spec, "_acquired_bundle", -1)
        self.scheduler.release(spec.resources, node_id, pg_id, bidx)
        tpu_ids = getattr(spec, "_tpu_ids", None)
        if tpu_ids and node_id is not None:
            self.scheduler.return_tpu_ids(node_id, tpu_ids)
            spec._tpu_ids = None  # type: ignore[attr-defined]

    def _run_actor_creation(self, spec: TaskSpec, worker: Executor) -> None:
        state = self._actors[spec.actor_id]
        try:
            cls = self.functions.load(spec.function_id)
            args, kwargs = self._resolve_args(spec, self._remote_conn(spec))
            _task_context.spec = spec
            try:
                if spec.runtime_env and self._remote_conn(spec) is None \
                        and not self._use_process_worker(spec):
                    from ray_tpu._private import runtime_env as _renv
                    _renv.setup(spec.runtime_env)
                    with _renv.applied(spec.runtime_env):
                        instance = self._invoke_actor_init(spec, cls, args,
                                                           kwargs)
                else:
                    instance = self._invoke_actor_init(spec, cls, args,
                                                       kwargs)
            finally:
                _task_context.spec = None
            if spec.invalidated:
                # Node died mid-__init__; a cloned creation owns the actor
                # now. Discard this thread's work entirely.
                self._return_worker(worker)
                self._dispatch()
                return
            executor = self._make_actor_executor(state)
            killed = False
            with state.lock:
                if state.dead:
                    # Killed mid-construction.
                    executor.stop()
                    killed = True
                else:
                    state.instance = instance
                    state.executor = executor
                    state.created.set()
                    # Flush tasks that dep-resolved before creation finished,
                    # preserving their arrival order.
                    for queued in state.pre_creation_queue:
                        self._submit_to_actor_executor(executor, queued,
                                                       state)
                    state.pre_creation_queue.clear()
            if killed:
                self._store_error(spec, state.death_cause)
                self._release_actor_resources(state)
            else:
                self._release_task_deps(spec)
                self.store.put_inline(spec.return_ids[0], None)
                self._record_event(spec, "FINISHED")
        except BaseException as e:  # noqa: BLE001
            if spec.invalidated or self._node_death_invalidated(spec, e):
                self._return_worker(worker)
                self._dispatch()
                return
            err = TaskError(e, traceback.format_exc(),
                            f"{spec.name}.__init__")
            with state.lock:
                state.dead = True
                state.death_cause = err
                state.created.set()
                unfinished = list(state.unfinished.values())
                state.unfinished.clear()
                state.pre_creation_queue.clear()
            self._store_error(spec, err)
            # A failed constructor must give back its reservation — nobody
            # will call kill() on an actor that never came up.
            self._release_actor_resources(state)
            for queued in unfinished:
                self._store_error(queued, err)
            with self._lock:
                if state.name:
                    self._named_actors.pop((state.namespace, state.name),
                                           None)
            if state.name and self.gcs_store is not None:
                # A never-constructed actor must not be rebound after a
                # head restart (detached or not).
                self.gcs_store.remove_actor(state.actor_id.hex())
        with self._lock:
            if self._inflight.get(spec.task_id) is spec:
                self._inflight.pop(spec.task_id, None)
        self._return_worker(worker)
        self._dispatch()

    def submit_actor_task(self, spec: TaskSpec) -> List[ObjectRef]:
        group = spec.concurrency_group
        if group is not None:
            gstate = self._actors.get(spec.actor_id)
            if gstate is not None and \
                    group not in gstate.concurrency_groups:
                # Typos and group calls on group-less actors fail LOUDLY
                # (reference: unknown concurrency group raises) — silent
                # default-lane routing would fake isolation. Checked
                # BEFORE any ref registration so nothing leaks.
                raise ValueError(
                    f"Actor {spec.actor_id.hex()[:8]} has no concurrency "
                    f"group {group!r}; declared: "
                    f"{sorted(gstate.concurrency_groups) or 'none'}")
        from ray_tpu.util import tracing
        if tracing.is_tracing_enabled():
            # Same head-of-trace discipline as submit_task: the sampling
            # decision is made once here; unsampled calls stay bare.
            ctx = tracing.inject_context()
            if ctx is not None:
                import time as _time
                with tracing.continue_context(
                        ctx, "driver::submit",
                        {"stage": "submit", "task": spec.name,
                         "actor": spec.actor_id.hex()[:8]}) as span:
                    spec.trace_ctx = tracing.span_context(span)
                    spec._trace_submit_mono = _time.monotonic()  # type: ignore[attr-defined]
                    spec._trace_submit_wall = span.start_time  # type: ignore[attr-defined]
                    return self._submit_actor_task_inner(spec)
        return self._submit_actor_task_inner(spec)

    def _submit_actor_task_inner(self, spec: TaskSpec) -> List[ObjectRef]:
        n = max(spec.num_returns, 1) if spec.num_returns != "dynamic" else 1
        spec.return_ids = [
            ObjectID.for_return(spec.task_id, i + 1) for i in range(n)]
        refs = [ObjectRef(oid) for oid in spec.return_ids]
        if spec.num_returns == 0:
            refs = []
        self._register_task_refs(spec)
        state = self._actors.get(spec.actor_id)
        if state is None or state.dead:
            cause = state.death_cause if state else None
            self._store_error(spec, cause or ActorDiedError(
                spec.actor_id, f"Actor {spec.actor_id} is dead."))
            return refs
        with state.lock:
            if state.dead:
                self._store_error(spec, state.death_cause or
                                  ActorDiedError(spec.actor_id))
                return refs
            state.unfinished[spec.task_id] = spec
        self._record_event(spec, "SUBMITTED")
        self._resolve_dependencies(spec)
        return refs

    def _abort_actor_task_seq(self, spec: TaskSpec) -> None:
        """Mark a sealed-without-running actor task's sequence number as
        satisfied so later tasks on the same handle still execute."""
        state = self._actors.get(spec.actor_id)
        if state is None:
            return
        with state.lock:
            state.unfinished.pop(spec.task_id, None)
            handle = spec.caller_handle_id or "default"
            seq_state = state.seq_state.setdefault(
                handle, {"next": 1, "waiting": {}, "aborted": set()})
            seq_state.setdefault("aborted", set()).add(spec.sequence_number)
            self._drain_actor_seq(state, seq_state)

    def _drain_actor_seq(self, state: ActorState, seq_state: dict) -> None:
        """Submit all consecutively-ready tasks. Caller holds state.lock."""
        aborted = seq_state.setdefault("aborted", set())
        while True:
            nxt = seq_state["next"]
            if nxt in aborted:
                aborted.discard(nxt)
                seq_state["next"] += 1
                continue
            if nxt not in seq_state["waiting"]:
                return
            ready = seq_state["waiting"].pop(nxt)
            seq_state["next"] += 1
            if state.created.is_set() and state.executor is not None:
                self._submit_to_actor_executor(state.executor, ready,
                                               state)
            else:
                state.pre_creation_queue.append(ready)

    def _dispatch_actor_task(self, spec: TaskSpec) -> None:
        """Called when the task's deps are resolved. Enforces per-handle
        submission order: a task only reaches the executor when every earlier
        task from the same handle has (its deps resolved and) been enqueued."""
        state = self._actors.get(spec.actor_id)
        if state is None:
            self._store_error(spec, ActorDiedError(spec.actor_id))
            return
        with state.lock:
            if state.dead:
                state.unfinished.pop(spec.task_id, None)
                self._store_error(spec, state.death_cause or
                                  ActorDiedError(spec.actor_id))
                return
            handle = spec.caller_handle_id or "default"
            seq_state = state.seq_state.setdefault(
                handle, {"next": 1, "waiting": {}, "aborted": set()})
            seq_state["waiting"][spec.sequence_number] = spec
            self._drain_actor_seq(state, seq_state)

    def _submit_to_actor_executor(self, executor, spec: TaskSpec,
                                  state: ActorState) -> None:
        """Per-method concurrency-group routing (reference:
        concurrency_group_manager.h GetExecutor): tagged calls go to
        their group's sub-executor; untagged (or group-less actors) use
        the default path."""
        group = getattr(spec, "concurrency_group", None)
        if group is not None and hasattr(executor, "submit_group"):
            executor.submit_group(
                group, lambda s=spec: self._run_actor_task(s, state))
        else:
            executor.submit(lambda s=spec: self._run_actor_task(s, state))

    def _finish_actor_task(self, spec: TaskSpec, state: ActorState) -> None:
        with state.lock:
            state.unfinished.pop(spec.task_id, None)

    def _run_actor_task(self, spec: TaskSpec, state: ActorState):
        """Executes in the actor's executor. May return a coroutine (async
        actors) which the AsyncioActorExecutor awaits."""
        import asyncio
        if state.dead:
            self._store_error(spec, state.death_cause or
                              ActorDiedError(spec.actor_id))
            self._finish_actor_task(spec, state)
            return None
        try:
            from ray_tpu._private.multinode import RemoteActorInstance
            from ray_tpu._private.worker_process import ProcessActorInstance
            conn = None
            to_process = False
            if isinstance(state.instance, RemoteActorInstance):
                conn = state.instance.conn
                method = state.instance.bind_method(
                    spec.method_name, spec.name,
                    store_limit=self._result_store_limit(spec),
                    num_returns=(spec.num_returns if
                                 isinstance(spec.num_returns, int)
                                 else 1))
            elif isinstance(state.instance, ProcessActorInstance):
                to_process = True
                method = state.instance.bind_method(
                    spec.method_name, spec.name)
            else:
                method = getattr(state.instance, spec.method_name)
            args, kwargs = self._resolve_args(spec, conn,
                                              to_process=to_process)
        except BaseException as e:  # noqa: BLE001
            self._store_error(spec, TaskError(e, traceback.format_exc(),
                                              spec.name))
            self._finish_actor_task(spec, state)
            return None

        ctx = getattr(spec, "trace_ctx", None)
        if ctx is not None and \
                getattr(spec, "_trace_submit_mono", None) is not None:
            import time as _time
            from ray_tpu.util import tracing as _tr
            _tr.record_complete_span(
                "sched::queue_wait", ctx,
                wall_start=getattr(spec, "_trace_submit_wall", 0.0),
                duration=_time.monotonic() - spec._trace_submit_mono,
                attributes={"stage": "queue", "task": spec.name})

        if asyncio.iscoroutinefunction(method):
            async def _acall():
                try:
                    _task_context.spec = spec
                    try:
                        from ray_tpu.util import tracing
                        # Thread-local context on an asyncio loop:
                        # concurrent requests on one replica may see an
                        # interleaved ACTIVE span, but per-span parenting
                        # stays correct because the ctx rides the spec.
                        with tracing.continue_context(
                                getattr(spec, "trace_ctx", None),
                                f"actor_task::{spec.name}",
                                {"stage": "execute"}):
                            result = await method(*args, **kwargs)
                    finally:
                        _task_context.spec = None
                    self._store_results(spec, result)
                    self._record_event(spec, "FINISHED")
                except GeneratorExit:
                    # The garbage collector is closing a stale parked
                    # coroutine (its actor's loop died — possibly from an
                    # already-shut-down runtime). Touching runtime/native
                    # state from the collector's context deadlocks;
                    # kill_actor sealed this task's refs already.
                    raise
                except BaseException as e:  # noqa: BLE001
                    self._store_error(spec, TaskError(
                        e, traceback.format_exc(), spec.name))
                finally:
                    self._finish_actor_task(spec, state)
            return _acall()
        try:
            _task_context.spec = spec
            try:
                from ray_tpu.util import tracing
                with tracing.continue_context(
                        getattr(spec, "trace_ctx", None),
                        f"actor_task::{spec.name}", {"stage": "execute"}):
                    result = method(*args, **kwargs)
            finally:
                _task_context.spec = None
            self._store_results(spec, result)
            self._record_event(spec, "FINISHED")
        except BaseException as e:  # noqa: BLE001
            self._store_error(spec, TaskError(e, traceback.format_exc(),
                                              spec.name))
        finally:
            self._finish_actor_task(spec, state)
        return None

    def kill_actor(self, actor_id: ActorID, no_restart: bool = True) -> None:
        state = self._actors.get(actor_id)
        if state is None:
            return
        if not no_restart and (state.max_restarts == -1
                               or state.num_restarts < state.max_restarts):
            self._restart_actor(state)
            return
        with state.lock:
            if state.dead:
                return
            state.dead = True
            state.death_cause = ActorDiedError(
                actor_id, f"Actor {actor_id} was killed via kill().")
            state.created.set()
            if state.executor is not None:
                state.executor.stop()
            unfinished = list(state.unfinished.values())
            state.unfinished.clear()
            state.pre_creation_queue.clear()
        try:
            self._destroy_remote_instance(state)
        except Exception:  # noqa: BLE001 - best effort only
            pass
        # Seal every submitted-but-unfinished task so gets raise instead of
        # hanging (first-write-wins in the store keeps completed results).
        for spec in unfinished:
            self._store_error(spec, state.death_cause)
        with self._lock:
            # A creation task still queued never ran: drop + seal it here.
            if state.creation_spec in self._ready:
                self._ready.remove(state.creation_spec)
                self._store_error(state.creation_spec, state.death_cause)
        self._release_actor_resources(state)
        with self._lock:
            if state.name:
                self._named_actors.pop((state.namespace, state.name), None)
        if self.gcs_store is not None:
            self.gcs_store.remove_actor(actor_id.hex())
        self._dispatch()

    def _restart_actor(self, state: ActorState) -> None:
        """Restart an actor in place: stop the current instance, fail its
        in-flight tasks, and re-run the creation task on a fresh executor
        (reference: max_restarts semantics, gcs_actor_manager.h:88 — state is
        lost unless the actor checkpoints itself)."""
        from ray_tpu._private import builtin_metrics
        builtin_metrics.actor_restarts().inc(tags={"kind": "restart"})
        cause = ActorDiedError(
            state.actor_id,
            f"Actor {state.actor_id} is restarting; in-flight tasks failed.")
        try:
            self._destroy_remote_instance(state)
        except Exception:  # noqa: BLE001 - best effort only
            pass
        with state.lock:
            state.num_restarts += 1
            old_executor = state.executor
            state.executor = None
            state.instance = None
            state.created.clear()
            unfinished = list(state.unfinished.values())
            state.unfinished.clear()
            state.pre_creation_queue.clear()
            if old_executor is not None:
                old_executor.stop()
            # Sequence slots held by the failed tasks must not block the
            # restarted actor.
            for spec in unfinished:
                handle = spec.caller_handle_id or "default"
                seq_state = state.seq_state.setdefault(
                    handle, {"next": 1, "waiting": {}, "aborted": set()})
                if spec.sequence_number >= seq_state["next"]:
                    seq_state.setdefault("aborted", set()).add(
                        spec.sequence_number)
            for seq_state in state.seq_state.values():
                self._drain_actor_seq(state, seq_state)
        for spec in unfinished:
            self._store_error(spec, cause)
        if state.name and self.gcs_store is not None:
            # Burn down the persisted budget too: the count must survive
            # a head restart (detached actors keep restarting after one).
            self.gcs_store.update_actor(state.actor_id.hex(),
                                        num_restarts=state.num_restarts)
        # Re-run the creation task (a fresh TaskSpec attempt on the same
        # actor id); resources were never released, so dispatch reuses the
        # original reservation by running creation on a pool worker directly.
        creation = state.creation_spec
        worker = None
        with self._lock:
            worker = self._pop_worker()
        if worker is None:
            # Pool exhausted; queue through the normal path without
            # re-acquiring resources.
            worker = SerialThreadExecutor(
                WorkerID.from_random(), name="ray_tpu-restart")
            with self._lock:
                self._all_workers.append(worker)
        # Reset the creation return object is not possible (sealed); restart
        # success is observable via task results.
        worker.submit(lambda: self._run_actor_creation_restart(
            creation, worker, state))

    def _run_actor_creation_restart(self, spec: TaskSpec, worker: Executor,
                                    state: ActorState) -> None:
        try:
            cls = self.functions.load(spec.function_id)
            args, kwargs = self._resolve_args(spec, self._remote_conn(spec))
            instance = self._invoke_actor_init(spec, cls, args, kwargs)
            executor = self._make_actor_executor(state)
            with state.lock:
                if state.dead:
                    executor.stop()
                else:
                    state.instance = instance
                    state.executor = executor
                    state.created.set()
                    for queued in state.pre_creation_queue:
                        self._submit_to_actor_executor(executor, queued,
                                                       state)
                    state.pre_creation_queue.clear()
        except BaseException as e:  # noqa: BLE001
            if getattr(spec, "invalidated", False) or \
                    self._node_death_invalidated(spec, e):
                # The node died under the restarting __init__; node-death
                # handling owns the next restart attempt (including this
                # spec's dependency pins — don't double-release).
                self._return_worker(worker)
                self._dispatch()
                return
            err = TaskError(e, traceback.format_exc(), f"{spec.name}.restart")
            with state.lock:
                state.dead = True
                state.death_cause = err
                state.created.set()
                unfinished = list(state.unfinished.values())
                state.unfinished.clear()
            for queued in unfinished:
                self._store_error(queued, err)
            self._release_actor_resources(state)
        self._release_task_deps(spec)
        self._return_worker(worker)
        self._dispatch()

    def get_named_actor(self, name: str, namespace: str = "default") -> ActorID:
        with self._lock:
            actor_id = self._named_actors.get((namespace, name))
        if actor_id is None:
            raise ValueError(
                f"Failed to look up actor {name!r} in namespace {namespace!r}. "
                "It was either not created with a name or has died.")
        return actor_id

    def actor_state(self, actor_id: ActorID) -> Optional[ActorState]:
        return self._actors.get(actor_id)

    # ------------------------------------------------------------------
    # Cancellation
    # ------------------------------------------------------------------

    def _cancel_queued_locked(self, task_id) -> bool:
        """Drop a task that has not been launched from whichever queue
        holds it, sealing TaskCancelledError; False if none does."""
        for i, spec in enumerate(self._ready):
            if spec.task_id == task_id:
                self._ready.pop(i)
                self._store_error(spec, TaskCancelledError(task_id))
                return True
        for dq in self._ready_by_class.values():
            for spec in dq:
                if spec.task_id == task_id:
                    dq.remove(spec)
                    self._store_error(spec, TaskCancelledError(task_id))
                    return True
        for waiters in self._pending_by_oid.values():
            for pending in waiters:
                if pending.spec.task_id == task_id:
                    pending.cancelled = True
                    self._store_error(pending.spec,
                                      TaskCancelledError(task_id))
                    if pending.spec.kind == TaskKind.ACTOR_TASK:
                        self._abort_actor_task_seq(pending.spec)
                    return True
        return False

    def cancel(self, ref: ObjectRef, force: bool = False) -> None:
        oid = ref.object_id()
        task_id = oid.task_id()
        # A finished task has nothing to cancel and a launched one sits in
        # no queue: neither is worth a scan of every queue under the lock
        # (which made dropping a 100k-task backlog quadratic).
        if self.store.contains(oid):
            return
        with self._lock:
            if task_id not in self._inflight and \
                    self._cancel_queued_locked(task_id):
                return
        # Running tasks: a task on a worker PROCESS is force-killable for
        # real — SIGKILL the worker, the blocked executor thread raises
        # and seals TaskCancelledError (reference: worker process kill on
        # ray.cancel(force=True)). Thread-backend tasks cannot be
        # interrupted; their result is discarded lazily.
        if force:
            # Kill UNDER the lock (non-blocking variant): the executing
            # thread pops _proc_tasks under this same lock before
            # releasing the worker to the pool, so the SIGKILL can never
            # land on a worker already re-leased to another task.
            with self._lock:
                handle = self._proc_tasks.get(task_id)
                spec = self._inflight.get(task_id)
                if handle is not None and spec is not None:
                    spec._cancel_requested = True  # type: ignore
                    handle.kill(wait=False)

    # ------------------------------------------------------------------
    # Placement groups
    # ------------------------------------------------------------------

    def create_placement_group(self, bundles: List[Dict[str, float]],
                               strategy: str = "PACK",
                               name: str = "") -> PlacementGroupID:
        pg_id = PlacementGroupID.from_random()
        self.scheduler.create_placement_group(pg_id, bundles, strategy)
        return pg_id

    def remove_placement_group(self, pg_id: PlacementGroupID) -> None:
        self.scheduler.remove_placement_group(pg_id)
        self._dispatch()

    # ------------------------------------------------------------------
    # Node membership (cluster_utils.Cluster / autoscaler entry points)
    # ------------------------------------------------------------------

    def add_node(self, resources: Dict[str, float],
                 labels: Optional[dict] = None) -> NodeID:
        node_id = self.scheduler.add_node(resources, labels=labels)
        # Bundles orphaned by an earlier node death land here if they fit.
        self.scheduler.reschedule_lost_bundles()
        self._dispatch()  # new capacity may unblock queued tasks
        self._maybe_spillback()  # ...or absorb misplaced daemon backlog
        return node_id

    def start_head_server(self, host: str = "127.0.0.1",
                          port: int = 0) -> Tuple[str, int]:
        """Open the head's TCP registration endpoint so node-daemon
        processes (`ray-tpu start --address host:port`) can join this
        cluster (reference: GCS server accepting raylet registration)."""
        with self._lock:
            if self._head_server is None:
                from ray_tpu._private.multinode import HeadServer
                server = HeadServer(self, host, port)
                server.start()
                self._head_server = server
        return self._head_server.address

    # -- internal KV (reference: gcs_kv_manager.h InternalKV) ----------

    def kv_put(self, namespace: str, key: bytes, value: bytes,
               overwrite: bool = True) -> bool:
        """Returns already_exists (reference internal_kv semantics)."""
        if self.gcs_store is not None:
            return self.gcs_store.kv_put(namespace, key, value, overwrite)
        with self._lock:
            ns = self._kv_mem.setdefault(namespace, {})
            existed = key in ns
            if overwrite or not existed:
                ns[key] = value
            return existed

    def kv_get(self, namespace: str, key: bytes):
        if self.gcs_store is not None:
            return self.gcs_store.kv_get(namespace, key)
        with self._lock:
            return self._kv_mem.get(namespace, {}).get(key)

    def kv_del(self, namespace: str, key: bytes) -> bool:
        if self.gcs_store is not None:
            return self.gcs_store.kv_del(namespace, key)
        with self._lock:
            return self._kv_mem.get(namespace, {}).pop(key, None) \
                is not None

    def kv_keys(self, namespace: str, prefix: bytes = b"") -> list:
        if self.gcs_store is not None:
            return self.gcs_store.kv_keys(namespace, prefix)
        with self._lock:
            return [k for k in self._kv_mem.get(namespace, {})
                    if k.startswith(prefix)]

    def new_node_id(self) -> "NodeID":
        """Pre-mint a node id (the handshake enqueues the 'registered'
        ack on the conn's sender BEFORE the node becomes schedulable, so
        the id must exist before register_remote_node runs)."""
        return NodeID.from_random()

    # ------------------------------------------------------------------
    # Log streaming fan-out (reference: worker.py print_logs subscribes
    # to the GCS log channel). Both paths converge on the "logs" pubsub
    # channel: JSON batches {pid, proc_name, source, task_name, lines,
    # node}; DriverLogPrinter (and anything else — tests, dashboards)
    # subscribes there.
    # ------------------------------------------------------------------

    def _membership_event(self, event: dict) -> None:
        """Membership fan-out sink (subscribed at init): node join/death
        events reach long-poll consumers on the "membership" pubsub
        channel keyed by node id — serve controllers and train executors
        react to a push instead of discovering death via their next
        failed RPC. Runs on the declarer's thread: publish only."""
        import json
        self.pubsub.publish("membership", str(event.get("node_id", "")),
                            json.dumps(event))
        # Journal the transition (head-local journal: direct append, no
        # piggyback latency). Joins are news; deaths are errors.
        kind = event.get("event", "")
        node_hex = str(event.get("node_id", ""))
        metrics = getattr(self, "_cluster_metrics", None)
        if metrics is None:  # an event before the pipeline exists
            return
        journal = metrics.events
        if kind == "joined":
            journal.record(
                "membership", f"node {node_hex[:12]} joined "
                f"(epoch {event.get('epoch')})",
                severity="info", node_id=node_hex,
                labels={"epoch": event.get("epoch", "")})
        elif kind == "dead":
            journal.record(
                "membership", f"node {node_hex[:12]} declared dead "
                f"({event.get('reason', 'unknown')}, "
                f"epoch {event.get('epoch')})",
                severity="error", node_id=node_hex,
                labels={"reason": event.get("reason", ""),
                        "epoch": event.get("epoch", "")})

    def _publish_log_batch(self, batch: dict) -> bool:
        """Head-local LogMonitor sink: stamp head identity, fan out."""
        import json
        msg = dict(batch)
        msg.setdefault("node", self.head_node_id.hex())
        self.pubsub.publish("logs", "", json.dumps(msg))
        return True

    def _log_batch_from_node(self, conn, msg: dict) -> None:
        """Wire sink for daemon-pushed log_batch frames (assigned to
        conn.on_log_batch at registration; runs on the conn's recv
        thread — publish only, no blocking work)."""
        import json
        batch = dict(msg)
        batch.pop("type", None)
        batch.pop("req_id", None)
        node = batch.pop("node_id", "")
        if not node and conn.node_id is not None:
            node = conn.node_id.hex()
        batch["node"] = node
        self.pubsub.publish("logs", "", json.dumps(batch))

    def _object_spilled_from_node(self, conn, msg: dict) -> None:
        """Wire sink for object_spilled frames: a daemon wrote this key
        through a DURABLE backend — the URI joins the location table so
        the daemon's death restores from disk instead of re-executing
        lineage (recv-thread: dict insert only). Bounded like the other
        location maps; past the cap recovery just falls down a tier."""
        recorded = False
        with self._lock:
            if len(self._spill_uris_by_key) < self._cfg_obj_loc_max:
                self._spill_uris_by_key[msg["key"]] = (
                    msg["uri"], int(msg.get("size", 0)))
                recorded = True
        # Spill URIs are the object directory's durable tier: mirror
        # them into the gcs_store so a REBORN head can still restore
        # from disk (head failover keeps tiered recovery working).
        if recorded and self.gcs_store is not None:
            try:
                self.gcs_store.record_spill_uri(
                    msg["key"], msg["uri"], int(msg.get("size", 0)))
            except OSError:
                logger.exception("could not persist spill URI")

    def _object_unspilled_from_node(self, conn, msg: dict) -> None:
        """Retraction: restore-promotion or a free deleted the file."""
        with self._lock:
            self._spill_uris_by_key.pop(msg["key"], None)
        if self.gcs_store is not None:
            try:
                self.gcs_store.remove_spill_uri(msg["key"])
            except OSError:
                logger.exception("could not retract spill URI")

    # ------------------------------------------------------------------
    # Cluster metrics (one Prometheus scrape for the whole cluster)
    # ------------------------------------------------------------------

    def _publish_head_metrics(self, batch: dict) -> bool:
        """Sink for this process's own metrics agent AND for batches its
        pool workers piggyback on task replies: merge locally under the
        head's node id."""
        self._cluster_metrics.update(self.head_node_id.hex(), batch)
        return True

    def _metrics_batch_from_node(self, conn, msg: dict) -> None:
        """Wire sink for daemon-pushed metrics_batch frames (assigned to
        conn.on_metrics_batch at registration; recv-thread — merge is a
        dict update, no blocking work)."""
        node = msg.get("node_id") or ""
        if not node and conn.node_id is not None:
            node = conn.node_id.hex()
        self._cluster_metrics.update(node, msg)

    def _publish_head_profile(self, batch: dict) -> bool:
        """Sink for the head profiler's windows AND for windows head
        pool workers piggyback on task replies: straight into the
        profile store under the head's node id."""
        self._cluster_metrics.update_profile(self.head_node_id.hex(),
                                             batch)
        return True

    def _profile_batch_from_node(self, conn, msg: dict) -> None:
        """Wire sink for daemon-pushed profile_batch frames (assigned to
        conn.on_profile_batch at registration; recv-thread — merge is a
        dict update, no blocking work)."""
        node = msg.get("node_id") or ""
        if not node and conn.node_id is not None:
            node = conn.node_id.hex()
        self._cluster_metrics.update_profile(node, msg)

    def _publish_head_flow(self, batch: dict) -> bool:
        """Sink for the head's own transfer-ledger drains AND for
        batches head pool workers piggyback on task replies: straight
        into the flow store under the head's node id."""
        self._cluster_metrics.update_flows(self.head_node_id.hex(),
                                           batch)
        return True

    def _flow_batch_from_node(self, conn, msg: dict) -> None:
        """Wire sink for daemon-pushed flow_batch frames (assigned to
        conn.on_flow_batch at registration; recv-thread — ingestion is
        bounded dict work, no blocking)."""
        node = msg.get("node_id") or ""
        if not node and conn.node_id is not None:
            node = conn.node_id.hex()
        self._cluster_metrics.update_flows(node, msg)

    def _collect_head_metrics(self) -> None:
        """Refresh head-side gauges right before each export snapshot —
        level-style series (queue depth, store bytes, pool size, actor
        count) cost nothing on the hot paths this way."""
        from ray_tpu._private import builtin_metrics, scheduler as _sched
        with self._lock:
            pending = sum(1 for _ in self._ready_specs_locked())
            actors = sum(1 for a in self._actors.values() if not a.dead)
        _sched.record_queue_depth(pending)
        builtin_metrics.actors_gauge().set(actors)
        record = getattr(self.scheduler, "record_metrics", None)
        if record is not None:  # native scheduler variant may lack it
            record()
        self.store.record_metrics()
        pool = self._process_pool
        if pool is not None:
            pool.record_metrics()

    def cluster_metrics_text(self) -> str:
        """The cluster-wide Prometheus exposition: a fresh head snapshot
        merged with the latest daemon/worker batches (remote origins are
        as fresh as their export interval)."""
        agent = self._metrics_agent
        if agent is not None:  # None after shutdown(): render what's held
            try:
                agent.poll_once()
            except Exception:  # noqa: BLE001 - scrape must not fail on this
                logger.exception("head metrics poll failed")
        return self._cluster_metrics.render()

    def cluster_chrome_spans(self) -> List[dict]:
        """Remote worker/daemon spans (shipped in metrics_batch frames)
        as chrome://tracing events for /api/timeline."""
        return self._cluster_metrics.chrome_spans()

    def _flush_trace_spans(self) -> None:
        """Pull this process's pending finished spans into the assembler
        before a trace read — remote origins stay as fresh as their
        export interval, but the head's own spans need not wait a tick."""
        agent = self._metrics_agent
        if agent is not None:
            try:
                agent.poll_once()
            except Exception:  # noqa: BLE001 - reads must not fail on this
                logger.exception("head trace flush failed")

    def trace_list(self, limit: Optional[int] = None) -> List[dict]:
        self._flush_trace_spans()
        return self._cluster_metrics.traces.list_traces(limit)

    def trace_get(self, trace_id: str) -> Optional[dict]:
        self._flush_trace_spans()
        return self._cluster_metrics.traces.get_trace(trace_id)

    def trace_summary(self) -> dict:
        self._flush_trace_spans()
        return self._cluster_metrics.traces.summary()

    def trace_perfetto(self, trace_id: Optional[str] = None) -> List[dict]:
        self._flush_trace_spans()
        return self._cluster_metrics.traces.perfetto(trace_id)

    def trace_flow_events(self) -> List[dict]:
        """Cross-process flow (s/f) arrows for /api/timeline."""
        self._flush_trace_spans()
        return self._cluster_metrics.traces.flow_events()

    # -- time-series signal plane (timeseries.py) ----------------------

    def get_timeseries(self, name: str,
                       labels: Optional[Dict[str, str]] = None,
                       window: Optional[float] = None,
                       step: Optional[float] = None) -> dict:
        """Windowed history + per-series summaries (reset-safe counter
        rates, gauge last/avg, histogram p50/p95) for one metric from
        the head's time-series store. The head's own registry is polled
        first so driver-side series are as fresh as the call."""
        self._flush_trace_spans()  # poll_once: fold + snapshot head
        return self._cluster_metrics.timeseries.query(
            name, labels=labels, window=window, step=step)

    def serve_stats(self, window: Optional[float] = None) -> dict:
        """Per-deployment traffic rollup over ``window`` seconds (default
        30): qps, p50/p95/mean latency, mean queue depth, replica count.
        The drop-in input for a metrics-driven replica autoscaler."""
        self._flush_trace_spans()
        w = 30.0 if window is None else float(window)
        ts = self._cluster_metrics.timeseries
        qps = ts.counter_rate("ray_tpu_serve_requests_total",
                              window=w, group_by="deployment")
        lat = ts.histogram_stats("ray_tpu_serve_request_latency_seconds",
                                 window=w, group_by="deployment")
        queue = ts.gauge_stats("ray_tpu_serve_queue_depth",
                               window=w, group_by="deployment")
        replicas = ts.gauge_stats("ray_tpu_serve_replicas",
                                  window=w, group_by="deployment")
        targets = ts.gauge_stats("ray_tpu_serve_target_replicas",
                                 window=w, group_by="deployment")
        deployments = {}
        for name in (set(qps) | set(lat) | set(queue) | set(replicas)):
            if not name:
                continue
            h = lat.get(name, {})
            tgt = targets.get(name, {}).get("last_max")
            deployments[name] = {
                "qps": qps.get(name, 0.0),
                "p50_s": h.get("p50", 0.0),
                "p95_s": h.get("p95", 0.0),
                "mean_latency_s": h.get("mean", 0.0),
                "requests": h.get("count", 0),
                # Queue depths are additive across routers; replica
                # counts are replicated views — max, not sum.
                "mean_queue_depth": queue.get(name, {}).get("avg_sum", 0.0),
                "replicas": int(replicas.get(name, {}).get("last_max", 0)),
                # Autoscaler-set target (None: not an autoscaled
                # deployment, or no autoscale pass in the window yet).
                "target_replicas": None if tgt is None else int(tgt),
            }
        return {"window_s": w, "deployments": deployments}

    def membership_snapshot(self) -> List[dict]:
        """Read-only membership internals (epoch / phi / heartbeat age)
        per live node, for status surfaces."""
        return self.membership.snapshot()

    def cluster_event_stats(self) -> Dict[str, dict]:
        """EventStats summaries shipped inside metrics_batch frames,
        keyed ``"<node_id>:<component>"`` (daemon control loops)."""
        return self._cluster_metrics.cluster_event_stats()

    def top_snapshot(self, window: Optional[float] = None) -> dict:
        """One `ray-tpu top` frame, rendered entirely from windowed
        store history: per-node usage + membership + task rates, object
        store bytes/spill rate, per-deployment serve stats, control-loop
        lag gauges."""
        self._flush_trace_spans()
        w = 30.0 if window is None else float(window)
        ts = self._cluster_metrics.timeseries
        node_rates: Dict[str, Dict[str, float]] = {}
        for status in ("SUBMITTED", "FINISHED", "FAILED"):
            rates = ts.counter_rate(
                "ray_tpu_node_task_events_total",
                labels={"status": status}, window=w, group_by="node_id")
            for node_hex, rate in rates.items():
                node_rates.setdefault(node_hex, {})[status.lower()] = rate
        usage = {}
        srv = getattr(self, "_head_server", None)
        if srv is not None:
            usage = srv.syncer.digest().get("nodes", {})
        membership = {row["node_id"]: row
                      for row in self.membership.snapshot()}
        nodes = []
        for node in self.scheduler.nodes_snapshot():
            hexid = node.get("NodeID", "")
            live = membership.get(hexid, {})
            used = usage.get(hexid, {})
            rates = node_rates.get(hexid, {})
            nodes.append({
                "node_id": hexid,
                "alive": node.get("Alive", False),
                "resources": node.get("Resources", {}),
                "epoch": live.get("epoch"),
                "phi": live.get("phi"),
                "last_heartbeat_age_s": live.get("last_heartbeat_age_s"),
                "rss_bytes": used.get("memory", {}).get("rss_bytes"),
                "object_store": used.get("object_store", {}),
                "resource_load": used.get("resource_load", {}),
                "tasks_submitted_per_s": rates.get("submitted", 0.0),
                "tasks_finished_per_s": rates.get("finished", 0.0),
                "tasks_failed_per_s": rates.get("failed", 0.0),
            })
        tasks = {
            "submitted_per_s": sum(ts.counter_rate(
                "ray_tpu_tasks_submitted_total", window=w).values()),
            "finished_per_s": sum(ts.counter_rate(
                "ray_tpu_tasks_finished_total", window=w).values()),
            "failed_per_s": sum(ts.counter_rate(
                "ray_tpu_tasks_failed_total", window=w).values()),
        }
        objects = {
            "store_bytes": ts.gauge_stats(
                "ray_tpu_object_store_bytes",
                window=w).get("", {}).get("last_sum", 0.0),
            "spill_bytes_per_s": sum(ts.counter_rate(
                "ray_tpu_object_spilled_bytes_total", window=w).values()),
            "restores_per_s": sum(ts.counter_rate(
                "ray_tpu_object_restores_total", window=w).values()),
        }
        loops = {
            key: stats["last_max"]
            for key, stats in ts.gauge_stats(
                "ray_tpu_loop_lag_seconds", window=w,
                group_by="loop").items() if key}
        # Firing alerts ride the same snapshot so `ray-tpu top`'s banner
        # costs no extra round-trip (evaluation is period-gated).
        cm = self._cluster_metrics
        try:
            cm.alerts.maybe_evaluate(ts)
        except Exception:  # noqa: BLE001 - a bad rule must not break top
            logger.exception("alert evaluation in top_snapshot failed")
        firing = cm.alerts.firing()
        return {
            "window_s": w,
            "nodes": nodes,
            "tasks": tasks,
            "objects": objects,
            "serve": self.serve_stats(window=w)["deployments"],
            "loops": loops,
            "transfer": cm.flows.summary_line(),
            "alerts": {
                "firing": firing,
                "firing_count": len(firing),
                "rules": [a["rule"] for a in firing],
            },
            "timeseries": {
                "series": ts.series_count(),
                "dropped_series": ts.dropped_series,
            },
        }

    # -- alerting plane + cluster event journal --------------------------

    def alerts_snapshot(self) -> dict:
        """Active alert instances, rule table, and firing history from
        the head's alert engine. The head's own registry is polled
        first (fresh head samples) and an evaluation is forced so the
        answer reflects the store as of this call, not the last merge
        tick."""
        self._flush_trace_spans()
        cm = self._cluster_metrics
        try:
            cm.alerts.maybe_evaluate(cm.timeseries)
        except Exception:  # noqa: BLE001 - reads must not fail on eval
            logger.exception("alert evaluation on read failed")
        return cm.alerts.snapshot()

    def add_alert_rule(self, rule) -> None:
        """Install (or replace, by name) a user alert rule — an
        ``alerting.AlertRule`` / ``BurnRateRule`` instance."""
        self._cluster_metrics.alerts.add_rule(rule)

    def remove_alert_rule(self, name: str) -> bool:
        return self._cluster_metrics.alerts.remove_rule(name)

    def subscribe_alerts(self, fn) -> None:
        """``fn(alert_dict)`` on every firing/resolved transition (the
        serve controller's scale_hint hook)."""
        self._cluster_metrics.alerts.subscribe(fn)

    def cluster_events(self, severity: Optional[str] = None,
                       source: Optional[str] = None,
                       node_id: Optional[str] = None,
                       since_seq: Optional[int] = None,
                       limit: Optional[int] = None) -> List[dict]:
        """Filtered journal rows (oldest first, ``age_s`` stamped). The
        head agent is polled first so head-emitted events don't wait an
        export tick."""
        self._flush_trace_spans()
        return self._cluster_metrics.events.query(
            severity=severity, source=source, node_id=node_id,
            since_seq=since_seq, limit=limit)

    def cluster_events_stats(self) -> dict:
        return self._cluster_metrics.events.stats()

    def cluster_event_annotations(self, limit: int = 200) -> List[dict]:
        """Grafana annotations-style feed derived from the journal."""
        self._flush_trace_spans()
        return self._cluster_metrics.events.annotations(limit=limit)

    # -- continuous profiling plane (profile_store.py) ------------------

    def profile_flame(self, component: Optional[str] = None,
                      node: Optional[str] = None,
                      window: Optional[float] = None,
                      fmt: str = "folded"):
        """Merged cluster/per-component flamegraph from the continuous
        windows ('folded' | 'speedscope' | 'dict'). The head's own
        profiler is drained first so driver stacks are as fresh as the
        call."""
        self._flush_trace_spans()  # poll_once also ships head profiles
        return self._cluster_metrics.profiles.flame(
            component=component, node_id=node, window=window, fmt=fmt)

    def profile_diff(self, window: float = 60.0,
                     component: Optional[str] = None,
                     node: Optional[str] = None,
                     limit: int = 50) -> List[dict]:
        """Window-vs-window stack diff ("what got hot")."""
        self._flush_trace_spans()
        return self._cluster_metrics.profiles.diff(
            window=window, component=component, node_id=node,
            limit=limit)

    def profile_incidents(self) -> List[dict]:
        """The loop-lag flight recorder's incident ring, newest first."""
        return self._cluster_metrics.profiles.incidents()

    def profile_stats(self) -> dict:
        return self._cluster_metrics.profiles.stats()

    # -- dataplane flow plane (flow.py) ---------------------------------

    def broadcast(self, ref: ObjectRef,
                  fanout: Optional[int] = None) -> dict:
        """Replicate one object onto every live daemon through a
        bounded-fanout spanning tree (reference: collective broadcast —
        the head stops being the serial source). A daemon-owned object
        roots the tree at its holder; a head-resident one seeds only its
        ``fanout`` direct children inline (head egress = fanout x size,
        flat in cluster width) and every deeper node waits on its
        parent's object server and pulls node-to-node. Blocks until the
        whole tree settles; returns a summary dict (nodes, depth,
        edges)."""
        return self._broadcast_object(ref.object_id(), fanout=fanout)

    def _broadcast_object(self, oid: ObjectID,
                          fanout: Optional[int] = None) -> dict:
        import time as _time
        from ray_tpu._private.multinode import _dumps
        fanout = max(1, int(fanout if fanout is not None
                            else self.config.broadcast_fanout))
        t_start = _time.monotonic()
        with self._lock:
            rv = self._remote_values.get(oid)
            conns = {nid: c for nid, c in self._remote_nodes.items()
                     if getattr(c, "object_addr", None) is not None}
            holders = set(self._object_replicas.get(oid) or ())
        payload = None
        root_id = None
        root_addr = None
        if rv is not None:
            root_id, key = rv
            holders.add(root_id)
            size = self.store.size_of(oid)
            root_conn = conns.get(root_id)
            if root_conn is None:
                raise ValueError(
                    f"cannot broadcast {oid.hex()[:12]}: its holder "
                    "node is not connected")
        else:
            # Head-resident: serialize once, seed direct children with
            # the bytes inline (the head has no object server to pull
            # from), deeper nodes cascade peer-to-peer.
            payload = _dumps(self.store.get(oid))
            size = len(payload)
            key = f"bcast-{oid.hex()}"
        targets = [nid for nid in conns if nid not in holders]
        summary = {"key": key, "size": size, "fanout": fanout,
                   "nodes": 0, "depth": 0, "edges": []}
        if not targets:
            return summary

        def addr(nid):
            return tuple(conns[nid].object_addr)

        if root_id is not None:
            root_addr = addr(root_id)
        # Array-indexed k-ary tree over [root?] + targets: parent of
        # position p is (p-1)//fanout. Head-rooted trees have no
        # position 0 holder — the first `fanout` targets sit at depth 1
        # (seeded inline) and position i parents onto (i-fanout)//fanout.
        plan = []  # (nid, parent_addr|None, alts, depth)
        depth_of: Dict[int, int] = {}
        root_alt = root_addr if root_id is not None else addr(targets[0])
        for i, nid in enumerate(targets):
            if root_id is not None:
                pos = i + 1
                parent_pos = (pos - 1) // fanout
                parent = (root_addr if parent_pos == 0
                          else addr(targets[parent_pos - 1]))
                gp_pos = (parent_pos - 1) // fanout
                grandp = (None if parent_pos == 0 else
                          root_addr if gp_pos == 0
                          else addr(targets[gp_pos - 1]))
                depth = depth_of[pos] = \
                    depth_of.get(parent_pos, 0) + 1
            elif i < fanout:
                parent = grandp = None  # head-seeded, depth 1
                depth = depth_of[i] = 1
            else:
                parent_i = (i - fanout) // fanout
                parent = addr(targets[parent_i])
                grandp = (addr(targets[(parent_i - fanout) // fanout])
                          if parent_i >= fanout else None)
                depth = depth_of[i] = depth_of[parent_i] + 1
            # Re-parenting ladder for a mid-tree death: grandparent
            # first, then the tree root — one failover per orphaned
            # subtree, never a dead broadcast.
            me = addr(nid)
            alts = [a for a in (grandp, root_alt)
                    if a is not None and a != parent and a != me]
            alts = list(dict.fromkeys(alts))
            plan.append((nid, parent, alts, depth))
        results: Dict[NodeID, Optional[dict]] = {}
        res_lock = threading.Lock()

        def _one(nid, parent, alts, depth):
            try:
                if parent is None and payload is not None:
                    r = conns[nid].push_object(key, size, data=payload)
                else:
                    r = conns[nid].push_object(
                        key, size, parent=parent, alts=alts,
                        wait_timeout_s=30.0 + 15.0 * depth)
            except Exception as exc:  # noqa: BLE001 - per-edge failure
                logger.warning("broadcast push of %s to node %s failed:"
                               " %s", key, nid.hex()[:12], exc)
                r = None
            with res_lock:
                results[nid] = r

        threads = [threading.Thread(target=_one, args=p, daemon=True,
                                    name=f"broadcast-edge-{i}")
                   for i, p in enumerate(plan)]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        hex_of = {addr(nid): nid.hex() for nid in conns}
        edges = []
        for nid, parent, _alts, depth in plan:
            r = results.get(nid)
            edges.append({
                "src": ("head" if parent is None and root_id is None
                        else hex_of.get(parent, "?")),
                "dst": nid.hex(), "depth": depth, "ok": r is not None,
                "bytes": 0 if r is None else size,
                "failovers": 0 if r is None else r.get("failovers", 0),
                "secs": None if r is None else r.get("secs"),
            })
        ok_nodes = [nid for nid, r in results.items() if r is not None]
        with self._lock:
            if root_id is None and ok_nodes and \
                    oid not in self._remote_values:
                # The object now lives on daemons too: future consumers
                # get replica markers instead of head-inlined payloads.
                self._remote_values[oid] = (ok_nodes[0], key)
                self._remote_keys[key] = oid
                self._broadcasted[oid] = None
            for nid in ok_nodes:
                if (root_id is None or nid != root_id) and \
                        len(self._object_replicas) < \
                        self._cfg_obj_loc_max:
                    self._object_replicas.setdefault(oid, {})[nid] = None
        if self.gcs_store is not None:
            try:
                for nid in ok_nodes:
                    self.gcs_store.record_object_replica(
                        oid.hex(), nid.hex())
            except OSError:
                pass
        builtin_metrics.broadcast_trees().inc()
        if ok_nodes:
            builtin_metrics.push_bytes().inc(size * len(ok_nodes))
        summary.update(
            nodes=len(ok_nodes),
            depth=max((e["depth"] for e in edges), default=0),
            edges=edges, root=(root_id.hex() if root_id else "head"),
            duration_s=_time.monotonic() - t_start)
        self._cluster_metrics.flows.note_broadcast(summary)
        return summary

    def flows_snapshot(self, window: Optional[float] = None) -> dict:
        """The per-link transfer matrix + per-object fan-out table
        (`/api/flows`, `ray-tpu xfer`). The head's own ledger is
        drained first so driver-side pulls are as fresh as the call."""
        self._flush_trace_spans()  # poll_once also ships head flows
        return self._cluster_metrics.flows.snapshot(window=window)

    def flow_stats(self) -> dict:
        return self._cluster_metrics.flows.stats()

    def profile_cluster(self, duration: float = 10.0, hz: int = 100,
                        fmt: str = "folded"):
        """Synchronized on-demand burst: fan a profile request to every
        live daemon IN PARALLEL while the head samples itself, and merge
        the folded stacks with ``component@node/pid`` roots (same shape
        as the continuous store's flame output)."""
        from ray_tpu._private.profiling import (folded_to_speedscope,
                                                sample_self)
        with self._lock:
            conns = dict(self._remote_nodes)
        merged: Dict[str, int] = {}
        merge_lock = threading.Lock()
        head_hex = self.head_node_id.hex()[:8]

        def _merge(root: str, counts: Dict[str, int]) -> None:
            with merge_lock:
                for stack, n in counts.items():
                    key = f"{root};{stack}"
                    merged[key] = merged.get(key, 0) + int(n)

        def _one_node(node_id, conn):
            try:
                counts = conn.profile(duration=duration, hz=hz,
                                      fmt="dict")
            except Exception:  # noqa: BLE001 - a dead node skips the burst
                logger.exception("profile burst failed for node %s",
                                 node_id.hex()[:8])
                return
            _merge(f"daemon@{node_id.hex()[:8]}/0", counts or {})

        threads = [threading.Thread(target=_one_node, args=(nid, conn),
                                    daemon=True,
                                    name=f"profile-burst-{i}")
                   for i, (nid, conn) in enumerate(conns.items())]
        for t in threads:
            t.start()
        _merge(f"driver@{head_hex}/{os.getpid()}",
               sample_self(duration, hz))
        for t in threads:
            t.join(timeout=duration + 60)
        if fmt == "dict":
            return merged
        if fmt == "speedscope":
            return folded_to_speedscope(merged, name="ray_tpu-burst",
                                        hz=hz)
        return "\n".join(f"{k} {v}"
                         for k, v in sorted(merged.items()))

    def profile_pid(self, pid: int, duration: float = 5.0,
                    hz: int = 100, fmt: str = "folded"):
        """Profile one process of the cluster by pid: the head itself,
        a head pool worker over its request pipe, or any daemon-owned
        worker via the owning daemon's burst endpoint (``--pid``
        without py-spy). Daemons are tried in turn — the one that knows
        the pid answers; the rest raise and are skipped."""
        from ray_tpu._private.profiling import (folded_to_speedscope,
                                                profile_self, sample_self)
        if int(pid) == os.getpid():
            return profile_self(duration, hz, fmt)
        pool = self._process_pool
        if pool is not None:
            for w in list(pool._all):
                if w.pid == int(pid) and not w.dead:
                    reply = w.request(
                        {"type": "profile", "duration": duration,
                         "hz": hz}, timeout=duration + 30)
                    if not reply.get("ok"):
                        raise RuntimeError(reply.get("error")
                                           or "worker profile failed")
                    counts = reply.get("stacks") or {}
                    if fmt == "dict":
                        return counts
                    if fmt == "speedscope":
                        return folded_to_speedscope(
                            counts, name=f"worker-{pid}", hz=hz)
                    return "\n".join(
                        f"{k} {v}" for k, v in sorted(counts.items()))
        with self._lock:
            conns = list(self._remote_nodes.items())
        errors = []
        for node_id, conn in conns:
            try:
                return conn.profile(duration=duration, hz=hz, fmt=fmt,
                                    pid=int(pid))
            except Exception as exc:  # noqa: BLE001 - not this node's pid
                errors.append(f"{node_id.hex()[:8]}: {exc}")
        detail = "; ".join(errors) if errors else "no live daemons"
        raise ValueError(
            f"pid {pid} is not a known worker/daemon of this cluster "
            f"({detail})")

    def register_remote_node(self, conn, info: Optional[dict] = None,
                             dispatch: bool = True,
                             node_id: Optional["NodeID"] = None) -> NodeID:
        # The connection must be visible BEFORE dispatch can place tasks
        # on the new node — otherwise a queued task assigned to it would
        # find no conn and silently run head-local.
        node_id = self.scheduler.add_node(dict(conn.resources),
                                          labels=conn.labels,
                                          node_id=node_id)
        # Daemon-pushed log/metrics batches flow into the driver fan-out
        # and the cluster metrics registry; durable-spill announcements
        # feed the object location table for tiered recovery.
        conn.on_log_batch = self._log_batch_from_node
        conn.on_metrics_batch = self._metrics_batch_from_node
        conn.on_profile_batch = self._profile_batch_from_node
        conn.on_flow_batch = self._flow_batch_from_node
        conn.on_object_spilled = self._object_spilled_from_node
        conn.on_object_unspilled = self._object_unspilled_from_node
        # Teach the flow store the node's object-server address so the
        # holder addresses in pull records resolve to node ids (link
        # matrix cells read node->node, not host:port->node).
        if getattr(conn, "object_addr", None):
            self._cluster_metrics.flows.note_node(
                node_id.hex(), conn.object_addr)
        with self._lock:
            self._remote_nodes[node_id] = conn
        # A daemon reconnecting to a RESTARTED head announces the actor
        # instances it still hosts; rebind the persisted named ones so
        # get_actor(name) answers again (reference: GCS restart +
        # RayletNotifyGCSRestart resubscription). EXCEPT when the
        # daemon's previous incarnation was fenced (declared dead after
        # a partition): those residents died exactly once with that
        # incarnation — a restarted copy may already run elsewhere, so
        # rebinding (or even leaving) the stale instances would
        # double-run detached-actor side effects. Destroy them instead.
        residents = (info or {}).get("resident_actors") or []
        prev_epoch = int((info or {}).get("prev_epoch") or 0)
        if residents and prev_epoch and \
                self.membership.is_fenced(prev_epoch):
            logger.warning(
                "Node %s re-registered from fenced incarnation %d: "
                "destroying %d stale resident actor(s) instead of "
                "rebinding", node_id.hex()[:12], prev_epoch,
                len(residents))
            stale_ids = [ActorID(bytes.fromhex(h)) for h in residents]
            # Deferred: the handshake path calls with dispatch=False and
            # the registration ack must reach the daemon first (see the
            # stale-name destroy below for the same pattern).
            threading.Thread(
                target=lambda: [conn.destroy_actor(aid)
                                for aid in stale_ids],
                name="ray_tpu-fenced-actor-destroy", daemon=True).start()
        else:
            unrecoverable = []
            for actor_hex in residents:
                try:
                    if not self._rebind_remote_actor(conn, node_id,
                                                     actor_hex):
                        unrecoverable.append(actor_hex)
                except Exception:  # noqa: BLE001 - best effort per actor
                    logger.exception("failed to rebind actor %s",
                                     actor_hex)
            if unrecoverable and self.gcs_store is not None:
                # Residents with no surviving record (e.g. serve
                # replicas of the dead head's generation, whose records
                # the recovery retired) are zombies: nothing can ever
                # route to them again, but they'd keep holding the
                # daemon's resources. Destroy them — deferred for the
                # same ack-ordering reason as the fenced path above.
                logger.warning(
                    "Node %s announced %d resident actor(s) with no "
                    "surviving record: destroying", node_id.hex()[:12],
                    len(unrecoverable))
                dead_ids = [ActorID(bytes.fromhex(h))
                            for h in unrecoverable]
                threading.Thread(
                    target=lambda: [conn.destroy_actor(aid)
                                    for aid in dead_ids],
                    name="ray_tpu-unrecoverable-actor-destroy",
                    daemon=True).start()
        self.scheduler.reschedule_lost_bundles()
        if dispatch:
            # NOT under the caller's conn._send_lock (the handshake path
            # passes dispatch=False): task sends are inline, and sending
            # on a connection whose send lock the caller already holds
            # would self-deadlock.
            self._dispatch()
        return node_id

    def _rebind_remote_actor(self, conn, node_id: NodeID,
                             actor_hex: str) -> bool:
        """Rebind one daemon-announced resident actor. Returns True when
        the resident stays valid (rebound, same-life refresh, or handled
        another way); False means no record survives for it and the
        caller should destroy the zombie instance."""
        from ray_tpu._private.multinode import RemoteActorInstance
        rec = (self.gcs_store.actors.get(actor_hex)
               if self.gcs_store is not None else None)
        if rec is None:
            # Not a persisted actor (or persistence disabled). With a
            # store attached, "no record" means retired/unrecoverable.
            return self.gcs_store is None
        actor_id = ActorID(bytes.fromhex(actor_hex))
        cls_bytes = rec.get("cls_bytes")
        if cls_bytes is not None:
            # Export BEFORE taking the runtime lock (the function table
            # has its own locking); an orphan export on the bail-out
            # paths below is harmless.
            fn_id = self.functions.export_bytes(cls_bytes)
        resources = dict(rec.get("resources") or {})
        stale = False
        with self._lock:
            existing = self._actors.get(actor_id)
            if existing is not None and not existing.dead:
                # Same-life daemon reconnect: refresh the wire proxy and
                # the placement so node-death handling tracks the NEW
                # connection.
                existing.instance = RemoteActorInstance(conn, actor_id)
                existing.creation_spec._node_id = node_id  # type: ignore
                return True
            if existing is not None:
                # Died in this head's eyes; do not resurrect — and tell
                # the caller so the zombie instance is torn down.
                return False
            name_owner = self._named_actors.get(
                (rec["namespace"], rec["name"])) if rec["name"] else None
            if name_owner is not None and name_owner != actor_id:
                stale = True  # handled below, outside the lock
            elif cls_bytes is None:
                # Unpicklable class: handles cannot be rebuilt, but the
                # instance is alive and harmless — leave it be.
                return True
            else:
                # Name check and registration happen under ONE lock
                # acquisition: a concurrent create_actor can never claim
                # the name between our check and our insert.
                lifetime = rec.get("lifetime")
                creation_args: tuple = ()
                creation_kwargs: dict = {}
                max_restarts = 0
                if lifetime == "detached":
                    # Detached records carry the pickled __init__ args,
                    # so the rebound actor keeps its FULL restart budget
                    # — a later node death re-runs the creation
                    # elsewhere. Undecodable payload degrades to
                    # rebind-without-restart (max_restarts=0), matching
                    # plain named actors.
                    payload = rec.get("creation_payload")
                    if payload is not None:
                        try:
                            creation_args, creation_kwargs = \
                                serialization.deserialize(payload)
                            max_restarts = rec["max_restarts"]
                        except Exception:  # noqa: BLE001
                            creation_args, creation_kwargs = (), {}
                spec = TaskSpec(
                    task_id=TaskID.for_normal_task(self.job_id),
                    kind=TaskKind.ACTOR_CREATION, function_id=fn_id,
                    args=creation_args, kwargs=creation_kwargs,
                    resources=resources,
                    num_returns=1, name=rec["name"] or "actor",
                    actor_id=actor_id)
                # The creation never re-runs on THIS head unless the
                # node dies — but the restart clone goes through the
                # normal creation path, which seals return_ids[0].
                spec.return_ids = [ObjectID.for_return(spec.task_id, 1)]
                # Node-death bookkeeping must see where the instance
                # lives, and release needs the acquire marker.
                spec._node_id = node_id  # type: ignore[attr-defined]
                spec._acquired_bundle = -1  # type: ignore[attr-defined]
                # Non-detached rebound actors cannot be restarted in
                # place (their creation args died with the old head) —
                # max_restarts=0.
                state = ActorState(actor_id, spec, max_restarts,
                                   rec["max_concurrency"],
                                   rec["name"], rec["namespace"],
                                   concurrency_groups=rec.get(
                                       "concurrency_groups"),
                                   lifetime=lifetime)
                state.num_restarts = int(rec.get("num_restarts") or 0)
                state.instance = RemoteActorInstance(conn, actor_id)
                state.executor = self._make_actor_executor(state)
                state.created.set()
                self._actors[actor_id] = state
                if rec["name"]:
                    self._named_actors[(rec["namespace"], rec["name"])] = \
                        actor_id
        if stale:
            # A NEW actor took this name on the restarted head before
            # the old daemon reconnected — the live one wins; drop the
            # stale record and tear down the zombie instance.
            logger.warning(
                "Not rebinding stale actor %s: name %r is taken by a "
                "newer actor", actor_hex[:12], rec["name"])
            if self.gcs_store is not None:
                self.gcs_store.remove_actor(actor_hex)
            # Deferred: the handshake thread holds conn._send_lock (the
            # ack must be the daemon's first frame) and destroy_actor
            # sends on that same non-reentrant lock — a direct call here
            # deadlocks the registration. The helper thread parks on the
            # lock and the destroy frame goes out right after the ack.
            threading.Thread(
                target=lambda: conn.destroy_actor(actor_id),
                name="ray_tpu-stale-actor-destroy", daemon=True).start()
            return True
        # The resident instance still consumes its creation resources on
        # that node — re-reserve them so the restarted head cannot
        # double-book the chips/CPUs (force: the node just (re)joined
        # advertising its FULL capacity, and the actor's claim predates
        # any new scheduling).
        if resources:
            self.scheduler.force_acquire(resources, node_id)
        from ray_tpu._private import builtin_metrics
        builtin_metrics.actor_restarts().inc(tags={
            "kind": ("detached_rebind"
                     if rec.get("lifetime") == "detached" else "rebind")})
        logger.info("Rebound daemon-resident actor %s (%s) after head "
                    "restart", rec["name"] or actor_hex[:12],
                    actor_hex[:12])
        return True

    def unregister_remote_node(self, node_id: NodeID) -> None:
        with self._lock:
            self._remote_nodes.pop(node_id, None)
        # Start the staleness clock on the node's series: Prometheus
        # gets a last look, then they fall out of the exposition.
        self._cluster_metrics.mark_node_dead(node_id.hex())
        self.remove_node(node_id)

    def _remote_conn(self, spec: TaskSpec):
        node_id = getattr(spec, "_node_id", None)
        if node_id is None:
            return None
        with self._lock:
            return self._remote_nodes.get(node_id)

    def remote_node_stats(self) -> Dict[str, dict]:
        """Per-daemon counters (object-transfer bytes etc.), keyed by node
        id hex — the observability hook for the node-to-node data plane."""
        with self._lock:
            conns = dict(self._remote_nodes)
        out = {}
        for node_id, conn in conns.items():
            try:
                out[node_id.hex()] = conn.get_stats()
            except Exception:  # noqa: BLE001 - dying node mid-query
                continue
        return out

    def _result_store_limit(self, spec: TaskSpec) -> int:
        """Results above this size stay daemon-resident. Multi-return
        tasks split PER ELEMENT daemon-side (shuffle partials must ride
        the inter-daemon data plane, not the head); dynamic generators
        come back whole (item count is unknown until unpacked)."""
        if spec.num_returns == "dynamic" or spec.num_returns == 0:
            return 0
        return self._cfg_inline_limit

    def _invoke_user(self, spec: TaskSpec, fn, args, kwargs):
        """The user-code call seam: local nodes call directly (thread
        backend) or in a leased worker process; tasks placed on a remote
        daemon proxy the call over its connection (this head thread
        blocks while the daemon's CPUs do the work)."""
        conn = self._remote_conn(spec)
        if conn is None:
            if self._use_process_worker(spec):
                return self._run_in_worker_process(spec, args, kwargs)
            return fn(*args, **kwargs)
        return conn.execute_task(spec, self.functions, args, kwargs,
                                 store_limit=self._result_store_limit(spec))

    # -- process workers (reference: raylet WorkerPool) -----------------

    def _get_process_pool(self):
        # Workers get a head address so nested ray_tpu API calls bind a
        # ClientRuntime (the connected-runtime property; see
        # _private/client_runtime.py) instead of an isolated auto-init.
        # This opens the loopback head port implicitly — same trust model
        # as the reference (every ray.init binds unauthenticated local
        # ports); multi-tenant hosts share that exposure either way.
        # start_head_server is idempotent + takes the lock itself; call it
        # BEFORE taking the runtime lock here (no nested acquisition).
        head_addr = self.start_head_server()
        with self._lock:
            if self._process_pool is None:
                from ray_tpu._private.worker_process import WorkerProcessPool
                native = self.store.native
                self._process_pool = WorkerProcessPool(
                    store_name=native.name if native is not None else None,
                    head_address=head_addr)
                # Batches head-pool workers piggyback on task replies
                # merge straight into the cluster registry (the workers
                # run on the head node).
                self._process_pool.metrics_sink = self._publish_head_metrics
                self._process_pool.profile_sink = \
                    self._publish_head_profile
                self._process_pool.flow_sink = self._publish_head_flow
            return self._process_pool

    def _use_process_worker(self, spec: TaskSpec) -> bool:
        """Process isolation policy: explicit opt-in (worker_process) or
        an isolation-requiring runtime env (pip/venv). TPU tasks never
        qualify — a TPU chip is single-process and this process owns it,
        so they run on the thread backend (idiomatic for JAX: XLA
        releases the GIL during compute)."""
        renv = spec.runtime_env or {}
        if renv.get("worker_process") is False:
            return False
        if spec.resources.get("TPU", 0) > 0:
            return False
        return bool(renv.get("worker_process") or renv.get("pip")
                    or renv.get("conda"))

    def _worker_exec_msg(self, spec: TaskSpec, args, kwargs, handle,
                         mode: str = "task", method: Optional[str] = None
                         ) -> dict:
        try:
            fn_bytes = self.functions.get_bytes(spec.function_id) \
                if mode != "actor_call" else None
        except KeyError:
            raise ValueError(
                f"Task/actor {spec.name} captured objects that cannot be "
                "serialized, so it cannot run in a worker process. Make "
                "it picklable or drop worker_process from runtime_env.")
        if fn_bytes is not None and spec.function_id in handle.shipped:
            fn_bytes = None
        elif fn_bytes is not None:
            handle.shipped.add(spec.function_id)
        return {
            "type": "exec",
            "mode": mode,
            "fn_id": spec.function_id,
            "fn_bytes": fn_bytes,
            "method": method,
            "task_id": spec.task_id.hex(),
            "payload": serialization.serialize((args, kwargs)),
            "runtime_env": {k: v for k, v in (spec.runtime_env or
                                              {}).items()
                            if k not in ("worker_process",)},
            "name": spec.name,
        }

    def _run_in_worker_process(self, spec: TaskSpec, args, kwargs):
        """Run one task on a leased worker subprocess. The executor
        thread blocks on the worker socket; a SIGKILL of the worker
        (force-cancel, OOM kill) surfaces as WorkerCrashedError."""
        from ray_tpu._private.worker_process import (WorkerCrashedError,
                                                     run_on_worker)
        from ray_tpu._private.runtime_env_pip import python_for_env
        pool = self._get_process_pool()
        # pip envs run under their venv interpreter (URI-cached venv,
        # built on first use); pool reuse is keyed by interpreter.
        handle = pool.lease(python_for_env(spec.runtime_env))
        handle.current_task = spec.task_id
        with self._lock:
            self._proc_tasks[spec.task_id] = handle
        try:
            msg = self._worker_exec_msg(spec, args, kwargs, handle)
            try:
                return run_on_worker(handle, msg)
            except TaskError as te:
                from ray_tpu._private.worker_process import \
                    WorkerFnMissingError
                if not isinstance(te.cause, WorkerFnMissingError):
                    raise
                # The worker lost/never-cached the function while our
                # shipped-set said otherwise — heal by resending with
                # bytes once.
                handle.shipped.discard(spec.function_id)
                msg = self._worker_exec_msg(spec, args, kwargs, handle)
                return run_on_worker(handle, msg)
        except WorkerCrashedError:
            if getattr(spec, "_cancel_requested", False):
                raise TaskCancelledError(spec.task_id)
            raise
        finally:
            handle.current_task = None
            with self._lock:
                self._proc_tasks.pop(spec.task_id, None)
            pool.release(handle)

    def _invoke_actor_init(self, spec: TaskSpec, cls, args, kwargs):
        conn = self._remote_conn(spec)
        if conn is not None:
            from ray_tpu._private.multinode import RemoteActorInstance
            conn.create_actor(spec, self.functions, args, kwargs)
            return RemoteActorInstance(conn, spec.actor_id)
        if self._use_process_worker(spec):
            # Dedicated worker process for the actor's whole life
            # (reference: dedicated workers for actors, worker_pool.h).
            from ray_tpu._private.worker_process import (
                ProcessActorInstance, run_on_worker)
            from ray_tpu._private.runtime_env_pip import python_for_env
            pool = self._get_process_pool()
            handle = pool.lease(python_for_env(spec.runtime_env))
            handle.actor_id = spec.actor_id.hex()
            try:
                msg = self._worker_exec_msg(spec, args, kwargs, handle,
                                            mode="actor_init")
                run_on_worker(handle, msg)
            except BaseException:
                handle.kill()
                raise
            return ProcessActorInstance(handle, pool)
        return cls(*args, **kwargs)

    def _destroy_remote_instance(self, state: "ActorState") -> None:
        """Best-effort teardown of a daemon-resident or worker-process
        actor instance."""
        from ray_tpu._private.multinode import RemoteActorInstance
        from ray_tpu._private.worker_process import ProcessActorInstance
        instance = state.instance
        if isinstance(instance, RemoteActorInstance):
            instance.conn.destroy_actor(state.actor_id)
        elif isinstance(instance, ProcessActorInstance):
            instance.destroy()

    def _node_death_invalidated(self, spec: TaskSpec,
                                exc: BaseException) -> bool:
        """After a RemoteNodeDiedError, wait briefly for the connection's
        death handler to invalidate the spec (it restarts actors / retries
        tasks itself); returns whether this thread should discard its
        work. Closes the race where the send side observes the dead socket
        before the recv side has run remove_node."""
        from ray_tpu._private.multinode import RemoteNodeDiedError
        if not isinstance(exc, RemoteNodeDiedError):
            return False
        import time as _time

        from ray_tpu._private.channel import Backoff
        # Jittered backoff, not a fixed-cadence spin: the death handler
        # usually invalidates within a millisecond or two, and under a
        # mass node death dozens of waiter threads polling in lockstep
        # contend on the spec locks the handler needs.
        bo = Backoff(0.002, 0.1)
        deadline = _time.monotonic() + 5.0
        while _time.monotonic() < deadline:
            if getattr(spec, "invalidated", False):
                return True
            bo.sleep()
        return bool(getattr(spec, "invalidated", False))

    def remove_node(self, node_id: NodeID) -> None:
        """Simulate node failure: running tasks there fail (and retry
        elsewhere within budget), actors restart elsewhere (max_restarts),
        objects whose primary copy lived there are reconstructed from
        lineage (reference: NodeManager death handling + ObjectRecovery)."""
        state = self.scheduler.remove_node(node_id)
        if state is None:
            return
        self._drop_node_leases(node_id)
        # 1) In-flight tasks on the dead node. A task whose results are
        # already sealed has effectively completed — its worker thread just
        # hasn't deregistered yet; retrying it would double-execute (the
        # lost-copy case is _recover_lost_objects' job, which re-runs from
        # lineage exactly once).
        with self._lock:
            doomed = [
                s for s in self._inflight.values()
                if getattr(s, "_node_id", None) == node_id
                and s.kind != TaskKind.ACTOR_CREATION
                and not (s.return_ids and all(
                    self.store.contains(oid) for oid in s.return_ids))]
            # Mark INSIDE the lock: _store_remote_result seals results
            # under the same lock, so a completing remote task either
            # sealed before this point (→ not doomed, recovery below
            # reconstructs its daemon-resident value) or observes
            # invalidated and discards — a stale seal can never shadow
            # the retry.
            for s in doomed:
                s.invalidated = True
        for spec in doomed:
            self._try_claim_finalize(spec)
            # _retry_after_node_death releases the zombie spec's dependency
            # pins AFTER the retry clone re-pins them (releasing first could
            # free the args the retry still needs).
            self._retry_after_node_death(spec, node_id)
        # 2) Actors homed on the dead node.
        with self._lock:
            actors_snapshot = list(self._actors.values())
        dead_actors = [a for a in actors_snapshot
                       if getattr(a.creation_spec, "_node_id", None) == node_id
                       and not a.dead]
        for actor in dead_actors:
            self._handle_actor_node_death(actor, node_id)
        # 3) Lost objects → lineage reconstruction.
        self._recover_lost_objects(node_id)
        self._recover_remote_values(node_id)
        # 4) PG bundles on the dead node move to live nodes (best effort).
        self.scheduler.reschedule_lost_bundles()
        self._dispatch()

    def _retry_after_node_death(self, spec: TaskSpec, node_id: NodeID) -> None:
        err = NodeDiedError(
            f"Task {spec.name} failed: node {node_id.hex()[:12]} died while "
            "it was running.")
        if spec.attempt_number < spec.max_retries:
            # Clone: the original spec stays invalidated so its (still
            # running) zombie thread can't store results or double-release.
            retry = spec.clone_for_retry()
            with self._lock:
                for oid in retry.return_ids:
                    if oid in self._lineage:
                        self._lineage[oid] = retry
            logger.warning("Node %s died; retrying task %s (attempt %d/%d)",
                           node_id.hex()[:12], spec.name,
                           retry.attempt_number, retry.max_retries)
            # Pin the retry's deps BEFORE dropping the zombie's pins, so
            # shared argument objects never hit zero in between.
            self._register_task_refs(retry)
            self._release_task_deps(spec)
            self._resolve_dependencies(retry)
        else:
            # Seal the error directly (the spec stays invalidated so the
            # zombie thread skips its own bookkeeping). Skip objects whose
            # every handle is gone — sealing them would leak forever.
            self._release_task_deps(spec)
            for oid in spec.return_ids:
                self._store_if_referenced(oid, err, is_exception=True)
            self._record_event(spec, "FAILED")

    def _handle_actor_node_death(self, state: ActorState,
                                 node_id: NodeID) -> None:
        cause = ActorDiedError(
            state.actor_id,
            f"The actor died because its node {node_id.hex()[:12]} died.")
        can_restart = (state.max_restarts == -1
                       or state.num_restarts < state.max_restarts)
        with state.lock:
            old_executor = state.executor
            state.executor = None
            state.instance = None
            state.created.clear()
            unfinished = list(state.unfinished.values())
            state.unfinished.clear()
            state.pre_creation_queue.clear()
            if old_executor is not None:
                old_executor.stop()
            if can_restart:
                state.num_restarts += 1
                for spec in unfinished:
                    handle = spec.caller_handle_id or "default"
                    seq_state = state.seq_state.setdefault(
                        handle, {"next": 1, "waiting": {}, "aborted": set()})
                    if spec.sequence_number >= seq_state["next"]:
                        seq_state["aborted"].add(spec.sequence_number)
                for seq_state in state.seq_state.values():
                    self._drain_actor_seq(state, seq_state)
            else:
                state.dead = True
                state.death_cause = cause
                state.created.set()
        for spec in unfinished:
            self._store_error(spec, cause)
        if not can_restart:
            with self._lock:
                if state.name and not state.detached:
                    # Detached actors keep their registry entry even when
                    # the restart budget is spent: ONLY kill() removes it
                    # (get_actor still resolves; calls raise ActorDied).
                    self._named_actors.pop((state.namespace, state.name), None)
            return
        if state.name and self.gcs_store is not None:
            self.gcs_store.update_actor(state.actor_id.hex(),
                                        num_restarts=state.num_restarts)
        # Re-dispatch a CLONE of the creation task through the normal path so
        # the actor comes up on an alive node with a fresh acquisition. The
        # original spec stays invalidated: if its __init__ is still running
        # on a zombie thread, that thread discards its work.
        state.creation_spec.invalidated = True
        doomed_creation = state.creation_spec
        creation = doomed_creation.clone_for_retry()
        with state.lock:
            state.creation_spec = creation
            state.resources_released = False
        logger.warning("Node %s died; restarting actor %s elsewhere "
                       "(restart %d)", node_id.hex()[:12],
                       state.name or state.actor_id.hex()[:8],
                       state.num_restarts)
        self._register_task_refs(creation)
        self._release_task_deps(doomed_creation)
        with self._lock:
            self._ready.append(creation)

    def _recover_lost_objects(self, node_id: NodeID) -> None:
        with self._lock:
            lost = [oid for oid, nid in self._object_locations.items()
                    if nid == node_id]
            for oid in lost:
                self._object_locations.pop(oid, None)
        # The sim keeps values in the head store with a virtual location;
        # only sealed ("present") copies count as lost primaries.
        self._reconstruct_or_seal(
            lost, node_id,
            skip=lambda oid: not self.store.contains(oid))

    def _recover_remote_values(self, node_id: NodeID) -> None:
        """Daemon-resident result payloads die with their daemon: values
        the head already materialized are safe; the rest walk the
        recovery tiers — another in-memory replica holder, then a
        durable spill URI, then lineage re-execution — and only a full
        miss seals ObjectLostError."""
        with self._lock:
            lost = [(oid, k) for oid, (nid, k)
                    in self._remote_values.items() if nid == node_id]
            for oid, key in lost:
                self._remote_values.pop(oid, None)
                self._remote_keys.pop(key, None)
            # The dead daemon's cached replicas died with it.
            for reps in self._object_replicas.values():
                reps.pop(node_id, None)
        self._reconstruct_or_seal([oid for oid, _k in lost], node_id,
                                  skip=self.store.is_materialized,
                                  keys=dict(lost))

    def _recover_from_replica(self, oid: ObjectID, key: str,
                              node_id: NodeID) -> bool:
        """Tier 1: another daemon pulled a copy of this object at some
        point — if it is STILL resident there (the cache is evictable,
        so ask), re-point the head's lazy fetch at that holder: no IO,
        no re-execution (reference: object directory giving the pull
        manager its next location)."""
        from ray_tpu._private.dataplane import stat_remote
        from ray_tpu._private.multinode import RemoteValueStub
        with self._lock:
            holders = [(nid, self._remote_nodes.get(nid))
                       for nid in (self._object_replicas.get(oid) or {})
                       if nid != node_id]
        for nid, conn in holders:
            if conn is None or conn.object_addr is None:
                continue
            try:
                size = stat_remote(conn.object_addr, key, timeout=5.0)
            except (OSError, ConnectionError):
                continue
            if size < 0:
                continue  # evicted there since the pull
            stub = RemoteValueStub(conn, key, size)
            if not self.store.replace_remote_fetch(oid, stub.fetch,
                                                   size):
                return False  # entry freed/materialized meanwhile
            with self._lock:
                self._remote_values[oid] = (nid, key)
                self._remote_keys[key] = oid
            builtin_metrics.object_restores().inc(
                tags={"source": "replica"})
            self._cluster_metrics.events.record(
                "objects", f"object {oid.hex()[:12]} re-pointed at "
                f"replica holder {nid.hex()[:12]}",
                severity="info", node_id=node_id.hex(),
                labels={"tier": "replica"})
            logger.warning(
                "object %s survives node %s death on replica holder %s",
                oid.hex()[:12], node_id.hex()[:12], nid.hex()[:12])
            return True
        return False

    def _recover_from_spill(self, oid: ObjectID, key: str,
                            node_id: NodeID) -> bool:
        """Tier 2: the dead daemon had spilled this object through a
        durable backend — any node (here: the head) can read the URI
        back. Restores eagerly into the head store; the producer task
        does NOT re-run. A missing/truncated file is a tier miss."""
        with self._lock:
            rec = self._spill_uris_by_key.pop(key, None)
        if rec is None:
            return False
        uri, size = rec
        from ray_tpu._private.multinode import _loads
        from ray_tpu._private.spill import read_uri
        payload = read_uri(uri, size)
        if payload is None:
            return False  # unreadable: fall down to lineage
        try:
            value = _loads(payload)
        except Exception:  # noqa: BLE001 - corrupt payload = tier miss
            logger.exception("spilled payload %s is corrupt", uri)
            return False
        self.store.invalidate([oid])
        self.store.put_inline(oid, value)
        builtin_metrics.object_restores().inc(tags={"source": "spill"})
        self._cluster_metrics.events.record(
            "objects", f"object {oid.hex()[:12]} restored from durable "
            f"spill after node {node_id.hex()[:12]} death",
            severity="info", node_id=node_id.hex(),
            labels={"tier": "spill"})
        logger.warning(
            "restored object %s from spill URI %s after node %s death",
            oid.hex()[:12], uri, node_id.hex()[:12])
        return True

    def _restore_from_lineage(self, oid: ObjectID) -> bool:
        """ObjectStore restore-miss hook: a head-local spilled entry's
        file is gone (chaos, scrubbed tmpdir). Re-execute the creating
        task — get() re-enters and waits for the re-seal. False when no
        usable lineage exists (the store then raises ObjectLostError)."""
        with self._lock:
            spec = self._lineage.get(oid)
        if spec is None or spec.kind == TaskKind.ACTOR_TASK or \
                getattr(spec, "invalidated", False) or \
                spec.attempt_number >= spec.max_retries:
            return False
        logger.warning(
            "spilled payload of object %s is unreadable; re-executing "
            "task %s from lineage", oid.hex()[:12], spec.name)
        clone = spec.clone_for_retry()
        with self._lock:
            for roid in clone.return_ids:
                if roid in self._lineage:
                    self._lineage[roid] = clone
        self.store.invalidate(list(clone.return_ids))
        builtin_metrics.object_restores().inc(tags={"source": "lineage"})
        self._cluster_metrics.events.record(
            "objects", f"object {oid.hex()[:12]} re-executing producer "
            f"task {spec.name} from lineage (spill unreadable)",
            severity="warning", labels={"tier": "lineage"})
        self._register_task_refs(clone)
        self._resolve_dependencies(clone)
        return True

    def _reconstruct_or_seal(self, lost: List[ObjectID], node_id: NodeID,
                             skip, keys: Optional[Dict[ObjectID, str]]
                             = None) -> None:
        """Shared node-death recovery policy, cheapest tier first: an
        object with another in-memory replica holder re-points its
        fetch; one with a durable spill URI restores from disk; the
        rest re-execute their creating task from lineage (within retry
        budget) or seal ObjectLostError (reference:
        object_recovery_manager.h + local_object_manager spill URLs).
        ``keys`` maps lost oids to their daemon object keys (the handle
        the replica/spill location tables are keyed by)."""
        to_reconstruct: Dict[TaskID, TaskSpec] = {}
        plain_lost: List[ObjectID] = []
        for oid in lost:
            if skip(oid):
                continue
            key = (keys or {}).get(oid)
            if key is not None:
                if self._recover_from_replica(oid, key, node_id):
                    continue
                if self._recover_from_spill(oid, key, node_id):
                    continue
            spec = self._lineage.get(oid)
            if spec is None or spec.kind == TaskKind.ACTOR_TASK or \
                    getattr(spec, "invalidated", False) or \
                    spec.attempt_number >= spec.max_retries:
                # No lineage (e.g. ray.put or actor-task result), or the
                # retry budget is spent: unrecoverable (reference seals
                # ObjectReconstructionFailedError in this case).
                plain_lost.append(oid)
            else:
                to_reconstruct[spec.task_id] = spec
                builtin_metrics.object_restores().inc(
                    tags={"source": "lineage"})
        invalidate = [oid for spec in to_reconstruct.values()
                      for oid in spec.return_ids]
        self.store.invalidate(invalidate)
        for oid in plain_lost:
            self.store.invalidate([oid])
            self.store.put_inline(oid, ObjectLostError(
                f"Object {oid.hex()} was on node {node_id.hex()[:12]} which "
                "died, and it cannot be reconstructed (no task lineage, or "
                "the task's retry budget is exhausted)."),
                is_exception=True)
        for spec in to_reconstruct.values():
            logger.warning("Reconstructing objects of task %s after node %s "
                           "death", spec.name, node_id.hex()[:12])
            clone = spec.clone_for_retry()
            with self._lock:
                for oid in clone.return_ids:
                    if oid in self._lineage:
                        self._lineage[oid] = clone
            self._register_task_refs(clone)
            self._resolve_dependencies(clone)

    # ------------------------------------------------------------------
    # Introspection / lifecycle
    # ------------------------------------------------------------------

    def _record_event(self, spec: TaskSpec, status: str) -> None:
        # Node attribution: set at _try_launch (None for pre-placement
        # SUBMITTED events) — feeds the per-node rate series and the
        # state API's node_id column.
        nid = getattr(spec, "_node_id", None)
        node_hex = nid.hex() if nid is not None else None
        builtin_metrics.record_task_event(status, node_hex)
        if len(self._task_events) < self._cfg_max_task_events:
            self._task_events.append({
                "task_id": spec.task_id.hex(),
                "name": spec.name,
                "status": status,
                "node_id": node_hex,
                "time": time.time(),
            })
        # State transitions fan out on the pubsub hub (reference:
        # TaskEventBuffer flush → GcsTaskManager → subscribers).
        self.pubsub.publish("task_events", spec.task_id.hex(), status)

    def task_events(self) -> List[dict]:
        return list(self._task_events)

    def pending_resource_demand(self) -> List[Dict[str, float]]:
        """Resource shapes of queued-but-unschedulable tasks (the analog of
        the reference's backlog/demand report feeding autoscaler
        LoadMetrics)."""
        with self._lock:
            return [dict(s.resources) for s in self._ready_specs_locked()
                    if s.resources]

    def cluster_resources(self) -> Dict[str, float]:
        return dict(self.scheduler.total)

    def available_resources(self) -> Dict[str, float]:
        return dict(self.scheduler.available)

    def shutdown(self) -> None:
        from ray_tpu.exceptions import RayError

        # Log subsystem first: the monitor's final drain still has a
        # live pubsub, and the printer flushes what's already queued.
        # clear_session() detaches the process globals so later spawns
        # in this process don't write into a dead session's directory
        # (the files themselves stay for `ray-tpu logs`).
        from ray_tpu._private import ray_logging
        if self._metrics_agent is not None:
            # No drain: the only sink is this runtime's own registry.
            self._metrics_agent.stop(drain=False)
            self._metrics_agent = None
        if self._log_monitor is not None:
            self._log_monitor.stop()
            self._log_monitor = None
        if self._log_printer is not None:
            self._log_printer.stop()
            self._log_printer = None
        ray_logging.clear_session()
        if self.gcs_store is not None:
            rec = self.gcs_store.jobs.get(self._gcs_job_key)
            if rec is not None:
                rec = dict(rec, status="FINISHED",
                           end_time=time.time())
                self.gcs_store.record_job(self._gcs_job_key, rec)
            # Land any throttled object-directory writes before exit.
            try:
                self.gcs_store.flush()
            except OSError:
                pass
        # Detached actors survive an orderly shutdown (reference: GCS-
        # owned lifetime): their host daemons are closed WITHOUT the
        # shutdown frame — the daemon treats it as connection loss,
        # keeps the resident instance, and a later head on the same
        # port + gcs_store_path rebinds it. Non-detached named actors
        # are reaped for real: registry record removed (no rebind after
        # an orderly exit) and resident instances on surviving daemons
        # destroyed.
        with self._lock:
            remote_nodes = dict(self._remote_nodes)
            actors = list(self._actors.values())
        detached_nodes = set()
        for state in actors:
            node_id = getattr(state.creation_spec, "_node_id", None)
            if state.detached and not state.dead \
                    and node_id in remote_nodes:
                detached_nodes.add(node_id)
        for state in actors:
            if state.detached and not state.dead:
                continue
            node_id = getattr(state.creation_spec, "_node_id", None)
            if state.name and self.gcs_store is not None:
                self.gcs_store.remove_actor(state.actor_id.hex())
            if node_id in detached_nodes and not state.dead:
                # This daemon outlives the driver; don't leave a zombie
                # resident instance it would re-announce on reconnect.
                try:
                    remote_nodes[node_id].destroy_actor(state.actor_id)
                except Exception:  # noqa: BLE001 - best effort
                    pass
        if self._head_server is not None:
            self._head_server.stop(keep_nodes=detached_nodes)
            self._head_server = None
        with self._lock:
            self._remote_nodes.clear()
            self._shutdown = True
            workers = list(self._all_workers)
            actors = list(self._actors.values())
        for state in actors:
            if state.executor is not None:
                state.executor.stop()
            state.dead = True
            state.created.set()
        for w in workers:
            w.stop()
        if self._process_pool is not None:
            self._process_pool.shutdown()
        if self.memory_monitor is not None:
            self.memory_monitor.stop()
        # Pooled data-plane sockets + owner borrow channels die with the
        # runtime — idle keep-alive connections to (possibly dead)
        # peers must not outlive it as CLOSE_WAIT fds.
        from ray_tpu._private import dataplane as _dp
        _dp.GLOBAL_PEER_CONNS.close()
        # The GC thread must be fully stopped BEFORE the native store is
        # closed: a free() racing close() would touch an unmapped arena
        # (segfault). Wake it, let it observe _shutdown, and join.
        self._gc_event.set()
        self._gc_thread.join(timeout=5)
        # Wake every blocked get with an error rather than hanging.
        self.store.fail_all_pending(
            RayError("The runtime was shut down while this object was "
                     "still pending."))
        if self.store.native is not None:
            if self._gc_thread.is_alive():
                # Better to leak the arena than unmap it under a live
                # free() (the join timed out — should not happen).
                logger.warning("GC thread still alive at shutdown; "
                               "leaving the native arena mapped")
            else:
                self.store.native.close()
