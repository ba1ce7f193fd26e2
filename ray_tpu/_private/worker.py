"""Global worker state and the top-level API implementations.

Analog of the reference's python/ray/_private/worker.py (ray.init/get/put/
wait/kill/cancel/get_actor live here; the module-level ``global_worker``
mirrors the reference's singleton).
"""

from __future__ import annotations

import atexit
import logging
import os
import threading
from typing import Any, Dict, List, Optional, Sequence, Union

from ray_tpu._private import builtin_metrics
from ray_tpu._private.ids import JobID
from ray_tpu._private.object_ref import ObjectRef
from ray_tpu._private.resource_spec import detect_node_resources
from ray_tpu._private.runtime import Runtime

logger = logging.getLogger("ray_tpu")


class Worker:
    def __init__(self):
        self._runtime: Optional[Runtime] = None
        self._lock = threading.Lock()
        self.job_id: Optional[JobID] = None
        self.namespace: str = "default"

    @property
    def connected(self) -> bool:
        return self._runtime is not None

    @property
    def runtime(self) -> Runtime:
        rt = self._runtime
        if rt is not None and getattr(rt, "is_client", False) and rt.closed:
            # The head connection died (head restart): drop the stale
            # client runtime so the next use reconnects.
            with self._lock:
                if self._runtime is rt:
                    self.set_runtime(None)
        if self._runtime is None:
            # Auto-init on first use, matching the reference's behavior of
            # implicit ray.init() in ray.get/put/remote. In a daemon/worker
            # execution context this binds a ClientRuntime wired to the
            # head (never an isolated local runtime — the anti-split-brain
            # rule; reference: every worker embeds a CoreWorker connected
            # to the GCS, core_worker.cc:1762).
            init()
        return self._runtime

    def set_runtime(self, runtime: Optional[Runtime], job_id=None):
        self._runtime = runtime
        self.job_id = job_id


global_worker = Worker()


def _client_context_address():
    """Detect a daemon/worker execution context: returns the head's
    (host, port) when this process should bind a ClientRuntime, else
    None (this process is — or may become — a head/driver)."""
    from ray_tpu._private import multinode as _mn
    daemon = _mn._current_daemon
    if daemon is not None:
        return tuple(daemon.head_address)
    addr = os.environ.get("RAY_TPU_HEAD_ADDRESS")
    if addr:
        host, _, port = addr.rpartition(":")
        return (host or "127.0.0.1", int(port))
    return None


def init(
    address: Optional[str] = None,
    *,
    num_cpus: Optional[float] = None,
    num_tpus: Optional[float] = None,
    num_gpus: Optional[float] = None,
    resources: Optional[Dict[str, float]] = None,
    object_store_memory: Optional[int] = None,
    namespace: Optional[str] = None,
    ignore_reinit_error: bool = False,
    logging_level: int = logging.INFO,
    include_dashboard: Optional[bool] = None,
    runtime_env: Optional[dict] = None,
    log_to_driver: bool = True,
    _memory: Optional[float] = None,
    _system_config: Optional[dict] = None,
    **kwargs,
) -> "ClientContext":
    """Start (or connect to) a cluster.

    Round 1 runs a single-node in-process cluster; ``address`` other than
    None/"local"/"auto" is reserved for the multi-node control plane.
    """
    with builtin_metrics.setup_stage("init", "setup::init"), \
            global_worker._lock:
        if global_worker.connected:
            if ignore_reinit_error:
                return ClientContext(global_worker)
            raise RuntimeError(
                "Calling init() again after it has already been called. "
                "Pass ignore_reinit_error=True to suppress this error.")
        client_addr = _client_context_address()
        if client_addr is not None:
            # User code executing inside a node daemon or a worker
            # subprocess: bind a head-connected ClientRuntime so nested
            # .remote(), get_actor, refs, and PGs all resolve cluster-wide
            # (_private/client_runtime.py; reference: CoreWorker-in-every-
            # worker, gcs_actor_manager.cc:241 named-actor resolution).
            from ray_tpu._private.client_runtime import ClientRuntime
            runtime = ClientRuntime(client_addr)
            global_worker.set_runtime(runtime, runtime.job_id)
            global_worker.namespace = namespace or runtime.namespace
            return ClientContext(global_worker)
        if address is not None and address.startswith("ray://"):
            raise ValueError(
                f"Thin-client connections use the client API: "
                f"`api = ray_tpu.util.client.connect({address!r})` against "
                "a driver running `ray_tpu.util.client.serve()`.")
        if address not in (None, "local", "auto"):
            # Design stance (differs from the reference): the DRIVER is
            # the head. Remote machines join as node daemons (`ray-tpu
            # start --address`), and remote DRIVERS attach through the
            # thin client — there is no detached-GCS mode to connect to.
            raise ValueError(
                f"init(address={address!r}): this runtime has no "
                "detached cluster to connect to — the driver IS the "
                "head. To add this machine to a cluster as a worker "
                f"node: `ray-tpu start --address {address}`. To drive "
                "a remote cluster from here: `api = ray_tpu.util."
                f"client.connect({address!r})` against a driver "
                "running `ray_tpu.util.client.serve()`.")
        if num_tpus is None and num_gpus is not None:
            # GPU-option compatibility: the reference's num_gpus maps onto
            # the accelerator resource, which is TPU here.
            num_tpus = num_gpus
        node = detect_node_resources(
            num_cpus=num_cpus, num_tpus=num_tpus, memory=_memory,
            resources=resources)
        job_id = JobID.next()
        runtime = Runtime(node, job_id, system_config=_system_config,
                          log_to_driver=log_to_driver)
        global_worker.set_runtime(runtime, job_id)
        if namespace:
            global_worker.namespace = namespace
        logging.basicConfig(level=logging_level)
        atexit.register(_atexit_shutdown)
        # Head failover: with persisted serve deployments in the
        # gcs_store, replay them in the background now that the worker
        # wiring is attached (deploys run through the normal actor API).
        try:
            runtime.maybe_rehydrate_serve_async()
        except Exception:  # noqa: BLE001 - rehydration is best-effort
            logging.getLogger(__name__).exception(
                "serve rehydration trigger failed")
        return ClientContext(global_worker)


def _atexit_shutdown():
    try:
        shutdown()
    except Exception:  # noqa: BLE001
        pass


def shutdown() -> None:
    with global_worker._lock:
        if global_worker._runtime is not None:
            global_worker._runtime.shutdown()
            global_worker.set_runtime(None)
            global_worker.namespace = "default"


def is_initialized() -> bool:
    return global_worker.connected


def start_head_server(port: int = 0, host: str = "127.0.0.1"):
    """Open this driver's node-registration endpoint so `ray-tpu start
    --address host:port` daemons (other processes/hosts) can join the
    cluster as schedulable nodes (reference: `ray start --head` GCS).
    Returns (host, port).

    SECURITY: the control-plane protocol is unauthenticated cloudpickle —
    any peer that can reach the port gets arbitrary code execution (same
    trust model as the reference's GCS). The default bind is loopback;
    pass host="0.0.0.0" explicitly to serve a real multi-host cluster,
    and only on a trusted network."""
    if not is_initialized():
        init()
    return global_worker.runtime.start_head_server(host, port)


class ClientContext:
    """Return value of ``init`` — address info + context-manager support."""

    def __init__(self, worker: Worker):
        self._worker = worker
        self.address_info = {
            "node_id": worker.runtime.head_node_id.hex(),
            "address": "local",
            "num_cpus": worker.runtime.node_resources.num_cpus,
            "num_tpus": worker.runtime.node_resources.num_tpus,
        }

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        shutdown()

    def __getitem__(self, key):
        return self.address_info[key]


def put(value: Any) -> ObjectRef:
    if isinstance(value, ObjectRef):
        raise TypeError("Calling put() on an ObjectRef is not allowed.")
    return global_worker.runtime.put(value)


def broadcast(object_ref: ObjectRef, *,
              fanout: Optional[int] = None) -> dict:
    """Eagerly replicate ``object_ref``'s payload onto every live node
    through a bounded-fanout spanning tree (collective dataplane). A
    hint, not a requirement: tasks using the ref afterwards read a
    local replica instead of pulling from one source. Returns a summary
    dict ({"nodes", "depth", "edges", ...}) describing the tree."""
    if not isinstance(object_ref, ObjectRef):
        raise TypeError("broadcast() expects an ObjectRef, got "
                        f"{type(object_ref).__name__}")
    return global_worker.runtime.broadcast(object_ref, fanout=fanout)


def get(object_refs: Union[ObjectRef, Sequence[ObjectRef]],
        *, timeout: Optional[float] = None) -> Any:
    is_single = isinstance(object_refs, ObjectRef)
    refs = [object_refs] if is_single else list(object_refs)
    for r in refs:
        if not isinstance(r, ObjectRef):
            raise TypeError(
                f"get() expects ObjectRef or a list of ObjectRefs, got "
                f"{type(r).__name__}")
    values = global_worker.runtime.get(refs, timeout)
    return values[0] if is_single else values


def wait(object_refs: Sequence[ObjectRef], *, num_returns: int = 1,
         timeout: Optional[float] = None, fetch_local: bool = True):
    refs = list(object_refs)
    if len(set(refs)) != len(refs):
        raise ValueError("wait() expects a list of unique ObjectRefs.")
    for r in refs:
        if not isinstance(r, ObjectRef):
            raise TypeError("wait() expects a list of ObjectRefs.")
    if num_returns <= 0:
        raise ValueError("num_returns must be > 0")
    if num_returns > len(refs):
        raise ValueError(
            f"num_returns ({num_returns}) cannot exceed the number of refs "
            f"({len(refs)})")
    return global_worker.runtime.wait(refs, num_returns, timeout, fetch_local)


def kill(actor, *, no_restart: bool = True) -> None:
    from ray_tpu.actor import ActorHandle
    if not isinstance(actor, ActorHandle):
        raise TypeError("kill() expects an ActorHandle.")
    global_worker.runtime.kill_actor(actor._actor_id, no_restart)


def cancel(object_ref: ObjectRef, *, force: bool = False,
           recursive: bool = True) -> None:
    global_worker.runtime.cancel(object_ref, force)


def get_actor(name: str, namespace: Optional[str] = None):
    from ray_tpu.actor import ActorHandle
    runtime = global_worker.runtime
    actor_id = runtime.get_named_actor(
        name, namespace or global_worker.namespace)
    state = runtime.actor_state(actor_id)
    try:
        cls = runtime.functions.load(state.creation_spec.function_id)
    except KeyError:
        # Class bytes unavailable (unpicklable head-local class looked up
        # from a client runtime): the handle still works — methods bind by
        # name, the class is only cosmetic here.
        cls = None
    return ActorHandle(actor_id, cls, name=name,
                       class_name=getattr(state, "class_name", ""))


def cluster_resources() -> Dict[str, float]:
    return global_worker.runtime.cluster_resources()


def available_resources() -> Dict[str, float]:
    return global_worker.runtime.available_resources()


def nodes() -> List[dict]:
    return global_worker.runtime.scheduler.nodes_snapshot()


def cluster_usage() -> dict:
    """Per-node resource/object-store/memory usage synced from the node
    daemons (the ray-syncer view, _private/syncer.py — reference:
    common/ray_syncer/ray_syncer.h gossip aggregated by the GCS). Keys:
    ``nodes`` (node_id → component payloads), ``available_total``,
    ``version``. Empty until daemons have reported (one health-check
    period); the head node itself schedules in-process and is not
    listed."""
    srv = getattr(global_worker.runtime, "_head_server", None)
    if srv is not None:
        return srv.syncer.digest()
    # In-daemon execution (TPU tasks / actor methods on a node daemon):
    # serve the gossiped digest the head pushes on health pings.
    from ray_tpu._private import multinode as _mn
    daemon = _mn._current_daemon
    if daemon is not None:
        digest = daemon.cluster_digest.get()
        if digest is not None:
            return digest
    return {"version": 0, "nodes": {}, "available_total": {}}


def free(object_refs: Sequence[ObjectRef]) -> None:
    global_worker.runtime.free_objects(
        [r.object_id() for r in object_refs])


def get_tpu_ids() -> List[int]:
    """TPU chip ids assigned to the current task/actor (analog of the
    reference's get_gpu_ids, python/ray/_private/worker.py:832). Concurrent
    tasks receive disjoint chip sets; fractional requests (<1 chip) share
    and get []."""
    from ray_tpu._private.runtime import current_task_spec
    spec = current_task_spec()
    if spec is None:
        return []
    ids = getattr(spec, "_tpu_ids", None)
    if ids is None and spec.actor_id is not None:
        # Actor methods inherit the chips reserved at actor creation.
        state = global_worker.runtime.actor_state(spec.actor_id)
        if state is not None:
            ids = getattr(state.creation_spec, "_tpu_ids", None)
    return sorted(ids or [])


def get_tpu_devices() -> list:
    """The ``jax.Device``s behind ``get_tpu_ids()``: chip ``i`` is
    ``jax.local_devices()[i]`` of the process that owns the node's chips
    (the driver in local mode, the node daemon under ``ray-tpu start``).
    Code that reserved no whole chip (the driver outside any task, a
    CPU-mesh test) gets every local device."""
    import jax
    devices = jax.local_devices()
    ids = get_tpu_ids()
    return [devices[i] for i in ids] if ids else devices
