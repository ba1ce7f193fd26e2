"""Process-based worker pool: real OS worker processes for task/actor
execution.

The analog of the reference's worker pool + per-process core-worker
execution loop (src/ray/raylet/worker_pool.h:156 PopWorker;
src/ray/core_worker/core_worker.cc:2377 ExecuteTask in a separate
process). Both the head runtime and node daemons lease workers from a
:class:`WorkerProcessPool`; each worker is a subprocess speaking the
framed cloudpickle protocol over an inherited socketpair.

What processes buy (and threads cannot):

* **real force-cancel / kill** — SIGKILL the worker, the task genuinely
  stops (reference: worker process kill on ``ray.cancel(force=True)``);
* **real OOM kill** — the victim's RSS is returned to the OS
  (reference: raylet worker_killing_policy);
* **crash isolation** — a segfaulting C extension takes down one worker,
  not the node.

Data path: arguments whose payload lives in the node's shm arena travel
as :class:`ArenaRef`/:class:`ArenaArrayRef` markers; the worker attaches
the arena by name (shm_store.cc metadata lives in the mapping, so any
process on the host shares the store) and reads zero-copy —
``jax.device_put`` on such a view is the host->TPU path with no copy.

TPU policy: workers are spawned WITHOUT the TPU backend environment
(a TPU chip is single-process; the chip-owning process — driver or
daemon — runs TPU tasks on threads, everything else can isolate).
"""

from __future__ import annotations

import logging
import os
import signal
import socket
import subprocess
import sys
import tempfile
import threading
import time
import traceback
import uuid
from typing import Any, Callable, Dict, Optional

from ray_tpu._private import builtin_metrics, procinfo, ray_logging

logger = logging.getLogger(__name__)


class WorkerCrashedError(RuntimeError):
    """The worker process died mid-task (crash, kill, or OOM kill)."""


class WorkerFnMissingError(RuntimeError):
    """The worker does not have the function cached and the parent
    withheld the bytes. The parent heals by resending WITH bytes (covers
    any path where a prior request marked the fn shipped but the worker
    failed before caching it)."""


class ArenaRef:
    """Marker for a serialized payload resident in the host shm arena."""

    __slots__ = ("key",)

    def __init__(self, key: str):
        self.key = key


class ArenaArrayRef:
    """Marker for a numpy array resident in the host shm arena (stored
    with put_array's header). Resolves to a READ-ONLY zero-copy view."""

    __slots__ = ("key",)

    def __init__(self, key: str):
        self.key = key


# ---------------------------------------------------------------------------
# Parent side
# ---------------------------------------------------------------------------


class _StdioTransport:
    """Socket-shaped transport over a child's stdin/stdout pipes — the
    CONTAINER transport: ``docker run -i`` cannot inherit a socketpair
    fd across the container boundary, but stdio crosses it natively
    (reference: _private/runtime_env/container.py wraps workers in
    podman; the control channel must survive the wrap)."""

    def __init__(self, proc: subprocess.Popen):
        self._proc = proc

    def sendall(self, data: bytes) -> None:
        self._proc.stdin.write(data)
        self._proc.stdin.flush()

    def recv(self, n: int) -> bytes:
        return self._proc.stdout.read1(n)

    def settimeout(self, timeout) -> None:
        pass  # pipes signal worker death via EOF, not timeouts

    def close(self) -> None:
        for stream in (self._proc.stdin, self._proc.stdout):
            try:
                stream.close()
            except Exception:  # noqa: BLE001
                pass


def container_engine() -> Optional[str]:
    """The available container engine binary (podman preferred, like the
    reference), or None. RAY_TPU_CONTAINER_ENGINE overrides detection."""
    import shutil
    forced = os.environ.get("RAY_TPU_CONTAINER_ENGINE")
    if forced:
        return forced if shutil.which(forced) else None
    for engine in ("podman", "docker"):
        if shutil.which(engine):
            return engine
    return None


class WorkerHandle:
    """One leased worker subprocess. At most one request in flight (the
    reference's workers are also one-task-at-a-time)."""

    def __init__(self, proc: subprocess.Popen, sock: socket.socket):
        self.proc = proc
        self.sock = sock
        self.pid = proc.pid
        self.dead = False
        self.actor_id: Optional[str] = None  # dedicated actor worker
        self.current_task: Optional[Any] = None  # task_id while executing
        self.shipped: set = set()  # fn_ids this worker has cached
        # Workers can't push unsolicited frames (strict request/reply),
        # so their metrics agent buffers batches that piggyback on task
        # replies; the pool points this at the host's forwarder.
        self.metrics_sink: Optional[Callable[[dict], Any]] = None
        # Same piggyback for continuous-profiling windows (folded
        # stacks accumulated by the worker's ProfilerAgent).
        self.profile_sink: Optional[Callable[[dict], Any]] = None
        # And for the worker's transfer-ledger drains (FlowRecorder).
        self.flow_sink: Optional[Callable[[dict], Any]] = None
        self._lock = threading.Lock()

    def request(self, msg: dict, timeout: Optional[float] = None) -> dict:
        """Send one request and block for its reply. A dead/killed worker
        raises WorkerCrashedError."""
        from ray_tpu._private.multinode import (_dumps, _loads, _recv_frame,
                                                _send_frame)
        with self._lock:
            if self.dead:
                raise WorkerCrashedError(
                    f"worker {self.pid} is already dead")
            try:
                self.sock.settimeout(timeout)
                _send_frame(self.sock, _dumps(msg))
                reply = _loads(_recv_frame(self.sock))
            except (OSError, ConnectionError, EOFError) as exc:
                self.dead = True
                raise WorkerCrashedError(
                    f"worker {self.pid} died mid-request "
                    f"(exit={self.proc.poll()}): {exc}") from exc
            finally:
                try:
                    self.sock.settimeout(None)
                except OSError:
                    pass
        if isinstance(reply, dict):
            batches = reply.pop("metrics_batch", None)
            sink = self.metrics_sink
            if batches and sink is not None:
                for batch in batches:
                    try:
                        sink(batch)
                    except Exception:  # noqa: BLE001 - metrics never fail a task
                        logger.exception("worker metrics forward failed")
            profiles = reply.pop("profile_batch", None)
            psink = self.profile_sink
            if profiles and psink is not None:
                for batch in profiles:
                    try:
                        psink(batch)
                    except Exception:  # noqa: BLE001 - profiling never fails a task
                        logger.exception("worker profile forward failed")
            flows = reply.pop("flow_batch", None)
            fsink = self.flow_sink
            if flows and fsink is not None:
                for batch in flows:
                    try:
                        fsink(batch)
                    except Exception:  # noqa: BLE001 - flow accounting never fails a task
                        logger.exception("worker flow forward failed")
        return reply

    def kill(self, wait: bool = True) -> None:
        """SIGKILL the worker — the real force-cancel/OOM-kill path; its
        RSS is returned to the OS. ``wait=False`` skips the reap (for
        callers holding locks; the pool's poll() reaps later)."""
        self.dead = True
        try:
            os.kill(self.pid, signal.SIGKILL)
        except ProcessLookupError:
            pass
        if wait:
            try:
                self.proc.wait(timeout=10)
            except subprocess.TimeoutExpired:
                pass
        try:
            self.sock.close()
        except OSError:
            pass

    def stop(self) -> None:
        """Graceful shutdown (idle workers at pool teardown)."""
        from ray_tpu._private.multinode import (_dumps,
                                                _send_frame_best_effort)
        self.dead = True
        _send_frame_best_effort(self.sock, _dumps({"type": "exit"}))
        try:
            self.proc.wait(timeout=5)
        except subprocess.TimeoutExpired:
            self.kill()
        try:
            self.sock.close()
        except OSError:
            pass
        cidfile = getattr(self, "cidfile", None)
        if cidfile is not None:  # containerized: clean exit reaps the cid
            try:
                os.unlink(cidfile)
            except OSError:
                pass


def _spawn_worker(store_name: Optional[str],
                  env_overrides: Optional[Dict[str, str]] = None,
                  python_exe: Optional[str] = None,
                  container: Optional[Dict[str, Any]] = None
                  ) -> WorkerHandle:
    env = dict(os.environ)
    env["RAY_TPU_WORKER"] = "1"
    if env_overrides:
        env.update(env_overrides)
    if container:
        return _spawn_container_worker(store_name, env, container)
    parent_sock, child_sock = socket.socketpair()
    cmd = [python_exe or sys.executable, "-m",
           "ray_tpu._private.worker_process",
           "--fd", str(child_sock.fileno())]
    if store_name:
        cmd += ["--store", store_name]

    def _die_with_parent():
        # PR_SET_PDEATHSIG: if the spawning driver/daemon dies (even
        # SIGKILL), the kernel reaps the worker too — no orphaned workers
        # burning CPU after a node death.
        try:
            import ctypes
            ctypes.CDLL("libc.so.6", use_errno=True).prctl(
                1, signal.SIGKILL, 0, 0, 0)
        except Exception:  # noqa: BLE001 - non-Linux: best effort
            pass

    # Capture stdout/stderr to per-proc session files (the log monitor
    # streams them to the driver); without a session the child simply
    # inherits the parent's streams — output is never swallowed.
    capture = ray_logging.open_worker_capture()
    popen_kwargs: Dict[str, Any] = {}
    if capture is not None:
        env["PYTHONUNBUFFERED"] = "1"  # print() must reach the tailer
        env[ray_logging.MARKER_ENV] = "1"
        popen_kwargs["stdout"] = capture.out
        popen_kwargs["stderr"] = capture.err
    try:
        proc = subprocess.Popen(cmd, env=env,
                                pass_fds=[child_sock.fileno()],
                                preexec_fn=_die_with_parent,
                                **popen_kwargs)
    except BaseException:
        if capture is not None:
            capture.abort()
        raise
    if capture is not None:
        capture.finalize(proc.pid)
    child_sock.close()
    return WorkerHandle(proc, parent_sock)


def _spawn_container_worker(store_name: Optional[str],
                            env: Dict[str, str],
                            container: Dict[str, Any]) -> WorkerHandle:
    """Spawn the worker INSIDE a container (reference:
    _private/runtime_env/container.py): the engine runs the worker image
    with /dev/shm shared (the object arena crosses the boundary as a
    named shm mapping) and the framed protocol rides stdio."""
    engine = container_engine()
    if engine is None:
        raise WorkerCrashedError(
            "runtime_env['container'] needs docker or podman on PATH")
    image = container.get("image")
    if not image:
        raise WorkerCrashedError(
            "runtime_env['container'] must set 'image'")
    # PDEATHSIG below only kills the ENGINE CLIENT process; under docker
    # the container itself runs under containerd and would outlive a
    # crashed daemon despite --rm. --cidfile gives the daemon (or the
    # next daemon on this host) a handle to reap strays; --init makes
    # in-container signal handling sane (zombie-reaping PID 1).
    cid_dir = os.path.join(tempfile.gettempdir(), "ray_tpu_containers")
    os.makedirs(cid_dir, exist_ok=True)
    _reap_stale_containers_once(engine, cid_dir)
    token = procinfo.start_token(os.getpid())
    cidfile = os.path.join(
        cid_dir,
        f"{os.getpid()}.{token if token is not None else ''}"
        f"-{uuid.uuid4().hex}.cid")
    cmd = [engine, "run", "--rm", "-i", "--init", "--network=host",
           "--cidfile", cidfile,
           "-v", "/dev/shm:/dev/shm"]
    # Only stderr is capturable here: stdout is the protocol pipe (the
    # worker's --stdio mode points fd 1 at stderr before user code, so
    # print() output lands in the captured .err).
    capture = ray_logging.open_worker_capture(sources=("err",))
    if capture is not None:
        env[ray_logging.MARKER_ENV] = "1"
    for key in ("RAY_TPU_WORKER", "RAY_TPU_HEAD_ADDRESS",
                ray_logging.MARKER_ENV):
        if env.get(key):
            cmd += ["-e", f"{key}={env[key]}"]
    cmd += list(container.get("run_options") or [])
    cmd += [image, container.get("python", "python"), "-m",
            "ray_tpu._private.worker_process", "--stdio"]
    if store_name:
        cmd += ["--store", store_name]

    def _die_with_parent():
        try:
            import ctypes
            ctypes.CDLL("libc.so.6", use_errno=True).prctl(
                1, signal.SIGKILL, 0, 0, 0)
        except Exception:  # noqa: BLE001 - non-Linux: best effort
            pass

    popen_kwargs: Dict[str, Any] = {}
    if capture is not None:
        popen_kwargs["stderr"] = capture.err
    try:
        proc = subprocess.Popen(cmd, env=env, stdin=subprocess.PIPE,
                                stdout=subprocess.PIPE,
                                preexec_fn=_die_with_parent,
                                **popen_kwargs)
    except BaseException:
        if capture is not None:
            capture.abort()
        raise
    if capture is not None:
        capture.finalize(proc.pid)
    handle = WorkerHandle(proc, _StdioTransport(proc))
    handle.cidfile = cidfile
    return handle


_reaped = threading.Event()


def _reap_stale_containers_once(engine: str, cid_dir: str) -> None:
    """Housekeeping, off the spawn hot path: the first container lease
    in this process kicks one background reap (each stale cid costs a
    `docker rm -f` of up to 30s — never serialized into a dispatch)."""
    if _reaped.is_set():
        return
    _reaped.set()
    threading.Thread(target=_reap_stale_containers,
                     args=(engine, cid_dir),
                     name="ray_tpu-container-reaper", daemon=True).start()


def _reap_stale_containers(engine: str, cid_dir: str) -> None:
    """Kill containers whose spawning daemon died (its pid is gone but
    the cidfile remains): the PDEATHSIG on the engine client cannot stop
    a containerd-managed container."""
    try:
        entries = os.listdir(cid_dir)
    except OSError:
        return
    for fname in entries:
        if not fname.endswith(".cid"):
            continue
        path = os.path.join(cid_dir, fname)
        try:
            ident = fname.split("-", 1)[0]
            # "<pid>.<start_token>" since r5; bare "<pid>" from older
            # daemons. The token defeats pid recycling: an unrelated
            # live process that inherited the pid must not keep an
            # orphaned container alive forever.
            spawner_token = None
            if "." in ident:
                pid_s, tok_s = ident.split(".", 1)
                spawner_pid = int(pid_s)
                spawner_token = int(tok_s) if tok_s else None
            else:
                spawner_pid = int(ident)
            if procinfo.same_process(spawner_pid, spawner_token):
                continue  # spawner alive: its container is legitimate
            with open(path) as f:
                cid = f.read().strip()
            if cid:
                subprocess.run([engine, "rm", "-f", cid],
                               capture_output=True, timeout=30)
            os.unlink(path)
        except (OSError, ValueError, subprocess.SubprocessError):
            continue


class WorkerProcessPool:
    """Leases worker subprocesses, reusing idle ones (reference:
    WorkerPool caches started workers keyed by runtime-env hash;
    PopWorker reuses before starting). Idle workers are keyed by their
    interpreter (base vs. a pip-venv python): a venv task never reuses a
    base worker and vice versa. Dedicated (actor) workers never return
    to the idle pool."""

    def __init__(self, store_name: Optional[str] = None,
                 max_workers: int = 64,
                 head_address=None, node_id_hex: Optional[str] = None,
                 object_addr=None):
        self.store_name = store_name
        self.max_workers = max_workers
        # Workers inherit the head address so nested ray_tpu API calls in
        # user code bind a ClientRuntime wired to the head (the connected-
        # runtime property; _private/client_runtime.py) instead of
        # auto-initializing an isolated split-brain runtime. The node id
        # lets worker-side puts register THIS node as the bytes' owner
        # (distributed ownership; stale after a head restart, in which
        # case registration fails and puts fall back to head-stored).
        self._env_overrides: Optional[Dict[str, str]] = None
        overrides = {}
        if head_address is not None:
            host, port = tuple(head_address)
            overrides["RAY_TPU_HEAD_ADDRESS"] = f"{host}:{port}"
        if node_id_hex:
            overrides["RAY_TPU_NODE_ID"] = node_id_hex
        if object_addr is not None:
            # This node's object server: worker-side puts stamp it into
            # owner hints so borrowers can go owner-ward (phase 3).
            host, port = tuple(object_addr)
            overrides["RAY_TPU_OBJECT_ADDR"] = f"{host}:{port}"
        if overrides:
            self._env_overrides = overrides
        self._idle: Dict[str, list] = {}
        self._all: list = []
        self._lock = threading.Lock()
        self._available = threading.Condition(self._lock)
        self._closed = False
        # Forwarder handed to every leased worker: batches the workers
        # piggyback on task replies flow through here to the head's
        # cluster registry (directly on the head; via metrics_batch
        # frames from a daemon).
        self.metrics_sink: Optional[Callable[[dict], Any]] = None
        self.profile_sink: Optional[Callable[[dict], Any]] = None
        self.flow_sink: Optional[Callable[[dict], Any]] = None
        # ALL spawns go through this single long-lived thread:
        # PR_SET_PDEATHSIG binds to the spawning THREAD, so a worker
        # forked from an ephemeral handler thread is SIGKILLed the
        # moment that thread exits (the daemon runs one thread per
        # request — its first worker died right after its first task).
        # The spawner lives until pool shutdown; its death then reaps
        # every worker, which is exactly the orphan protection wanted.
        import concurrent.futures
        self._spawner = concurrent.futures.ThreadPoolExecutor(
            max_workers=1, thread_name_prefix="ray_tpu-worker-spawn")

    def lease(self, python_exe: Optional[str] = None,
              container: Optional[Dict[str, Any]] = None) -> WorkerHandle:
        """Lease a worker for the given interpreter (None = base) or
        container image, spawning up to max_workers total; BLOCKS when
        the pool is saturated until a worker is released (backpressure,
        not failure — callers already queued behind the scheduler).
        Idle workers are keyed by interpreter AND image: a containerized
        worker never serves a bare task or another image's."""
        key = python_exe or ""
        if container:
            key += f"|container:{container.get('image')}"
        # The common case — an idle worker is parked — must not pay two
        # monotonic reads plus a locked histogram observe per lease: the
        # clock starts only once the request actually waits, spawns, or
        # evicts; an immediate hit records a plain int add.
        lease_start: Optional[float] = None
        while True:
            evict = None
            with self._lock:
                while True:
                    idle = self._idle.setdefault(key, [])
                    while idle:
                        w = idle.pop()
                        if not w.dead and w.proc.poll() is None:
                            return self._leased(w, lease_start)
                        # Died while parked: without this, it counts
                        # toward max_workers forever (capacity leak).
                        w.dead = True
                        if w in self._all:
                            self._all.remove(w)
                    if self._closed:
                        raise WorkerCrashedError("worker pool is shut down")
                    if lease_start is None:
                        lease_start = time.monotonic()
                    if len([w for w in self._all if not w.dead]) \
                            < self.max_workers:
                        break
                    # At capacity: evict an idle worker of ANOTHER
                    # interpreter key to make room — otherwise a pool
                    # full of idle base workers deadlocks the first
                    # venv lease (reference: WorkerPool kills idle
                    # workers of other runtime envs under pressure).
                    for other, lst in self._idle.items():
                        if other != key and lst:
                            evict = lst.pop()
                            if evict in self._all:
                                self._all.remove(evict)
                            break
                    if evict is not None:
                        break
                    self._available.wait(timeout=10)
            if evict is not None:
                evict.stop()
                evict = None
                continue  # re-enter: capacity freed
            w = self._spawner.submit(
                _spawn_worker, self.store_name,
                env_overrides=self._env_overrides,
                python_exe=python_exe, container=container).result()
            w.pool_key = key
            with self._lock:
                if self._closed:
                    pass  # fall through; stop below
                else:
                    self._all.append(w)
                    return self._leased(w, lease_start)
            w.stop()
            raise WorkerCrashedError("worker pool is shut down")

    def _leased(self, w: WorkerHandle,
                lease_start: Optional[float]) -> WorkerHandle:
        w.metrics_sink = self.metrics_sink
        w.profile_sink = self.profile_sink
        w.flow_sink = self.flow_sink
        if lease_start is None:
            builtin_metrics.record_lease_immediate()
        else:
            builtin_metrics.worker_lease_wait().observe(
                time.monotonic() - lease_start)
        return w

    def record_metrics(self) -> None:
        """Refresh the pool-size gauge (metrics-agent collector)."""
        with self._lock:
            alive = len([w for w in self._all if not w.dead])
        builtin_metrics.worker_pool_size().set(alive)

    def prestart(self, n: int) -> None:
        """Spawn up to ``n`` base-interpreter workers into the idle pool
        ahead of demand (reference: worker_pool.h PrestartWorkers): the
        Popen returns immediately and the child warms up concurrently,
        so the first real task pays a queue pop instead of a process
        start."""
        def one():
            try:
                self.release(self.lease(None))
            except Exception:  # noqa: BLE001 - prestart is best-effort
                pass

        for _ in range(max(0, n)):
            threading.Thread(target=one, daemon=True,
                             name="ray_tpu-worker-prestart").start()

    def release(self, w: WorkerHandle) -> None:
        if w.dead:
            # Reap killed workers here (the force-cancel/OOM path kills
            # with wait=False while holding the runtime lock): without
            # the wait() the SIGKILLed process lingers as a zombie.
            try:
                w.proc.wait(timeout=10)
            except Exception:  # noqa: BLE001 - already reaped / stuck
                pass
        with self._lock:
            if not w.dead and not self._closed and w.actor_id is None:
                self._idle.setdefault(
                    getattr(w, "pool_key", ""), []).append(w)
            self._available.notify()

    def running_workers(self) -> list:
        with self._lock:
            return [w for w in self._all
                    if not w.dead and w.current_task is not None]

    def shutdown(self) -> None:
        with self._lock:
            self._closed = True
            workers = list(self._all)
            self._all.clear()
            self._idle.clear()
        for w in workers:
            if not w.dead:
                w.stop()
        # Last: the spawner thread's death PDEATHSIG-kills any worker
        # that somehow escaped the stop() sweep above.
        self._spawner.shutdown(wait=False)


# ---------------------------------------------------------------------------
# Shared request/response helpers (parent side)
# ---------------------------------------------------------------------------


def run_on_worker(handle: WorkerHandle, msg: dict):
    """Execute one request on a worker; unpack the reply into a value or
    raise. Worker death surfaces as WorkerCrashedError (a SYSTEM failure:
    retriable, like a died worker process in the reference)."""
    from ray_tpu._private.multinode import _loads
    reply = handle.request(msg)
    if reply.get("ok"):
        return _loads(reply["value"])
    exc, remote_tb = _loads(reply["error"])
    from ray_tpu.exceptions import TaskError
    raise TaskError(exc, remote_tb, msg.get("name", "task"))


class ProcessActorInstance:
    """Placeholder stored as ActorState.instance for actors living in a
    dedicated worker process; method lookups return proxy closures
    (mirrors multinode.RemoteActorInstance for daemon-resident actors)."""

    def __init__(self, handle: WorkerHandle, pool: WorkerProcessPool):
        self.handle = handle
        self.pool = pool

    def bind_method(self, method_name: str, task_name: str,
                    store_limit: int = 0):
        from ray_tpu._private import serialization

        def call(*args, **kwargs):
            # Runs inside the head-side actor_task:: span
            # (_run_actor_task's continue_context): propagate it so the
            # worker-process span parents across the process boundary.
            from ray_tpu.util import tracing
            return run_on_worker(self.handle, {
                "type": "exec",
                "mode": "actor_call",
                "method": method_name,
                "payload": serialization.serialize((args, kwargs)),
                "name": task_name,
                "trace_ctx": tracing.span_context(tracing.current_span()),
            })
        return call

    def destroy(self) -> None:
        self.handle.kill()


# ---------------------------------------------------------------------------
# Worker side (subprocess entrypoint)
# ---------------------------------------------------------------------------


#: The _WorkerMain serving THIS worker process (None elsewhere): lets
#: the client runtime reach the shared shm arena for node-resident puts
#: (distributed ownership — client_runtime._put_node_resident).
_current_executor: Optional["_WorkerMain"] = None


class _WorkerMain:
    def __init__(self, sock: socket.socket, store_name: Optional[str]):
        global _current_executor
        _current_executor = self
        self.sock = sock
        self.store_name = store_name
        self._arena = None
        self._arena_tried = False
        self._functions: Dict[bytes, Any] = {}
        self._actor = None  # dedicated actor instance
        # Metrics export rides task replies (workers cannot push
        # unsolicited frames): the agent runs with no thread, serve()
        # polls it at most once per interval and attaches buffered
        # batches to the next reply; the parent forwards them head-ward.
        from ray_tpu._private.metrics_agent import MetricsAgent
        self._metrics_buffer: list = []
        self._profile_buffer: list = []
        self._flow_buffer: list = []
        # publish_profile makes the agent own a ProfilerAgent for this
        # worker: sampling runs continuously on its own thread even
        # between tasks; the windows ride task replies like metrics.
        self._metrics_agent = MetricsAgent(
            self._buffer_metrics_batch, component="worker", start=False,
            publish_profile=self._buffer_profile_batch,
            publish_flow=self._buffer_flow_batch)
        self._last_metrics_poll = 0.0

    def _buffer_metrics_batch(self, batch: dict) -> bool:
        self._metrics_buffer.append(batch)
        # Bounded: an idle stretch can't pile up batches (the periodic
        # full refresh re-converges the head after any drop).
        del self._metrics_buffer[:-8]
        return True

    def _buffer_profile_batch(self, batch: dict) -> bool:
        # Bounded like metrics — but a squeezed-out window would be
        # real sample loss, so a full buffer REFUSES the batch instead:
        # the agent refunds the stacks into the live window and they
        # merge into the next drain.
        if len(self._profile_buffer) >= 8:
            return False
        self._profile_buffer.append(batch)
        return True

    def _buffer_flow_batch(self, batch: dict) -> bool:
        # A squeezed-out batch would be dropped transfer records, so a
        # full buffer REFUSES (the agent refunds into the recorder).
        if len(self._flow_buffer) >= 8:
            return False
        self._flow_buffer.append(batch)
        return True

    def _attach_metrics(self, reply: dict) -> None:
        agent = self._metrics_agent
        if not agent.enabled:
            return
        now = time.monotonic()
        if now - self._last_metrics_poll >= agent.interval_s:
            self._last_metrics_poll = now
            try:
                agent.poll_once()
            except Exception:  # noqa: BLE001 - metrics never fail a task
                logger.exception("worker metrics poll failed")
        if self._metrics_buffer:
            reply["metrics_batch"] = self._metrics_buffer[:]
            del self._metrics_buffer[:]
        if self._profile_buffer:
            reply["profile_batch"] = self._profile_buffer[:]
            del self._profile_buffer[:]
        if self._flow_buffer:
            reply["flow_batch"] = self._flow_buffer[:]
            del self._flow_buffer[:]

    def _get_arena(self):
        if not self._arena_tried:
            self._arena_tried = True
            if self.store_name:
                try:
                    from ray_tpu._private.native_store import \
                        NativeObjectStore
                    self._arena = NativeObjectStore(name=self.store_name,
                                                    create=False)
                except Exception:  # noqa: BLE001 - arena gone/unbuildable
                    logger.exception("worker could not attach shm arena")
        return self._arena

    def _load_function(self, fn_id: bytes, fn_bytes: Optional[bytes]):
        fn = self._functions.get(fn_id)
        if fn is None:
            if fn_bytes is None:
                raise WorkerFnMissingError(
                    "worker has no cached copy of this function; parent "
                    "must resend with fn_bytes")
            from ray_tpu._private import serialization
            fn = serialization.loads_function(fn_bytes)
            self._functions[fn_id] = fn
        return fn

    def _resolve(self, obj, pinned_keys):
        """Resolve arena markers to values (zero-copy views for arrays).
        A missing entry means it was evicted between the parent's check
        and this read — an ObjectPullError, so the head retries the task
        as a system failure while reconstruction re-runs the producer."""
        from ray_tpu._private.dataplane import ObjectPullError
        if isinstance(obj, ArenaArrayRef):
            arena = self._get_arena()
            if arena is None:
                raise RuntimeError("shm arena unavailable in worker")
            arr = arena.get_array(obj.key)
            if arr is None:
                raise ObjectPullError(
                    f"array {obj.key} no longer in the shm arena "
                    "(evicted under pressure before the worker's read)")
            # get_array pinned the entry; release after the task body so
            # repeated tasks never pin objects forever.
            pinned_keys.append(obj.key)
            return arr  # READ-ONLY zero-copy view over the mapping
        if isinstance(obj, ArenaRef):
            arena = self._get_arena()
            if arena is None:
                raise RuntimeError("shm arena unavailable in worker")
            view = arena.get_bytes(obj.key)
            if view is None:
                raise ObjectPullError(
                    f"object {obj.key} no longer in the shm arena "
                    "(evicted under pressure before the worker's read)")
            from ray_tpu._private.multinode import _loads
            try:
                return _loads(view)
            finally:
                view.release()
                arena.release(obj.key)
        return obj

    def _exec(self, msg: dict):
        from ray_tpu._private.multinode import _loads
        mode = msg.get("mode", "task")
        # Load the function FIRST: once cached, a later arg failure
        # cannot leave the parent's shipped-set out of sync.
        if mode == "actor_call":
            if self._actor is None:
                raise RuntimeError("actor_call before actor_init")
            fn = getattr(self._actor, msg["method"])
        else:
            fn = self._load_function(msg["fn_id"], msg.get("fn_bytes"))
        # Task context: get_tpu_ids / nested client-runtime gets read it
        # (a blocked nested get ships task_id so the head can release the
        # task's resources while it waits).
        import types as _types

        from ray_tpu._private.runtime import _task_context
        _task_context.spec = _types.SimpleNamespace(
            _tpu_ids=None, actor_id=None, name=msg.get("name", ""),
            task_id_hex=msg.get("task_id"))
        if ray_logging.markers_enabled():
            # Announce the task on the captured streams so the tailer
            # prefixes its output with the task name, not just the pid.
            ray_logging.emit_task_marker(msg.get("name", ""))
        pinned_keys: list = []
        try:
            args, kwargs = _loads(msg["payload"])
            args = [self._resolve(a, pinned_keys) for a in args]
            kwargs = {k: self._resolve(v, pinned_keys)
                      for k, v in kwargs.items()}
            renv = msg.get("runtime_env")

            def invoke():
                # Final hop of cross-process propagation: the span ships
                # back piggybacked on this reply's metrics_batch. ctx is
                # None on every untraced task (one dict read).
                from ray_tpu.util import tracing
                prefix = ("actor_task" if mode == "actor_call" else
                          "actor_init" if mode == "actor_init" else
                          "task")
                with tracing.continue_context(
                        msg.get("trace_ctx"),
                        f"{prefix}::{msg.get('name', '')}",
                        {"stage": "execute"}):
                    result = fn(*args, **kwargs)
                    import inspect
                    if inspect.iscoroutine(result):
                        import asyncio
                        result = asyncio.run(result)
                return result

            if renv:
                from ray_tpu._private import runtime_env as _renv
                _renv.setup(renv)
                if mode == "actor_init":
                    # A dedicated actor worker IS the actor's process:
                    # its env_vars persist for the process lifetime
                    # (reference actor runtime_env semantics), so
                    # threads the actor spawns (e.g. Train loops
                    # reading RAY_TPU_JAX_PLATFORM) and later method
                    # calls all see them — the scoped form here lost a
                    # race that deadlocked multi-controller training.
                    import os as _os
                    _os.environ.update(renv.get("env_vars") or {})
                    result = invoke()
                else:
                    with _renv.applied(renv):
                        result = invoke()
            else:
                result = invoke()
        finally:
            _task_context.spec = None
            arena = self._arena
            for key in pinned_keys:
                try:
                    arena.release(key)
                except Exception:  # noqa: BLE001
                    pass
        if mode == "actor_init":
            self._actor = result
            return None
        return result

    def _result_reply(self, msg: dict, value, _dumps) -> dict:
        """Build the result reply, writing big payloads STRAIGHT into
        the shared shm arena (plasma's mission: results land in the
        store, never in an RPC reply) — the bytes skip the stdio pipe
        and the daemon's re-pickle; it only adopts the keys. Multi-
        return tasks split PER ELEMENT (a shuffle map's 32 partitions
        each become an independent arena entry). Arena-full or shape
        mismatch falls back to the inline path (the daemon's table.put
        can spill to disk)."""
        from ray_tpu._private import serialization
        arena_limit = msg.get("arena_limit", 0)
        num_returns = msg.get("num_returns", 1)
        arena = self._get_arena() if arena_limit else None
        if arena is None:
            return {"ok": True, "value": _dumps(value)}
        import uuid as _uuid

        def _one(el) -> dict:
            # serialize_parts keeps big array buffers as raw views: an
            # arena-bound result is laid down header+buffers in one
            # allocation with a single data memcpy (no full-payload
            # pickle copy on this end).
            pp = serialization.serialize_parts(el)
            size = sum(len(p) for p in pp)
            if size > arena_limit:
                key = f"wres-{_uuid.uuid4().hex}"
                if arena.put_parts(key, pp, size=size):
                    return {"arena_key": key, "size": size}
            if len(pp) == 1 and isinstance(pp[0], bytes):
                return {"value": pp[0]}
            return {"value": b"".join(bytes(p) for p in pp)}

        if num_returns > 1:
            if not isinstance(value, (tuple, list)) or \
                    len(value) != num_returns:
                # Wrong shape: the daemon's mismatch path describes it.
                return {"ok": True, "value": _dumps(value)}
            return {"ok": True, "parts": [_one(el) for el in value]}
        reply = _one(value)
        reply["ok"] = True
        return reply

    def serve(self) -> None:
        from ray_tpu._private.multinode import (_dumps, _loads, _recv_frame,
                                                _send_frame)
        while True:
            try:
                msg = _loads(_recv_frame(self.sock))
            except (ConnectionError, OSError):
                return  # parent died — exit with it
            kind = msg.get("type")
            if kind == "exit":
                return
            if kind == "ping":
                reply = {"ok": True, "pid": os.getpid()}
                self._attach_metrics(reply)
                _send_frame(self.sock, _dumps(reply))
                continue
            if kind == "profile":
                # On-demand burst relayed by the owning daemon
                # (`ray-tpu profile --pid`): sample our own stacks at
                # the requested rate and reply with the raw folded
                # mapping. Strict request/reply holds: this occupies
                # the pipe for the duration, like any task would.
                try:
                    from ray_tpu._private.profiling import sample_self
                    # skip_profiler=False: a worker may be just this
                    # serve thread — skipping the sampling thread would
                    # return an EMPTY profile for any idle worker.
                    counts = sample_self(
                        min(float(msg.get("duration", 5.0)), 60.0),
                        int(msg.get("hz", 100)), skip_profiler=False)
                    reply = {"ok": True, "pid": os.getpid(),
                             "stacks": counts}
                except BaseException as exc:  # noqa: BLE001 - ship to parent
                    reply = {"ok": False,
                             "error": f"{type(exc).__name__}: {exc}"}
                try:
                    _send_frame(self.sock, _dumps(reply))
                except (OSError, ConnectionError):
                    return
                continue
            try:
                value = self._exec(msg)
                reply = self._result_reply(msg, value, _dumps)
            except BaseException as exc:  # noqa: BLE001 - ship to parent
                try:
                    payload = _dumps((exc, traceback.format_exc()))
                except Exception:  # noqa: BLE001 - unpicklable exception
                    payload = _dumps((RuntimeError(
                        f"{type(exc).__name__}: {exc}"),
                        traceback.format_exc()))
                reply = {"ok": False, "error": payload}
            self._attach_metrics(reply)
            try:
                _send_frame(self.sock, _dumps(reply))
            except (OSError, ConnectionError):
                return


def _main() -> None:
    import argparse
    import faulthandler

    # Stack dumps on demand: `kill -USR1 <worker>` prints every thread
    # to stderr (inherited from the spawning process) — the diagnostic
    # channel for wedged workers, mirroring the reference's py-spy-based
    # dashboard stack dumps.
    faulthandler.enable()
    try:
        faulthandler.register(signal.SIGUSR1)
    except (AttributeError, ValueError):  # non-main thread / platform
        pass

    # Worker processes NEVER run TPU tasks (runtime._uses_worker_process
    # and the daemon's routing both keep TPU work in the chip-owning
    # process), and must never take the chip either: a chip serves one
    # process at a time, and a worker whose jax initialized the TPU
    # backend would deadlock on its lockfile (/tmp/libtpu_lockfile)
    # against the owner. jax is not imported here (that costs seconds on
    # every spawn); the variable pins whatever user code imports later.
    # Hard assignment, not setdefault: however this worker was started
    # (a container's or a conda env's own environment), it gets the CPU.
    os.environ["JAX_PLATFORMS"] = "cpu"

    parser = argparse.ArgumentParser()
    parser.add_argument("--fd", type=int, default=None)
    parser.add_argument("--stdio", action="store_true",
                        help="speak the framed protocol over stdio "
                             "(container transport: fds cannot cross "
                             "the container boundary)")
    parser.add_argument("--store", default=None)
    args = parser.parse_args()
    if args.stdio:
        # Claim the REAL stdout for frames, then point fd 1 at stderr so
        # user-code prints can never corrupt the protocol stream.
        real_out = os.fdopen(os.dup(1), "wb", buffering=0)
        real_in = os.fdopen(os.dup(0), "rb", buffering=0)
        os.dup2(2, 1)

        class _StdioServer:
            def recv(self, n):
                return real_in.read(n) or b""

            def sendall(self, data):
                real_out.write(data)

            def settimeout(self, timeout):
                pass

            def close(self):
                pass

        _WorkerMain(_StdioServer(), args.store).serve()
        return
    if args.fd is None:
        parser.error("one of --fd or --stdio is required")
    sock = socket.socket(fileno=args.fd)
    _WorkerMain(sock, args.store).serve()


if __name__ == "__main__":
    from ray_tpu._private.worker_process import _main as _canonical_main

    _canonical_main()
