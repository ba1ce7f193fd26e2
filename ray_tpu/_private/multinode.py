"""Multi-process cluster substrate: head server + node daemons over TCP.

The real-process analog of the reference's control plane (SURVEY.md §2.1:
gRPC `src/ray/rpc/` + GCS server + raylets): the driver process acts as
head (owner of all objects, scheduler authority — the collapsed
GCS/owner model this runtime uses throughout), and **node daemons** are
separate OS processes (possibly on other hosts) that register resources
and execute user code pushed to them. The wire protocol is
length-prefixed cloudpickle frames over one persistent TCP connection per
node — the moral equivalent of the reference's PushTask gRPC stream, with
connection death standing in for raylet health-check failure
(gcs_health_check_manager.h): the head converts a dropped connection into
`Runtime.remove_node`, which drives the existing retry / actor-restart /
lineage-reconstruction machinery.

Execution model: scheduling, retries, and the object DIRECTORY stay on
the head; only the *user-code call* (`fn(*args)`, `cls(*args)`,
`instance.method(*args)`) crosses the wire. Normal tasks dispatch
ASYNC — `execute_task_async` + per-connection completion drainers, no
head thread parked per in-flight call (reference: callback-driven
direct task transport) — and same-class tasks stream onto worker
LEASES whose daemon-side serial executors order execution locally
(one accounted acquisition ↔ one running task; blocked nested gets
spill/unspill the queue). Actor calls hold one head executor thread
per actor-concurrency slot — the ordering authority, mirroring the
reference's one-worker-per-actor model; thread count scales with
actors, never with queued tasks (1M queued tasks = 3 threads,
tests/test_core.py deep-queue envelope). Small results return inline
in the reply (core_worker.cc PushTaskReply); big results stay
daemon-resident and travel the chunked data plane (dataplane.py), as
do node-resident distributed-ownership puts.

Daemons run actors too: the instance lives in the daemon process
(constructed there), and the head-side actor executor proxies each method
call, preserving per-handle ordering. Daemon death restarts actors
elsewhere through the normal node-death path.
"""

from __future__ import annotations

import contextlib
import logging
import os
import socket
import struct
import threading
import traceback
from time import monotonic as _monotonic
from typing import Any, Dict, Optional, Tuple

from ray_tpu._private import chaos as _chaos
from ray_tpu._private import procinfo
from ray_tpu._private import wire as _wire

logger = logging.getLogger(__name__)

_FRAME = struct.Struct(">Q")
_MAX_FRAME = 1 << 34  # 16 GiB sanity bound

#: Shared stateless no-op context: the untraced daemon execute path pays
#: one dict read and zero allocations for tracing.
_NULL_SPAN = contextlib.nullcontext()


def _trace_span(ctx: Optional[dict], name: str, stage: str):
    """A continue_context span when the request carries a sampled trace
    context (propagated from the driver), the shared no-op otherwise."""
    if ctx is None:
        return _NULL_SPAN
    from ray_tpu.util import tracing
    return tracing.continue_context(ctx, name, {"stage": stage})


class RemoteNodeDiedError(RuntimeError):
    """The node connection dropped while a call was in flight. NOT a
    TaskError: the runtime treats it as a system failure (node death),
    and the in-flight spec is invalidated/retried by remove_node."""


# ---------------------------------------------------------------------------
# Framing
# ---------------------------------------------------------------------------


def _send_frame_parts(sock: socket.socket, parts,
                      lock: Optional[threading.Lock] = None) -> None:
    """Length-prefix and write a frame given as buffer parts — payload
    buffers go to the kernel by scatter-gather (channel.sock_send_parts)
    without being joined behind the length prefix."""
    from ray_tpu._private.channel import sock_send_parts
    total = _parts_size(parts)
    hdr = _FRAME.pack(total)
    if lock is not None:
        with lock:
            sock_send_parts(sock, (hdr, *parts))
    else:
        sock_send_parts(sock, (hdr, *parts))


def _send_frame(sock: socket.socket, payload: bytes,
                lock: Optional[threading.Lock] = None) -> None:
    _send_frame_parts(sock, (payload,), lock)


def _send_frame_best_effort(sock: socket.socket, payload: bytes,
                            lock: Optional[threading.Lock] = None) -> bool:
    """Send a frame whose loss is acceptable (rejection notices,
    fire-and-forget teardown messages to possibly-dead peers). Returns
    False instead of raising on transport failure. Frames that must
    arrive go through a ResilientChannel / _CoalescingSender instead —
    the log lint bans ad-hoc OSError suppression around _send_frame."""
    try:
        _send_frame(sock, payload, lock)
        return True
    except OSError:
        return False


def _close_quiet(sock: socket.socket) -> None:
    try:
        sock.close()
    except OSError:
        pass


def _recv_exact(sock: socket.socket, n: int) -> bytes:
    buf = bytearray()
    while len(buf) < n:
        chunk = sock.recv(n - len(buf))
        if not chunk:
            raise ConnectionError("connection closed")
        buf.extend(chunk)
    return bytes(buf)


def _recv_frame(sock: socket.socket) -> bytes:
    (length,) = _FRAME.unpack(_recv_exact(sock, _FRAME.size))
    if length > _MAX_FRAME:
        raise ConnectionError(f"frame of {length} bytes exceeds bound")
    return _recv_exact(sock, length)


def _dumps(obj: Any) -> bytes:
    from ray_tpu._private import serialization
    return serialization.serialize(obj)


def _loads(data: bytes) -> Any:
    from ray_tpu._private import serialization
    return serialization.deserialize(data)


def _dumps_parts(obj: Any) -> list:
    """Serialize into bytes-like parts (serialization.serialize_parts):
    big array payloads keep their data buffers as views so the object
    table can lay them into the arena with one memcpy."""
    from ray_tpu._private import serialization
    return serialization.serialize_parts(obj)


def _parts_size(parts) -> int:
    # memoryview len() counts elements, not bytes (non-'B' formats).
    return sum(p.nbytes if isinstance(p, memoryview) else len(p)
               for p in parts)


def _join_parts(parts: list) -> bytes:
    if len(parts) == 1 and isinstance(parts[0], bytes):
        return parts[0]
    return b"".join(bytes(p) for p in parts)


def _encode_frame_parts(msg: dict) -> list:
    """Typed binary layout for hot-path ops (wire.py phase 2) as a part
    list — payload bytes stay by reference — pickle envelope for
    everything else."""
    parts = _wire.encode_typed_parts(msg)
    return parts if parts is not None else [_dumps(msg)]


def _encode_frame(msg: dict) -> bytes:
    """Joined form of :func:`_encode_frame_parts`."""
    return _join_parts(_encode_frame_parts(msg))


def _decode_frames(raw: bytes) -> list:
    """Decode one wire frame into its message dict(s): binary batches
    and legacy dict batches both flatten to a list."""
    parts = _wire.decode_batch(raw)
    if parts is not None:
        return [_decode_one(p) for p in parts]
    msg = _decode_one(raw)
    if isinstance(msg, dict) and msg.get("type") in ("task_batch",
                                                     "reply_batch"):
        # Legacy dict batch: validate the envelope before touching its
        # fields — a drifted peer fails with the exact field name.
        _wire.validate_message(msg)
        return list(msg["msgs"])
    return [msg]


def _decode_one(raw: bytes):
    msg = _wire.decode_typed(raw)
    return msg if msg is not None else _loads(raw)


def _args_are_plain(args, kwargs) -> bool:
    """True when no top-level arg is a data-plane marker (the only
    place the head ever puts one — see Runtime._resolve_args)."""
    from ray_tpu._private.dataplane import ObjectMarker
    markers = (ObjectMarker, RemoteArgMarker)
    return not (any(isinstance(a, markers) for a in args)
                or any(isinstance(v, markers) for v in kwargs.values()))


class _CoalescingSender:
    """Single writer for one control socket. Callers enqueue message
    dicts; the sender thread writes them, coalescing whatever has
    accumulated into ONE ``batch_type`` frame (reference: the gRPC
    transport's stream batching amortizes per-message overhead the same
    way). Under load this collapses N pickle dumps + N sendall syscalls
    into one of each; when idle the thread wakes per message and sends
    it solo, so single-task latency pays nothing.

    All writes for the socket MUST go through this object once it is
    attached — a direct ``_send_frame`` from another thread would
    interleave bytes mid-frame. The enqueue lock also serializes
    ``resolver`` callbacks (fn_bytes shipping decisions), which makes
    the decide-and-order step atomic across submitting threads.
    """

    MAX_BATCH = 64            # messages per batch frame
    SOLO_BYTES = 256 * 1024   # payloads this big travel alone
    MAX_BATCH_BYTES = 1 << 20  # cumulative payload cap per batch
    QUEUE_CAP_BYTES = 64 << 20  # backpressure: block senders past this

    def __init__(self, transport, batch_type: str,
                 on_fail=None, name: str = "sender"):
        if isinstance(transport, socket.socket):
            transport = _SocketTransport(transport)
        self._transport = transport
        self._batch_type = batch_type
        self._on_fail = on_fail
        from collections import deque
        self._dq: Any = deque()
        self._cv = threading.Condition()
        self._closed = False
        self._queued_bytes = 0
        self._sending = False  # a popped batch is being written
        self._thread = threading.Thread(
            target=self._run, name=f"ray_tpu-{name}", daemon=True)
        self._thread.start()

    def send(self, msg: dict, resolver=None, nbytes: int = 0) -> bool:
        """Enqueue; returns False if the sender is closed. ``resolver``
        runs under the enqueue lock (may mutate msg, may raise — in
        which case nothing is enqueued). ``nbytes`` is a payload-size
        hint for batch splitting and backpressure."""
        with self._cv:
            while (self._queued_bytes > self.QUEUE_CAP_BYTES
                   and not self._closed):
                self._cv.wait(1.0)
            if self._closed:
                return False
            if resolver is not None:
                resolver(msg)
            self._dq.append((msg, nbytes))
            self._queued_bytes += nbytes
            self._cv.notify_all()
        return True

    def close(self) -> None:
        with self._cv:
            self._closed = True
            self._cv.notify_all()

    def flush(self, timeout: float = 1.0) -> None:
        """Best-effort wait for the queue to drain (shutdown paths)."""
        import time as _time
        deadline = _time.monotonic() + timeout
        with self._cv:
            while (self._dq or self._sending) and \
                    _time.monotonic() < deadline:
                self._cv.wait(0.05)

    def _pop_batch(self):
        batch = []
        total = 0
        while self._dq and len(batch) < self.MAX_BATCH:
            msg, nb = self._dq[0]
            if batch and (nb >= self.SOLO_BYTES
                          or total + nb > self.MAX_BATCH_BYTES):
                break
            self._dq.popleft()
            self._queued_bytes -= nb
            batch.append(msg)
            total += nb
            if nb >= self.SOLO_BYTES:
                break
        return batch

    def _run(self) -> None:
        from ray_tpu._private.channel import ChannelBroken
        while True:
            with self._cv:
                while not self._dq and not self._closed:
                    self._cv.wait()
                if not self._dq:
                    return  # closed and drained
                batch = self._pop_batch()
                self._sending = True
                self._cv.notify_all()  # backpressured senders re-check
            try:
                if len(batch) == 1:
                    self._transport.send_parts(
                        *_encode_frame_parts(batch[0]))
                else:
                    # Binary batch: each message encodes ONCE (typed or
                    # pickle) into a part list; the batch frame is just
                    # those parts behind per-frame length prefixes — the
                    # accumulated payload bytes are never re-joined.
                    self._transport.send_parts(*_wire.encode_batch_parts(
                        [_encode_frame_parts(m) for m in batch]))
            except ChannelBroken:
                # The frame already sits in the channel's resend ring
                # and is replayed by the resume attach; park until the
                # channel recovers. Only a closed channel / exhausted
                # reconnect window escalates to on_fail (node death).
                self._done_sending()
                if self._transport.wait_recovered():
                    continue
                self._fail()
                return
            except OSError:
                self._done_sending()
                self._fail()
                return
            except Exception:  # noqa: BLE001 - one poisoned msg must
                # not kill the connection: retry each solo, drop the
                # one that cannot serialize.
                if not self._send_solo(batch):
                    return
            self._done_sending()

    def _send_solo(self, batch) -> bool:
        from ray_tpu._private.channel import ChannelBroken
        for msg in batch:
            try:
                self._transport.send_parts(*_encode_frame_parts(msg))
            except ChannelBroken:
                if self._transport.wait_recovered():
                    continue  # ringed frame replays on resume
                self._done_sending()
                self._fail()
                return False
            except OSError:
                self._done_sending()
                self._fail()
                return False
            except Exception:
                logger.exception(
                    "dropping unserializable control frame %s",
                    msg.get("type"))
        return True

    def _fail(self) -> None:
        self.close()
        if self._on_fail is not None:
            try:
                self._on_fail()
            except Exception:  # noqa: BLE001 - teardown
                logger.exception("sender failure handler")

    def _done_sending(self) -> None:
        with self._cv:
            self._sending = False
            self._cv.notify_all()


class _SocketTransport:
    """Raw-socket transport for :class:`_CoalescingSender` users whose
    channels do not resume (client sessions, worker IPC)."""

    __slots__ = ("_sock", "_lock")

    def __init__(self, sock: socket.socket, lock=None):
        self._sock = sock
        self._lock = lock

    def send_frame(self, payload: bytes) -> None:
        _send_frame_parts(self._sock, (payload,), self._lock)

    def send_parts(self, *parts) -> None:
        _send_frame_parts(self._sock, parts, self._lock)

    def wait_recovered(self) -> bool:
        return False


# ---------------------------------------------------------------------------
# Head side
# ---------------------------------------------------------------------------


class _Pending:
    """A blocked caller (event mode) or an async continuation (callback
    mode — the reference's ClientCallManager completion path: no head
    thread is parked while the daemon works)."""

    __slots__ = ("event", "reply", "callback")

    def __init__(self, callback=None):
        self.callback = callback
        self.event = None if callback is not None else threading.Event()
        self.reply: Optional[dict] = None


class NodeConnection:
    """Head-side handle to one node daemon: request/reply multiplexing
    over the persistent socket (analog of the reference's per-raylet
    rpc client with a ClientCallManager)."""

    def __init__(self, sock: socket.socket, address: Tuple[str, int],
                 resources: Dict[str, float], labels: Optional[dict],
                 object_addr: Optional[Tuple[str, int]] = None,
                 store_name: Optional[str] = None,
                 reconnect_window_s: float = 30.0,
                 resend_ring_bytes: int = 64 << 20,
                 ack_every: Optional[int] = None,
                 ack_flush_ms: Optional[int] = None):
        from ray_tpu._private.channel import ResilientChannel
        self._sock = sock
        # Resilient session channel: all post-handshake traffic (both
        # directions) flows through it; a transient socket failure
        # parks senders until the daemon re-dials and resumes instead
        # of cascading into remove_node.
        self.channel = ResilientChannel(
            sock, site="head", ring_bytes=resend_ring_bytes,
            window_s=reconnect_window_s, ack_every=ack_every,
            ack_flush_ms=ack_flush_ms)
        import uuid
        # Capability for the resume handshake: the daemon must present
        # it to re-attach, so a stray/imposter dial cannot hijack a
        # session.
        self.channel_token = uuid.uuid4().hex
        self.address = address
        self.resources = resources
        self.labels = labels or {}
        # The daemon's object-server endpoint (peer-to-peer data plane)
        # and shm arena name (same-host zero-copy attach).
        self.object_addr = tuple(object_addr) if object_addr else None
        self.store_name = store_name
        self._send_lock = threading.Lock()
        self._lock = threading.Lock()
        self._pending: Dict[int, _Pending] = {}
        self._req_counter = 0
        self._closed = False
        self._shipped_functions: set = set()
        self.node_id = None  # set at registration
        self._on_death = None
        # Set by HeadServer: a broken session channel wakes the
        # membership loop NOW — a SIGKILLed daemon is probed (and
        # declared dead) in probe-timeout time, not on the next sweep.
        self.on_channel_broken = None
        # Runtime hooks for daemon-pushed frames (no req_id — the recv
        # loop routes them here instead of the pending table).
        self.on_log_batch = None
        self.on_metrics_batch = None
        self.on_profile_batch = None
        self.on_flow_batch = None
        self.on_object_spilled = None
        self.on_object_unspilled = None
        # Dedicated liveness socket (see HeadServer._health_check_loop):
        # pings must not share the data channel — large frames or a full
        # send buffer would stall them and fake a death (or hide one).
        self.health_sock: Optional[socket.socket] = None
        import time
        self.registered_at = time.monotonic()
        # Updated by recv_loop on every inbound frame batch; the head's
        # health sweep reads it as proof of life when pings time out.
        self.last_frame_at = self.registered_at
        # Chaos injection (reference: RAY_testing_* fault flags): each
        # request fails with this probability — exercised by the chaos
        # tests to prove retries survive a flaky control plane.
        self.rpc_failure_pct = 0
        import random
        self._chaos_rng = random.Random(0xC4A05)
        # Bytes of object payload that transited the HEAD for this node
        # (driver gets). Node-to-node pulls never touch this counter —
        # tests assert the head is out of the task-arg data path.
        self.head_fetch_bytes = 0
        # Dedicated completion drainer: recv_loop only enqueues, so the
        # reply stream never stalls behind a slow continuation, while
        # completions skip a shared pool's submit/wakeup overhead
        # (measured ~40% of remote-task throughput at 5k+ tasks/s).
        import queue as _queue
        self._completion_q: "_queue.SimpleQueue" = _queue.SimpleQueue()
        self._completion_thread: Optional[threading.Thread] = None
        self._drainer_dead = False  # guarded by self._lock
        # Single-writer coalescing sender: every outbound frame for this
        # daemon goes through it (task submits batch under load).
        self._sender = _CoalescingSender(
            self.channel, "task_batch", on_fail=self.close,
            name=f"send-{address[1]}")

    # -- plumbing --------------------------------------------------------

    def _next_req(self) -> int:
        with self._lock:
            self._req_counter += 1
            return self._req_counter

    def _request(self, msg: dict, fn_resolver=None,
                 timeout: Optional[float] = None) -> dict:
        """Send a request and block until its reply (or node death).

        ``fn_resolver`` (if given) decides the message's fn_bytes field
        *inside the send lock*: frames share one socket, so deciding
        "already shipped" and sending must be atomic — otherwise a
        concurrent first use could send fn_bytes=None ahead of the frame
        actually carrying the bytes."""
        req_id = self._next_req()
        msg["req_id"] = req_id
        # Outbound control frames are schema-checked at the SOURCE: a
        # drifted field fails here with the offending name, not on the
        # daemon as an opaque handler error (reference: the proto
        # contract enforces this at compile time).
        _wire.validate_message(msg)
        waiter = _Pending()
        with self._lock:
            if self._closed:
                raise RemoteNodeDiedError(
                    f"node {self.address} connection is closed")
            self._pending[req_id] = waiter
        resolver = None
        if fn_resolver is not None:
            def resolver(m, _fr=fn_resolver):
                m["fn_bytes"] = _fr()
        try:
            sent = self._sender.send(
                msg, resolver=resolver,
                nbytes=len(msg.get("payload") or b""))
        except BaseException:
            with self._lock:
                self._pending.pop(req_id, None)
            raise
        if not sent:
            with self._lock:
                self._pending.pop(req_id, None)
            raise RemoteNodeDiedError(
                f"node {self.address} connection is closed")
        if not waiter.event.wait(timeout):
            with self._lock:
                self._pending.pop(req_id, None)
            raise TimeoutError(
                f"node {self.address} did not reply to "
                f"{msg.get('type')} within {timeout}s")
        reply = waiter.reply
        if reply is None or reply.get("type") == "died":
            raise RemoteNodeDiedError(
                f"node {self.address} died while a call was in flight")
        return reply

    def _fire_and_forget(self, msg: dict) -> None:
        """Send with req_id 0 — the daemon's reply (if any) is dropped by
        the recv loop. Never blocks on the daemon (GC/teardown paths)."""
        msg["req_id"] = 0
        _wire.validate_message(msg)
        self._sender.send(msg)  # closed sender: daemon is gone anyway

    def recv_loop(self) -> None:
        """Reply pump; runs on a daemon thread owned by HeadServer.
        Callback-mode completions are handed to this connection's
        drainer thread so a slow continuation (deserialize + store +
        dispatch) never stalls the reply stream."""
        from ray_tpu._private.channel import ChannelBroken, ChannelClosed
        try:
            while True:
                try:
                    raw = self.channel.recv_frame()
                except ChannelBroken:
                    # Transient transport failure: the daemon re-dials
                    # and resumes within the reconnect window. Node
                    # death fires only when the window closes (or the
                    # membership loop confirms the process is gone —
                    # woken immediately via the hook).
                    hook = self.on_channel_broken
                    if hook is not None:
                        hook()
                    if self.channel.wait_recovered():
                        continue
                    break
                except ChannelClosed:
                    break
                replies = _decode_frames(raw)
                # Liveness evidence for the health sweep: a node whose
                # data channel is actively delivering frames is alive no
                # matter how starved its ping thread is (GB-scale
                # transfers on an oversubscribed host can stall the
                # health channel long past the miss threshold).
                self.last_frame_at = _monotonic()
                for reply in replies:
                    kind = reply.get("type")
                    if kind in ("log_batch", "metrics_batch",
                                "profile_batch", "flow_batch",
                                "object_spilled", "object_unspilled"):
                        # Daemon-initiated push, not a reply: hand to
                        # the runtime's fan-out and move on.
                        handler = {
                            "log_batch": self.on_log_batch,
                            "metrics_batch": self.on_metrics_batch,
                            "profile_batch": self.on_profile_batch,
                            "flow_batch": self.on_flow_batch,
                            "object_spilled": self.on_object_spilled,
                            "object_unspilled": self.on_object_unspilled,
                        }[kind]
                        if handler is not None:
                            try:
                                handler(self, reply)
                            except Exception:  # noqa: BLE001
                                logger.exception("%s handling failed",
                                                 kind)
                        del reply
                        continue
                    with self._lock:
                        waiter = self._pending.pop(
                            reply.get("req_id"), None)
                    if waiter is not None:
                        waiter.reply = reply
                        if waiter.callback is not None:
                            self._dispatch_completion(waiter.callback,
                                                      reply)
                        else:
                            waiter.event.set()
                    # Drop locals NOW: an idle connection must not pin
                    # the last task's completion (its callback closes
                    # over the spec, whose args hold ObjectRefs — a
                    # refcount leak).
                    del waiter, reply
                del replies
        except (ConnectionError, OSError):
            pass
        finally:
            self.close()

    def _dispatch_completion(self, callback, reply) -> None:
        with self._lock:
            if not self._drainer_dead:
                if self._completion_thread is None:
                    self._completion_thread = threading.Thread(
                        target=self._drain_completions,
                        name=f"ray_tpu-completions-{self.address[1]}",
                        daemon=True)
                    self._completion_thread.start()
                # Enqueue under the lock: the drainer flips _drainer_dead
                # under the same lock BEFORE its final drain, so nothing
                # can land behind the sentinel unseen.
                self._completion_q.put((callback, reply))
                return
        self._run_completion(callback, reply)  # drainer gone: inline

    def _run_completion(self, callback, reply) -> None:
        from ray_tpu._private.event_stats import GLOBAL
        try:
            with GLOBAL.timed("head.task_completion"):
                callback(reply)
        except Exception:  # noqa: BLE001 - continuations must not kill
            logger.exception("remote-task completion failed")

    def _drain_completions(self) -> None:
        import queue as _queue
        while True:
            item = self._completion_q.get()
            if item is None:
                with self._lock:
                    self._drainer_dead = True
                # Anything enqueued before the flag flip is already in
                # the queue: drain it, THEN exit (no lost completions).
                while True:
                    try:
                        item = self._completion_q.get_nowait()
                    except _queue.Empty:
                        return
                    if item is not None:
                        self._run_completion(*item)
                    del item
            else:
                self._run_completion(*item)
                del item  # see recv_loop: no ref pinning

    def close(self) -> None:
        with self._lock:
            if self._closed:
                return
            self._closed = True
            pending = list(self._pending.values())
            self._pending.clear()
        on_death = self._on_death
        if on_death is not None:
            # Node-death bookkeeping FIRST (invalidate + retry in-flight
            # specs), THEN wake blocked callers so they observe
            # spec.invalidated and discard instead of double-retrying.
            try:
                on_death(self)
            except Exception:  # noqa: BLE001 - never strand waiters
                logger.exception("remote-node death handler failed")
        for waiter in pending:
            waiter.reply = {"type": "died"}
            if waiter.callback is not None:
                self._dispatch_completion(waiter.callback, waiter.reply)
            else:
                waiter.event.set()
        self.channel.close()  # wakes parked senders/receivers, closes sock
        if self.health_sock is not None:
            try:
                self.health_sock.close()
            except OSError:
                pass
        # After the died-completions above: drainer exits once they ran.
        self._completion_q.put(None)
        self._sender.close()

    # -- user-code proxies ----------------------------------------------

    def _function_payload(self, fn_id: bytes, functions) -> Optional[bytes]:
        if fn_id in self._shipped_functions:
            return None
        try:
            payload = functions.get_bytes(fn_id)
        except KeyError:
            raise ValueError(
                "This function/class captured objects that cannot be "
                "serialized, so it cannot run on a remote node. Make it "
                "importable/picklable, or pin it to the head node.")
        self._shipped_functions.add(fn_id)
        return payload

    def _unpack(self, reply: dict, name: str) -> Any:
        if reply["ok"]:
            if "mismatch_desc" in reply:
                return MismatchedReturn(reply["mismatch_desc"])
            if "stored_key" in reply:
                return RemoteValueStub(self, reply["stored_key"],
                                       reply["size"])
            if "parts" in reply:
                # Multi-return split: each element is inline or a
                # daemon-resident stub of its own.
                return [
                    RemoteValueStub(self, p["stored_key"], p["size"])
                    if "stored_key" in p else _loads(p["value"])
                    for p in reply["parts"]]
            return _loads(reply["value"])
        from ray_tpu.exceptions import TaskError
        exc, remote_tb = _loads(reply["error"])
        raise TaskError(exc, remote_tb, name)

    def execute_task_async(self, spec, functions, args, kwargs,
                           store_limit: int, callback,
                           lease_id: Optional[str] = None,
                           class_id: Optional[str] = None) -> None:
        """Send an execute_task request whose reply is delivered to
        ``callback(reply_dict)`` on the completion pool — no head thread
        blocks while the daemon works (the thread-per-call fix; the
        reference's CoreWorkerClient is equally callback-driven). Node
        death delivers ``{"type": "died"}``; chaos injection and send
        failures deliver the same (system failure → retry path)."""
        if self.rpc_failure_pct and \
                self._chaos_rng.random() * 100 < self.rpc_failure_pct:
            self._dispatch_completion(callback, {"type": "died",
                                                 "chaos": True})
            return
        req_id = self._next_req()
        waiter = _Pending(callback)
        msg = {
            "type": "execute_task",
            "req_id": req_id,
            "fn_id": spec.function_id,
            "payload": _dumps((args, kwargs)),
            "name": spec.name,
            "task_id": spec.task_id.hex(),
            "runtime_env": spec.runtime_env,
            "tpu_ids": getattr(spec, "_tpu_ids", None),
            "num_cpus": float(getattr(spec, "resources", {}).get(
                "CPU", 1.0) or 0.0),
            "store_limit": store_limit,
        }
        if isinstance(spec.num_returns, int) and spec.num_returns > 1:
            msg["num_returns"] = spec.num_returns
        trace_ctx = getattr(spec, "trace_ctx", None)
        if trace_ctx is not None:
            # Cross-process propagation: the daemon parents its execute
            # span to the head-side submit span (extra wire fields are
            # additive — schema validation allows them).
            msg["trace_ctx"] = trace_ctx
        if lease_id is not None:
            msg["lease_id"] = lease_id
        if class_id is not None:
            msg["class_id"] = class_id
        if _args_are_plain(args, kwargs):
            # No object markers anywhere at top level: the daemon can
            # forward the payload bytes to its worker subprocess without
            # the unpickle→resolve→repickle round (markers only ever
            # appear at top level — _resolve_args resolves there).
            msg["plain_args"] = True
        _wire.validate_message(msg)
        with self._lock:
            closed = self._closed
            if not closed:
                self._pending[req_id] = waiter
        if closed:
            # OUTSIDE self._lock: _dispatch_completion re-takes it (the
            # lock is not reentrant).
            self._dispatch_completion(callback, {"type": "died"})
            return
        def resolver(m):
            m["fn_bytes"] = self._function_payload(
                spec.function_id, functions)

        try:
            sent = self._sender.send(msg, resolver=resolver,
                                     nbytes=len(msg["payload"]))
        except ValueError:
            with self._lock:
                self._pending.pop(req_id, None)
            raise  # unpicklable function: a USER error, raise inline
        except BaseException:
            with self._lock:
                self._pending.pop(req_id, None)
            raise
        if not sent:
            with self._lock:
                self._pending.pop(req_id, None)
            self._dispatch_completion(callback, {"type": "died"})

    def execute_task(self, spec, functions, args, kwargs,
                     store_limit: int = 0) -> Any:
        # Chaos fires ONLY here: the normal-task submit path absorbs the
        # injected failure through the system-retry budget. Actor calls,
        # creation, and fetches have no per-request retry to hide behind,
        # so injecting there would turn chaos into user-visible errors.
        if self.rpc_failure_pct and \
                self._chaos_rng.random() * 100 < self.rpc_failure_pct:
            raise RemoteNodeDiedError(
                f"injected RPC failure (testing_rpc_failure_pct="
                f"{self.rpc_failure_pct})")
        msg = {
            "type": "execute_task",
            "fn_id": spec.function_id,
            "payload": _dumps((args, kwargs)),
            "name": spec.name,
            "task_id": spec.task_id.hex(),
            "runtime_env": spec.runtime_env,
            "tpu_ids": getattr(spec, "_tpu_ids", None),
            "store_limit": store_limit,
            "num_returns": (spec.num_returns if
                            isinstance(spec.num_returns, int) else 1),
        }
        trace_ctx = getattr(spec, "trace_ctx", None)
        if trace_ctx is not None:
            msg["trace_ctx"] = trace_ctx
        reply = self._request(msg, fn_resolver=lambda: self._function_payload(
            spec.function_id, functions))
        return self._unpack(reply, spec.name)

    def fetch_object(self, key: str,
                     timeout: Optional[float] = None) -> bytes:
        t0 = _monotonic()
        reply = self._request({"type": "fetch_object", "key": key},
                              timeout=timeout)
        from ray_tpu._private import flow
        if not reply["ok"]:
            try:
                flow.global_flow_recorder().record(
                    key=key, nbytes=0, duration_s=_monotonic() - t0,
                    direction="in",
                    peer=self.object_addr or self.address,
                    outcome="error")
            except Exception:  # noqa: BLE001 - accounting only
                pass
            exc, remote_tb = _loads(reply["error"])
            raise exc
        self.head_fetch_bytes += len(reply["raw"])
        # Head-side fetches ride the session channel, not the dataplane
        # pull path — they are object transfers all the same, so they
        # land in the flow ledger with the daemon as src.
        try:
            flow.global_flow_recorder().record(
                key=key, nbytes=len(reply["raw"]),
                duration_s=_monotonic() - t0, direction="in",
                peer=self.object_addr or self.address)
        except Exception:  # noqa: BLE001 - accounting only
            pass
        return reply["raw"]

    def free_object(self, key: str) -> None:
        self._fire_and_forget({"type": "free_object", "key": key})

    def adopt_object(self, key: str, size: int) -> bool:
        """Ask the daemon to take BOOKKEEPING ownership of an arena
        entry a sibling worker process wrote directly into the shared
        shm (distributed-ownership puts): registers its size so spill
        liveness sees it, and confirms the payload is still resident.
        False = already evicted/absent — the caller must fall back."""
        reply = self._request({"type": "adopt_object", "key": key,
                              "size": int(size)})
        return bool(_loads(reply["value"]))

    def push_object(self, key: str, size: int, *,
                    data: Optional[bytes] = None, parent=None, alts=(),
                    wait_timeout_s: float = 60.0,
                    timeout: Optional[float] = None) -> dict:
        """Tree-broadcast directive: replicate ``key`` onto this daemon.
        ``data`` seeds the payload inline (the head feeding its direct
        tree children); otherwise the daemon blocking-waits on
        ``parent``'s object server and pulls, re-parenting through
        ``alts`` if the parent dies mid-broadcast. Blocks until the
        daemon acks the landed copy — the reply IS the completion
        notice that updates the head's replica table."""
        reply = self._request({
            "type": "push_object", "key": key, "size": int(size),
            "data": data,
            "parent": list(parent) if parent else None,
            "alts": [list(a) for a in alts],
            "wait_timeout_s": float(wait_timeout_s),
        }, timeout=timeout)
        return _loads(reply["value"]) if reply["ok"] else \
            self._unpack(reply, f"push_object {key}")

    def drop_lease(self, lease_id: str) -> None:
        """The head released this lease: the daemon retires its serial
        executor and returns the pinned worker subprocess to the pool."""
        self._fire_and_forget({"type": "drop_lease", "lease_id": lease_id})

    def reclaim_tasks(self, class_id: str, max_n: int) -> None:
        """Spillback: ask the daemon to hand back up to max_n queued
        tasks of this class (each answers its own req_id with
        reclaimed=True; the head re-dispatches through the normal
        completion path)."""
        self._fire_and_forget({"type": "reclaim_tasks",
                               "class_id": class_id,
                               "max_n": int(max_n)})

    def spill_lease(self, lease_id: str) -> None:
        """The lease's running task blocked in a nested get (its capacity
        was lent out head-side): the daemon moves the lease queue's
        waiting tasks onto free threads, so a pipelined child can never
        deadlock behind its own blocked parent."""
        self._fire_and_forget({"type": "spill_lease", "lease_id": lease_id})

    def unspill_lease(self, lease_id: str) -> None:
        """The blocked get returned (or the blocked task finalized): the
        daemon resumes SERIAL execution for this lease. Frame ordering
        makes this race-free — tasks the head attaches after clearing
        ``blocked`` travel behind this frame, so only the tasks that
        raced the spill window bypass the queue (sanctioned: the lease's
        capacity was lent out for exactly that window)."""
        self._fire_and_forget({"type": "unspill_lease",
                               "lease_id": lease_id})

    def create_actor(self, spec, functions, args, kwargs) -> None:
        reply = self._request({
            "type": "create_actor",
            "actor_id": spec.actor_id.hex(),
            "fn_id": spec.function_id,
            "payload": _dumps((args, kwargs)),
            "name": spec.name,
            "task_id": spec.task_id.hex(),
            "runtime_env": spec.runtime_env,
            "tpu_ids": getattr(spec, "_tpu_ids", None),
        }, fn_resolver=lambda: self._function_payload(
            spec.function_id, functions))
        self._unpack(reply, f"{spec.name}.__init__")

    def call_actor_method(self, actor_id, method_name, name,
                          args, kwargs, store_limit: int = 0,
                          num_returns: int = 1,
                          trace_ctx: Optional[dict] = None) -> Any:
        msg = {
            "type": "actor_call",
            "actor_id": actor_id.hex(),
            "method": method_name,
            "payload": _dumps((args, kwargs)),
            "name": name,
            "store_limit": store_limit,
            "num_returns": num_returns,
        }
        if trace_ctx is not None:
            msg["trace_ctx"] = trace_ctx
        reply = self._request(msg)
        return self._unpack(reply, name)

    def destroy_actor(self, actor_id) -> None:
        self._fire_and_forget({"type": "destroy_actor",
                               "actor_id": actor_id.hex()})

    def get_stats(self, timeout: Optional[float] = 10.0) -> dict:
        """Daemon-side counters (object-transfer bytes, actor count)."""
        reply = self._request({"type": "stats"}, timeout=timeout)
        return _loads(reply["value"])

    def profile(self, duration: float = 5.0, hz: int = 100,
                fmt: str = "folded", pid: Optional[int] = None):
        """Ask the daemon to sample ITS OWN stacks (cooperative remote
        profiling; reference: dashboard profile endpoints). ``pid``
        retargets the burst at one of the daemon's pool workers — the
        daemon relays a profile request over that worker's pipe."""
        msg = {"type": "profile", "duration": duration, "hz": hz,
               "fmt": fmt}
        if pid is not None:
            msg["pid"] = int(pid)
        reply = self._request(msg, timeout=duration + 30)
        return _loads(reply["value"])


def describe_value(value) -> str:
    """'<type> of length <n>' for num_returns-mismatch errors — one
    wording shared by the daemon and head reporters."""
    return (f"{type(value).__name__} of length "
            f"{len(value) if hasattr(value, '__len__') else 'n/a'}")


class MismatchedReturn:
    """Marker for a num_returns>1 task whose oversized result had the
    wrong shape: the daemon describes the value instead of storing a
    stub nobody could ever consume (and that would leak in its table)
    or shipping gigabytes to the head just to format an error."""

    __slots__ = ("desc",)

    def __init__(self, desc: str):
        self.desc = desc


class RemoteValueStub:
    """Head-side handle to a result the daemon kept locally (it exceeded
    remote_object_inline_limit_bytes): the ObjectStore materializes it on
    first get via fetch(). Never pickled."""

    __slots__ = ("conn", "key", "size")

    def __init__(self, conn: "NodeConnection", key: str, size: int):
        self.conn = conn
        self.key = key
        self.size = size

    def fetch(self, timeout=None):
        from ray_tpu.exceptions import ObjectLostError
        try:
            return _loads(self.conn.fetch_object(self.key, timeout=timeout))
        except RemoteNodeDiedError as exc:
            raise ObjectLostError(
                f"Object payload {self.key} was on node "
                f"{self.conn.address}, which died before it was fetched "
                "(reconstruction, if possible, re-seals the object)."
            ) from exc


class RemoteArgMarker:
    """Locality marker: an argument whose payload already lives in the
    target daemon's object table travels as this tiny stub and is resolved
    daemon-side — the task-arg analog of a plasma-local read."""

    __slots__ = ("key",)

    def __init__(self, key: str):
        self.key = key


class RemoteActorInstance:
    """Placeholder stored as ActorState.instance for daemon-resident
    actors; method lookups return wire-call closures."""

    def __init__(self, conn: NodeConnection, actor_id):
        self.conn = conn
        self.actor_id = actor_id

    def bind_method(self, method_name: str, task_name: str,
                    store_limit: int = 0, num_returns: int = 1):
        def call(*args, **kwargs):
            # The closure runs INSIDE the head-side actor_task:: span
            # (_run_actor_task's continue_context): propagate THAT span
            # so the daemon-side span parents to it across the wire.
            # span_context (not inject_context) — an untraced call must
            # not mint a new root at this internal layer.
            from ray_tpu.util import tracing
            return self.conn.call_actor_method(
                self.actor_id, method_name, task_name, args, kwargs,
                store_limit, num_returns=num_returns,
                trace_ctx=tracing.span_context(tracing.current_span()))
        return call


class HeadServer:
    """Listens for node-daemon registrations (the GCS node-manager
    surface: register → add_node; disconnect → remove_node)."""

    def __init__(self, runtime, host: str = "0.0.0.0", port: int = 0):
        self.runtime = runtime
        self._listener = socket.create_server((host, port))
        self.address = self._listener.getsockname()[:2]
        self._threads = []
        self._conns: Dict[Any, NodeConnection] = {}
        self._client_sessions: list = []
        self._closed = False
        self._accept_thread = threading.Thread(
            target=self._accept_loop, name="ray_tpu-head-server",
            daemon=True)
        # Liveness (reference: gcs_health_check_manager.h, upgraded to
        # accrual suspicion + a hard lease — _private/membership.py):
        # EOF catches a dead process; the per-period health probe plus
        # free channel-frame evidence feed each node's phi score, so a
        # hung daemon crosses the suspicion threshold (or the lease)
        # instead of a fixed miss count. A broken session channel sets
        # _probe_wake for an immediate probe (sub-second SIGKILL
        # detection at the 0.25s default period).
        cfg = runtime.config
        self._probe_period = float(cfg.health_probe_period_s)
        self._probe_timeout = float(cfg.health_probe_timeout_s)
        self._lease_s = float(cfg.node_lease_s)
        self._suspicion = float(cfg.node_suspicion_threshold)
        self._probe_wake = threading.Event()
        self._hb_thread = threading.Thread(
            target=self._membership_loop, name="ray_tpu-head-health",
            daemon=True)
        # Cluster-wide usage view fed by daemon pong piggybacks
        # (reference: ray_syncer receiver side in the GCS).
        from ray_tpu._private.syncer import ClusterSyncState
        self.syncer = ClusterSyncState()

    def start(self) -> Tuple[str, int]:
        self._accept_thread.start()
        if self._probe_period > 0:
            self._hb_thread.start()
        return self.address

    def _membership_loop(self) -> None:
        """Suspicion-driven liveness (see _private/membership.py).

        Every ``health_probe_period_s`` (or immediately, when a broken
        session channel sets ``_probe_wake``): fold channel activity
        into each node's accrual detector — frames are free liveness
        evidence, no probe needed for a chatty node — then ping the
        dedicated health socket with ``health_probe_timeout_s``.
        Failures classify HARD (reset/refused while the session channel
        is also broken: the process is gone, declare now) or SOFT
        (timeout or blackholed partition: evidence feeding the phi
        score). Death fires at ``node_suspicion_threshold`` or,
        unconditionally, once silence exceeds ``node_lease_s``."""
        digest_sent: Dict[Any, int] = {}
        from ray_tpu._private.event_stats import GLOBAL
        from ray_tpu._private import builtin_metrics
        import time as _time
        while not self._closed:
            t_wait = _time.monotonic()
            woken = self._probe_wake.wait(self._probe_period)
            self._probe_wake.clear()
            if self._closed:
                return
            if not woken:
                # Head saturation signal: how far past the intended
                # period the sweep actually woke (early wakes excluded —
                # they are on purpose). A busy/GIL-starved head shows up
                # here before anything times out.
                lag = (_time.monotonic() - t_wait) - self._probe_period
                try:
                    builtin_metrics.loop_lag().set(
                        max(0.0, lag), tags={"loop": "head.membership"})
                except Exception:  # noqa: BLE001 - gauge is best-effort
                    pass
            with GLOBAL.timed("head.health_sweep"):
                current = list(self._conns.items())
                # Departed nodes (EOF path) must not leak entries.
                alive_ids = {nid for nid, _ in current}
                for nid in list(digest_sent):
                    if nid not in alive_ids:
                        digest_sent.pop(nid, None)
                # One digest per sweep, shipped to a node only when
                # newer than what it last acked (the only-changed rule
                # the daemon->head direction already follows).
                digest = self.syncer.digest()
                for node_id, conn in current:
                    self._probe_node(node_id, conn, digest, digest_sent)

    def _probe_node(self, node_id, conn: NodeConnection, digest: dict,
                    digest_sent: Dict[Any, int]) -> None:
        import time
        membership = self.runtime.membership
        live = membership.liveness(node_id.hex())
        if live is None:
            return  # already declared dead (racing close)
        # Channel traffic is free liveness: any frame batch the recv
        # loop saw since our last look counts as an arrival — a node
        # mid-transfer (or mid-XLA-compile, pushing metrics_batch
        # heartbeats) never needs its ping answered to stay alive.
        if conn.last_frame_at > live.detector.last_arrival:
            live.record_arrival(conn.last_frame_at)
        hc = conn.health_sock
        hard = soft = None
        if hc is None:
            # Health channel still connecting: no probe possible — only
            # the hard lease bounds how long we wait for it.
            if time.monotonic() - max(conn.registered_at,
                                      live.detector.last_arrival) \
                    > self._lease_s:
                if membership.declare_dead(
                        node_id.hex(), "no health channel within lease"):
                    from ray_tpu._private import builtin_metrics
                    builtin_metrics.node_deaths().inc(
                        tags={"kind": "lease"})
                    logger.warning(
                        "Node %s never opened its health channel within "
                        "the %.1fs lease; declaring it dead",
                        node_id.hex()[:12], self._lease_s)
                    conn.close()
            return
        try:
            # Tiny frames on the dedicated socket: bounded by the socket
            # timeout, never queued behind data transfers and never
            # contending for the data send lock.
            hc.settimeout(self._probe_timeout)
            if _chaos.ACTIVE:
                _chaos.maybe_inject("head.health.send", hc)
            ping: dict = {"type": "ping"}
            if digest["version"] > digest_sent.get(node_id, -1):
                ping["cluster_digest"] = digest
            _send_frame(hc, _dumps(ping))
            if _chaos.ACTIVE:
                _chaos.maybe_inject("head.health.recv", hc)
            pong = _loads(_recv_frame(hc))
            if "cluster_digest" in ping:
                digest_sent[node_id] = digest["version"]
            sync = pong.get("sync")
            if sync:
                self.syncer.apply(node_id.hex(), sync)
            live.record_arrival()
            return
        except (_chaos.ChaosPartition, TimeoutError) as exc:
            # Unreachable, not provably dead: a partition heals, a
            # starved pong thread recovers. Evidence, not a verdict.
            soft = exc
        except (ConnectionError, OSError) as exc:
            hard = exc
        if hard is not None and conn.channel.broken:
            # Session channel broken AND the dedicated health socket
            # actively refused/reset: the process is gone. Declare now
            # instead of burning the reconnect window waiting for a
            # resume that can never come.
            if membership.declare_dead(
                    node_id.hex(), f"process gone: {hard}"):
                from ray_tpu._private import builtin_metrics
                builtin_metrics.node_deaths().inc(tags={"kind": "hard"})
                logger.warning(
                    "Node %s: broken session channel and failed health "
                    "ping (%s); declaring it dead",
                    node_id.hex()[:12], hard)
                conn.close()  # → on_death → remove_node
            return
        live.soft_failures += 1
        now = time.monotonic()
        silent = live.silent_for(now)
        phi = live.phi(now)
        if silent <= self._lease_s and phi < self._suspicion:
            return
        kind = "lease" if silent > self._lease_s else "suspicion"
        if membership.declare_dead(
                node_id.hex(),
                f"{kind}: phi={phi:.1f} silent={silent:.2f}s "
                f"soft_failures={live.soft_failures}"):
            from ray_tpu._private import builtin_metrics
            builtin_metrics.node_deaths().inc(tags={"kind": kind})
            logger.warning(
                "Node %s declared dead (%s: phi=%.1f after %.2fs of "
                "silence, %d failed probes)", node_id.hex()[:12], kind,
                phi, silent, live.soft_failures)
            conn.close()  # → on_death → remove_node

    def _accept_loop(self) -> None:
        while not self._closed:
            try:
                sock, addr = self._listener.accept()
            except OSError:
                return
            # Handshake on a short-lived thread with a deadline: one
            # stalled/silent client (port scanner, half-open socket) must
            # not block the accept loop — with the health-channel grace
            # kill, a blocked accept would take down every node whose
            # channel assignment is pending.
            threading.Thread(target=self._handshake, args=(sock, addr),
                             name="ray_tpu-head-handshake",
                             daemon=True).start()

    def _handshake(self, sock: socket.socket, addr) -> None:
        import time as _time

        from ray_tpu._private.event_stats import GLOBAL
        _t0 = _time.monotonic()
        node_id = None
        try:
            sock.settimeout(15)
            register = _loads(_recv_frame(sock))
            sock.settimeout(None)
            if register.get("type") == "client_runtime":
                # A daemon/worker-side user-code process binding a
                # connected runtime (client_runtime.py) — the anti-
                # split-brain surface: nested submits, named actors,
                # refs all resolve against THIS head. Same version
                # handshake as daemons: a client from another release
                # is told exactly why it cannot join.
                try:
                    _wire.check_peer_protocol(
                        register.get("protocol"),
                        f"client runtime at {addr}")
                except _wire.ProtocolMismatch as exc:
                    logger.error("rejecting client runtime: %s", exc)
                    _send_frame_best_effort(sock, _dumps({
                        "type": "register_rejected",
                        "error": str(exc),
                        "head_protocol": _wire.PROTOCOL_VERSION}))
                    sock.close()
                    return
                from ray_tpu._private.client_runtime import ClientSession
                from ray_tpu._private.worker import global_worker as _gw
                session = ClientSession(
                    self.runtime, sock, addr,
                    on_close=self._client_sessions_discard)
                _send_frame(sock, _dumps({
                    "type": "client_registered",
                    "job_id": self.runtime.job_id.hex(),
                    "session_id": self.runtime.session_id,
                    "namespace": _gw.namespace,
                    "head_node_id": self.runtime.head_node_id.hex(),
                    "num_cpus": self.runtime.node_resources.num_cpus,
                    "num_tpus": self.runtime.node_resources.num_tpus,
                }))
                self._client_sessions.append(session)
                threading.Thread(target=session.serve,
                                 name="ray_tpu-client-session",
                                 daemon=True).start()
                GLOBAL.record("head.client_session",
                              _time.monotonic() - _t0)
                return
            if register.get("type") == "resume":
                self._handle_resume(sock, addr, register, _t0)
                return
            if register.get("type") == "health_channel":
                # Second connection from an already-registered daemon,
                # reserved for liveness pings. (Snapshot: recv/health
                # threads pop _conns concurrently.)
                for conn in list(self._conns.values()):
                    if conn.node_id is not None and \
                            conn.node_id.hex() == register["node_id"]:
                        conn.health_sock = sock
                        break
                else:
                    # A declared-dead (or never-known) incarnation's
                    # health thread re-announcing: fence it — counted,
                    # not warned per-announce (a partitioned daemon's
                    # reconnect loop would spam the log).
                    from ray_tpu._private import builtin_metrics, events
                    builtin_metrics.frames_fenced().inc()
                    events.emit(
                        "membership", "fenced unknown health-channel "
                        "announce", severity="warning",
                        node_id=str(register.get("node_id", "")),
                        labels={"kind": "health_channel"})
                    sock.close()
                return
            assert register["type"] == "register", register
            # Version handshake (reference: node_manager.proto contract
            # is compiled in; here it travels explicitly): a daemon
            # from another release is REJECTED with a clear error, not
            # left to fail on some later frame's missing field.
            try:
                _wire.check_peer_protocol(register.get("protocol"),
                                          f"node daemon at {addr}")
            except _wire.ProtocolMismatch as exc:
                logger.error("rejecting daemon registration: %s", exc)
                _send_frame_best_effort(sock, _dumps({
                    "type": "register_rejected",
                    "error": str(exc),
                    "head_protocol": _wire.PROTOCOL_VERSION}))
                sock.close()
                return
            cfg = self.runtime.config
            conn = NodeConnection(
                sock, tuple(addr),
                register["resources"],
                register.get("labels"),
                object_addr=register.get("object_addr"),
                store_name=register.get("store_name"),
                reconnect_window_s=float(cfg.channel_reconnect_window_s),
                resend_ring_bytes=int(cfg.channel_resend_ring_bytes),
                ack_every=int(cfg.channel_ack_every),
                ack_flush_ms=int(cfg.channel_ack_flush_ms))
            conn.rpc_failure_pct = int(
                self.runtime.config.testing_rpc_failure_pct)
            # Registration makes the node schedulable, which can
            # immediately dispatch queued tasks onto this connection
            # from worker threads. The sender is the socket's single
            # writer and its queue is FIFO, so enqueueing the ack
            # BEFORE register_remote_node publishes the conn guarantees
            # "registered" is the first frame the daemon reads — task
            # frames queue behind it. (Pre-r5 this held the send lock
            # instead; the sender thread does not take that lock.)
            node_id = self.runtime.new_node_id()
            conn.node_id = node_id
            # Mint this incarnation's epoch (fenced membership, wire
            # v9) and stamp the channel BEFORE the ack goes out: every
            # enveloped frame of this session carries the epoch, and
            # the ack teaches the daemon its incarnation.
            epoch = self.runtime.membership.mint_epoch(
                node_id.hex(), probe_period_s=self._probe_period or 0.25)
            conn.channel.epoch = epoch
            # session_id rides the ack (additive optional field) so the
            # daemon can join the session's log directory tree.
            conn._sender.send({"type": "registered",
                               "node_id": node_id.hex(),
                               "session_id": self.runtime.session_id,
                               "channel_token": conn.channel_token,
                               "node_epoch": epoch})
            # dispatch=False: the post-ack _dispatch below places
            # queued work once the reply pump is running.
            self.runtime.register_remote_node(
                conn, register, dispatch=False, node_id=node_id)
            conn._on_death = self._on_conn_death
            conn.on_channel_broken = self._probe_wake.set
            self._conns[node_id] = conn
        except Exception:  # noqa: BLE001 - one bad join must not
            # strand a half-registered node.
            if node_id is not None:
                self._conns.pop(node_id, None)
                try:
                    self.runtime.membership.declare_dead(
                        node_id.hex(), "registration failed")
                    self.runtime.unregister_remote_node(node_id)
                except Exception:  # noqa: BLE001
                    logger.exception("rollback of failed node "
                                     "registration failed")
            try:
                sock.close()
            except OSError:
                pass
            GLOBAL.record("head.handshake_failed",
                          _time.monotonic() - _t0)
            return
        t = threading.Thread(target=conn.recv_loop,
                             name=f"ray_tpu-node-{node_id.hex()[:8]}",
                             daemon=True)
        t.start()
        self._threads.append(t)
        # Place queued work on the new node AFTER the send lock is
        # released and the reply pump is running (inline task sends
        # take the send lock; see register_remote_node dispatch=False).
        self.runtime._dispatch()
        GLOBAL.record("head.handshake", _time.monotonic() - _t0)
        logger.info("Node daemon %s joined as %s with %s",
                    addr, node_id.hex()[:12], register["resources"])

    def _handle_resume(self, sock: socket.socket, addr, register: dict,
                       _t0: float) -> None:
        """Re-attach a daemon's broken session channel (wire v7).

        Raw (un-enveloped) handshake: validate protocol + node id +
        channel token, reply ``resumed`` with our last-seen seq, then
        attach the fresh socket — the attach replays every unacked
        frame past the daemon's position. Any rejection sends the
        daemon back to a full re-register, which keeps head-restart
        rebinds (detached actors) as fast as before."""
        import time as _time

        from ray_tpu._private.event_stats import GLOBAL
        try:
            _wire.check_peer_protocol(register.get("protocol"),
                                      f"resuming daemon at {addr}")
        except _wire.ProtocolMismatch as exc:
            _send_frame_best_effort(sock, _dumps({
                "type": "resume_rejected", "error": str(exc)}))
            sock.close()
            return
        epoch = int(register.get("epoch") or 0)
        if epoch and self.runtime.membership.is_fenced(epoch):
            # A declared-dead incarnation back from the far side of a
            # partition: its session (and its actors) died exactly once
            # when the lease expired. The FENCED verdict (vs a generic
            # rejection) tells the daemon to drop its stale residents
            # and re-register as a fresh incarnation.
            from ray_tpu._private import builtin_metrics, events
            builtin_metrics.frames_fenced().inc()
            events.emit(
                "membership",
                f"fenced resume from dead incarnation {epoch}",
                severity="warning",
                node_id=str(register.get("node_id", "")),
                labels={"kind": "resume", "epoch": epoch})
            logger.info(
                "Fencing resume from dead incarnation %d of node %s",
                epoch, str(register.get("node_id"))[:12])
            _send_frame_best_effort(sock, _dumps({
                "type": "fenced", "epoch": epoch,
                "error": "incarnation declared dead; re-register as a "
                         "new node"}))
            sock.close()
            return
        conn = None
        for cand in list(self._conns.values()):
            if cand.node_id is not None and \
                    cand.node_id.hex() == register.get("node_id"):
                conn = cand
                break
        if conn is None or conn._closed or \
                register.get("token") != conn.channel_token:
            _send_frame_best_effort(sock, _dumps({
                "type": "resume_rejected",
                "error": "unknown session (node removed or head "
                         "restarted); re-register"}))
            sock.close()
            return
        # Raw reply BEFORE attach: the daemon reads it to learn our
        # last-seen seq; the replayed (enveloped) frames follow it.
        try:
            _send_frame(sock, _dumps({"type": "resumed",
                                      "last_seq": conn.channel.in_seq}))
        except OSError:
            sock.close()
            return
        if not conn.channel.attach(sock, int(register.get("last_seq", 0))):
            # Resend ring evicted past the daemon's position (or the
            # channel is closed): lossless replay is impossible, so the
            # session is unrecoverable — node death, as before v7.
            conn.close()
            _close_quiet(sock)
            return
        conn.last_frame_at = _monotonic()
        GLOBAL.record("head.channel_resume", _time.monotonic() - _t0)
        logger.info("Node %s resumed its session channel",
                    conn.node_id.hex()[:12] if conn.node_id else addr)

    def _client_sessions_discard(self, session) -> None:
        """Dead client sessions must not accumulate under worker churn."""
        try:
            self._client_sessions.remove(session)
        except ValueError:
            pass

    def _on_conn_death(self, conn: NodeConnection) -> None:
        if self._closed:
            return
        self._conns.pop(conn.node_id, None)
        if conn.node_id is not None:
            self.syncer.remove_node(conn.node_id.hex())
            # EOF/teardown paths reach here without the membership loop:
            # fence the incarnation (exactly-once — a racing probe's
            # declare_dead already returned True and this is a no-op).
            self.runtime.membership.declare_dead(
                conn.node_id.hex(), "connection closed")
        self.runtime.unregister_remote_node(conn.node_id)

    def event_stats(self):
        """Per-handler latency/queue summaries (reference:
        instrumented_io_context.stats() via RAY_event_stats)."""
        from ray_tpu._private.event_stats import GLOBAL
        return GLOBAL.summary()

    def stop(self, keep_nodes=()) -> None:
        """``keep_nodes``: node ids hosting detached actors. Those
        daemons get NO shutdown frame — just a socket close, which their
        run() loop treats as connection loss: resident actors are kept
        for the reconnect window so a restarted head (same port +
        gcs_store_path) can rebind them."""
        self._closed = True
        self._probe_wake.set()  # membership loop exits promptly
        keep = set(keep_nodes or ())
        try:
            self._listener.close()
        except OSError:
            pass
        for node_id, conn in list(self._conns.items()):
            conn._on_death = None  # orderly shutdown, not node death
            if node_id not in keep:
                # Through the sender (the socket's single writer),
                # flushed before close() tears the socket down.
                conn._sender.send({"type": "shutdown", "req_id": 0})
            conn._sender.flush()
            conn.close()
        self._conns.clear()
        # Copy first: session.close() removes itself from the list via
        # the on_close callback — iterating the live list skips entries.
        for session in list(self._client_sessions):
            session.close()
        self._client_sessions.clear()


# ---------------------------------------------------------------------------
# Daemon side
# ---------------------------------------------------------------------------


#: The NodeDaemon serving this process, if any — lets user code running
#: in-daemon (TPU tasks, actor methods) read the gossiped cluster view
#: locally via ray_tpu.cluster_usage() without a round-trip to the head.
_current_daemon: Optional["NodeDaemon"] = None


class _ClassQueue:
    """Daemon-LOCAL dispatch queue for one scheduling class (reference:
    local_task_manager.cc:101 — the raylet owns a per-class queue and
    dispatches to whichever of its leased workers frees up; the head
    only grants capacity). Every lease slot of the class pulls from this
    one FIFO, so the daemon — not the head — decides which worker runs
    which queued task: a slow task no longer head-of-line-blocks the
    work the head happened to pipeline behind it on the same lease.

    Blocked-capacity lending: when the head reports a slot's running
    task blocked in a nested get (spill_lease), that slot's accounted
    capacity was released head-side — the daemon spins up a TEMPORARY
    slot against it (the reference's NotifyDirectCallTaskBlocked
    semantics: a blocked worker's CPU is re-grantable). The temp slot
    retires on unspill. This keeps the deadlock guarantee (a child
    queued behind its blocked parent always finds a slot) without
    draining whole queues onto unbounded threads."""

    def __init__(self, daemon: "NodeDaemon", class_id: str):
        self._daemon = daemon
        self.class_id = class_id
        from collections import deque
        self.dq: Any = deque()
        self.cv = threading.Condition()
        self.slots: set = set()        # live _LeaseExecutor objects
        self.temp_slots = 0            # live temp-slot threads
        self._retire_pending = 0       # unspills waiting to retire one
        self._closed = False           # session over: temp slots exit

    def put(self, item) -> None:
        with self.cv:
            self.dq.append(item)
            self.cv.notify()

    def put_front(self, item) -> None:
        with self.cv:
            self.dq.appendleft(item)
            self.cv.notify()

    def get(self, timeout: float = 0.5):
        with self.cv:
            if not self.dq:
                self.cv.wait(timeout)
            return self.dq.popleft() if self.dq else None

    def pop_tail(self, max_n: int) -> list:
        """Reclaim (head spillback): hand back up to max_n NOT-STARTED
        tasks from the tail — the most recently pipelined, so FIFO
        fairness for the rest is untouched."""
        out = []
        with self.cv:
            while self.dq and len(out) < max_n:
                out.append(self.dq.pop())
        return out

    def qsize(self) -> int:
        return len(self.dq)

    def spill(self) -> None:
        """One slot's task blocked head-side: lend its capacity to a
        temporary slot serving this queue."""
        with self.cv:
            self.temp_slots += 1
        threading.Thread(target=self._temp_loop,
                         name=f"ray_tpu-temp-{self.class_id}",
                         daemon=True).start()

    def unspill(self) -> None:
        """The blocked task resumed: retire one temp slot (after its
        current task, if it grabbed one)."""
        with self.cv:
            self._retire_pending += 1
            self.cv.notify_all()

    def close(self) -> None:
        """Session teardown: every temp slot must exit — the head that
        would have sent the retiring unspill is gone."""
        with self.cv:
            self._closed = True
            self.cv.notify_all()

    def _temp_loop(self) -> None:
        try:
            while True:
                with self.cv:
                    if self._closed:
                        return
                    if self._retire_pending > 0:
                        self._retire_pending -= 1
                        return
                item = self.get(timeout=0.2)
                if item is None:
                    continue
                sock, msg = item
                # No pinned worker: per-task pool lease (temp slots are
                # short-lived; pinning would hoard subprocesses).
                self._daemon._handle_counted(sock, msg)
        finally:
            with self.cv:
                self.temp_slots -= 1

    def drain_to_threads(self) -> None:
        """Last slot retired with work still queued (head/daemon
        accounting drift — should not happen): never strand tasks."""
        while True:
            with self.cv:
                if not self.dq:
                    return
                sock, msg = self.dq.popleft()
            threading.Thread(target=self._daemon._handle_counted,
                             args=(sock, msg), daemon=True).start()


class _LeaseExecutor:
    """Daemon-side half of a worker lease (reference: raylet's leased
    worker + direct_task_transport pipelining): one dedicated thread =
    one accounted resource acquisition. In SHARED mode (CPU classes) the
    thread is a slot on the class's local dispatch queue — the daemon
    decides which slot runs which task (_ClassQueue). In SERIAL mode
    (TPU classes, whose tasks carry chip ids the head accounted to THIS
    lease) it keeps its own strict-FIFO queue, so two tasks holding the
    same chips can never overlap. Worker-process tasks pin ONE
    subprocess for the lease's lifetime (no per-task pool traffic)."""

    def __init__(self, daemon: "NodeDaemon", lease_id: str,
                 cq: Optional[_ClassQueue] = None):
        self._daemon = daemon
        self.lease_id = lease_id
        self._cq = cq
        import queue as _queue
        self._q: "_queue.SimpleQueue" = _queue.SimpleQueue()
        self._stopping = False
        self.worker_handle = None  # pinned worker subprocess (if any)
        self.worker_python = None
        self.tasks_run = 0
        # SERIAL mode only — set while the lease's running task is
        # blocked in a nested get: tasks that raced onto the wire before
        # the head stopped attaching must bypass the serial queue, or
        # one could land behind the blocked parent it is a dependency
        # of. CLEARED by the head's unspill_lease when the get returns.
        self.spilled = False
        if cq is not None:
            with cq.cv:
                cq.slots.add(self)
        self._thread = threading.Thread(
            target=self._run_shared if cq is not None else self._run,
            name=f"ray_tpu-lease-{lease_id}", daemon=True)
        self._thread.start()

    def submit(self, sock, msg: dict) -> None:
        if self._cq is not None:
            self._cq.put((sock, msg))
        else:
            self._q.put((sock, msg))

    def stop(self) -> None:
        self._stopping = True
        if self._cq is not None:
            with self._cq.cv:
                self._cq.cv.notify_all()
        else:
            self._q.put(None)

    def spill(self) -> None:
        """The lease's running task blocked in a nested get; its
        capacity was lent out head-side. SHARED mode: lend it to a temp
        slot. SERIAL mode: move every waiting task off this serial
        queue onto its own handler thread (concurrency sanctioned by
        the released capacity) — a child pipelined behind its blocked
        parent must never deadlock."""
        if self._cq is not None:
            self._cq.spill()
            return
        self.spilled = True
        import queue as _queue
        while True:
            try:
                item = self._q.get_nowait()
            except _queue.Empty:
                return
            if item is None:
                self._q.put(None)  # re-arm the stop sentinel
                return
            sock, msg = item
            threading.Thread(target=self._daemon._handle_counted,
                             args=(sock, msg), daemon=True).start()

    def unspill(self) -> None:
        """Resume normal capacity (the head cleared lease.blocked)."""
        if self._cq is not None:
            self._cq.unspill()
            return
        self.spilled = False

    def _run(self) -> None:
        while True:
            item = self._q.get()
            if item is None:
                break
            sock, msg = item
            msg["_lease_exec"] = self  # daemon-local pin context
            self.tasks_run += 1
            self._daemon._handle_counted(sock, msg)
        self._release_pinned()

    def _run_shared(self) -> None:
        cq = self._cq
        try:
            while True:
                item = cq.get(timeout=0.5)
                if self._stopping:
                    if item is not None:
                        cq.put_front(item)  # another slot takes it
                    break
                if item is None:
                    continue
                sock, msg = item
                msg["_lease_exec"] = self  # daemon-local pin context
                self.tasks_run += 1
                self._daemon._handle_counted(sock, msg)
        finally:
            with cq.cv:
                cq.slots.discard(self)
                last = not cq.slots
            if last and cq.qsize():
                cq.drain_to_threads()
            self._release_pinned()

    def _release_pinned(self) -> None:
        handle = self.worker_handle
        self.worker_handle = None
        if handle is not None:
            try:
                self._daemon._get_pool().release(handle)
            except Exception:  # noqa: BLE001 - teardown best-effort
                pass


def _reap_stale_spill_dirs(parent: str) -> None:
    """Remove ray_tpu_spill_<pid> dirs whose owning process is dead
    (reference: the raylet reclaims its spill directory on restart)."""
    import shutil
    try:
        entries = os.listdir(parent)
    except OSError:
        return
    for fname in entries:
        if not fname.startswith("ray_tpu_spill_"):
            continue
        try:
            pid = int(fname.rsplit("_", 1)[1])
        except ValueError:
            continue
        if pid == os.getpid() or procinfo.pid_alive(pid):
            continue
        shutil.rmtree(os.path.join(parent, fname), ignore_errors=True)


class NodeDaemon:
    """The per-node daemon (raylet + worker-pool analog): executes pushed
    CPU tasks in real worker subprocesses (crash isolation — a dying
    task kills one worker, not the node), runs TPU tasks in-process (the
    chip is single-process), hosts actor instances. Owns the node's
    object table (shm arena, shared with its workers) + object server —
    the distributed data plane's local half (_private/dataplane.py)."""

    def __init__(self, head_address: Tuple[str, int],
                 resources: Dict[str, float],
                 labels: Optional[dict] = None,
                 object_store_memory: int = 1 << 28,
                 spill_dir: Optional[str] = None):
        self.head_address = head_address
        self.resources = resources
        self.labels = labels or {}
        self._functions: Dict[bytes, Any] = {}
        # Raw fn_bytes cached by the single recv-loop thread BEFORE the
        # request is handed to a handler thread. The head ships bytes only
        # on first use; a concurrent second request (fn_bytes=None) could
        # otherwise race the first handler's load and fail spuriously.
        self._fn_raw: Dict[bytes, bytes] = {}
        self._actors: Dict[str, Any] = {}
        self._actor_tpu_ids: Dict[str, Any] = {}
        # Node object table (local half of the data plane): big results
        # stay here — in the shm arena when available — until freed;
        # peer daemons pull them directly over the object server (which
        # binds lazily in run(), on the head-facing interface).
        from ray_tpu._private import dataplane
        from ray_tpu._private.dataplane import (NodeObjectTable,
                                                PullAdmission)
        from ray_tpu._private.ray_config import make_ray_config
        _cfg = make_ray_config(None)
        # Pull tuning travels through RayConfig so the flag pipeline
        # (env > system config > defaults) governs the data plane too.
        dataplane.configure_pulls(int(_cfg.pull_chunk_bytes),
                                  int(_cfg.pull_parallelism))
        # Disk spill keeps memory pressure from ever LOSING a block
        # (reference: raylet spill/restore, local_object_manager.h).
        # Directory precedence: explicit arg > the object_spilling_
        # directory config flag (the same one the head store honors —
        # a user pointing spill at NVMe scratch gets BOTH stores there)
        # > a per-daemon dir under the system temp dir.
        if spill_dir is None:
            spill_dir = _cfg.object_spilling_directory or None
        if spill_dir is None:
            import tempfile
            spill_dir = os.path.join(
                tempfile.gettempdir(),
                f"ray_tpu_spill_{os.getpid()}")
        else:
            spill_dir = os.path.join(
                spill_dir, f"ray_tpu_spill_{os.getpid()}")
        self._spill_dir = spill_dir
        # Crashed daemons (SIGKILL/OOM) never run close(): reap sibling
        # ray_tpu_spill_<pid> dirs AND /dev/shm arenas whose pid is
        # gone, in the background (a dead shuffle can leave tens of GB
        # behind in each).
        def _reap(parent=os.path.dirname(spill_dir)):
            _reap_stale_spill_dirs(parent)
            from ray_tpu._private.native_store import reap_stale_arenas
            reap_stale_arenas()

        threading.Thread(target=_reap,
                         name="ray_tpu-spill-reaper", daemon=True).start()
        self._table = NodeObjectTable(capacity=object_store_memory,
                                      spill_dir=spill_dir)
        # Durable spill tier (reference: local_object_manager.h external
        # storage): a configured spill URI swaps the table's backend so
        # spilled payloads survive this daemon's death. session:// needs
        # the head's session id — upgraded at registration; other
        # schemes (file://, mock-s3://, registered remotes) bind now.
        self._spill_uri = str(_cfg.object_spill_uri or "")
        if self._spill_uri and \
                not self._spill_uri.startswith("session://"):
            from ray_tpu._private.spill import backend_for_uri
            try:
                self._table.set_spill_backend(backend_for_uri(
                    self._spill_uri, fallback_dir=spill_dir))
            except ValueError:
                logger.exception("invalid object_spill_uri %r; keeping "
                                 "the local spill directory",
                                 self._spill_uri)
        # Durable-spill announcements ride the session's reply sender;
        # the head records URIs in its object location table.
        self._table.on_spilled = self._announce_spilled
        self._table.on_unspilled = self._announce_unspilled
        # Pull admission control (reference: pull_manager.h:52): bounds
        # bytes in flight into this node, task args first.
        self._table.admission = PullAdmission(
            int(_cfg.pull_manager_max_inflight_bytes))
        self._object_server = None
        import uuid as _uuid
        self._uid = _uuid.uuid4().hex[:8]
        # Incremented per head session (reconnects): result keys embed it
        # so a stale handler's late put can never overwrite an object a
        # NEW session stored under the same (restarted) req_id.
        self._session_n = 0
        self._send_lock = threading.Lock()
        self._sock: Optional[socket.socket] = None
        # The session's ResilientChannel (survives resume socket
        # swaps); handlers and publish paths key on it, not the socket.
        self._chan = None
        # Per-session reply sender (channel -> _CoalescingSender): the
        # single writer for head-bound replies; completions accumulated
        # by concurrent handler threads coalesce into reply_batch
        # frames. Handlers of a DEAD session find no sender and fall
        # back to a direct send into the closed channel (dropped).
        self._reply_senders: Dict[Any, Any] = {}
        self._stop = threading.Event()
        self.node_id_hex: Optional[str] = None
        # Incarnation epoch from the registration ack (wire v9): stamps
        # every enveloped frame; carried by resume ("am I still this
        # incarnation?") and by the next register as prev_epoch (so a
        # head that fenced us can sweep any stale residue).
        self._node_epoch = 0
        # Worker-process pool (reference: raylet WorkerPool): CPU tasks
        # run in real worker subprocesses by default — crash isolation
        # for the node; a segfaulting task kills one worker, not the
        # daemon. TPU tasks stay in-daemon (the chip is single-process).
        import os as _os
        self._use_worker_processes = _os.environ.get(
            "RAY_TPU_DAEMON_WORKER_PROCESSES", "1") != "0"
        self._pool = None
        self._pool_lock = threading.Lock()
        self._prefetch_pool = None  # lazy; parallel task-arg pulls
        self._prestarted = False
        self._session_registered = False
        self._health_started = False
        # Started once per daemon on the first registration that hands
        # us a session id (like _health_started): tails this process's
        # capture files — its own raylet streams + spawned workers —
        # and ships batches head-ward.
        self._log_monitor = None
        # Interval exporter for this daemon's metric registry (plus the
        # batches its leased workers piggyback on task replies); ships
        # metrics_batch frames through the session's reply sender.
        self._metrics_agent = None
        self._object_server_host: Optional[str] = None
        # Resource-usage sync (reference: common/ray_syncer): changed
        # component snapshots piggyback on health-channel pongs; the
        # head's aggregated cluster digest rides back on pings.
        from ray_tpu._private.syncer import (DigestCache,
                                             NodeSyncReporter)
        self.syncer_reporter = NodeSyncReporter()
        self.cluster_digest = DigestCache()
        self._inflight = 0
        self._inflight_cpu = 0.0
        self._inflight_lock = threading.Lock()
        # Daemon-local dispatch queues: class_id -> _ClassQueue (the
        # node's own task queues; see _ClassQueue docstring). Recv-loop
        # writes, slot threads read.
        self._class_queues: Dict[str, _ClassQueue] = {}
        # Live worker leases: lease_id -> _LeaseExecutor (recv-loop only).
        self._lease_executors: Dict[str, _LeaseExecutor] = {}
        self._lease_tasks_total = 0
        self._register_sync_collectors()

    def _register_sync_collectors(self) -> None:
        from ray_tpu._private import syncer as _sync

        def resource_load():
            with self._inflight_lock:
                inflight = self._inflight
                cpu_used = self._inflight_cpu
            avail = dict(self.resources)
            if "CPU" in avail:
                avail["CPU"] = max(0.0, avail["CPU"] - cpu_used)
            return {"total": dict(self.resources), "available": avail,
                    "inflight_tasks": inflight,
                    "actors": len(self._actors)}

        def object_store():
            return self._table.usage()

        def memory():
            try:
                with open("/proc/self/status") as f:
                    for line in f:
                        if line.startswith("VmRSS:"):
                            kb = int(line.split()[1])
                            return {"rss_bytes": kb * 1024}
            except OSError:
                pass
            return None

        def backlog():
            # Local dispatch state: per-class queue depth + lent-out
            # temp slots. The head reads this through the syncer for
            # spillback decisions and the state API — it does NOT see
            # the queues directly (they are daemon-owned).
            classes = {cid: cq.qsize()
                       for cid, cq in list(self._class_queues.items())}
            return {"classes": classes,
                    "queued": sum(classes.values()),
                    "temp_slots": sum(
                        cq.temp_slots
                        for cq in list(self._class_queues.values()))}

        self.syncer_reporter.register(_sync.RESOURCE_LOAD, resource_load)
        self.syncer_reporter.register(_sync.OBJECT_STORE, object_store)
        self.syncer_reporter.register(_sync.MEMORY, memory)
        self.syncer_reporter.register(_sync.BACKLOG, backlog)

    def _reclaim_tasks(self, sock, msg: dict) -> None:
        """Head spillback (reference: cluster_task_manager.cc spillback):
        hand back up to max_n queued-not-started tasks of a class so the
        head can re-dispatch them onto capacity that freed elsewhere.
        Each reclaimed task's req_id answers {"reclaimed": True} — the
        head's normal completion path re-routes it."""
        cq = self._class_queues.get(msg.get("class_id"))
        popped = (cq.pop_tail(int(msg.get("max_n", 0)))
                  if cq is not None else [])
        for psock, pmsg in popped:
            self._send_reply(psock, {"req_id": pmsg.get("req_id", 0),
                                     "ok": True, "reclaimed": True})
        if msg.get("req_id"):
            self._reply(sock, msg["req_id"], value=len(popped))

    def _load_function(self, fn_id: bytes, fn_bytes: Optional[bytes]):
        fn = self._functions.get(fn_id)
        if fn is None:
            from ray_tpu._private import serialization
            if fn_bytes is None:
                # The recv loop cached the raw bytes from the first frame
                # that shipped them (frames are ordered on one socket, so
                # by the time a fn_bytes=None request is READ, the cache
                # is already populated).
                fn_bytes = self._fn_raw.get(fn_id)
            if fn_bytes is None:
                raise RuntimeError("head sent no bytes for unknown function")
            fn = serialization.loads_function(fn_bytes)
            self._functions[fn_id] = fn
            # _fn_raw keeps the raw bytes too: every NEW worker process
            # needs them shipped once (the reference likewise retains
            # function exports in GCS KV for the job's lifetime).
        return fn

    def _send_reply(self, session, msg: dict, nbytes: int = 0) -> None:
        """Route a reply through the session's coalescing sender (the
        channel's single writer). Handlers that outlive their session
        find no sender and fall back to a direct send into the closed
        channel — which raises and gets dropped, the intent (see
        _reply's docstring on head restarts). ``session`` is the
        ResilientChannel the request arrived on (a raw socket for
        legacy callers)."""
        sender = self._reply_senders.get(session)
        if sender is not None and sender.send(msg, nbytes=nbytes):
            return
        if isinstance(msg.get("value"), (list, tuple)):
            # OOB part-list values only flow through the typed encoder;
            # the raw fallback pickles the dict, so join first.
            msg = dict(msg, value=_join_parts(list(msg["value"])))
        if hasattr(session, "send_frame"):
            session.send_frame(_dumps(msg))
        else:
            _send_frame(session, _dumps(msg), self._send_lock)

    def _reply(self, sock, req_id: int, *, value: Any = None,
               error: Optional[BaseException] = None,
               tb: str = "") -> None:
        """``sock`` is the session socket the REQUEST arrived on. After a
        head restart, handler threads of the dead session still hold the
        old (closed) socket — their replies raise OSError and are
        dropped instead of reaching the new head with req_ids that
        collide with the new session's counter (the restarted head
        re-runs those tasks anyway)."""
        if error is not None:
            try:
                payload = _dumps((error, tb))
            except Exception:  # noqa: BLE001 - unpicklable exception
                payload = _dumps((RuntimeError(
                    f"{type(error).__name__}: {error}"), tb))
            msg = {"req_id": req_id, "ok": False, "error": payload}
            self._send_reply(sock, msg, nbytes=len(payload))
            return
        payload = _dumps(value)
        self._send_reply(sock, {"req_id": req_id, "ok": True,
                                "value": payload}, nbytes=len(payload))

    def _reply_result(self, sock, req_id: int, result: Any,
                      store_limit: int, num_returns: int = 1) -> None:
        """Small results return inline (the reference's PushTaskReply
        path); big ones stay in this daemon's object table and only a
        (key, size) stub travels back. Multi-return tasks split PER
        ELEMENT — each return object is independently inline or
        daemon-resident, so shuffle partials never transit the head."""
        if num_returns > 1 and (not isinstance(result, (tuple, list))
                                or len(result) != num_returns):
            # Wrong shape for a multi-return task: the head will raise —
            # describe the actual value here (it is already deserialized)
            # rather than parking an unconsumable stub in the table.
            self._send_reply(sock, {
                "req_id": req_id, "ok": True,
                "mismatch_desc": describe_value(result)})
            return
        if num_returns > 1 and store_limit and \
                isinstance(result, (tuple, list)) and \
                len(result) == num_returns:
            element_parts = [_dumps_parts(element) for element in result]
            sizes = [_parts_size(pp) for pp in element_parts]
            if sum(sizes) > store_limit:
                parts = []
                for i, (pp, size) in enumerate(zip(element_parts, sizes)):
                    if size > store_limit:
                        key = (f"obj-{self._uid}-s{self._session_n}-"
                               f"{req_id}-r{i}")
                        self._table.put_parts(key, pp, size=size)
                        parts.append({"stored_key": key,
                                      "size": size})
                    else:
                        parts.append({"value": _join_parts(pp)})
                self._send_reply(
                    sock, {"req_id": req_id, "ok": True, "parts": parts},
                    nbytes=sum(len(p.get("value") or b"")
                               for p in parts))
                return
            # Small total: the plain inline reply below is cheaper than
            # per-element bookkeeping head-side.
        result_parts = _dumps_parts(result)
        size = _parts_size(result_parts)
        if store_limit and size > store_limit:
            # Globally unique key: peer daemons cache pulled copies under
            # the same name, so it must not collide across nodes.
            key = f"obj-{self._uid}-s{self._session_n}-{req_id}"
            self._table.put_parts(key, result_parts, size=size)
            self._send_reply(sock, {"req_id": req_id, "ok": True,
                                    "stored_key": key,
                                    "size": size})
        else:
            # Part list straight through: the typed reply encoder hands
            # the pickle-5 OOB buffers to send_parts unjoined.
            self._send_reply(sock, {"req_id": req_id, "ok": True,
                                    "value": result_parts},
                             nbytes=size)

    def _pull_marker(self, a) -> None:
        """Land a marker argument's payload in the local table: direct
        peer pull with holder failover (the marker's alt_addrs are the
        head's other known in-memory holders), then the durable spill
        URI as the last data-plane resort — only when every tier misses
        does the caller's error escalate into lineage reconstruction."""
        from ray_tpu._private.dataplane import (PULL_PRIORITY_TASK_ARGS,
                                                ObjectPullError,
                                                pull_object)
        owner = getattr(a, "owner_addr", None)
        spill_uri = getattr(a, "spill_uri", None)
        try:
            if owner is None:
                raise KeyError(
                    f"object payload {a.key} is not resident on "
                    "this node (already freed?)")
            # Direct peer pull — the head never sees these bytes
            # (reference: ObjectManager node-to-node chunked pull).
            pull_object(tuple(owner), a.key, self._table,
                        priority=PULL_PRIORITY_TASK_ARGS,
                        size_hint=getattr(a, "size", 0) or 0,
                        fallback_addrs=getattr(a, "alt_addrs", ()) or ())
            return
        except (ObjectPullError, KeyError, OSError) as exc:
            if not spill_uri:
                raise
            import time as _time
            from ray_tpu._private.spill import read_uri
            t0 = _time.monotonic()
            payload = read_uri(spill_uri,
                               getattr(a, "size", 0) or 0)
            if payload is None:
                raise ObjectPullError(
                    f"object {a.key}: every holder failed ({exc}) and "
                    f"its spill URI {spill_uri} is unreadable") from exc
            logger.warning("restored %s from spill URI %s after holder "
                           "failure: %s", a.key, spill_uri, exc)
            self._table.put(a.key, payload)
            try:
                from ray_tpu._private import builtin_metrics, flow
                builtin_metrics.object_restores().inc(
                    tags={"source": "spill"})
                # Spill restores are transfers too: the ledger entry
                # carries tier="spill" and a synthetic "spill" peer, so
                # the head's matrix shows restore bandwidth per node.
                flow.global_flow_recorder().record(
                    key=a.key, nbytes=len(payload),
                    duration_s=_time.monotonic() - t0,
                    direction="in", peer="spill", tier="spill")
            except Exception:  # noqa: BLE001 - accounting only
                pass

    def _handle_push_object(self, msg: dict) -> dict:
        """One spanning-tree broadcast edge landing on this node. Either
        the payload rides inline (``data``: head seeding a direct child)
        or this node blocking-waits on its ``parent``'s object server
        until the parent's own copy arrives, then pulls node-to-node.
        A dead parent re-parents through ``alts`` (grandparent, then
        root), so one SIGKILL orphans a subtree for exactly one failover
        instead of killing the broadcast."""
        import time as _time

        from ray_tpu._private import flow
        from ray_tpu._private.dataplane import (PULL_PRIORITY_TASK_ARGS,
                                                ObjectPullError, pull_object,
                                                wait_remote)
        key = msg["key"]
        if self._table.stat(key) >= 0:
            return {"bytes": 0, "failovers": 0, "secs": 0.0,
                    "already": True}
        data = msg.get("data")
        if data is not None:
            t0 = _time.monotonic()
            self._table.put(key, data)
            secs = _time.monotonic() - t0
            try:
                # Head-seeded edges are the only ones that cost head
                # egress: the synthetic "head" peer makes them a
                # distinct row in the flow matrix.
                flow.global_flow_recorder().record(
                    key=key, nbytes=len(data), duration_s=secs,
                    direction="in", peer="head", tier="push")
            except Exception:  # noqa: BLE001 - accounting only
                pass
            return {"bytes": len(data), "failovers": 0, "secs": secs}
        wait_s = float(msg.get("wait_timeout_s", 60.0))
        candidates = []
        if msg.get("parent"):
            candidates.append(tuple(msg["parent"]))
        candidates.extend(tuple(a) for a in msg.get("alts", ()))
        last_exc: Optional[BaseException] = None
        for i, cand in enumerate(candidates):
            try:
                got = wait_remote(cand, key, timeout=wait_s)
                if got < 0:
                    raise ObjectPullError(
                        f"object {key} never landed on parent "
                        f"{cand[0]}:{cand[1]} within {wait_s:.0f}s")
                t0 = _time.monotonic()
                pull_object(cand, key, self._table,
                            priority=PULL_PRIORITY_TASK_ARGS,
                            size_hint=got,
                            fallback_addrs=candidates[i + 1:],
                            tier="push")
                return {"bytes": got, "failovers": i,
                        "secs": _time.monotonic() - t0}
            except (ObjectPullError, OSError, ConnectionError) as exc:
                last_exc = exc
        raise ObjectPullError(
            f"broadcast push of {key} failed: no parent in "
            f"{candidates!r} produced the object") from last_exc

    def _resolve_markers(self, args, kwargs):
        from ray_tpu._private.dataplane import (ObjectMarker,
                                                ObjectPullError)
        self._prefetch_marker_args(args, kwargs)

        def resolve(a):
            if isinstance(a, (ObjectMarker, RemoteArgMarker)):
                with self._table.pinned(a.key) as payload:
                    if payload is not None:
                        return _loads(payload)
                self._pull_marker(a)
                with self._table.pinned(a.key) as payload:
                    if payload is None:  # evicted immediately (pressure)
                        raise ObjectPullError(
                            f"object {a.key} was evicted right after its "
                            "pull (object store too small?)")
                    return _loads(payload)
            return a
        return ([resolve(a) for a in args],
                {k: resolve(v) for k, v in kwargs.items()})

    def _get_pool(self):
        with self._pool_lock:
            if self._pool is None:
                from ray_tpu._private.worker_process import WorkerProcessPool
                # head_address: workers bind a ClientRuntime for nested
                # ray_tpu API calls (see _private/client_runtime.py).
                object_addr = None
                if self._object_server is not None and \
                        self._object_server_host:
                    object_addr = (self._object_server_host,
                                   self._object_server.port)
                self._pool = WorkerProcessPool(
                    store_name=self._table.arena_name,
                    head_address=self.head_address,
                    node_id_hex=self.node_id_hex,
                    object_addr=object_addr)
                # Worker metric batches hop worker -> this daemon ->
                # head, keeping the worker's own pid/component labels.
                self._pool.metrics_sink = self._publish_metrics_batch
                self._pool.profile_sink = self._publish_profile_batch
                self._pool.flow_sink = self._publish_flow_batch
            return self._pool

    def _task_uses_worker_process(self, msg: dict) -> bool:
        if msg.get("tpu_ids"):
            return False  # the daemon owns the chips; stay in-process
        renv = msg.get("runtime_env") or {}
        if renv.get("worker_process") is False:
            return False
        return self._use_worker_processes or bool(
            renv.get("worker_process") or renv.get("pip")
            or renv.get("venv") or renv.get("conda")
            or renv.get("container"))

    def _prefetch_marker_args(self, args, kwargs) -> None:
        """Pull a task's missing peer-owned argument payloads in
        PARALLEL before the sequential resolve walk (reference:
        pull_manager batches a task's arg pulls; one-at-a-time pulls
        made a 32-arg reduce task pay 32 serial round-trips). Errors
        are swallowed here — resolve() re-pulls the stragglers and
        raises with full context."""
        from ray_tpu._private.dataplane import (PULL_PRIORITY_TASK_ARGS,
                                                ObjectMarker, pull_object)
        missing = {}
        for a in list(args) + list(kwargs.values()):
            if isinstance(a, (ObjectMarker, RemoteArgMarker)):
                owner = getattr(a, "owner_addr", None)
                if owner is not None and a.key not in missing and \
                        not self._table.contains(a.key):
                    missing[a.key] = (tuple(owner),
                                      getattr(a, "size", 0) or 0,
                                      getattr(a, "alt_addrs", ()) or ())
        if len(missing) < 2:
            return  # a single pull gains nothing from the pool
        pool = self._prefetch_pool
        if pool is None:
            import concurrent.futures as _cf
            with self._pool_lock:
                pool = self._prefetch_pool
                if pool is None:
                    # PERSISTENT: a per-task executor would pay thread
                    # spawn/join on every multi-arg dispatch.
                    pool = _cf.ThreadPoolExecutor(
                        8, thread_name_prefix="ray_tpu-prefetch")
                    self._prefetch_pool = pool
        futures = [
            pool.submit(pull_object, owner, key, self._table,
                        priority=PULL_PRIORITY_TASK_ARGS, size_hint=size,
                        fallback_addrs=alts)
            for key, (owner, size, alts) in missing.items()]
        for f in futures:
            f.exception()  # wait; failures re-raise in resolve()

    def _resolve_markers_for_worker(self, args, kwargs):
        """Like _resolve_markers, but arena-resident payloads stay as
        ArenaRef markers: the worker attaches the same shm arena and
        reads them zero-copy (no daemon→worker copy of big args).

        Every ArenaRef'd key is PINNED (arena refcount) for the dispatch;
        the returned pin list must be released when the worker is done.
        Without the pin, disk spill could evict the entry between this
        resolve and the worker's read (plasma semantics: an argument of
        a dispatched task holds a reference, local_task_manager.cc pins
        args for the task's runtime)."""
        from ray_tpu._private.dataplane import (ObjectMarker,
                                                ObjectPullError)
        from ray_tpu._private.worker_process import ArenaRef
        self._prefetch_marker_args(args, kwargs)
        pinned: list = []

        def _pin_in_arena(arena, key) -> bool:
            view = arena.get_bytes(key)
            if view is None:
                return False
            try:
                view.release()
            except BufferError:
                pass
            pinned.append(key)  # arena refcount held until release_pins
            return True

        def resolve(a):
            if isinstance(a, (ObjectMarker, RemoteArgMarker)):
                if not self._table.contains(a.key):
                    self._pull_marker(a)
                arena = self._table._arena
                if arena is not None:
                    if _pin_in_arena(arena, a.key):
                        return ArenaRef(a.key)
                    # Spilled? A read restores+promotes it; retry the pin
                    # so the worker still gets the zero-copy path. If
                    # promotion failed (arena still full) use the bytes
                    # we already read — never a second full disk read on
                    # a node that is under memory pressure.
                    if self._table._spill_dir is not None:
                        data = self._table._read_spilled(a.key)
                        if data is not None:
                            if _pin_in_arena(arena, a.key):
                                return ArenaRef(a.key)
                            return _loads(data)
                with self._table.pinned(a.key) as payload:
                    if payload is None:
                        raise ObjectPullError(
                            f"object {a.key} evicted right after pull")
                    return _loads(payload)
            return a
        try:
            return ([resolve(a) for a in args],
                    {k: resolve(v) for k, v in kwargs.items()}, pinned)
        except BaseException:
            self._release_arena_pins(pinned)
            raise

    def _release_arena_pins(self, pinned) -> None:
        arena = self._table._arena
        if arena is None:
            return
        for key in pinned:
            try:
                arena.release(key)
            except Exception:  # noqa: BLE001 - release is best-effort
                pass

    def _execute_on_worker(self, sock, msg: dict, req_id: int) -> None:
        """Run a pushed task on a leased worker subprocess and forward
        its (already serialized) result without re-encoding."""
        from ray_tpu._private.runtime_env_pip import python_for_env
        from ray_tpu._private.worker_process import (WorkerCrashedError,
                                                     WorkerFnMissingError)
        pool = self._get_pool()
        renv = msg.get("runtime_env") or {}
        python = python_for_env(renv)
        container = renv.get("container")
        lease_ex = msg.get("_lease_exec")
        if lease_ex is not None and not container:
            # Leased task: the lease pins ONE worker subprocess for its
            # whole lifetime (reference: a granted lease IS a worker).
            # Containerized tasks always pool-lease (the pool keys by
            # image; pinning would mix images on one lease).
            handle = lease_ex.worker_handle
            if handle is None or handle.dead or \
                    lease_ex.worker_python != python:
                if handle is not None:
                    pool.release(handle)
                handle = pool.lease(python)
                lease_ex.worker_handle = handle
                lease_ex.worker_python = python
        else:
            handle = pool.lease(python, container=container)
            lease_ex = None  # containerized: never pin
        arg_pins: list = []
        try:
            if msg.get("plain_args"):
                # Head vouched the payload holds no markers: forward the
                # bytes to the worker untouched (no unpickle→repickle).
                args_payload = msg["payload"]
            else:
                with _trace_span(msg.get("trace_ctx"),
                                 "data::resolve_args", "pull"):
                    args, kwargs, arg_pins = \
                        self._resolve_markers_for_worker(
                            *_loads(msg["payload"]))
                args_payload = _dumps((args, kwargs))
            fn_id = msg["fn_id"]

            # Big results write straight into the shared arena
            # worker-side (no stdio pipe copy); the daemon adopts the
            # entries below. Multi-returns split per element in the
            # worker (a shuffle map's partitions each land separately).
            arena_limit = 0
            if self._table.arena_name is not None:
                arena_limit = int(msg.get("store_limit", 0) or 0)

            def build(fn_bytes):
                renv = {k: v for k, v in (msg.get("runtime_env")
                                          or {}).items()
                        if k != "worker_process"}
                return {
                    "type": "exec",
                    "mode": "task",
                    "fn_id": fn_id,
                    "fn_bytes": fn_bytes,
                    "payload": args_payload,
                    "runtime_env": renv,
                    "name": msg.get("name", "task"),
                    "task_id": msg.get("task_id"),
                    "arena_limit": arena_limit,
                    "num_returns": msg.get("num_returns", 1),
                    # Second hop of the propagation: the worker
                    # subprocess parents its execute span to the same
                    # driver-side context.
                    "trace_ctx": msg.get("trace_ctx"),
                }

            def fn_payload():
                fb = msg.get("fn_bytes") or self._fn_raw.get(fn_id)
                if fb is None:
                    raise RuntimeError(
                        "no function bytes available for worker dispatch")
                return fb

            if fn_id in handle.shipped:
                reply = handle.request(build(None))
                if not reply.get("ok"):
                    exc, _tb = _loads(reply["error"])
                    if isinstance(exc, WorkerFnMissingError):
                        # Shipped-set out of sync (a prior request died
                        # before the worker cached the fn): heal once.
                        handle.shipped.discard(fn_id)
                        reply = handle.request(build(fn_payload()))
                        handle.shipped.add(fn_id)
            else:
                reply = handle.request(build(fn_payload()))
                handle.shipped.add(fn_id)
        except WorkerCrashedError as exc:
            # Ships to the head as TaskError(cause=WorkerCrashedError),
            # which the head classifies as system-retriable.
            self._reply(sock, req_id, error=exc, tb=traceback.format_exc())
            return
        finally:
            self._release_arena_pins(arg_pins)
            if lease_ex is not None:
                if handle.dead:  # crashed: un-pin; next task re-leases
                    pool.release(handle)
                    lease_ex.worker_handle = None
            else:
                pool.release(handle)
        if reply.get("ok") and "arena_key" in reply:
            # Worker wrote the result straight into the shared arena:
            # take bookkeeping ownership and answer the head with a
            # stub — zero result bytes through daemon or head.
            key, size = reply["arena_key"], int(reply["size"])
            if self._table.adopt(key, size):
                self._send_reply(sock, {"req_id": req_id, "ok": True,
                                        "stored_key": key, "size": size})
            else:
                # Evicted between the worker's put and adoption (only
                # possible on eviction-mode arenas): ObjectPullError is
                # system-retriable — the head re-runs the task instead
                # of surfacing a user failure.
                from ray_tpu._private.dataplane import ObjectPullError
                self._reply(sock, req_id, error=ObjectPullError(
                    f"worker result {key} vanished from the arena "
                    "before adoption"))
            return
        if reply.get("ok") and "parts" in reply:
            # Per-element worker results: arena entries get adopted;
            # inline elements bigger than the stub limit still stay
            # daemon-resident via table.put (arena was full).
            store_limit = msg.get("store_limit", 0)
            out_parts = []
            inline_bytes = 0
            for i, p in enumerate(reply["parts"]):
                if "arena_key" in p:
                    if not self._table.adopt(p["arena_key"], p["size"]):
                        from ray_tpu._private.dataplane import \
                            ObjectPullError
                        self._reply(sock, req_id, error=ObjectPullError(
                            f"worker result {p['arena_key']} vanished "
                            "from the arena before adoption"))
                        return
                    out_parts.append({"stored_key": p["arena_key"],
                                      "size": p["size"]})
                elif store_limit and len(p["value"]) > store_limit:
                    key = (f"obj-{self._uid}-s{self._session_n}-"
                           f"{req_id}-r{i}")
                    self._table.put(key, p["value"])
                    out_parts.append({"stored_key": key,
                                      "size": len(p["value"])})
                else:
                    out_parts.append({"value": p["value"]})
                    inline_bytes += len(p["value"])
            self._send_reply(sock, {"req_id": req_id, "ok": True,
                                    "parts": out_parts},
                             nbytes=inline_bytes)
            return
        if reply.get("ok"):
            payload = reply["value"]
            store_limit = msg.get("store_limit", 0)
            num_returns = msg.get("num_returns", 1)
            if num_returns > 1 and store_limit and \
                    len(payload) > store_limit:
                # Split per return element (one extra deserialize on the
                # big path only; small results forward untouched below).
                self._reply_result(sock, req_id, _loads(payload),
                                   store_limit, num_returns)
            elif store_limit and len(payload) > store_limit:
                key = f"obj-{self._uid}-s{self._session_n}-{req_id}"
                self._table.put(key, payload)
                self._send_reply(sock, {"req_id": req_id, "ok": True,
                                        "stored_key": key,
                                        "size": len(payload)})
            else:
                self._send_reply(sock, {"req_id": req_id, "ok": True,
                                        "value": payload},
                                 nbytes=len(payload))
        else:
            self._send_reply(
                sock, {"req_id": req_id, "ok": False,
                       "error": reply["error"]},
                nbytes=len(reply["error"]))

    #: frame kinds that run user code and hold node resources; data-
    #: plane/control frames (fetch_object, stats, ...) never count.
    _USER_CODE_KINDS = frozenset(
        {"execute_task", "create_actor", "actor_call"})

    def _handle_counted(self, sock, msg: dict) -> None:
        import time as _time

        from ray_tpu._private.event_stats import GLOBAL
        counted = msg.get("type") in self._USER_CODE_KINDS
        cpus = float(msg.get("num_cpus", 1.0)) if counted else 0.0
        if counted:
            with self._inflight_lock:
                self._inflight += 1
                self._inflight_cpu += cpus
        _t0 = _time.monotonic()
        try:
            self._handle(sock, msg)
        finally:
            # Per-handler daemon EventStats ride the next metrics_batch
            # to the head (/api/event_stats "cluster" view).
            GLOBAL.record(f"daemon.{msg.get('type') or 'frame'}",
                          _time.monotonic() - _t0)
            if counted:
                with self._inflight_lock:
                    self._inflight -= 1
                    self._inflight_cpu -= cpus

    def _handle(self, sock, msg: dict) -> None:
        req_id = msg.get("req_id", 0)
        kind = msg.get("type")
        try:
            if kind == "execute_task":
                if self._task_uses_worker_process(msg):
                    self._execute_on_worker(sock, msg, req_id)
                    return
                ctx = msg.get("trace_ctx")
                fn = self._load_function(msg["fn_id"], msg.get("fn_bytes"))
                # Marker resolution is the daemon's arg-pull stage:
                # data-plane pulls inside record as child spans of it.
                with _trace_span(ctx, "data::resolve_args", "pull"):
                    args, kwargs = self._resolve_markers(
                        *_loads(msg["payload"]))
                with _trace_span(ctx, f"task::{msg.get('name', '')}",
                                 "execute"):
                    result = self._run_in_env(msg, fn, args, kwargs)
                self._reply_result(sock, req_id, result,
                                   msg.get("store_limit", 0),
                                   msg.get("num_returns", 1))
            elif kind == "create_actor":
                ctx = msg.get("trace_ctx")
                cls = self._load_function(msg["fn_id"], msg.get("fn_bytes"))
                with _trace_span(ctx, "data::resolve_args", "pull"):
                    args, kwargs = self._resolve_markers(
                        *_loads(msg["payload"]))
                with _trace_span(ctx, f"actor_init::{msg.get('name', '')}",
                                 "execute"):
                    instance = self._run_in_env(msg, cls, args, kwargs)
                self._actors[msg["actor_id"]] = instance
                self._actor_tpu_ids[msg["actor_id"]] = msg.get("tpu_ids")
                self._reply(sock, req_id, value=None)
            elif kind == "actor_call":
                ctx = msg.get("trace_ctx")
                instance = self._actors[msg["actor_id"]]
                method = getattr(instance, msg["method"])
                with _trace_span(ctx, "data::resolve_args", "pull"):
                    args, kwargs = self._resolve_markers(
                        *_loads(msg["payload"]))
                # Methods inherit the chips reserved at actor creation.
                msg = dict(msg,
                           tpu_ids=self._actor_tpu_ids.get(msg["actor_id"]))
                # The span brackets the coroutine run too (async actor
                # methods execute inside asyncio.run, not at call time).
                with _trace_span(ctx, f"actor_task::{msg.get('name', '')}",
                                 "execute"):
                    result = self._run_in_env(msg, method, args, kwargs)
                    import inspect
                    if inspect.iscoroutine(result):
                        import asyncio
                        result = asyncio.run(result)
                self._reply_result(sock, req_id, result,
                                   msg.get("store_limit", 0),
                                   msg.get("num_returns", 1))
            elif kind == "destroy_actor":
                self._actors.pop(msg["actor_id"], None)
                self._actor_tpu_ids.pop(msg["actor_id"], None)
                self._reply(sock, req_id, value=None)
            elif kind == "fetch_object":
                with self._table.pinned(msg["key"]) as raw:
                    if raw is None:
                        raise KeyError(
                            f"object payload {msg['key']} is not resident "
                            "on this node (already freed?)")
                    data = bytes(raw)
                self._send_reply(sock, {"req_id": req_id, "ok": True,
                                        "raw": data},
                                 nbytes=len(data))
            elif kind == "free_object":
                self._table.free(msg["key"])
                self._reply(sock, req_id, value=None)
            elif kind == "adopt_object":
                # Worker-process put (distributed ownership): the worker
                # wrote the payload straight into the shared arena; this
                # node takes lifetime ownership (spill-liveness
                # bookkeeping lives with the table's own lock
                # discipline, dataplane.NodeObjectTable.adopt).
                self._reply(sock, req_id, value=self._table.adopt(
                    msg["key"], msg["size"]))
            elif kind == "push_object":
                # Tree broadcast (runs on this frame's own _route_frame
                # thread, so a GB-scale landing never stalls the recv
                # loop).
                self._reply(sock, req_id,
                            value=self._handle_push_object(msg))
            elif kind == "profile":
                # Self-sampled stacks (reference: profile_manager.py
                # py-spy-on-demand, here cooperative — no ptrace). A
                # pid field retargets the burst at a pool worker via
                # its request pipe. Runs on a per-message thread
                # (_route_frame), so the seconds-long burst never
                # stalls the daemon recv loop.
                from ray_tpu._private.profiling import profile_self
                from ray_tpu._private.ray_config import \
                    runtime_config_value
                cap = float(runtime_config_value(
                    "profile_max_duration_s", 60.0))
                duration = min(float(msg.get("duration", 5.0)), cap)
                hz = int(msg.get("hz", 100))
                fmt = msg.get("fmt", "folded")
                pid = msg.get("pid")
                if pid is not None and int(pid) != os.getpid():
                    self._reply(sock, req_id,
                                value=self._profile_worker(
                                    int(pid), duration, hz, fmt))
                else:
                    self._reply(sock, req_id, value=profile_self(
                        duration, hz, fmt))
            elif kind == "stats":
                self._reply(sock, req_id, value={
                    "transfer": dict(self._table.stats),
                    "table": self._table.usage(),
                    "num_actors": len(self._actors),
                    "leases": len(self._lease_executors),
                    "lease_tasks_total": self._lease_tasks_total,
                    "pool_workers": (len(self._pool._all)
                                     if self._pool is not None else 0),
                })
            elif kind == "shutdown":
                self._stop.set()
            else:
                raise ValueError(f"unknown message type {kind!r}")
        except BaseException as exc:  # noqa: BLE001 - ship to the head
            try:
                self._reply(sock, req_id, error=exc,
                            tb=traceback.format_exc())
            except OSError:
                pass

    def _serve_health_channel(self) -> None:
        """Dedicated liveness socket: echo pings on a thread of its own,
        so the head can tell 'process hung' from 'data channel busy'.
        The connect retries with backoff — the head declares nodes that
        never open this channel dead, so one refused connect (listener
        backlog during a mass join) must not be fatal."""
        from ray_tpu._private.channel import Backoff
        bo = Backoff(0.2, 5.0)
        while not self._stop.is_set():
            try:
                hc = socket.create_connection(self.head_address,
                                              timeout=10)
                hc.settimeout(None)
                _send_frame(hc, _dumps({"type": "health_channel",
                                        "node_id": self.node_id_hex}))
                bo.reset()  # connected: a later drop backs off afresh
                # New channel == new peer state, BOTH directions: re-ship
                # every component snapshot (a restarted head starts from
                # nothing) and forget the old head's digest (the new
                # head's version counter restarts near zero).
                self.syncer_reporter.reset_peer()
                self.cluster_digest.reset()
                while not self._stop.is_set():
                    if _chaos.ACTIVE:
                        _chaos.maybe_inject("daemon.health.recv", hc)
                    ping = _loads(_recv_frame(hc))
                    self.cluster_digest.apply(
                        ping.get("cluster_digest"))
                    if _chaos.ACTIVE:
                        _chaos.maybe_inject("daemon.health.send", hc)
                    _send_frame(hc, _dumps(
                        {"type": "pong",
                         "sync": self.syncer_reporter.poll()}))
                return
            except (ConnectionError, OSError):
                bo.sleep()

    def _run_in_env(self, msg: dict, fn, args, kwargs):
        # Publish the head-assigned chip ids through the worker context so
        # ray_tpu.get_tpu_ids() works inside remotely executed tasks.
        import types

        from ray_tpu._private import ray_logging
        from ray_tpu._private.runtime import _task_context
        name = msg.get("name") or ""
        if name and ray_logging.markers_enabled():
            # In-daemon execution writes to the daemon's captured
            # streams; the marker attributes subsequent output to this
            # task (actor calls: `Cls.method pid=` driver prefixes).
            ray_logging.emit_task_marker(name)
        _task_context.spec = types.SimpleNamespace(
            _tpu_ids=msg.get("tpu_ids"), actor_id=None,
            name=msg.get("name", ""),
            task_id_hex=msg.get("task_id"))
        try:
            renv = msg.get("runtime_env")
            if renv:
                from ray_tpu._private import runtime_env as _renv
                _renv.setup(renv)
                with _renv.applied(renv):
                    return fn(*args, **kwargs)
            return fn(*args, **kwargs)
        finally:
            _task_context.spec = None

    def run(self, reconnect_window: Optional[float] = None) -> None:
        """Connect, register, and serve. On connection loss (head died
        or restarted) the daemon KEEPS its actors and object table and
        retries the head address for ``reconnect_window`` seconds — a
        restarted head (gcs_store_path persistence) rebinds the resident
        actors on re-registration (reference: raylet surviving GCS
        restart + resubscribe). An orderly head shutdown frame exits
        immediately.

        ``reconnect_window=None`` (the CLI default) reads
        ``RAY_TPU_head_failover_window_s`` — wide enough (120s) for a
        supervisor-restarted or standby head to come up, replay its
        gcs_store, and accept this daemon's re-registration."""
        import time as _time

        from ray_tpu._private.channel import Backoff
        global _current_daemon
        _current_daemon = self
        if reconnect_window is None:
            from ray_tpu._private.ray_config import runtime_config_value
            reconnect_window = float(
                runtime_config_value("head_failover_window_s", 120.0))
        ever_registered = False
        deadline = _time.monotonic() + max(reconnect_window, 0.0)
        # Jittered backoff: after a head restart every daemon in the
        # cluster re-dials at once — without jitter they'd hammer the
        # fresh listener in lockstep (thundering herd).
        bo = Backoff(0.2, 2.0)
        try:
            while not self._stop.is_set():
                self._session_registered = False
                try:
                    self._serve_once()
                except _wire.ProtocolMismatch:
                    raise  # permanent: retrying a version rejection spins
                except (ConnectionError, OSError) as exc:
                    if self._session_registered:
                        pass  # live session dropped; fall through, retry
                    elif reconnect_window <= 0:
                        raise
                    last_exc = exc
                if self._stop.is_set():
                    break
                if self._session_registered:
                    ever_registered = True
                    # A real session dropped — fresh reconnect window.
                    deadline = _time.monotonic() + reconnect_window
                    bo.reset()
                if reconnect_window <= 0 or _time.monotonic() >= deadline:
                    if not ever_registered:
                        raise ConnectionError(
                            f"could not join head {self.head_address} "
                            f"within {reconnect_window}s: {last_exc}")
                    logger.warning(
                        "Head %s unreachable for %.0fs; daemon exiting",
                        self.head_address, reconnect_window)
                    try:
                        from ray_tpu._private import builtin_metrics
                        builtin_metrics.daemon_redials().inc(
                            tags={"outcome": "gave_up"})
                    except Exception:  # noqa: BLE001 - exit path
                        pass
                    break
                bo.sleep()
        finally:
            # Any exit path — orderly shutdown, window expiry, or an
            # unexpected error (corrupt frame, bad ack) — releases the
            # object server port, worker pool, and the shm arena.
            self._teardown()

    def _teardown(self) -> None:
        if self._log_monitor is not None:
            self._log_monitor.stop()
        if self._metrics_agent is not None:
            self._metrics_agent.stop()
        if self._object_server is not None:
            self._object_server.close()
        if self._pool is not None:
            self._pool.shutdown()
        self._table.close()
        try:  # table.close() already unlinked every spilled file
            os.rmdir(self._spill_dir)
        except OSError:
            pass

    def _serve_once(self) -> None:
        """One connect-register-serve session against the head. Raises
        ConnectionError/OSError when the connection drops."""
        self._session_n += 1
        self._sock = socket.create_connection(self.head_address)
        try:
            self._sock.setsockopt(socket.IPPROTO_TCP, socket.TCP_NODELAY, 1)
        except OSError:
            pass
        # The IP this daemon uses to reach the head is the one peers (and
        # the head) can reach IT on — bind AND advertise the object server
        # there (object payloads are served unauthenticated, so the
        # exposure policy must match the control plane's, never 0.0.0.0).
        from ray_tpu._private.dataplane import ObjectServer
        local_ip = self._sock.getsockname()[0]
        if self._object_server is not None and \
                self._object_server_host != local_ip:
            # The head-facing interface changed (multi-homed host / head
            # moved): the advertised address must match the bind.
            self._object_server.close()
            self._object_server = None
        if self._object_server is None:  # survives same-IP reconnects
            self._object_server = ObjectServer(self._table, host=local_ip)
            self._object_server_host = local_ip
        _send_frame(self._sock, _dumps({
            "type": "register",
            "protocol": _wire.PROTOCOL_VERSION,
            "resources": self.resources,
            "labels": self.labels,
            "object_addr": (local_ip, self._object_server.port),
            "store_name": self._table.arena_name,
            # A restarted head (gcs persistence) rebinds these.
            "resident_actors": list(self._actors.keys()),
            # Our previous incarnation (0 = first life): a head that
            # fenced that epoch knows any residue we still carry is
            # stale and must not be rebound.
            "prev_epoch": self._node_epoch,
        }), self._send_lock)
        # Everything after the raw register frame flows through the
        # resilient channel (v7 seq envelopes): the head's first
        # enveloped frame is the "registered" ack at seq 1.
        from ray_tpu._private.channel import (ChannelBroken,
                                              ResilientChannel)
        from ray_tpu._private.ray_config import make_ray_config
        _ccfg = make_ray_config(None)
        chan = ResilientChannel(
            self._sock, site="daemon",
            ring_bytes=int(_ccfg.channel_resend_ring_bytes),
            window_s=float(_ccfg.channel_reconnect_window_s),
            ack_every=int(_ccfg.channel_ack_every),
            ack_flush_ms=int(_ccfg.channel_ack_flush_ms))
        self._chan = chan
        # register_rejected arrives raw (the head never built a
        # channel for a rejected dial); recv_frame passes it through.
        ack = _loads(chan.recv_frame())
        if ack.get("type") == "register_rejected":
            # Version mismatch: surface the head's words and STOP —
            # reconnect-retrying a permanent rejection would spin.
            raise _wire.ProtocolMismatch(ack["error"])
        assert ack["type"] == "registered", ack
        self.node_id_hex = ack["node_id"]
        channel_token = ack.get("channel_token")
        # Adopt the minted incarnation epoch (v9): every frame we send
        # from here on is stamped with it, so a head that later fences
        # this incarnation drops (and counts) stale frames instead of
        # applying them.
        self._node_epoch = int(ack.get("node_epoch") or 0)
        chan.epoch = self._node_epoch
        self._session_registered = True
        if getattr(self, "_was_registered", False):
            # A re-registration (head restarted, or resume window blew):
            # the failover loop delivered us to a live head again.
            try:
                from ray_tpu._private import builtin_metrics
                builtin_metrics.daemon_redials().inc(
                    tags={"outcome": "reregistered"})
            except Exception:  # noqa: BLE001 - metrics best-effort
                pass
        self._was_registered = True
        logger.info("Registered with head %s as node %s",
                    self.head_address, self.node_id_hex[:12])
        session_id = ack.get("session_id")
        if session_id and self._spill_uri.startswith("session://"):
            # session:// roots under the driver session's shared dir —
            # only now (ack in hand) is the session id known. Earlier
            # spills (pre-registration work) stay on their local-dir
            # records; only new writes land durably.
            from ray_tpu._private.spill import SessionSpillBackend
            try:
                self._table.set_spill_backend(
                    SessionSpillBackend(session_id))
            except OSError:
                logger.exception("could not enable session:// spill")
        if session_id and self._log_monitor is None:
            self._start_log_streaming(session_id)
        if self._metrics_agent is None:
            from ray_tpu._private.metrics_agent import MetricsAgent
            agent = MetricsAgent(
                self._publish_metrics_batch, component="daemon",
                publish_profile=self._publish_profile_batch,
                publish_flow=self._publish_flow_batch)
            agent.add_collector(self._collect_daemon_metrics)
            self._metrics_agent = agent
        if self._use_worker_processes and not self._prestarted:
            # Warm the worker pool once per daemon (reference:
            # worker_pool.h PrestartWorkers): leases then pin an
            # already-started worker instead of paying a spawn.
            self._prestarted = True
            from ray_tpu._private.ray_config import make_ray_config
            if int(make_ray_config(None).worker_prestart_count) > 0:
                cpus = int(self.resources.get("CPU", 1) or 1)
                self._get_pool().prestart(min(cpus, 8))
        if not self._health_started:
            # Started ONCE per daemon (even across reconnects): the
            # health thread reconnects on its own, re-announcing
            # whatever node_id_hex currently holds.
            self._health_started = True
            threading.Thread(target=self._serve_health_channel,
                             name="ray_tpu-daemon-health",
                             daemon=True).start()
        # Single writer for this session's replies, keyed by the CHANNEL
        # (stable across resume socket swaps). A send failure parks the
        # sender until resume; only window exhaustion closes the channel,
        # which pops the recv loop below out of its read.
        sender = _CoalescingSender(
            chan, "reply_batch", on_fail=chan.close,
            name=f"reply-{self.node_id_hex[:8]}")
        self._reply_senders[chan] = sender
        try:
            while not self._stop.is_set():
                try:
                    raw = chan.recv_frame()
                except ChannelBroken:
                    if self._stop.is_set():
                        break
                    # Transient transport failure: re-dial and resume —
                    # the session (lease executors, resident actors,
                    # class queues) survives; unacked frames replay on
                    # both sides. Only a failed resume tears down.
                    if self._try_resume(chan, channel_token):
                        try:
                            from ray_tpu._private import builtin_metrics
                            builtin_metrics.daemon_redials().inc(
                                tags={"outcome": "resumed"})
                        except Exception:  # noqa: BLE001
                            pass
                        continue
                    raise ConnectionError(
                        "session channel lost (resume failed)")
                msgs = _decode_frames(raw)
                for msg in msgs:
                    # Inbound control frames are schema-checked before
                    # any handler sees them: a head from another build
                    # fails HERE with the exact field, not deep in a
                    # handler. (Typed binary frames are validated by
                    # construction, but the decoded dict re-checks —
                    # one rule set for both encodings.)
                    _wire.validate_message(msg)
                    if not self._route_frame(msg):
                        self._stop.set()
                        break
        finally:
            # Head session over: its leases are meaningless — retire the
            # executors and return their pinned workers.
            sender.close()
            self._reply_senders.pop(chan, None)
            chan.close()
            for ex in self._lease_executors.values():
                ex.stop()
            self._lease_executors.clear()
            # Queued work died with the head; temp slots must not
            # outlive the session that lent them capacity.
            for cq in self._class_queues.values():
                cq.close()
            self._class_queues.clear()
            try:
                self._sock.close()
            except OSError:
                pass

    def _try_resume(self, chan, token: Optional[str]) -> bool:
        """Re-dial the head and resume a broken session channel.

        True: the channel re-attached (session state survives, unacked
        frames replayed both ways). False: resume impossible — rejected
        by the head, window exhausted, or orderly stop — and the caller
        tears the session down for a full re-register."""
        import time as _time

        from ray_tpu._private.channel import (Backoff, close_socket,
                                              connection_refused)
        if not token:
            return False
        deadline = (chan.broken_at or _time.monotonic()) + chan.window_s
        bo = Backoff(0.2, 2.0)
        refused = 0
        while not self._stop.is_set() and _time.monotonic() < deadline:
            sock = None
            try:
                sock = socket.create_connection(self.head_address,
                                                timeout=5)
                sock.settimeout(10)
                try:
                    sock.setsockopt(socket.IPPROTO_TCP,
                                    socket.TCP_NODELAY, 1)
                except OSError:
                    pass
                if _chaos.ACTIVE:
                    # A partition must blackhole the resume path too —
                    # otherwise a "partitioned" daemon could quietly
                    # re-attach mid-blackhole.
                    _chaos.maybe_inject("daemon.resume.send", sock)
                _send_frame(sock, _dumps({
                    "type": "resume",
                    "protocol": _wire.PROTOCOL_VERSION,
                    "node_id": self.node_id_hex,
                    "token": token,
                    "epoch": self._node_epoch,
                    "last_seq": chan.in_seq}))
                reply = _loads(_recv_frame(sock))
                if reply.get("type") == "fenced":
                    # This incarnation was declared dead while we were
                    # unreachable; head-side, its actors died with it
                    # (exactly once). Drop the stale residents NOW so
                    # the coming re-registration cannot offer them for
                    # rebinding — a restarted copy may already be
                    # running elsewhere, and two live instances of one
                    # detached actor is the split-brain this fence
                    # exists to prevent.
                    logger.warning(
                        "session fenced (incarnation %d declared dead); "
                        "dropping %d stale resident actors and "
                        "re-registering", self._node_epoch,
                        len(self._actors))
                    self._actors.clear()
                    self._actor_tpu_ids.clear()
                    close_socket(sock)
                    return False
                if reply.get("type") != "resumed":
                    # Head restarted / node already declared dead: a
                    # full re-register is the right (and fast) path.
                    logger.warning("channel resume rejected: %s",
                                   reply.get("error"))
                    close_socket(sock)
                    return False
                sock.settimeout(None)
                if chan.attach(sock, int(reply.get("last_seq", 0))):
                    self._sock = sock  # SIGTERM handler pops the reader
                    return True
                close_socket(sock)
                return False
            except (ConnectionError, OSError) as exc:
                if sock is not None:
                    close_socket(sock)
                if connection_refused(exc):
                    # Nothing is LISTENING at the head address: the head
                    # process is gone, and with it the channel ring this
                    # resume would replay into. Burning the rest of the
                    # resume window here would eat into the failover
                    # window — bail to the outer re-register loop, which
                    # keeps re-dialing for head_failover_window_s and
                    # can join a REBORN head. A couple of confirmations
                    # guard against one stray RST during a restart race.
                    refused += 1
                    if refused >= 3:
                        logger.warning(
                            "head %s refused %d consecutive resume "
                            "dials (process gone); falling back to "
                            "re-register", self.head_address, refused)
                        return False
                else:
                    refused = 0
                bo.sleep()
        return False

    def _start_log_streaming(self, session_id: str) -> None:
        """Join the driver session's log tree (the registration ack
        carries the session id): this daemon's own stdout/stderr move
        into per-proc ``raylet-<pid>`` files, its python logging onto a
        structured ``raylet-<pid>.log``, and a LogMonitor tails every
        capture file this process creates (raylet + spawned workers),
        shipping batches head-ward."""
        from ray_tpu._private import ray_logging
        from ray_tpu._private.log_monitor import LogMonitor
        try:
            log_dir = ray_logging.setup_session(
                session_id, f"node-{(self.node_id_hex or '')[:12]}")
        except OSError:
            logger.exception("could not join session log dir")
            return
        ray_logging.attach_file_logging(log_dir)
        redirected = ray_logging.redirect_process_streams(log_dir)
        if redirected:
            # Streams are captured (not a tty): in-daemon task/actor
            # execution can announce task names via stream markers —
            # actor calls show `Cls.method pid=` in driver streaming
            # like worker-subprocess output does.
            os.environ[ray_logging.MARKER_ENV] = "1"
        monitor = LogMonitor(self._publish_log_batch)
        for path, source in redirected:
            monitor.add_file(path, "raylet", os.getpid(), source)
        ray_logging.register_capture_callback(monitor.add_file)
        self._log_monitor = monitor

    def _announce_spilled(self, key: str, uri: str, size: int) -> None:
        """Durable-spill notice (NodeObjectTable.on_spilled): the head
        adds the URI to its location table so this daemon's death
        restores the object from disk instead of re-running lineage.
        Best-effort between sessions — a re-register re-announces
        nothing, but the spill record survives on disk either way."""
        chan = self._chan
        sender = self._reply_senders.get(chan) if chan is not None \
            else None
        if sender is not None:
            sender.send({"type": "object_spilled", "key": key,
                         "uri": uri, "size": int(size)})

    def _announce_unspilled(self, key: str) -> None:
        """Retraction (restore-promotion or free deleted the file)."""
        chan = self._chan
        sender = self._reply_senders.get(chan) if chan is not None \
            else None
        if sender is not None:
            sender.send({"type": "object_unspilled", "key": key})

    def _publish_log_batch(self, batch: dict) -> bool:
        """Ship one tail batch through the session's coalescing reply
        sender (the socket's single writer — log frames interleave
        safely with task replies). Logs are best-effort: between head
        sessions there is no sender and the batch is dropped; the full
        text stays on disk for `ray-tpu logs`."""
        chan = self._chan
        sender = self._reply_senders.get(chan) if chan is not None \
            else None
        if sender is None:
            return False
        msg = dict(batch)
        msg["type"] = "log_batch"
        msg["node_id"] = self.node_id_hex or ""
        return bool(sender.send(msg))

    def _publish_metrics_batch(self, batch: dict) -> bool:
        """Ship one metrics batch (the daemon's own registry snapshot,
        or a worker's piggybacked batch) through the session's reply
        sender. Returning False (no live head session) makes the agent
        resend a full snapshot once the channel recovers."""
        chan = self._chan
        sender = self._reply_senders.get(chan) if chan is not None \
            else None
        if sender is None:
            return False
        msg = dict(batch)
        msg["type"] = "metrics_batch"
        msg["node_id"] = self.node_id_hex or ""
        if msg.get("component") == "daemon":
            # Piggyback this daemon's control-loop EventStats (additive
            # wire-v9 field) so /api/event_stats sees every node, not
            # just the head process. Worker batches relayed through the
            # same sink keep their own identity — no stats attached.
            from ray_tpu._private.event_stats import GLOBAL
            stats = GLOBAL.summary()
            if stats:
                msg["event_stats"] = stats
        return bool(sender.send(msg))

    def _publish_profile_batch(self, batch: dict) -> bool:
        """Ship one folded-stack window (the daemon's own profiler, or
        a worker's piggybacked window) as a ``profile_batch`` push.
        Additive post-v9: an old head's recv loop drops the unknown
        push type on the floor, so mixed clusters stay compatible."""
        chan = self._chan
        sender = self._reply_senders.get(chan) if chan is not None \
            else None
        if sender is None:
            return False
        msg = dict(batch)
        msg["type"] = "profile_batch"
        msg["node_id"] = self.node_id_hex or ""
        return bool(sender.send(msg))

    def _publish_flow_batch(self, batch: dict) -> bool:
        """Ship one drained transfer-ledger window (this daemon's own
        FlowRecorder, or a worker's piggybacked batch) as a
        ``flow_batch`` push. Additive post-v9: an old head's recv loop
        drops the unknown push type on the floor."""
        chan = self._chan
        sender = self._reply_senders.get(chan) if chan is not None \
            else None
        if sender is None:
            return False
        msg = dict(batch)
        msg["type"] = "flow_batch"
        msg["node_id"] = self.node_id_hex or ""
        return bool(sender.send(msg))

    def _profile_worker(self, pid: int, duration: float, hz: int,
                        fmt: str):
        """Relay a profile burst to the pool worker owning ``pid`` over
        its request pipe (cooperative — the worker samples itself, no
        ptrace/py-spy needed on the node). The pipe is one-in-flight: a
        worker mid-task starts sampling when its current task ends."""
        pool = self._pool
        handle = None
        if pool is not None:
            for w in list(pool._all):
                if w.pid == pid:
                    handle = w
                    break
        if handle is None:
            raise ValueError(
                f"pid {pid} is not a live worker of this node")
        reply = handle.request({"type": "profile", "duration": duration,
                                "hz": hz},
                               timeout=duration + 30)
        if not reply.get("ok"):
            raise RuntimeError(reply.get("error")
                               or "worker profile failed")
        counts = reply.get("stacks") or {}
        if fmt == "dict":
            return counts
        if fmt == "speedscope":
            from ray_tpu._private.profiling import folded_to_speedscope
            return folded_to_speedscope(counts, name=f"worker-{pid}",
                                        hz=hz)
        return "\n".join(f"{k} {v}" for k, v in sorted(counts.items()))

    def _collect_daemon_metrics(self) -> None:
        """Refresh daemon-side gauges before each export snapshot."""
        pool = self._pool
        if pool is not None:
            record = getattr(pool, "record_metrics", None)
            if record is not None:
                record()

    def _route_frame(self, msg: dict) -> bool:
        """Route one inbound control message (recv-loop thread only).
        Returns False for shutdown."""
        if msg.get("type") == "shutdown":
            return False
        # Serialize function installation: cache raw bytes here on
        # the recv thread, not in the handler threads.
        fb = msg.get("fn_bytes")
        if fb is not None and msg.get("fn_id") is not None:
            self._fn_raw.setdefault(msg["fn_id"], fb)
        lease_id = msg.get("lease_id")
        if msg.get("type") == "drop_lease":
            ex = self._lease_executors.pop(lease_id, None)
            if ex is not None:
                ex.stop()
        elif msg.get("type") == "spill_lease":
            ex = self._lease_executors.get(lease_id)
            if ex is not None:
                ex.spill()
        elif msg.get("type") == "unspill_lease":
            ex = self._lease_executors.get(lease_id)
            if ex is not None:
                ex.unspill()
        elif msg.get("type") == "reclaim_tasks":
            self._reclaim_tasks(self._chan, msg)
        elif lease_id is not None:
            # Leased task: onto the class's shared local-dispatch queue
            # (CPU classes — the daemon picks the slot), or the lease's
            # strict-FIFO serial executor (TPU classes: chip ids were
            # accounted to this lease, overlap would double-book them).
            ex = self._lease_executors.get(lease_id)
            if ex is None:
                cq = None
                class_id = msg.get("class_id")
                if class_id is not None and not msg.get("tpu_ids"):
                    cq = self._class_queues.get(class_id)
                    if cq is None:
                        cq = _ClassQueue(self, class_id)
                        self._class_queues[class_id] = cq
                ex = _LeaseExecutor(self, lease_id, cq)
                self._lease_executors[lease_id] = ex
            self._lease_tasks_total += 1
            if ex.spilled:
                # Spilled SERIAL lease (a task blocked in a nested get):
                # late frames bypass the serial queue too.
                threading.Thread(target=self._handle_counted,
                                 args=(self._chan, msg),
                                 daemon=True).start()
            else:
                ex.submit(self._chan, msg)
        else:
            # Pass THIS session's channel: a handler outliving the
            # session replies into a closed channel (dropped), never
            # into a later session whose fresh req_id counter would
            # collide with this frame's req_id.
            threading.Thread(target=self._handle_counted,
                             args=(self._chan, msg),
                             daemon=True).start()
        return True


def run_node(address: str, *, num_cpus: float = 1.0,
             num_tpus: Optional[float] = None,
             memory: float = 1 << 30,
             resources: Optional[Dict[str, float]] = None,
             labels: Optional[dict] = None,
             object_store_memory: int = 1 << 28,
             spill_dir: Optional[str] = None) -> None:
    """Entry point for `ray-tpu start --address host:port` and
    `python -m ray_tpu._private.multinode`. ``num_tpus=None`` counts the
    host's chips: this daemon is then the process that owns them (TPU
    tasks run in its threads, never in its worker subprocesses)."""
    host, _, port = address.rpartition(":")
    node_resources: Dict[str, float] = {"CPU": float(num_cpus),
                                        "memory": float(memory)}
    if num_tpus is None:
        from ray_tpu._private.resource_spec import autodetect_num_tpus
        num_tpus, _ = autodetect_num_tpus()
    if num_tpus:
        node_resources["TPU"] = float(num_tpus)
    if resources:
        node_resources.update(resources)
    daemon = NodeDaemon((host or "127.0.0.1", int(port)), node_resources,
                        labels,
                        object_store_memory=int(object_store_memory),
                        spill_dir=spill_dir)

    # Graceful SIGTERM: pop run() out of its recv loop so its finally
    # runs the ONE _teardown (arena unlink, pool shutdown, spill-dir
    # removal). The handler itself must not touch table locks — a
    # SIGTERM landing mid-_teardown would self-deadlock on the
    # non-reentrant lock the suspended frame already holds. (SIGKILL
    # cannot be trapped — the stale reapers cover that.)
    import signal as _signal

    def _terminate(_signum, _frame):
        daemon._stop.set()
        sock = daemon._sock
        if sock is not None:
            _close_quiet(sock)

    with contextlib.suppress(ValueError):  # non-main thread: skip
        _signal.signal(_signal.SIGTERM, _terminate)
    daemon.run()


def _main() -> None:
    import argparse
    import json
    parser = argparse.ArgumentParser(
        description="ray_tpu node daemon: join a head and execute tasks")
    parser.add_argument("--address", required=True,
                        help="head host:port (ray_tpu.start_head_server)")
    parser.add_argument("--num-cpus", type=float, default=1.0)
    parser.add_argument("--num-tpus", type=float, default=0.0,
                        help="chips this daemon owns. 0 unless given: this "
                             "module entry starts extra daemons beside a "
                             "driver (tests, benches, cluster_utils), and "
                             "those must not claim the chips the driver "
                             "owns. `ray-tpu start` counts the host's chips")
    parser.add_argument("--memory", type=float, default=float(1 << 30))
    parser.add_argument("--resources", type=str, default=None,
                        help='extra resources as JSON, e.g. \'{"spot": 1}\'')
    parser.add_argument("--labels", type=str, default=None,
                        help="node labels as JSON (autoscaler providers "
                             "tag their nodes here)")
    parser.add_argument("--object-store-memory", type=float,
                        default=float(1 << 28),
                        help="bytes for this node's object table (shm "
                             "arena when available)")
    parser.add_argument("--spill-dir", type=str, default=None,
                        help="directory for disk spill of cold objects "
                             "under memory pressure (default: a per-"
                             "daemon dir under the system temp dir)")
    args = parser.parse_args()
    logging.basicConfig(level=logging.INFO)
    run_node(args.address, num_cpus=args.num_cpus, num_tpus=args.num_tpus,
             memory=args.memory,
             resources=json.loads(args.resources) if args.resources
             else None,
             labels=json.loads(args.labels) if args.labels else None,
             object_store_memory=int(args.object_store_memory),
             spill_dir=args.spill_dir)


if __name__ == "__main__":
    # `python -m` runs this file as __main__ — delegate to the canonical
    # import so the daemon's classes are identical to the ones the head
    # pickles by reference (isinstance across the wire depends on it).
    from ray_tpu._private.multinode import _main as _canonical_main

    _canonical_main()
