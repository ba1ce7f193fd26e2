"""Fenced membership and fast failure detection (wire v9).

Covers the three legs of the membership subsystem:

* **Epoch fencing** — every registration mints a monotonically
  increasing ``node_epoch`` (persisted through the gcs_store, so a
  restarted head keeps minting above its previous life); a declared
  death fences the epoch, and stale-incarnation frames / resume
  attempts are dropped+counted (``ray_tpu_frames_fenced_total``), never
  applied.
* **Accrual suspicion + lease** — per-node phi-accrual suspicion fed by
  channel liveness, adapting to each node's cadence (slow-cadence nodes
  are not falsely declared); ``RAY_TPU_node_lease_s`` bounds detection
  unconditionally; hard socket failures (SIGKILL) declare sub-second.
* **Partition chaos** — the ``partition`` chaos kind blackholes
  transport sites deterministically (p/seed/after/times grammar, ``ms``
  heal window), proving partition -> suspicion -> declaration ->
  fenced resume -> clean re-register with exactly-once detached-actor
  side effects.
"""

import json
import os
import signal
import socket
import struct
import subprocess
import sys
import threading
import time

import pytest

import ray_tpu
from ray_tpu._private import builtin_metrics, chaos, membership
from ray_tpu._private import wire as _wire


def _fenced_total() -> float:
    return sum(builtin_metrics.frames_fenced().series().values())


# -- unit: accrual detector ----------------------------------------------

def test_phi_adapts_to_node_cadence():
    """The same silence is damning for a chatty node and unremarkable
    for one that routinely goes quiet (an XLA compile must not read as
    death)."""
    base = time.monotonic()
    fast = membership.AccrualDetector(floor_s=0.05)
    slow = membership.AccrualDetector(floor_s=0.05)
    fast.last_arrival = slow.last_arrival = base
    for i in range(1, 21):
        fast.record(base + 0.05 * i)          # frame every 50ms
    for i in range(1, 5):
        slow.record(base + 5.0 * i)           # quiet 5s between reports
    t_fast = fast.last_arrival + 2.0
    t_slow = slow.last_arrival + 2.0
    assert fast.phi(t_fast) > 8.0             # 2s silent: way off-cadence
    assert slow.phi(t_slow) < 1.0             # 2s silent: routine
    # Silence shorter than the cadence is never suspicious.
    assert fast.phi(fast.last_arrival + 0.01) < 0.1


def test_phi_floor_defeats_burst_skew():
    """A burst of sub-millisecond arrivals must not shrink the mean so
    far that a routine pause looks fatal — the floor clamps it."""
    base = time.monotonic()
    det = membership.AccrualDetector(floor_s=0.25)
    det.last_arrival = base
    for i in range(1, 50):
        det.record(base + 0.001 * i)          # 1ms burst
    assert det.mean_interval() == pytest.approx(0.25)
    assert det.phi(det.last_arrival + 0.3) < 1.0


# -- unit: membership table ----------------------------------------------

def test_declare_dead_exactly_once_and_fences_epoch():
    table = membership.MembershipTable()
    e1 = table.mint_epoch("node-a")
    assert table.current_epoch("node-a") == e1
    assert not table.is_fenced(e1)
    assert not table.is_fenced(0)             # 0 = epoch unknown
    assert table.declare_dead("node-a", "test") is True
    assert table.declare_dead("node-a", "test") is False  # exactly once
    assert table.is_fenced(e1)
    # An epoch this head never minted (daemon re-registering across a
    # head restart) is NOT fenced — the rebind path depends on that.
    assert not table.is_fenced(e1 + 1000)


def test_second_incarnation_gets_fresh_liveness_budget():
    """A re-registered daemon is a new incarnation: new (higher) epoch,
    zeroed suspicion state — and the old epoch stays fenced while the
    new one is clean."""
    table = membership.MembershipTable()
    e1 = table.mint_epoch("node-a")
    live1 = table.liveness("node-a")
    live1.soft_failures = 7                   # partition evidence piled up
    table.declare_dead("node-a", "partition")
    e2 = table.mint_epoch("node-a")
    assert e2 > e1
    live2 = table.liveness("node-a")
    assert live2 is not live1
    assert live2.soft_failures == 0
    assert live2.epoch == e2
    assert table.is_fenced(e1) and not table.is_fenced(e2)


def test_epoch_counter_survives_head_restart(tmp_path):
    """Epochs persist through the gcs_store: a restarted head mints
    strictly above everything its previous life handed out, so the old
    life's fenced epochs can never be re-issued."""
    from ray_tpu._private.gcs_store import GcsStore
    path = str(tmp_path / "gcs.pkl")
    store = GcsStore(path)
    t1 = membership.MembershipTable(store)
    epochs = [t1.mint_epoch("node-a"), t1.mint_epoch("node-b"),
              t1.mint_epoch("node-a")]
    t2 = membership.MembershipTable(GcsStore(path))  # "restarted head"
    assert t2.mint_epoch("node-c") > max(epochs)


def test_membership_events_fan_out():
    table = membership.MembershipTable()
    events = []

    def bad(_event):
        raise RuntimeError("one bad subscriber must not break the rest")

    table.subscribe(bad)
    table.subscribe(events.append)
    epoch = table.mint_epoch("node-a")
    table.declare_dead("node-a", "why not")
    assert [e["event"] for e in events] == ["joined", "dead"]
    assert events[0]["epoch"] == events[1]["epoch"] == epoch
    assert events[1]["reason"] == "why not"
    table.unsubscribe(events.append)
    table.mint_epoch("node-b")
    assert len(events) == 2


# -- unit: partition chaos grammar ---------------------------------------

@pytest.fixture
def chaos_reset():
    yield
    chaos.reset()


def _drop_pattern(spec, site, n):
    chaos.configure(spec)
    pattern = []
    for _ in range(n):
        try:
            chaos.maybe_inject(site)
            pattern.append(False)
        except chaos.ChaosPartition:
            pattern.append(True)
    chaos.reset()
    return pattern


def test_partition_same_seed_same_drops(chaos_reset):
    spec = "partition:p=0.4:seed=7:site=head"
    p1 = _drop_pattern(spec, "head.send", 200)
    p2 = _drop_pattern(spec, "head.send", 200)
    assert p1 == p2
    assert any(p1) and not all(p1)            # p<1: some pass, some drop
    assert _drop_pattern("partition:p=0.4:seed=8:site=head",
                         "head.send", 200) != p1


def test_partition_after_times_grammar(chaos_reset):
    pattern = _drop_pattern("partition:site=head:after=3:times=2",
                            "head.recv", 8)
    assert pattern == [False, False, False, True, True,
                       False, False, False]


def test_partition_only_fires_at_transport_sites(chaos_reset):
    chaos.configure("partition:site=head")
    chaos.maybe_inject("head.dispatch")       # not .send/.recv: no-op
    with pytest.raises(chaos.ChaosPartition):
        chaos.maybe_inject("head.health.send")
    stats = chaos.stats()
    assert stats[0]["fired"] == 1


def test_partition_heal_window_is_permanent(chaos_reset):
    """``ms`` arms on the FIRST fire: inside the window every matching
    call is blackholed (p/times notwithstanding); after it elapses the
    partition is healed forever."""
    chaos.configure("partition:site=head:ms=120")
    with pytest.raises(chaos.ChaosPartition):
        chaos.maybe_inject("head.send")       # arms the window
    with pytest.raises(chaos.ChaosPartition):
        chaos.maybe_inject("head.health.recv")
    time.sleep(0.15)
    for _ in range(20):                       # healed: never fires again
        chaos.maybe_inject("head.send")
    assert chaos.stats()[0]["fired"] == 2


def test_partition_is_soft_evidence_classification():
    """ChaosPartition must look like an unreachable peer (transient
    OSError for the channel layer) but be distinguishable from a hard
    reset so membership can classify it as soft evidence."""
    from ray_tpu._private.channel import is_transient
    exc = chaos.ChaosPartition("blackholed")
    assert isinstance(exc, OSError)
    assert is_transient(exc)
    assert not isinstance(exc, ConnectionError)


# -- unit: stale-epoch frames at the channel layer -----------------------

def _send_enveloped(sock, seq, ack, epoch, payload):
    frame = _wire.wrap_seq(seq, ack, payload, epoch)
    sock.sendall(struct.pack(">Q", len(frame)) + frame)


def test_stale_epoch_frame_dropped_and_counted():
    """A frame stamped with another incarnation's epoch is dropped and
    counted, never returned; epoch-0 (pre-registration) frames pass."""
    from ray_tpu._private.channel import ResilientChannel
    left, right = socket.socketpair()
    chan = ResilientChannel(right, site="test", ring_bytes=1 << 16,
                            window_s=0.5)
    chan.epoch = 7
    before = _fenced_total()
    try:
        _send_enveloped(left, 1, 0, 99, b"stale-incarnation")
        _send_enveloped(left, 1, 0, 7, b"current")
        assert chan.recv_frame() == b"current"
        assert _fenced_total() == before + 1
        _send_enveloped(left, 2, 0, 0, b"epoch-unknown")
        assert chan.recv_frame() == b"epoch-unknown"
        assert _fenced_total() == before + 1
    finally:
        chan.close()
        left.close()


def test_wire_v9_envelope_roundtrip():
    env = _wire.wrap_seq(5, 3, b"payload", epoch=42)
    assert _wire.unwrap_seq(env) == (5, 3, 42, b"payload")
    # Additive: epoch defaults to 0 for writers that don't know it yet.
    assert _wire.unwrap_seq(_wire.wrap_seq(1, 0, b"x"))[2] == 0


# -- integration helpers -------------------------------------------------

def _spawn_daemon(port, *, num_cpus=2, resources=None, env=None):
    cmd = [sys.executable, "-m", "ray_tpu._private.multinode",
           "--address", f"127.0.0.1:{port}",
           "--num-cpus", str(num_cpus)]
    if resources:
        cmd += ["--resources", json.dumps(resources)]
    return subprocess.Popen(cmd, env=env, stdout=subprocess.DEVNULL,
                            stderr=subprocess.DEVNULL)


def _wait_for_resource(name, amount, timeout=25):
    deadline = time.monotonic() + timeout
    while time.monotonic() < deadline:
        if ray_tpu.cluster_resources().get(name, 0) >= amount:
            return
        time.sleep(0.1)
    raise TimeoutError(
        f"resource {name}>={amount} never appeared: "
        f"{ray_tpu.cluster_resources()}")


# -- integration: resume fencing at the protocol level -------------------

def test_resume_from_fenced_epoch_refused():
    """A resume handshake carrying a fenced epoch gets a ``fenced``
    reply (and bumps ``ray_tpu_frames_fenced_total``); an unknown
    session with a bogus token gets ``resume_rejected`` — the daemon's
    cue to re-register."""
    from ray_tpu._private.multinode import (_dumps, _loads, _recv_frame,
                                            _send_frame)
    from ray_tpu._private.worker import global_worker
    ray_tpu.shutdown()
    ray_tpu.init(num_cpus=1, num_tpus=0)
    try:
        host, port = ray_tpu.start_head_server(port=0, host="127.0.0.1")
        runtime = global_worker.runtime
        epoch = runtime.membership.mint_epoch("feedfacecafe")
        runtime.membership.declare_dead("feedfacecafe", "partitioned away")
        before = _fenced_total()

        with socket.create_connection((host, port), timeout=5) as sock:
            _send_frame(sock, _dumps({
                "type": "resume", "protocol": _wire.PROTOCOL_VERSION,
                "node_id": "feedfacecafe", "token": "whatever",
                "epoch": epoch, "last_seq": 0}))
            reply = _loads(_recv_frame(sock))
        assert reply["type"] == "fenced"
        assert reply["epoch"] == epoch
        assert _fenced_total() > before

        # Old/unknown token (no fenced epoch): plain rejection.
        with socket.create_connection((host, port), timeout=5) as sock:
            _send_frame(sock, _dumps({
                "type": "resume", "protocol": _wire.PROTOCOL_VERSION,
                "node_id": "feedfacecafe", "token": "stale-token",
                "epoch": 0, "last_seq": 0}))
            reply = _loads(_recv_frame(sock))
        assert reply["type"] == "resume_rejected"
    finally:
        ray_tpu.shutdown()


# -- integration: hard-failure detection speed ---------------------------

def test_sigkill_daemon_declared_dead_fast():
    """At DEFAULT settings a SIGKILLed daemon is declared dead in well
    under the lease: the broken channel wakes the membership loop, the
    health probe hits a reset socket, and the hard path declares
    immediately."""
    from ray_tpu._private.worker import global_worker
    ray_tpu.shutdown()
    ray_tpu.init(num_cpus=1, num_tpus=0)
    p = None
    try:
        host, port = ray_tpu.start_head_server(port=0, host="127.0.0.1")
        p = _spawn_daemon(port, resources={"mortal": 1})
        _wait_for_resource("mortal", 1)
        dead = threading.Event()

        def on_event(event):
            if event.get("event") == "dead":
                dead.set()

        runtime = global_worker.runtime
        # The hard path needs the daemon's health channel, which opens
        # some 40 ms after its resources show: a kill inside that window
        # is bounded by the lease instead (a different path, and this
        # test's commonest flake).
        deadline = time.monotonic() + 10
        while not all(conn.health_sock is not None
                      for conn in list(runtime._remote_nodes.values())):
            assert time.monotonic() < deadline, "no health channel"
            time.sleep(0.01)
        runtime.membership.subscribe(on_event)
        try:
            p.send_signal(signal.SIGKILL)
            t0 = time.perf_counter()
            assert dead.wait(timeout=10), "death never declared"
            detect_s = time.perf_counter() - t0
        finally:
            runtime.membership.unsubscribe(on_event)
        # Sub-second by design; 2s leaves margin for a loaded CI box.
        assert detect_s < 2.0, f"detection took {detect_s:.2f}s"
    finally:
        if p is not None and p.poll() is None:
            p.kill()
        if p is not None:
            p.wait(timeout=10)
        ray_tpu.shutdown()


# -- integration: partition -> suspicion -> fence -> re-register ---------

def test_partition_fences_old_incarnation_exactly_once(tmp_path):
    """The acceptance scenario: a daemon hosting a detached actor is
    partitioned (head-side bidirectional blackhole) past the lease, the
    head declares it dead and fences the epoch; the daemon's resume is
    refused with ``fenced``; after the partition heals it re-registers
    as a NEW incarnation, the detached actor is rebound exactly once,
    and every invocation executed exactly once (no duplicate side
    effects from the stale instance)."""
    from ray_tpu._private.worker import global_worker
    marker = str(tmp_path / "ticks.txt")
    ray_tpu.shutdown()
    ray_tpu.init(num_cpus=2, num_tpus=0, _system_config={
        "health_probe_period_s": 0.1,
        "health_probe_timeout_s": 0.4,
        "node_lease_s": 1.5,
    })
    p = None
    events = []
    try:
        host, port = ray_tpu.start_head_server(port=0, host="127.0.0.1")
        runtime = global_worker.runtime
        runtime.membership.subscribe(events.append)
        p = _spawn_daemon(port, resources={"part": 1})
        _wait_for_resource("part", 1)

        @ray_tpu.remote(resources={"part": 1}, max_restarts=-1)
        class Effector:
            """Appends one line per invocation: a duplicated side
            effect (stale instance still applying frames) shows up as a
            repeated tag."""

            def __init__(self, path):
                import uuid
                self.path = path
                self.uid = uuid.uuid4().hex[:8]

            def tick(self, tag):
                with open(self.path, "a") as f:
                    f.write(f"{tag} {self.uid}\n")
                return self.uid

        actor = Effector.options(
            name="fenced-effector", lifetime="detached").remote(marker)
        uid_before = ray_tpu.get(actor.tick.remote("pre"), timeout=30)

        # Bidirectional blackhole of every head<->daemon transport site
        # (session send/recv + health probes) for 3.5s — long past the
        # 1.5s lease.
        t_partition = time.monotonic()
        chaos.configure("partition:site=head:ms=3500")
        deadline = time.monotonic() + 30
        while not any(e["event"] == "dead" for e in events):
            assert time.monotonic() < deadline, \
                f"partitioned node never declared dead: {events}"
            time.sleep(0.1)
        first_epoch = next(e["epoch"] for e in events
                           if e["event"] == "joined")
        assert runtime.membership.is_fenced(first_epoch)

        # Call into the (dead, restart-pending) actor mid-partition.
        # Actor invocations are at-most-once: the call either executes
        # exactly once on a NEW incarnation or fails with
        # ActorDiedError — it must never run twice (stale instance +
        # restarted one).
        mid_ref = actor.tick.remote("mid")

        # Heal: short-lived incarnations minted inside the blackhole may
        # each die at their lease; once the window has elapsed the
        # daemon's next registration sticks and the detached actor comes
        # back. Probe with uniquely-tagged ticks (at-most-once: a failed
        # probe is a dropped call, never a duplicated one).
        time.sleep(max(0.0, t_partition + 4.0 - time.monotonic()))
        from ray_tpu.exceptions import ActorDiedError
        try:
            mid_uid = ray_tpu.get(mid_ref, timeout=60)
        except ActorDiedError:
            mid_uid = None  # dropped with a dead incarnation, not dup'd
        if mid_uid is not None:
            assert mid_uid != uid_before
        uid_after = None
        post_tags = []
        deadline = time.monotonic() + 40
        while uid_after is None:
            tag = f"post{len(post_tags)}"
            post_tags.append(tag)
            try:
                handle = ray_tpu.get_actor("fenced-effector")
                uid_after = ray_tpu.get(handle.tick.remote(tag),
                                        timeout=10)
            except Exception:  # noqa: BLE001 - incarnation still settling
                assert time.monotonic() < deadline, \
                    "actor never came back after the partition healed"
                time.sleep(0.3)
        assert uid_after != uid_before

        joined = [e["epoch"] for e in events if e["event"] == "joined"]
        assert joined[-1] > first_epoch
        assert runtime.membership.is_fenced(first_epoch)
        assert not runtime.membership.is_fenced(joined[-1])

        # Exactly-once side effects: no tag ever appears twice, and
        # every post-fence execution came from a NEW incarnation (the
        # stale instance applied nothing after its epoch was fenced).
        with open(marker) as f:
            lines = [ln.split() for ln in f.read().splitlines()]
        tags = [tag for tag, _uid in lines]
        assert tags.count("pre") == 1
        assert tags.count("mid") == (1 if mid_uid is not None else 0)
        for tag in post_tags:
            assert tags.count(tag) <= 1       # dropped or ran ONCE
        assert tags.count(post_tags[-1]) == 1
        for tag, uid in lines:
            if tag != "pre":
                assert uid != uid_before
        assert {uid for tag, uid in lines if tag == post_tags[-1]} \
            == {uid_after}
    finally:
        chaos.reset()
        if p is not None and p.poll() is None:
            p.kill()
        if p is not None:
            p.wait(timeout=10)
        ray_tpu.shutdown()
