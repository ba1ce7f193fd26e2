"""Collective dataplane: spanning-tree broadcast, striped multi-source
pulls, the blocking wait op, and locality-aware placement (reference:
ObjectManager push/pull managers + locality-aware lease policy)."""

import json
import socket
import subprocess
import sys
import threading
import time

import numpy as np
import pytest

import ray_tpu
from ray_tpu._private import dataplane
from ray_tpu._private.dataplane import (NodeObjectTable, ObjectServer,
                                        pull_object, wait_remote)


def _patterned(n: int) -> bytes:
    # Position-dependent bytes: a chunk landing at the wrong offset (or
    # served from the wrong range) changes the payload.
    return bytes((i * 31 + (i >> 8)) & 0xFF for i in range(n))


@pytest.fixture
def small_chunks(monkeypatch):
    monkeypatch.setenv("RAY_TPU_PULL_CHUNK_BYTES", str(64 * 1024))
    monkeypatch.setenv("RAY_TPU_PULL_PARALLELISM", "4")
    monkeypatch.setenv("RAY_TPU_PULL_STRIPE_MAX_SOURCES", "4")


# -- striped multi-source pulls --------------------------------------------


def test_striped_pull_disjoint_ranges_across_sources(small_chunks,
                                                     monkeypatch):
    """Four holders of the same object each serve a share of the chunk
    ranges; the landing is byte-identical and every stripe slot moved
    bytes."""
    payload = _patterned(1 << 20)  # 16 chunks at 64 KB
    tables = [NodeObjectTable() for _ in range(4)]
    servers = [ObjectServer(t, host="127.0.0.1") for t in tables]
    # The four fetch workers share one queue: on a busy machine the first
    # to start can drain it before the others run. Each holds its first
    # chunk until all four have taken one, so that which holder serves a
    # slot is read from the striping and not from the scheduler.
    all_started, first = threading.Barrier(4, timeout=60), threading.local()
    fetch = dataplane._fetch_chunk

    def fetch_chunk(*args):
        if threading.current_thread().name.startswith(
                "ray_tpu-pull-chunk-") and not hasattr(first, "taken"):
            first.taken = True
            all_started.wait()
        return fetch(*args)

    monkeypatch.setattr(dataplane, "_fetch_chunk", fetch_chunk)
    try:
        for t in tables:
            t.put("blob", payload)
        addrs = [("127.0.0.1", s.port) for s in servers]
        dst = NodeObjectTable()
        stats: dict = {"bytes": 0, "chunks": 1, "parallelism": 1,
                       "failovers": 0}
        assert dataplane._pull_chunked(
            addrs, "blob", dst, len(payload), 30.0, None,
            dataplane.PULL_PRIORITY_GET, stats=stats)
        with dst.pinned("blob") as got:
            assert bytes(got) == payload
        # Every byte was served exactly once, spread over the sources.
        assert sum(stats["striped"].values()) == len(payload)
        assert stats["sources_used"] >= 2
        assert stats["failovers"] == 0
        for served in stats["striped"].values():
            assert served > 0
    finally:
        for s in servers:
            s.close()


def test_striped_pull_survives_dead_source(small_chunks):
    """A dead holder in the stripe set joins the monotonic dead set;
    its ranges resume from the live holders and the landing stays
    byte-identical."""
    payload = _patterned(512 * 1024)
    tables = [NodeObjectTable() for _ in range(2)]
    servers = [ObjectServer(t, host="127.0.0.1") for t in tables]
    # A listener that is closed immediately: connects are refused.
    dead_probe = ObjectServer(NodeObjectTable(), host="127.0.0.1")
    dead_addr = ("127.0.0.1", dead_probe.port)
    dead_probe.close()
    try:
        for t in tables:
            t.put("blob", payload)
        live = [("127.0.0.1", s.port) for s in servers]
        dst = NodeObjectTable()
        pull_object(live[0], "blob", dst, size_hint=len(payload),
                    fallback_addrs=[dead_addr, live[1]])
        with dst.pinned("blob") as got:
            assert bytes(got) == payload
    finally:
        for s in servers:
            s.close()


# -- blocking wait op -------------------------------------------------------


def test_wait_op_blocks_until_object_lands():
    table = NodeObjectTable()
    server = ObjectServer(table, host="127.0.0.1")
    addr = ("127.0.0.1", server.port)
    payload = _patterned(64 * 1024)
    try:
        timer = threading.Timer(0.3, lambda: table.put("late", payload))
        timer.start()
        t0 = time.monotonic()
        size = wait_remote(addr, "late", timeout=10.0)
        waited = time.monotonic() - t0
        timer.join()
        assert size == len(payload)
        assert waited >= 0.2, "wait returned before the put"
    finally:
        server.close()


def test_wait_op_times_out_with_minus_one():
    table = NodeObjectTable()
    server = ObjectServer(table, host="127.0.0.1")
    try:
        t0 = time.monotonic()
        assert wait_remote(("127.0.0.1", server.port), "never",
                           timeout=0.4) == -1
        assert time.monotonic() - t0 < 5.0
    finally:
        server.close()


# -- locality-aware placement ----------------------------------------------


def test_locality_preference_picks_largest_holder():
    """The preference sums primary + replica holder bytes per node and
    picks the argmax; tasks without daemon-resident args get None."""
    from ray_tpu._private.ids import JobID, NodeID, ObjectID, TaskID
    from ray_tpu._private.object_ref import ObjectRef
    from ray_tpu._private.runtime import Runtime

    task = TaskID.for_normal_task(JobID.from_int(7))
    oid_a = ObjectID.for_put(task, 1)
    oid_b = ObjectID.for_put(task, 2)
    node_x, node_y = NodeID.from_random(), NodeID.from_random()

    class _Store:
        def size_of(self, oid):
            return {oid_a: 100, oid_b: 40}.get(oid, 0)

    class _Stub:
        _remote_values = {oid_a: (node_x, "ka"), oid_b: (node_y, "kb")}
        _object_replicas = {oid_b: {node_x: None}}
        store = _Store()

    class _Spec:
        args = [ObjectRef(oid_a), ObjectRef(oid_b), 42]
        kwargs = {}

    # node_x holds oid_a (100) + a replica of oid_b (40) = 140 > 40.
    assert Runtime._locality_preference(_Stub(), _Spec()) == node_x

    class _NoRemote:
        args = [1, 2]
        kwargs = {}

    assert Runtime._locality_preference(_Stub(), _NoRemote()) is None


def test_locality_spillback_counts_outcome(ray_start_regular,
                                           monkeypatch):
    """With the spillback threshold forced to 0 every preferred node
    counts as overloaded: placements carrying a locality preference
    record outcome=spillback, never local."""
    from ray_tpu._private import builtin_metrics
    from ray_tpu._private.worker import global_worker

    rt = global_worker.runtime
    monkeypatch.setattr(rt, "_cfg_locality_spillback", 0.0)
    host, port = ray_tpu.start_head_server(port=0, host="127.0.0.1")
    proc = subprocess.Popen(
        [sys.executable, "-m", "ray_tpu._private.multinode",
         "--address", f"127.0.0.1:{port}", "--num-cpus", "4",
         "--resources", json.dumps({"remote": 4})],
        stdout=subprocess.DEVNULL, stderr=subprocess.DEVNULL)
    try:
        deadline = time.monotonic() + 20
        while time.monotonic() < deadline:
            if ray_tpu.cluster_resources().get("remote", 0) >= 4:
                break
            time.sleep(0.1)
        else:
            raise TimeoutError("daemon never registered")

        @ray_tpu.remote(resources={"remote": 1})
        def produce():
            return np.arange(1 << 18)  # 2 MB, daemon-resident

        ref = produce.remote()
        ray_tpu.wait([ref], num_returns=1, fetch_local=False)

        def outcomes():
            series = builtin_metrics.lease_locality().series()
            return {tags[0]: v for tags, v in series.items()}

        before = outcomes()

        @ray_tpu.remote
        def consume(arr):
            return int(arr[-1])

        assert ray_tpu.get(consume.remote(ref)) == (1 << 18) - 1
        after = outcomes()
        assert after.get("spillback", 0) > before.get("spillback", 0)
        assert after.get("local", 0) == before.get("local", 0)
    finally:
        if proc.poll() is None:
            proc.kill()
        proc.wait(timeout=10)


# -- spanning-tree broadcast -----------------------------------------------


def _spawn_daemon(port, resources):
    return subprocess.Popen(
        [sys.executable, "-m", "ray_tpu._private.multinode",
         "--address", f"127.0.0.1:{port}", "--num-cpus", "2",
         "--resources", json.dumps(resources)],
        stdout=subprocess.DEVNULL, stderr=subprocess.DEVNULL)


@pytest.fixture
def broadcast_cluster(ray_start_regular):
    """Head + 4 daemons, spawned ONE AT A TIME so registration order
    (and therefore broadcast tree position) matches the procs list.
    Each daemon carries a distinct n{i} resource for pinned reads."""
    host, port = ray_tpu.start_head_server(port=0, host="127.0.0.1")
    procs = []
    try:
        for i in range(4):
            procs.append(_spawn_daemon(port, {f"n{i}": 2}))
            deadline = time.monotonic() + 20
            while time.monotonic() < deadline:
                if ray_tpu.cluster_resources().get(f"n{i}", 0) >= 2:
                    break
                time.sleep(0.05)
            else:
                raise TimeoutError(f"daemon {i} never registered")
        yield port, procs
    finally:
        for p in procs:
            if p.poll() is None:
                p.kill()
            p.wait(timeout=10)


def _read_on(i: int, ref):
    @ray_tpu.remote(resources={f"n{i}": 1})
    def digest(arr):
        return (int(arr.size), float(arr[:100].sum()))

    return ray_tpu.get(digest.remote(ref), timeout=60)


def test_broadcast_tree_replicates_head_object(broadcast_cluster):
    """Head-resident object, fanout 2, 4 daemons: the head seeds only
    its two direct children (egress = fanout x size), depth-2 nodes
    cascade peer-to-peer, and every daemon reads the same bytes."""
    arr = np.arange(1 << 19, dtype=np.int64)  # 4 MB
    ref = ray_tpu.put(arr)
    tree = ray_tpu.broadcast(ref, fanout=2)
    assert tree["nodes"] == 4, tree
    assert tree["depth"] == 2, tree
    ok_edges = [e for e in tree["edges"] if e["ok"]]
    assert len(ok_edges) == 4
    # Head egress is bounded by the fanout, not the cluster width.
    assert sum(1 for e in ok_edges if e["src"] == "head") == 2
    expect = (arr.size, float(arr[:100].sum()))
    for i in range(4):
        assert _read_on(i, ref) == expect
    # The flow plane remembers the tree for `ray-tpu xfer --tree`.
    from ray_tpu._private.worker import global_worker
    bc = global_worker.runtime.flows_snapshot().get("broadcast")
    assert bc is not None and len(bc["edges"]) == 4
    assert bc["age_s"] >= 0.0
    # Broadcast twice is a no-op refresh, not an error: daemons answer
    # "already resident".
    tree2 = ray_tpu.broadcast(ref, fanout=2)
    assert tree2["nodes"] == 0 or tree2["nodes"] == 4


def test_broadcast_chaos_sigkill_mid_tree(broadcast_cluster):
    """Chain broadcast (fanout 1) with an interior node SIGKILLed: every
    surviving daemon converges byte-identical. Depending on how fast the
    head notices the corpse, the plan either drops it (3 clean edges) or
    routes through it (4 edges, the corpse's edge failed and its orphan
    re-parented via the alts ladder)."""
    port, procs = broadcast_cluster
    arr = np.arange(1 << 19, dtype=np.int64)  # 4 MB
    ref = ray_tpu.put(arr)
    procs[1].kill()
    tree = ray_tpu.broadcast(ref, fanout=1)
    procs[1].wait(timeout=10)
    survivors = [e for e in tree["edges"] if e["ok"]]
    assert len(survivors) == 3, tree
    if len(tree["edges"]) == 4:
        # The head planned through the corpse: its own edge failed and
        # the orphaned subtree re-parented instead of dying with it.
        failed = [e for e in tree["edges"] if not e["ok"]]
        assert len(failed) == 1, tree
        assert any(e["failovers"] >= 1 for e in survivors), tree
    expect = (arr.size, float(arr[:100].sum()))
    for i in (0, 2, 3):
        assert _read_on(i, ref) == expect


def test_push_object_reparents_through_alts(broadcast_cluster):
    """The daemon-side failover ladder, deterministically: seed one
    daemon with a fresh key inline, then direct a second daemon to pull
    it from a dead parent with the holder as the alternate. The directive
    must report exactly one failover and land the full payload."""
    from ray_tpu._private.multinode import _dumps
    from ray_tpu._private.worker import global_worker

    rt = global_worker.runtime
    with rt._lock:
        conns = {nid: c for nid, c in rt._remote_nodes.items()
                 if getattr(c, "object_addr", None) is not None}
    nids = sorted(conns, key=lambda n: n.hex())
    holder, puller = conns[nids[0]], conns[nids[1]]
    payload = _dumps(np.arange(1 << 16, dtype=np.int64))
    key = "push-reparent-test"
    seeded = holder.push_object(key, len(payload), data=payload,
                                timeout=30.0)
    assert seeded["bytes"] == len(payload)
    # A port nothing listens on: bind, learn the number, close.
    s = socket.socket()
    s.bind(("127.0.0.1", 0))
    dead_addr = s.getsockname()
    s.close()
    got = puller.push_object(
        key, len(payload), parent=dead_addr,
        alts=[tuple(holder.object_addr)],
        wait_timeout_s=10.0, timeout=60.0)
    assert got["bytes"] == len(payload), got
    assert got["failovers"] == 1, got


def test_broadcast_counters_and_push_tier(broadcast_cluster):
    from ray_tpu._private import builtin_metrics

    trees_before = sum(builtin_metrics.broadcast_trees()
                       .series().values())
    push_before = sum(builtin_metrics.push_bytes().series().values())
    ref = ray_tpu.put(np.ones(1 << 18))  # 2 MB
    tree = ray_tpu.broadcast(ref, fanout=2)
    assert tree["nodes"] == 4
    assert sum(builtin_metrics.broadcast_trees().series().values()) \
        == trees_before + 1
    assert sum(builtin_metrics.push_bytes().series().values()) \
        >= push_before + 4 * tree["size"]
