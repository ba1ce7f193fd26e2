"""Multi-process cluster tests: real node-daemon subprocesses joining a
head over TCP (the analog of the reference's multi-raylet fixtures, but
with genuine OS processes — SURVEY.md §4's Cluster model upgraded from
virtual nodes to the wire protocol in _private/multinode.py)."""

import json
import os
import signal
import subprocess
import sys
import time

import numpy as np
import pytest

import ray_tpu


def _spawn_daemon(port, *, num_cpus=4, resources=None):
    cmd = [sys.executable, "-m", "ray_tpu._private.multinode",
           "--address", f"127.0.0.1:{port}",
           "--num-cpus", str(num_cpus)]
    if resources:
        cmd += ["--resources", json.dumps(resources)]
    return subprocess.Popen(cmd, stdout=subprocess.DEVNULL,
                            stderr=subprocess.DEVNULL)


def _wait_for_resource(name, amount, timeout=60):
    deadline = time.monotonic() + timeout
    while time.monotonic() < deadline:
        if ray_tpu.cluster_resources().get(name, 0) >= amount:
            return
        time.sleep(0.1)
    raise TimeoutError(
        f"resource {name}>={amount} never appeared: "
        f"{ray_tpu.cluster_resources()}")


@pytest.fixture
def head_with_daemons(ray_start_regular):
    """Head + 2 real daemon subprocesses, each with a 'remote' resource
    so tests can force placement off the head node."""
    host, port = ray_tpu.start_head_server(port=0, host="127.0.0.1")
    procs = [
        _spawn_daemon(port, num_cpus=4, resources={"remote": 2})
        for _ in range(2)]
    try:
        _wait_for_resource("remote", 4)
        yield port, procs
    finally:
        for p in procs:
            if p.poll() is None:
                p.kill()
            p.wait(timeout=10)


def test_remote_node_task_execution(head_with_daemons):
    @ray_tpu.remote(resources={"remote": 1})
    def where(x):
        import os
        return os.getpid(), x * 2

    head_pid = os.getpid()
    results = ray_tpu.get([where.remote(i) for i in range(8)])
    pids = {pid for pid, _ in results}
    assert sorted(v for _, v in results) == [0, 2, 4, 6, 8, 10, 12, 14]
    assert head_pid not in pids, "tasks must run in the daemon processes"
    assert len(pids) >= 1

    # numpy payloads round-trip the wire
    @ray_tpu.remote(resources={"remote": 1})
    def matsum(a):
        return float(a.sum())

    arr = np.ones((256, 256), np.float32)
    assert ray_tpu.get(matsum.remote(arr)) == 256 * 256


def test_remote_node_error_propagation(head_with_daemons):
    from ray_tpu.exceptions import TaskError

    @ray_tpu.remote(max_retries=0, resources={"remote": 1})
    def boom():
        raise ValueError("remote kaboom")

    with pytest.raises(TaskError) as err:
        ray_tpu.get(boom.remote())
    assert isinstance(err.value.cause, ValueError)
    assert "remote kaboom" in str(err.value)


def test_remote_node_actor(head_with_daemons):
    @ray_tpu.remote(resources={"remote": 1})
    class Counter:
        def __init__(self, start):
            self.v = start

        def add(self, d):
            self.v += d
            return self.v

        def pid(self):
            import os
            return os.getpid()

    c = Counter.remote(100)
    assert ray_tpu.get([c.add.remote(1) for _ in range(5)]) == \
        [101, 102, 103, 104, 105]
    assert ray_tpu.get(c.pid.remote()) != os.getpid()
    ray_tpu.kill(c)


def test_remote_node_death_retries_elsewhere(head_with_daemons):
    port, procs = head_with_daemons

    @ray_tpu.remote(resources={"remote": 1}, max_retries=3)
    def slow(i):
        import os
        import time as t
        t.sleep(1.0)
        return os.getpid(), i

    refs = [slow.remote(i) for i in range(4)]
    time.sleep(0.4)  # let tasks land on both daemons
    procs[0].send_signal(signal.SIGKILL)
    procs[0].wait(timeout=10)
    results = ray_tpu.get(refs, timeout=60)
    assert sorted(i for _, i in results) == [0, 1, 2, 3]
    # the dead daemon's pid may appear for tasks that finished pre-kill,
    # but every task completed despite the node death
    assert ray_tpu.cluster_resources().get("remote", 0) == 2


def test_remote_actor_restarts_on_node_death(ray_start_regular):
    host, port = ray_tpu.start_head_server(port=0, host="127.0.0.1")
    p1 = _spawn_daemon(port, num_cpus=2, resources={"remote": 1})
    _wait_for_resource("remote", 1)

    @ray_tpu.remote(resources={"remote": 1}, max_restarts=2)
    class Stateful:
        def __init__(self):
            import os
            self.pid = os.getpid()
            self.n = 0

        def bump(self):
            self.n += 1
            return self.pid, self.n

    a = Stateful.remote()
    pid1, n = ray_tpu.get(a.bump.remote())
    assert n == 1
    p2 = _spawn_daemon(port, num_cpus=2, resources={"remote": 1})
    _wait_for_resource("remote", 2)
    try:
        p1.send_signal(signal.SIGKILL)
        p1.wait(timeout=10)
        # restart loses state (reference max_restarts semantics) and lands
        # on the surviving daemon
        deadline = time.monotonic() + 30
        while True:
            try:
                pid2, n2 = ray_tpu.get(a.bump.remote(), timeout=10)
                break
            except Exception:
                if time.monotonic() > deadline:
                    raise
                time.sleep(0.5)
        assert pid2 != pid1
        assert n2 == 1  # fresh state after restart
    finally:
        for p in (p1, p2):
            if p.poll() is None:
                p.kill()
            p.wait(timeout=10)


def test_object_ref_args_resolve_to_values(head_with_daemons):
    """ObjectRef args are resolved on the head and shipped by value."""
    @ray_tpu.remote
    def produce():
        return np.arange(1000)

    @ray_tpu.remote(resources={"remote": 1})
    def consume(arr):
        return int(arr.sum())

    ref = produce.remote()  # runs on the head (no 'remote' resource)
    assert ray_tpu.get(consume.remote(ref)) == 499500


def test_remote_tpu_ids_visible_in_daemon(ray_start_regular):
    host, port = ray_tpu.start_head_server(port=0, host="127.0.0.1")
    cmd = [sys.executable, "-m", "ray_tpu._private.multinode",
           "--address", f"127.0.0.1:{port}",
           "--num-cpus", "2", "--num-tpus", "2"]
    p = subprocess.Popen(cmd, stdout=subprocess.DEVNULL,
                         stderr=subprocess.DEVNULL)
    try:
        _wait_for_resource("TPU", 2)

        @ray_tpu.remote(num_tpus=1)
        def chips():
            return ray_tpu.get_tpu_ids()

        a, b = ray_tpu.get([chips.remote(), chips.remote()])
        assert len(a) == 1 and len(b) == 1
        assert set(a).isdisjoint(b), (a, b)  # disjoint chip assignment
    finally:
        if p.poll() is None:
            p.kill()
        p.wait(timeout=10)


@pytest.fixture
def head_small_inline_limit():
    """Cluster whose remote results above 1000 bytes stay daemon-resident
    (exercises the lazy-fetch data plane with small test payloads)."""
    ray_tpu.shutdown()
    ray_tpu.init(num_cpus=4, num_tpus=0, _memory=1e9,
                 _system_config={"remote_object_inline_limit_bytes": 1000})
    host, port = ray_tpu.start_head_server(port=0, host="127.0.0.1")
    p = _spawn_daemon(port, num_cpus=4, resources={"remote": 4})
    try:
        _wait_for_resource("remote", 4)
        yield port, p
    finally:
        if p.poll() is None:
            p.kill()
        p.wait(timeout=10)
        ray_tpu.shutdown()


def test_big_results_stay_daemon_resident(head_small_inline_limit):
    runtime = ray_tpu._private.worker.global_worker.runtime

    @ray_tpu.remote(resources={"remote": 1})
    def big():
        return np.arange(100_000)  # ~800KB >> 1000B limit

    ref = big.remote()
    # the store seals a lazy entry: ready for wait, value not yet local
    done, _ = ray_tpu.wait([ref], num_returns=1, timeout=30)
    assert done == [ref]
    oid = ref.object_id()
    assert runtime._remote_values.get(oid) is not None
    assert not runtime.store.is_materialized(oid)
    # first get pulls it over the wire and memoizes
    arr = ray_tpu.get(ref)
    assert int(arr.sum()) == 4999950000
    assert runtime.store.is_materialized(oid)


def test_remote_arg_locality_markers(head_small_inline_limit):
    """A daemon-resident value passed to a task on the same daemon is
    resolved locally there, not round-tripped through the head."""
    @ray_tpu.remote(resources={"remote": 1})
    def produce():
        return np.arange(50_000)

    @ray_tpu.remote(resources={"remote": 1})
    def consume(a):
        return int(a.sum())

    ref = produce.remote()
    ray_tpu.wait([ref], timeout=30)
    runtime = ray_tpu._private.worker.global_worker.runtime
    oid = ref.object_id()
    assert oid in runtime._remote_values  # still daemon-resident
    assert ray_tpu.get(consume.remote(ref)) == 1249975000
    # the head never materialized it: the arg traveled as a marker
    assert not runtime.store.is_materialized(oid)


def test_daemon_resident_value_reconstructed_on_death(
        head_small_inline_limit):
    port, p = head_small_inline_limit

    @ray_tpu.remote(resources={"remote": 1}, max_retries=2)
    def big(i):
        return np.full(30_000, i)

    ref = big.remote(7)
    ray_tpu.wait([ref], timeout=30)
    runtime = ray_tpu._private.worker.global_worker.runtime
    assert ref.object_id() in runtime._remote_values
    # second daemon joins, first dies before the value was fetched
    p2 = _spawn_daemon(port, num_cpus=4, resources={"remote": 4})
    try:
        _wait_for_resource("remote", 8)
        p.send_signal(signal.SIGKILL)
        p.wait(timeout=10)
        # lineage re-executes the task on the survivor
        arr = ray_tpu.get(ref, timeout=60)
        assert arr.shape == (30_000,) and int(arr[0]) == 7
    finally:
        if p2.poll() is None:
            p2.kill()
        p2.wait(timeout=10)


def test_hung_daemon_detected_by_health_checks(ray_start_regular):
    """A SIGSTOPped daemon keeps its socket open but stops replying; the
    head's membership loop (accrual suspicion + hard lease,
    gcs_health_check_manager analog) declares it dead and the node
    leaves the cluster."""
    ray_tpu.shutdown()
    ray_tpu.init(num_cpus=2, num_tpus=0,
                 _system_config={"health_probe_period_s": 0.05,
                                 "health_probe_timeout_s": 0.3,
                                 "node_lease_s": 3.0})
    host, port = ray_tpu.start_head_server(port=0, host="127.0.0.1")
    p = _spawn_daemon(port, num_cpus=2, resources={"remote": 2})
    try:
        _wait_for_resource("remote", 2)
        p.send_signal(signal.SIGSTOP)  # hung, not dead: TCP stays open
        deadline = time.monotonic() + 20
        while ray_tpu.cluster_resources().get("remote", 0) > 0:
            assert time.monotonic() < deadline, \
                "health checks never declared the hung daemon dead"
            time.sleep(0.2)
    finally:
        p.send_signal(signal.SIGCONT)
        p.kill()
        p.wait(timeout=10)
        ray_tpu.shutdown()


def test_autoscaler_launches_real_daemons(ray_start_regular):
    """End to end: infeasible demand -> autoscaler launches a REAL daemon
    process -> the task runs there; idle timeout terminates it."""
    from ray_tpu.autoscaler import (DaemonProcessNodeProvider,
                                    StandardAutoscaler)

    provider = DaemonProcessNodeProvider()
    autoscaler = StandardAutoscaler(provider, {
        "max_workers": 2,
        "idle_timeout_minutes": 0.0001,
        "available_node_types": {
            "burst-worker": {"resources": {"CPU": 2, "burst": 2},
                             "min_workers": 0, "max_workers": 2},
        },
    })

    @ray_tpu.remote(resources={"burst": 1})
    def job():
        import os
        return os.getpid()

    ref = job.remote()  # infeasible until the autoscaler acts
    result = autoscaler.update()
    assert result["launched"] == 1
    _wait_for_resource("burst", 2)
    pid = ray_tpu.get(ref, timeout=60)
    assert pid != os.getpid()
    # idle node is reaped once the timeout passes
    deadline = time.monotonic() + 60
    while autoscaler.num_terminations == 0:
        assert time.monotonic() < deadline, "the idle node was never reaped"
        time.sleep(0.1)
        autoscaler.update()
    deadline = time.monotonic() + 60
    while ray_tpu.cluster_resources().get("burst", 0) > 0:
        assert time.monotonic() < deadline, "the reaped node's resources stay"
        time.sleep(0.1)


def test_rpc_chaos_injection_survived_by_retries(ray_start_regular):
    """testing_rpc_failure_pct makes control-plane requests randomly
    fail; task retries absorb it (reference: RAY_testing_* chaos flags
    exercised against a flaky RPC layer)."""
    ray_tpu.shutdown()
    ray_tpu.init(num_cpus=2, num_tpus=0,
                 _system_config={"testing_rpc_failure_pct": 20})
    host, port = ray_tpu.start_head_server(port=0, host="127.0.0.1")
    p = _spawn_daemon(port, num_cpus=4, resources={"remote": 4})
    try:
        _wait_for_resource("remote", 4)

        @ray_tpu.remote(resources={"remote": 1}, max_retries=10)
        def flaky_path(i):
            return i * 3

        out = ray_tpu.get([flaky_path.remote(i) for i in range(20)],
                          timeout=120)
        assert out == [i * 3 for i in range(20)]
    finally:
        if p.poll() is None:
            p.kill()
        p.wait(timeout=10)
        ray_tpu.shutdown()


def test_daemon_labels_reach_node_table(ray_start_regular):
    """`ray-tpu start --labels` (the cloud providers' provider_node_id
    self-tagging channel) lands in the head's node table."""
    host, port = ray_tpu.start_head_server(port=0, host="127.0.0.1")
    p = subprocess.Popen(
        [sys.executable, "-m", "ray_tpu._private.multinode",
         "--address", f"127.0.0.1:{port}", "--num-cpus", "1",
         "--resources", json.dumps({"lbl": 1}),
         "--labels", json.dumps({"provider_node_id": "node-42",
                                 "zone": "us-x1-a"})],
        stdout=subprocess.DEVNULL, stderr=subprocess.DEVNULL)
    try:
        _wait_for_resource("lbl", 1)
        node = next(n for n in ray_tpu.nodes()
                    if n["Labels"].get("provider_node_id") == "node-42")
        assert node["Labels"]["zone"] == "us-x1-a"
        assert node["Alive"]
    finally:
        p.kill()
        p.wait(timeout=10)
