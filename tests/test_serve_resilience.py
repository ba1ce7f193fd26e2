"""Serve resilience: failover, draining, deadlines, backpressure, chaos.

Model: reference python/ray/serve/tests/test_failure.py +
test_backpressure.py. Counters are read as before/after deltas on the
in-process metrics registry (actors run on the thread backend, so the
router's and controller's increments land in the same registry).
"""

import threading
import time

import pytest

import ray_tpu
from ray_tpu import serve
from ray_tpu._private import builtin_metrics, chaos
from ray_tpu.exceptions import BackPressureError, GetTimeoutError


@pytest.fixture
def serve_session():
    ray_tpu.shutdown()
    ray_tpu.init(num_cpus=8, num_tpus=0)
    yield
    chaos.reset()
    serve.shutdown()
    ray_tpu.shutdown()


@pytest.fixture
def serve_env(monkeypatch):
    """serve_session variant for tests that need RAY_TPU_serve_* env
    overrides baked into the runtime config (set BEFORE init)."""
    started = []

    def start(**env):
        for key, value in env.items():
            monkeypatch.setenv(key, str(value))
        ray_tpu.shutdown()
        ray_tpu.init(num_cpus=8, num_tpus=0)
        started.append(True)

    yield start
    if started:
        chaos.reset()
        serve.shutdown()
        ray_tpu.shutdown()


def _total(counter, outcome=None):
    if outcome is None:
        return sum(counter.series().values())
    return sum(v for k, v in counter.series().items() if outcome in k)


def _replica_names(name):
    from ray_tpu.serve._private.controller import get_or_create_controller
    controller = get_or_create_controller()
    return ray_tpu.get(controller.replica_states.remote(name), timeout=10)


def _wait_for(predicate, timeout=15.0, msg="condition"):
    deadline = time.monotonic() + timeout
    while time.monotonic() < deadline:
        if predicate():
            return
        time.sleep(0.05)
    raise AssertionError(f"timed out waiting for {msg}")


def test_transparent_failover_on_replica_death(serve_session):
    """Killing a replica mid-traffic loses zero requests: the router
    re-dispatches to a live replica and the caller's refs resolve."""
    @serve.deployment(num_replicas=2)
    class Echo:
        def __call__(self, x):
            return x

    handle = serve.run(Echo.bind())
    assert ray_tpu.get(handle.remote("warm"), timeout=30) == "warm"
    before = _total(builtin_metrics.serve_failovers())

    victim = _replica_names("Echo")[0]["name"]
    ray_tpu.kill(ray_tpu.get_actor(victim))
    # Fire into the now-stale membership table: roughly half these picks
    # land on the dead replica and must fail over transparently.
    refs = [handle.remote(i) for i in range(20)]
    assert ray_tpu.get(refs, timeout=60) == list(range(20))
    assert _total(builtin_metrics.serve_failovers()) > before


def test_application_errors_are_not_retried(serve_session):
    """Failover triggers on SYSTEM failures only: an exception raised by
    the deployment surfaces to the caller unchanged, no re-dispatch."""
    @serve.deployment(num_replicas=2)
    class Boom:
        def __call__(self, x):
            raise ValueError(f"boom-{x}")

    handle = serve.run(Boom.bind())
    before = _total(builtin_metrics.serve_failovers())
    with pytest.raises(Exception, match="boom-7"):
        ray_tpu.get(handle.remote(7), timeout=30)
    assert _total(builtin_metrics.serve_failovers()) == before


def test_graceful_scaledown_drains_clean(serve_session):
    """Scale-down retires the victim through DRAINING: in-flight requests
    finish, the drain completes 'clean', nothing is hard-killed."""
    @serve.deployment(num_replicas=2, version="v", name="drainme",
                      max_concurrent_queries=8)
    class Slow:
        def __call__(self, x):
            time.sleep(0.4)
            return x

    handle = serve.run(Slow.bind())
    clean_before = _total(builtin_metrics.serve_drained(), "clean")
    timeout_before = _total(builtin_metrics.serve_drained(), "timeout")
    refs = [handle.remote(i) for i in range(6)]
    time.sleep(0.05)  # let requests land on both replicas
    serve.run(Slow.options(num_replicas=1).bind())
    # Every in-flight request still completes (the victim finishes them).
    assert ray_tpu.get(refs, timeout=60) == list(range(6))
    _wait_for(
        lambda: _total(builtin_metrics.serve_drained(), "clean")
        > clean_before,
        msg="clean drain")
    assert _total(builtin_metrics.serve_drained(), "timeout") \
        == timeout_before
    assert serve.status()["drainme"]["live_replicas"] == 1


def test_rolling_redeploy_under_load(serve_session):
    """Redeploy while traffic flows: replacements start first, the old
    generation drains, and no client-visible request fails."""
    @serve.deployment(num_replicas=2, version="v1", name="roll")
    class V1:
        def __call__(self, _):
            time.sleep(0.02)
            return "v1"

    handle = serve.run(V1.bind())
    assert ray_tpu.get(handle.remote(None), timeout=30) == "v1"
    drained_before = _total(builtin_metrics.serve_drained())

    errors, results, stop = [], [], threading.Event()

    def load():
        while not stop.is_set():
            try:
                results.append(ray_tpu.get(handle.remote(None), timeout=30))
            except Exception as exc:  # noqa: BLE001 - client-visible
                errors.append(exc)

    workers = [threading.Thread(target=load) for _ in range(4)]
    for w in workers:
        w.start()
    try:
        _wait_for(lambda: len(results) >= 8, timeout=60,
                  msg="traffic to flow on v1")

        @serve.deployment(num_replicas=2, version="v2", name="roll")
        class V2:
            def __call__(self, _):
                time.sleep(0.02)
                return "v2"

        serve.run(V2.bind())
        _wait_for(lambda: "v2" in results or errors, timeout=60,
                  msg="traffic to reach v2")
    finally:
        stop.set()
        for w in workers:
            w.join(timeout=30)
    assert errors == []
    assert "v2" in results  # traffic reached the new generation
    # Both v1 replicas were retired through DRAINING (counted outcomes).
    _wait_for(
        lambda: _total(builtin_metrics.serve_drained())
        >= drained_before + 2,
        msg="both v1 replicas drained")


def test_handle_timeout_s_deadline(serve_session):
    """handle.options(timeout_s=...) settles the ref with GetTimeoutError
    at the deadline and drains the router's load-table charge."""
    @serve.deployment(num_replicas=1, max_concurrent_queries=4)
    class Sleepy:
        def __call__(self, s):
            time.sleep(s)
            return s

    handle = serve.run(Sleepy.bind())
    assert ray_tpu.get(handle.remote(0), timeout=30) == 0
    ref = handle.options(timeout_s=0.3).remote(2.0)
    t0 = time.monotonic()
    with pytest.raises(GetTimeoutError):
        ray_tpu.get(ref, timeout=30)
    assert time.monotonic() - t0 < 2.0  # deadline, not the full sleep
    router = handle._router
    _wait_for(
        lambda: not router._requests
        and sum(router._ongoing.values()) == 0,
        timeout=5, msg="load table drained after expiry")
    # The deployment still serves fresh requests on the same handle.
    assert ray_tpu.get(handle.remote(0), timeout=30) == 0


def test_backpressure_sheds_with_backpressure_error(serve_session):
    """Beyond (replicas x max_concurrent_queries) + max_queued_requests
    outstanding, assign fast-fails with BackPressureError."""
    @serve.deployment(num_replicas=1, max_concurrent_queries=1,
                      max_queued_requests=2)
    class Busy:
        def __call__(self, x):
            time.sleep(0.4)
            return x

    handle = serve.run(Busy.bind())
    assert ray_tpu.get(handle.remote(-1), timeout=30) == -1
    shed_before = _total(builtin_metrics.serve_shed())
    refs, shed = [], 0
    for i in range(10):
        try:
            refs.append(handle.remote(i))
        except BackPressureError as exc:
            shed += 1
            assert "Busy" in str(exc)
    assert shed >= 1
    assert len(refs) >= 3  # capacity (1) + queue (2) admitted
    assert _total(builtin_metrics.serve_shed()) == shed_before + shed
    # Admitted requests all complete.
    assert ray_tpu.get(refs, timeout=60) == list(range(len(refs)))


def test_handle_options_validated_and_shared_router(serve_session):
    @serve.deployment
    def echo(x):
        return x

    handle = serve.run(echo.bind())
    with pytest.raises(TypeError, match="num_retries"):
        handle.options(num_retries=5)
    configured = handle.options(timeout_s=9.0, max_retries=1)
    assert configured._router is handle._router  # no new control traffic
    assert configured._timeout_s == 9.0
    chained = configured.options(max_retries=2)
    assert chained._timeout_s == 9.0  # prior options preserved
    assert chained._max_retries == 2
    assert ray_tpu.get(configured.remote("ok"), timeout=30) == "ok"


def test_startup_timeout_and_budget_bound_reconcile(serve_env):
    """A replica that never becomes ready fails the deploy within
    serve_startup_timeout_s x (1 + serve_start_budget) with a clear
    error, instead of wedging serve.run forever."""
    serve_env(RAY_TPU_serve_startup_timeout_s="1",
              RAY_TPU_serve_start_budget="0")

    @serve.deployment(num_replicas=1)
    class Hang:
        def __init__(self):
            time.sleep(60)

    t0 = time.monotonic()
    with pytest.raises(Exception, match="failed to start"):
        serve.run(Hang.bind())
    assert time.monotonic() - t0 < 30


def test_failing_health_check_replaces_replica(serve_env):
    """serve_health_failure_threshold consecutive check_health failures
    drain the replica and a replacement takes over."""
    serve_env(RAY_TPU_serve_health_check_period_s="0.1")

    @serve.deployment(num_replicas=1, name="sickly")
    class Sickly:
        def __init__(self):
            self.sick = False

        def make_sick(self, _):
            self.sick = True
            return True

        def check_health(self):
            if self.sick:
                raise RuntimeError("unhealthy")

        def __call__(self, x):
            return x

    handle = serve.run(Sickly.bind())
    assert ray_tpu.get(handle.remote(1), timeout=30) == 1
    original = {r["name"] for r in _replica_names("sickly")}
    failures_before = _total(builtin_metrics.serve_health_check_failures())
    ray_tpu.get(handle.make_sick.remote(None), timeout=30)

    def replaced():
        states = _replica_names("sickly")
        running = {r["name"] for r in states if r["state"] == "RUNNING"}
        return bool(running) and not (running & original)

    _wait_for(replaced, timeout=20, msg="replica replacement")
    assert _total(builtin_metrics.serve_health_check_failures()) \
        >= failures_before + 3
    # The fresh replica serves (and reports healthy: its flag is reset).
    assert ray_tpu.get(handle.remote(2), timeout=30) == 2


def test_chaos_replica_kill_fails_over(serve_session):
    """The serve.replica_kill chaos site makes one replica play dead
    mid-run; the router fails its requests over with zero losses."""
    @serve.deployment(num_replicas=2, name="chaosed")
    class Echo:
        def __call__(self, x):
            return x

    handle = serve.run(Echo.bind())
    assert ray_tpu.get(handle.remote("warm"), timeout=30) == "warm"
    before = _total(builtin_metrics.serve_failovers())
    chaos.configure("kill:site=serve.replica_kill:after=3:times=1")
    try:
        for i in range(30):
            assert ray_tpu.get(handle.remote(i), timeout=30) == i
        stats = chaos.stats()
        assert stats[0]["fired"] == 1, stats
    finally:
        chaos.reset()
    assert _total(builtin_metrics.serve_failovers()) > before


def test_availability_under_replica_churn(serve_session):
    """ISSUE 7 acceptance: sustained load on 3 replicas while a killer
    thread repeatedly kills one — zero client-visible failures, at
    least one transparent failover, bounded tail latency."""
    @serve.deployment(num_replicas=3, name="churn",
                      max_concurrent_queries=8)
    class Echo:
        def __call__(self, x):
            time.sleep(0.005)
            return x

    handle = serve.run(Echo.bind())
    assert ray_tpu.get(handle.remote(-1), timeout=30) == -1
    failovers_before = _total(builtin_metrics.serve_failovers())

    stop = threading.Event()
    kills = []

    def killer():
        while not stop.wait(0.4):
            try:
                states = _replica_names("churn")
                running = [s for s in states if s["state"] == "RUNNING"]
                if len(running) <= 1:
                    continue
                ray_tpu.kill(ray_tpu.get_actor(running[0]["name"]))
                kills.append(running[0]["name"])
            except Exception:  # noqa: BLE001 - victim already gone
                pass

    errors, latencies = [], []

    def load(seed):
        for i in range(40):
            t0 = time.monotonic()
            try:
                out = ray_tpu.get(handle.remote((seed, i)), timeout=30)
                assert tuple(out) == (seed, i)
                latencies.append(time.monotonic() - t0)
            except Exception as exc:  # noqa: BLE001 - client-visible
                errors.append(exc)
            time.sleep(0.01)

    kt = threading.Thread(target=killer, daemon=True)
    kt.start()
    workers = [threading.Thread(target=load, args=(s,)) for s in range(4)]
    for w in workers:
        w.start()
    for w in workers:
        w.join(timeout=120)
    stop.set()
    kt.join(timeout=5)

    assert errors == [], errors
    assert kills, "the killer never found a victim"
    assert _total(builtin_metrics.serve_failovers()) > failovers_before
    latencies.sort()
    p95 = latencies[int(len(latencies) * 0.95)]
    assert p95 < 10.0, f"p95 {p95:.2f}s unbounded under churn"


def test_proxy_503_with_retry_after_on_overload(serve_session):
    import urllib.error
    import urllib.request

    @serve.deployment(num_replicas=1, max_concurrent_queries=1,
                      max_queued_requests=0, route_prefix="/slow")
    def slow(request):
        time.sleep(1.0)
        return "done"

    serve.run(slow.bind(), port=0)
    port = serve.http_port()

    first_result = []

    def occupy():
        with urllib.request.urlopen(
                f"http://127.0.0.1:{port}/slow", timeout=30) as resp:
            first_result.append(resp.status)

    t = threading.Thread(target=occupy)
    t.start()
    time.sleep(0.3)  # first request is now in flight on the one replica
    with pytest.raises(urllib.error.HTTPError) as e:
        urllib.request.urlopen(f"http://127.0.0.1:{port}/slow", timeout=30)
    assert e.value.code == 503
    assert e.value.headers["Retry-After"] == "1"
    t.join(timeout=30)
    assert first_result == [200]  # the in-flight request was NOT shed


def test_proxy_route_refresh_after_delete(serve_session):
    import urllib.error
    import urllib.request

    @serve.deployment(route_prefix="/ephemeral")
    def ephemeral(request):
        return "here"

    serve.run(ephemeral.bind(), port=0)
    port = serve.http_port()
    with urllib.request.urlopen(
            f"http://127.0.0.1:{port}/ephemeral", timeout=10) as resp:
        assert resp.read() == b"here"
    serve.delete("ephemeral")

    def gone():
        try:
            urllib.request.urlopen(
                f"http://127.0.0.1:{port}/ephemeral", timeout=10)
            return False
        except urllib.error.HTTPError as e:
            return e.code == 404

    _wait_for(gone, timeout=10, msg="route removal to reach the proxy")


def test_proxy_keeps_serving_while_controller_down(serve_session):
    """The controller is OFF the request path: killing it must not take
    down HTTP traffic to already-routed deployments."""
    import urllib.request

    from ray_tpu.serve._private.controller import CONTROLLER_NAME

    @serve.deployment(route_prefix="/steady", num_replicas=2)
    def steady(request):
        return "ok"

    serve.run(steady.bind(), port=0)
    port = serve.http_port()
    with urllib.request.urlopen(
            f"http://127.0.0.1:{port}/steady", timeout=10) as resp:
        assert resp.read() == b"ok"

    ray_tpu.kill(ray_tpu.get_actor(CONTROLLER_NAME))
    time.sleep(0.3)
    for _ in range(5):
        with urllib.request.urlopen(
                f"http://127.0.0.1:{port}/steady", timeout=10) as resp:
            assert resp.read() == b"ok"
