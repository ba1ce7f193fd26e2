"""Core API tests: tasks, objects, dependencies, errors, retries.

Modeled on the reference's python/ray/tests/test_basic.py coverage.
"""

import time

import pytest

import ray_tpu as ray
from ray_tpu.exceptions import GetTimeoutError, TaskError


def test_put_get(ray_start_regular):
    ref = ray.put(42)
    assert ray.get(ref) == 42
    ref2 = ray.put({"a": [1, 2, 3]})
    assert ray.get(ref2) == {"a": [1, 2, 3]}


def test_put_objectref_rejected(ray_start_regular):
    with pytest.raises(TypeError):
        ray.put(ray.put(1))


def test_simple_task(ray_start_regular):
    @ray.remote
    def f(x):
        return x * 2

    assert ray.get(f.remote(21)) == 42


def test_task_many(ray_start_regular):
    @ray.remote
    def f(x):
        return x + 1

    refs = [f.remote(i) for i in range(100)]
    assert ray.get(refs) == list(range(1, 101))


def test_task_args_kwargs(ray_start_regular):
    @ray.remote
    def f(a, b=10, *, c=100):
        return a + b + c

    assert ray.get(f.remote(1)) == 111
    assert ray.get(f.remote(1, 2, c=3)) == 6


def test_object_ref_dependency(ray_start_regular):
    @ray.remote
    def f(x):
        return x + 1

    r = f.remote(0)
    for _ in range(10):
        r = f.remote(r)
    assert ray.get(r) == 11


def test_dependency_in_kwargs(ray_start_regular):
    @ray.remote
    def f(*, x):
        return x * 3

    assert ray.get(f.remote(x=ray.put(5))) == 15


def test_nested_refs_not_resolved(ray_start_regular):
    """A ref inside a container arrives as a ref (reference semantics)."""
    @ray.remote
    def f(lst):
        return isinstance(lst[0], ray.ObjectRef)

    assert ray.get(f.remote([ray.put(1)]))


def test_multiple_returns(ray_start_regular):
    @ray.remote(num_returns=3)
    def f():
        return 1, 2, 3

    a, b, c = f.remote()
    assert ray.get([a, b, c]) == [1, 2, 3]


def test_num_returns_zero(ray_start_regular):
    @ray.remote(num_returns=0)
    def f():
        return None

    assert f.remote() is None


def test_wrong_num_returns_errors(ray_start_regular):
    @ray.remote(num_returns=2)
    def f():
        return 1

    a, b = f.remote()
    with pytest.raises(TaskError):
        ray.get(a)


def test_task_error_propagates(ray_start_regular):
    @ray.remote(max_retries=0)
    def f():
        raise ValueError("boom")

    with pytest.raises(TaskError) as exc_info:
        ray.get(f.remote())
    assert isinstance(exc_info.value.cause, ValueError)
    assert "boom" in str(exc_info.value)


def test_dependency_error_propagates(ray_start_regular):
    @ray.remote(max_retries=0)
    def bad():
        raise RuntimeError("upstream")

    @ray.remote
    def good(x):
        return x

    with pytest.raises(TaskError):
        ray.get(good.remote(bad.remote()))


def test_retry_exceptions(ray_start_regular):
    attempts = {"n": 0}

    @ray.remote(max_retries=3, retry_exceptions=True)
    def flaky(marker):
        attempts["n"] += 1
        if attempts["n"] < 3:
            raise RuntimeError("transient")
        return "ok"

    # Thread-backend: closure state is shared, so the counter observes retries.
    assert ray.get(flaky.remote(1)) == "ok"
    assert attempts["n"] == 3


def test_retry_exception_allowlist(ray_start_regular):
    @ray.remote(max_retries=5, retry_exceptions=[KeyError])
    def f():
        raise ValueError("not retriable")

    with pytest.raises(TaskError):
        ray.get(f.remote())


def test_get_timeout(ray_start_regular):
    @ray.remote
    def slow():
        time.sleep(10)

    with pytest.raises(GetTimeoutError):
        ray.get(slow.remote(), timeout=0.1)


def test_wait_basic(ray_start_regular):
    @ray.remote
    def f(t):
        time.sleep(t)
        return t

    fast = f.remote(0.01)
    slow = f.remote(5)
    ready, not_ready = ray.wait([fast, slow], num_returns=1, timeout=3)
    assert ready == [fast]
    assert not_ready == [slow]


def test_wait_timeout_returns_partial(ray_start_regular):
    @ray.remote
    def slow():
        time.sleep(10)

    ready, not_ready = ray.wait([slow.remote()], num_returns=1, timeout=0.1)
    assert ready == []
    assert len(not_ready) == 1


def test_wait_validates(ray_start_regular):
    r = ray.put(1)
    with pytest.raises(ValueError):
        ray.wait([r, r])
    with pytest.raises(ValueError):
        ray.wait([r], num_returns=2)


def test_nested_tasks(ray_start_regular):
    @ray.remote
    def inner(x):
        return x + 1

    @ray.remote
    def outer(x):
        return ray.get(inner.remote(x)) + 10

    assert ray.get(outer.remote(0)) == 11


def test_deeply_nested_tasks_no_deadlock(ray_start_regular):
    @ray.remote(num_cpus=1)
    def rec(n):
        if n == 0:
            return 0
        return ray.get(rec.remote(n - 1)) + 1

    # Deeper than num_cpus: requires blocked-get resource release.
    assert ray.get(rec.remote(12)) == 12


def test_options_override(ray_start_regular):
    @ray.remote
    def f():
        return ray.get_runtime_context().get_assigned_resources()

    res = ray.get(f.options(num_cpus=2).remote())
    assert res.get("CPU") == 2.0


def test_infeasible_task_stays_pending(ray_start_regular):
    """Infeasible tasks queue as autoscaler demand instead of failing
    (reference behavior: a warning + pending until the cluster grows)."""
    from ray_tpu.exceptions import GetTimeoutError

    @ray.remote(num_cpus=10_000)
    def f():
        return 1

    ref = f.remote()
    with pytest.raises(GetTimeoutError):
        ray.get(ref, timeout=0.5)
    rt = ray._private.worker.global_worker.runtime
    assert {"CPU": 10_000.0} in rt.pending_resource_demand()


def test_invalid_option_rejected(ray_start_regular):
    with pytest.raises(ValueError):
        @ray.remote(bogus_option=1)
        def f():
            pass


def test_remote_function_direct_call_rejected(ray_start_regular):
    @ray.remote
    def f():
        return 1

    with pytest.raises(TypeError):
        f()


def test_free(ray_start_regular):
    ref = ray.put("data")
    ray.free([ref])
    with pytest.raises(ray.exceptions.ObjectFreedError):
        ray.get(ref)


def test_cancel_pending(ray_start_regular):
    @ray.remote(num_cpus=8)
    def hog():
        time.sleep(30)

    @ray.remote
    def victim():
        return 1

    hog_ref = hog.remote()
    time.sleep(0.1)
    victim_ref = victim.remote()  # queued behind the hog
    ray.cancel(victim_ref)
    with pytest.raises(ray.exceptions.TaskCancelledError):
        ray.get(victim_ref, timeout=5)
    ray.cancel(hog_ref)


def test_cluster_resources(ray_start_regular):
    res = ray.cluster_resources()
    assert res["CPU"] == 8.0


def test_tpu_resource_accounting():
    ray.shutdown()
    ray.init(num_cpus=4, num_tpus=4)

    @ray.remote(num_tpus=2)
    def use_tpu():
        time.sleep(0.2)
        return ray.get_tpu_ids()

    # Two concurrent 2-chip tasks must get disjoint chip sets.
    a, b = ray.get([use_tpu.remote(), use_tpu.remote()])
    assert len(a) == 2 and len(b) == 2
    assert not (set(a) & set(b)), f"chip collision: {a} vs {b}"
    assert set(a) | set(b) <= {0, 1, 2, 3}
    assert ray.cluster_resources()["TPU"] == 4.0
    ray.shutdown()


def test_reinit_guard(ray_start_regular):
    with pytest.raises(RuntimeError):
        ray.init(num_cpus=1)
    ray.init(ignore_reinit_error=True)


def test_object_ref_pickling_roundtrip(ray_start_regular):
    import pickle
    ref = ray.put(123)
    ref2 = pickle.loads(pickle.dumps(ref))
    assert ref2 == ref
    assert ray.get(ref2) == 123


def test_large_array_roundtrip(ray_start_regular):
    import numpy as np
    arr = np.arange(1_000_000, dtype=np.float32)
    ref = ray.put(arr)
    out = ray.get(ref)
    assert out is arr or (out == arr).all()


def test_deep_queue_no_thread_explosion(ray_start_regular):
    """BASELINE envelope: a deep backlog of queued (infeasible-for-now)
    tasks costs memory only — no thread per queued task, no dispatch
    stall (reference: 1M queued tasks on one node; scaled to 20k for
    CI, where a thread a task would be 20k threads as surely as 100k or
    1M; measured 1M locally: 3 threads, 2.07GB RSS, 31k submits/s)."""
    import threading

    @ray.remote(resources={"not_yet_available": 1}, num_cpus=0)
    def later(i):
        return i

    before = threading.active_count()
    refs = [later.remote(i) for i in range(20_000)]
    assert threading.active_count() <= before + 2, (
        f"{threading.active_count() - before} threads grew out of "
        "20k queued tasks")
    # The queue is live, not wedged: adding the resource drains it.
    runtime = ray._private.worker.global_worker.runtime
    node_id = runtime.add_node({"not_yet_available": 4, "CPU": 4})
    out = ray.get(refs[:100], timeout=120)
    assert out == list(range(100))
    for r in refs[100:]:
        ray.cancel(r, force=True)
    runtime.remove_node(node_id)
