"""Cluster-wide continuous profiling plane: folded-stack merge
semantics, ProfilerAgent sampling + drain/refund, the head-side
ProfileStore (windowed buckets, membership-driven eviction, bounded
memory under stack churn, diffs), the loop-lag flight recorder, the
profile_batch wire schema, the dashboard endpoints (flame / incidents
/ 400s on bad knobs), `ray-tpu profile --report`, and a 2-daemon
acceptance run asserting /api/profile/flame merges stacks from head,
daemon, AND worker origins."""

import json
import subprocess
import sys
import time
import urllib.error
import urllib.request

import pytest

import ray_tpu
from ray_tpu.util import metrics as um
from ray_tpu._private.profile_store import ProfileStore
from ray_tpu._private.profiling import ProfilerAgent, merge_folded


@pytest.fixture(autouse=True)
def _fresh_registry():
    um.clear_registry()
    yield
    um.clear_registry()


def _spawn_daemon(port, *, num_cpus=2, resources=None, env=None):
    import os
    cmd = [sys.executable, "-m", "ray_tpu._private.multinode",
           "--address", f"127.0.0.1:{port}",
           "--num-cpus", str(num_cpus)]
    if resources:
        cmd += ["--resources", json.dumps(resources)]
    full_env = dict(os.environ)
    if env:
        full_env.update(env)
    return subprocess.Popen(cmd, stdout=subprocess.DEVNULL,
                            stderr=subprocess.DEVNULL, env=full_env)


def _wait_for_resource(name, amount, timeout=20):
    deadline = time.monotonic() + timeout
    while time.monotonic() < deadline:
        if ray_tpu.cluster_resources().get(name, 0) >= amount:
            return
        time.sleep(0.1)
    raise TimeoutError(
        f"resource {name}>={amount} never appeared: "
        f"{ray_tpu.cluster_resources()}")


def _get(port, path):
    with urllib.request.urlopen(
            f"http://127.0.0.1:{port}{path}", timeout=10) as resp:
        return resp.status, resp.read()


# ---------------------------------------------------------------------------
# Folded-stack merge semantics
# ---------------------------------------------------------------------------


def test_merge_folded_associative_and_additive():
    """(a+b)+c == a+(b+c) and counts add — the property the whole plane
    leans on: per-thread accumulate, refund-after-drop, bucket merge,
    and cross-origin flame render all reuse the same fold."""
    a = {"t [running];f (m.py:1)": 2}
    b = {"t [running];f (m.py:1)": 3, "t [waiting];g (m.py:9)": 1}
    c = {"t [waiting];g (m.py:9)": 4}
    left = merge_folded(merge_folded(dict(a), b), c)
    right = merge_folded(dict(a), merge_folded(dict(b), c))
    assert left == right == {"t [running];f (m.py:1)": 5,
                             "t [waiting];g (m.py:9)": 5}
    # In-place on dst, src untouched.
    dst = dict(a)
    out = merge_folded(dst, b)
    assert out is dst
    assert b["t [running];f (m.py:1)"] == 3


def test_profiler_agent_samples_drain_refund():
    """The sampler accumulates annotated stacks; drain empties the
    window; refund puts a failed publish back so no samples are lost."""
    import threading
    agent = ProfilerAgent("test", hz=200)
    try:
        park = threading.Event()  # Condition.wait leaf -> [waiting]
        deadline = time.monotonic() + 5
        while time.monotonic() < deadline:
            with agent._lock:
                if agent._samples >= 5:
                    break
            park.wait(0.05)
    finally:
        agent.stop()
    window = agent.drain()
    assert window is not None
    assert window["samples"] >= 5
    assert window["duration_s"] > 0
    # Every key carries the thread's running/waiting annotation.
    for key in window["stacks"]:
        head = key.split(";", 1)[0]
        assert head.endswith("[running]") or head.endswith("[waiting]"), key
    # The main thread is parked in Event.wait during sampling: the
    # waiting annotation must actually fire, not just parse.
    assert any("[waiting]" in k.split(";", 1)[0]
               for k in window["stacks"]), list(window["stacks"])[:4]
    assert agent.drain() is None  # drained clean
    agent.refund(window["stacks"])
    again = agent.drain()
    assert again is not None and again["stacks"] == window["stacks"]


# ---------------------------------------------------------------------------
# The sampler's tick as the process's heartbeat: the /proc parsers, the
# cause rule, a late tick
# ---------------------------------------------------------------------------

CPU_STAT_V2 = """usage_usec 136329162685
user_usec 127849837835
system_usec 8479324849
nr_periods 1180
nr_throttled 37
throttled_usec 2450000
nr_bursts 0
burst_usec 0
"""
CPU_STAT_V1 = """nr_periods 204
nr_throttled 3
throttled_time 310000000
nr_bursts 0
burst_time 0
"""
PROC_STAT = """cpu  12784965 0 805147 69700596 66932 0 42782 123200 0 0
cpu0 1598120 0 100643 8712574 8366 0 5347 15400 0 0
intr 1 2 3
"""
PRESSURE = """some avg10=0.98 avg60=0.91 avg300=1.27 total=7897842452
full avg10=0.00 avg60=0.00 avg300=0.00 total=12
"""


@pytest.mark.parametrize("parser,text,want", [
    ("schedstat_seconds", "1176354000 55826000 2\n", (1.176354, 0.055826)),
    ("schedstat_seconds", "", None),
    ("schedstat_seconds", None, None),  # a missing file
    ("throttled_seconds", CPU_STAT_V2, 2.45),
    ("throttled_seconds", CPU_STAT_V1, 0.31),
    ("throttled_seconds", "usage_usec 5\n", None),  # v2 with no quota
    ("throttled_seconds", None, None),
    ("pressure_seconds", PRESSURE, 7897.842452),
    ("pressure_seconds", "full avg10=0.00 total=12\n", None),
    ("pressure_seconds", None, None),
    ("steal_seconds", "intr 1 2 3\n", None),
    ("steal_seconds", None, None),
])
def test_the_proc_parsers_on_recorded_texts(parser, text, want):
    from ray_tpu._private import profiling
    got = getattr(profiling, parser)(text)
    assert got == (want if want is None else pytest.approx(want))


def test_steal_is_seconds_a_cpu(monkeypatch):
    from ray_tpu._private import profiling
    monkeypatch.setattr(profiling, "_CPUS", 8)
    monkeypatch.setattr(profiling, "_CLK_TCK", 100)
    assert profiling.steal_seconds(PROC_STAT) == pytest.approx(154.0)


# /proc/self/cgroup, the cpu.stat files that exist, the one that is read.
@pytest.mark.parametrize("cgroup,files,want", [
    ("0::/jobs/a\n", {"jobs/a/cpu.stat": CPU_STAT_V2}, "jobs/a/cpu.stat"),
    ("0::/\n", {"cpu.stat": CPU_STAT_V2}, "cpu.stat"),
    ("4:memory:/m\n2:cpu,cpuacct:/jobs\n0::/\n",
     {"cpu,cpuacct/jobs/cpu.stat": CPU_STAT_V1,
      "cpu.stat": "usage_usec 5\n"}, "cpu,cpuacct/jobs/cpu.stat"),
    # v1 mounted as ``cpu`` beside ``cpuacct``; the unified tree has no
    # throttling to read.
    ("2:cpuacct:/\n1:cpu:/\n0::/\n",
     {"cpu/cpu.stat": CPU_STAT_V1, "unified/cpu.stat": "usage_usec 5\n"},
     "cpu/cpu.stat"),
    ("0::/gone\n", {}, None),
    (None, {}, None),
])
def test_the_cgroup_s_cpu_stat_is_found(tmp_path, cgroup, files, want):
    import os

    from ray_tpu._private import profiling
    for name, text in files.items():
        path = tmp_path / name
        path.parent.mkdir(parents=True, exist_ok=True)
        path.write_text(text)
    got = profiling.cgroup_cpu_stat(cgroup, root=str(tmp_path))
    assert got == (want and os.path.join(str(tmp_path), want))


def test_host_counters_read_what_this_machine_has():
    from ray_tpu._private import profiling
    host = profiling.HostCounters()
    try:
        first = host.read()
        assert {"gc", "cpu"} <= set(first)
        assert all(v >= 0 for v in first.values())
        host.on_gc("start", {})
        time.sleep(0.01)
        host.on_gc("stop", {})
        second = host.read()
        assert second["gc"] - first["gc"] >= 0.01
        if "runqueue" in first:
            import threading
            assert threading.get_native_id() in host.thread_cpu()
        assert all(second[k] >= first[k] for k in first)
    finally:
        host.close()
    # Its files gone (as on a machine without them), it reads the rest, and
    # no table of threads where the kernel keeps no ``schedstat``.
    assert set(host.read()) == {"gc", "cpu"}
    assert host.thread_cpu() is None


QUIET = {"runqueue": 0.001, "throttled": 0.0, "steal": 0.002, "gc": 0.0,
         "pressure_cpu": 0.003, "pressure_io": 0.0, "pressure_memory": 0.0}


# A tick 0.4 s late, 0.5 s after the counters were last read, and what each
# moved by meanwhile: the thread's own runnable time is all the lateness's,
# of the others the lateness has its four fifths.
@pytest.mark.parametrize("deltas,want", [
    (dict(QUIET, runqueue=0.39, pressure_cpu=0.45, cpu=0.01), "runqueue"),
    (dict(QUIET, throttled=0.35, runqueue=0.2), "throttled"),
    (dict(QUIET, steal=0.26), "steal"),
    (dict(QUIET, pressure_cpu=0.3, runqueue=0.1), "pressure_cpu"),
    (dict(QUIET, pressure_io=0.4), "pressure_io"),
    (dict(QUIET, pressure_memory=0.25), "pressure_memory"),
    (dict(QUIET, gc=0.38, cpu=0.4), "gc"),
    # Not runnable, nothing moved, and the process ran: another thread
    # held the interpreter's lock.
    (dict(QUIET, cpu=0.41), "gil"),
    # The same beside this machine's other tenants' pressure, which runs
    # through the whole interval: under a quarter of the lateness is quiet.
    (dict(QUIET, pressure_cpu=0.12, cpu=0.41), "gil"),
    # The same with the process standing still: frozen from outside.
    (dict(QUIET, cpu=0.0), "unknown"),
    # Something moved, by less than half the lateness.
    (dict(QUIET, runqueue=0.15, cpu=0.4), "unknown"),
    (dict(QUIET, steal=0.24, cpu=0.4), "unknown"),  # 0.192 of it late
    # A machine that counts no runnable time cannot say "not runnable".
    ({"gc": 0.0, "cpu": 0.4}, "unknown"),
    ({}, "unknown"),
])
def test_the_cause_rule_on_planted_deltas(deltas, want):
    from ray_tpu._private import profiling
    assert profiling.late_cause(0.4, deltas, 0.5) == want


def test_a_short_lateness_takes_its_share_of_the_interval():
    """A tick 34 ms late after a sleep of 100: the machine's pressure of
    30 ms over the interval is a quarter the lateness's, the thread's own
    34 ms of runnable time all of it."""
    from ray_tpu._private import profiling
    quiet = dict(QUIET, runqueue=0.0, steal=0.0, pressure_cpu=0.03)
    assert profiling.late_cause(0.034, dict(quiet, cpu=0.094),
                                0.134) == "gil"
    assert profiling.late_cause(0.034, dict(quiet, cpu=0.094)) == \
        "pressure_cpu"  # with no interval given, all of it
    assert profiling.late_cause(0.034, dict(quiet, runqueue=0.034,
                                            cpu=0.001), 0.134) == "runqueue"


class _Clock:
    """``time`` for ``profiling``: the test's own clock, which a wait
    moves."""

    def __init__(self):
        self.now = 100.0

    def perf_counter(self):
        return self.now

    monotonic = process_time = perf_counter

    def time(self):
        return 1e9 + self.now


@pytest.fixture
def late_loop(monkeypatch):
    """``ProfilerAgent._loop`` run on this thread over a clock whose second
    wait oversleeps by 0.35 s, with the runqueue counter moving by 0.3 s
    meanwhile; returns what it left."""
    from ray_tpu._private import builtin_metrics, profiling
    from ray_tpu.util import tracing
    clock, reads, walks = _Clock(), [], []

    class Host:
        on_gc = staticmethod(lambda phase, info: None)
        close = staticmethod(lambda: None)
        thread_cpu = staticmethod(
            lambda: {1: clock.now / 100, 2: 2 * clock.now})

        def read(self):
            reads.append(clock.now)
            late = sum(1 for t in reads if t > 100.5)
            return {"runqueue": 0.3 if late else 0.0, "gc": 0.0,
                    "steal": 0.01 * len(reads), "cpu": clock.now / 100}

    agent = ProfilerAgent("test", hz=10, start=False)
    waits = []

    def wait(timeout):
        waits.append(timeout)
        clock.now += timeout + (0.35 if len(waits) == 2 else 0.0)
        return len(waits) > 4

    monkeypatch.setattr(profiling, "time", clock)
    monkeypatch.setattr(profiling, "HostCounters", Host)
    monkeypatch.setattr(profiling, "_thread_name", "thread-{}".format)
    monkeypatch.setattr(agent._stop, "wait", wait)
    monkeypatch.setattr(agent, "_sample_once",
                        lambda me: walks.append(clock.now) or 3)
    tracing.clear_spans()
    tracing.set_sample_rate(None)
    tracing.enable_tracing()
    try:
        agent._loop()
    finally:
        tracing.disable_tracing()
    ticks = [s for s in tracing.get_spans() if s.name == "host::tick"]
    tracing.clear_spans()
    builtin_metrics.flush_fast_counters()
    return {"agent": agent, "ticks": ticks, "walks": walks}


def test_a_late_tick_is_one_span_with_its_cause(late_loop):
    ticks = late_loop["ticks"]
    # The grid's ticks, the three that the oversleep passed skipped.
    assert [round(s.perf_start, 6) for s in ticks] == [
        100.0, 100.1, 100.2, 100.6, 100.7]
    assert late_loop["walks"] == pytest.approx(
        [100.0, 100.1, 100.55, 100.6, 100.7])
    assert [round(s.duration, 6) for s in ticks] == [0, 0, 0.35, 0, 0]
    assert len({s.trace_id for s in ticks}) == 1  # one trace an agent
    late = ticks[2].attributes
    assert late == {
        "cause": "runqueue", "runnable_s": 0.3, "gc_s": 0.0,
        "steal_s": 0.01, "process_cpu_s": pytest.approx(0.0045),
        "busiest_thread": "thread-2",
        "busiest_thread_cpu_s": pytest.approx(0.9)}
    assert all(s.attributes == {} for s in ticks if s is not ticks[2])


def test_a_late_tick_feeds_the_counter_and_the_sampler_s_lag(late_loop):
    series = {e["name"]: e["series"] for e in um.snapshot()}
    assert series["ray_tpu_process_late_seconds_total"] == {
        ("runqueue",): pytest.approx(0.35)}
    assert series["ray_tpu_loop_lag_seconds"] == {
        ("sampler.test",): pytest.approx(0.35)}
    # Kept for the step's call site: what fell inside an interval.
    agent = late_loop["agent"]
    assert agent.late_between(100.0, 101.0) == (
        pytest.approx(0.35), "runqueue")
    assert agent.late_between(100.3, 100.4) == (
        pytest.approx(0.1), "runqueue")
    assert agent.late_between(100.6, 101.0) == (0.0, "none")


def test_off_a_tick_leaves_no_span_and_reads_no_thread_table(monkeypatch):
    from ray_tpu._private import profiling
    from ray_tpu.util import tracing
    agent = ProfilerAgent("test", hz=10, start=False)
    host = profiling.HostCounters()
    monkeypatch.setattr(host, "thread_cpu",
                        lambda: pytest.fail("read with nothing recording"))
    try:
        now = time.perf_counter()
        before = profiling._Reading(host.read(), None, now - 0.3)
        before = agent._tick(now - 0.2, now, host, before)
    finally:
        host.close()
    assert before.threads is None and before.at == now
    assert [s for s in tracing.get_spans() if s.name == "host::tick"] == []
    [(woke, late, cause)] = agent._late
    assert woke == now and late == pytest.approx(0.2)


def test_a_running_agent_ticks_on_its_grid():
    """The real loop on its own thread: the gc callback comes and goes
    with it, and its lag reaches the gauge."""
    import gc

    from ray_tpu._private import builtin_metrics
    callbacks = len(gc.callbacks)
    agent = ProfilerAgent("grid", hz=50)
    try:
        deadline = time.monotonic() + 5
        while time.monotonic() < deadline and agent._samples < 5:
            time.sleep(0.02)
        assert len(gc.callbacks) == callbacks + 1
    finally:
        agent.stop()
    assert len(gc.callbacks) == callbacks
    builtin_metrics.flush_fast_counters()
    [lag] = [e["series"] for e in um.snapshot()
             if e["name"] == "ray_tpu_loop_lag_seconds"]
    assert 0 <= lag[("sampler.grid",)] < 5


def test_disabled_agent_no_thread():
    agent = ProfilerAgent("test", hz=0)
    assert not agent.enabled
    assert agent._thread is None
    assert agent.drain() is None


# ---------------------------------------------------------------------------
# ProfileStore: flame, eviction, bounds, diff
# ---------------------------------------------------------------------------


def test_flame_merges_origins_with_prefix():
    store = ProfileStore(window_s=300, max_origins=8, max_stacks=100,
                         staleness=30)
    store.ingest("aa" * 8, 10, "daemon",
                 {"t [running];work (d.py:1)": 7})
    store.ingest("", 1, "driver", {"t [running];drive (h.py:2)": 3})
    flame = store.flame()
    assert f"daemon@{'aa' * 4}/10;t [running];work (d.py:1) 7" in flame
    assert "driver@head/1;t [running];drive (h.py:2) 3" in flame
    # speedscope document shape
    doc = store.flame(fmt="speedscope")
    assert doc["profiles"][0]["samples"]
    assert len(doc["shared"]["frames"]) >= 4
    # component filter
    only = store.flame(component="driver")
    assert "daemon@" not in only and "driver@" in only
    with pytest.raises(ValueError):
        store.flame(fmt="nope")


def test_dead_node_windows_evicted_on_membership_push():
    """A membership death push starts the staleness clock for the
    node's profile origins; they are gone after the window (wired via
    ClusterMetrics.mark_node_dead, same path as the time-series
    store)."""
    from ray_tpu._private.membership import MembershipTable
    from ray_tpu._private.metrics_agent import ClusterMetrics

    cm = ClusterMetrics(staleness=0.2)
    table = MembershipTable()
    table.mint_epoch("aa" * 8)

    def on_event(ev):  # the runtime's _membership_event equivalent
        if ev.get("event") == "dead":
            cm.mark_node_dead(ev["node_id"])

    table.subscribe(on_event)
    cm.update_profile("aa" * 8, {"pid": 1, "component": "daemon",
                                 "stacks": {"t [running];f (d.py:1)": 2}})
    cm.update_profile("bb" * 8, {"pid": 1, "component": "daemon",
                                 "stacks": {"t [running];g (d.py:2)": 2}})
    assert len(cm.profiles.origins()) == 2
    assert table.declare_dead("aa" * 8, reason="test")
    time.sleep(0.3)
    cm.evict_stale()
    origins = cm.profiles.origins()
    assert [nid for nid, _, _ in origins] == ["bb" * 8]


def test_bounded_memory_under_stack_shape_churn():
    """Unbounded distinct stacks (deep recursion with varying linenos,
    codegen'd frames) must not grow a bucket past profile_max_stacks:
    overflow folds into <truncated> keeping total weight honest, and
    the drop counter records it. Origin count is capped the same way."""
    store = ProfileStore(window_s=300, max_origins=4, max_stacks=50,
                         staleness=30)
    for i in range(500):
        store.ingest("aa" * 8, 1, "daemon",
                     {f"t [running];f (gen.py:{i})": 1})
    merged = store.merged(prefix_origin=False)
    assert len(merged) <= 51  # 50 distinct + <truncated>
    assert sum(merged.values()) == 500  # weight never silently dropped
    assert merged.get("<truncated>", 0) == 450
    assert store.dropped_stacks == 450
    # Origin cap: the 5th distinct (node, pid, component) is refused.
    for pid in range(2, 10):
        store.ingest("bb" * 8, pid, "worker",
                     {"t [running];w (w.py:1)": 1})
    assert len(store.origins()) <= 4
    assert store.dropped_origins > 0
    assert store.stats()["dropped_stacks"] == 450


def test_window_vs_window_diff():
    store = ProfileStore(window_s=600, max_origins=4, max_stacks=100,
                         staleness=30, bucket_s=30.0)
    now = time.monotonic()
    # Previous window: cold stack. Current window: hot stack.
    store.ingest("aa" * 8, 1, "daemon",
                 {"t [running];cold (d.py:1)": 10}, now=now - 90)
    store.ingest("aa" * 8, 1, "daemon",
                 {"t [running];hot (d.py:2)": 25}, now=now - 5)
    rows = store.diff(window=60.0)
    by_stack = {r["stack"]: r for r in rows}
    hot = next(v for k, v in by_stack.items() if "hot" in k)
    cold = next(v for k, v in by_stack.items() if "cold" in k)
    assert hot["delta"] == 25 and hot["previous"] == 0
    assert cold["delta"] == -10 and cold["current"] == 0
    # Sorted by |delta| descending.
    assert abs(rows[0]["delta"]) >= abs(rows[-1]["delta"])


# ---------------------------------------------------------------------------
# Loop-lag flight recorder
# ---------------------------------------------------------------------------


def test_flight_recorder_records_incident_with_stacks(monkeypatch):
    monkeypatch.setenv("RAY_TPU_PROFILE_FLIGHT_LAG_S", "0.5")
    store = ProfileStore(window_s=300, max_origins=8, max_stacks=100,
                         staleness=30)
    store.ingest("aa" * 8, 7, "daemon",
                 {"t [running];spin (d.py:3)": 9})
    # Below threshold: nothing.
    assert not store.observe_loop_lag("agent.daemon", 0.4, "aa" * 8, 7,
                                      "daemon")
    assert store.observe_loop_lag("agent.daemon", 2.5, "aa" * 8, 7,
                                  "daemon")
    # Same loop re-crossing inside the cooldown must not flood the ring.
    assert not store.observe_loop_lag("agent.daemon", 3.0, "aa" * 8, 7,
                                      "daemon")
    # A DIFFERENT loop is its own cooldown key.
    assert store.observe_loop_lag("dashboard", 2.0, "", 1, "driver")
    incs = store.incidents()
    assert len(incs) == 2
    assert incs[0]["loop"] == "dashboard"  # newest first
    daemon_inc = incs[1]
    assert daemon_inc["lag_s"] == 2.5
    assert daemon_inc["threshold_s"] == 0.5
    assert daemon_inc["top_stacks"], daemon_inc
    assert any("spin" in s for s, _ in daemon_inc["top_stacks"])
    assert daemon_inc["age_s"] >= 0
    # The driver had no window yet -> falls back to cluster scope.
    assert incs[0]["scope"] == "cluster"
    assert daemon_inc["scope"] == "origin"


def test_flight_recorder_triggered_by_metrics_batch(monkeypatch):
    """The trigger is wired into ClusterMetrics.update: a loop_lag
    gauge sample above threshold in ANY merged batch snapshots an
    incident."""
    monkeypatch.setenv("RAY_TPU_PROFILE_FLIGHT_LAG_S", "1.0")
    from ray_tpu._private.metrics_agent import ClusterMetrics
    cm = ClusterMetrics(staleness=30)
    cm.update_profile("aa" * 8, {"pid": 7, "component": "daemon",
                                 "stacks": {"t [running];f (d.py:1)": 3}})
    cm.update("aa" * 8, {"pid": 7, "component": "daemon", "metrics": [
        {"name": "ray_tpu_loop_lag_seconds", "type": "gauge", "desc": "",
         "tag_keys": ("loop",), "series": {("agent.daemon",): 4.0}}],
        "spans": []})
    incs = cm.profiles.incidents()
    assert len(incs) == 1
    assert incs[0]["loop"] == "agent.daemon"
    assert incs[0]["lag_s"] == 4.0
    # Sub-threshold lag leaves the ring alone.
    cm.update("aa" * 8, {"pid": 7, "component": "daemon", "metrics": [
        {"name": "ray_tpu_loop_lag_seconds", "type": "gauge", "desc": "",
         "tag_keys": ("loop",), "series": {("other.loop",): 0.2}}],
        "spans": []})
    assert len(cm.profiles.incidents()) == 1


def test_flight_recorder_ring_bounded(monkeypatch):
    monkeypatch.setenv("RAY_TPU_PROFILE_FLIGHT_LAG_S", "0.1")
    monkeypatch.setenv("RAY_TPU_PROFILE_MAX_INCIDENTS", "3")
    store = ProfileStore(window_s=300, max_origins=8, max_stacks=10,
                         staleness=30)
    for i in range(10):  # distinct loops dodge the per-loop cooldown
        store.observe_loop_lag(f"loop{i}", 1.0, "", 1, "driver")
    incs = store.incidents()
    assert len(incs) == 3
    assert incs[0]["loop"] == "loop9"


# ---------------------------------------------------------------------------
# Wire schema (additive post-v9)
# ---------------------------------------------------------------------------


def test_wire_profile_batch_schema():
    from ray_tpu._private import wire

    wire.validate_message({"type": "profile_batch", "node_id": "aa",
                           "pid": 1, "component": "daemon",
                           "stacks": {"t;f": 1}, "samples": 1,
                           "duration_s": 0.5})
    with pytest.raises(wire.WireSchemaError):
        wire.validate_message({"type": "profile_batch", "pid": 1})
    with pytest.raises(wire.WireSchemaError):
        wire.validate_message({"type": "profile_batch", "pid": "x",
                               "component": "daemon", "stacks": {}})
    # profile gained an OPTIONAL pid (burst retargeting) — both forms
    # must validate for v9 compatibility.
    wire.validate_message({"type": "profile", "req_id": 1,
                           "duration": 1.0, "hz": 10})
    wire.validate_message({"type": "profile", "req_id": 1,
                           "duration": 1.0, "hz": 10, "pid": 123})


# ---------------------------------------------------------------------------
# Dashboard endpoints + CLI report (head-local runtime)
# ---------------------------------------------------------------------------


def test_dashboard_profile_endpoints(ray_start_regular, monkeypatch):
    monkeypatch.setenv("RAY_TPU_PROFILE_FLIGHT_LAG_S", "1.0")
    from ray_tpu._private.worker import global_worker
    from ray_tpu.dashboard.head import DashboardHead

    rt = global_worker.runtime
    # Seed the store directly: endpoint shape tests must not depend on
    # sampler timing.
    rt._cluster_metrics.update_profile(
        "", {"pid": 1, "component": "driver",
             "stacks": {"t [running];drive (h.py:1)": 4}})
    rt._cluster_metrics.update(
        "", {"pid": 1, "component": "driver", "metrics": [
            {"name": "ray_tpu_loop_lag_seconds", "type": "gauge",
             "desc": "", "tag_keys": ("loop",),
             "series": {("dashboard",): 9.0}}], "spans": []})
    head = DashboardHead(port=0)
    port = head.start()
    try:
        status, body = _get(port, "/api/profile/flame")
        assert status == 200
        assert b"driver@head/1;t [running];drive (h.py:1)" in body
        status, body = _get(port, "/api/profile/flame?fmt=speedscope")
        assert json.loads(body)["profiles"]
        status, body = _get(port, "/api/profile/incidents")
        out = json.loads(body)
        assert out["incidents"] and out["incidents"][0]["loop"] == \
            "dashboard"
        assert out["stats"]["origins"] >= 1
        status, body = _get(port, "/api/profile/diff?window=30")
        assert "diff" in json.loads(body)
        # Satellite: malformed knobs are a 400, never an unhandled 500.
        for query in ("/api/profile?duration=abc",
                      "/api/profile?duration=-5",
                      "/api/profile?hz=zap",
                      "/api/profile?hz=0",
                      "/api/profile?pid=banana",
                      "/api/profile/flame?window=abc",
                      "/api/profile/flame?window=-1",
                      "/api/profile/diff?window=nope"):
            with pytest.raises(urllib.error.HTTPError) as err:
                _get(port, query)
            assert err.value.code == 400, query
    finally:
        head.stop()


def test_cli_profile_report(ray_start_regular, monkeypatch, capsys):
    monkeypatch.setenv("RAY_TPU_PROFILE_FLIGHT_LAG_S", "1.0")
    from ray_tpu._private.worker import global_worker
    from ray_tpu.scripts import cli

    rt = global_worker.runtime
    rt._cluster_metrics.update_profile(
        "", {"pid": 1, "component": "driver",
             "stacks": {"t [running];hotspot (h.py:1)": 6}})
    rt._cluster_metrics.update(
        "", {"pid": 1, "component": "driver", "metrics": [
            {"name": "ray_tpu_loop_lag_seconds", "type": "gauge",
             "desc": "", "tag_keys": ("loop",),
             "series": {("agent.driver",): 3.0}}], "spans": []})
    assert cli.main(["profile", "--report"]) == 0
    out = capsys.readouterr().out
    assert "loop=agent.driver" in out
    assert "lag=3.000s" in out
    assert "hotspot" in out


def test_profile_pid_resolves_head_pool_worker(ray_start_regular):
    """Satellite: --pid reaches a known worker through its owning
    process's burst endpoint — no py-spy anywhere."""
    from ray_tpu._private.worker import global_worker

    @ray_tpu.remote(runtime_env={"worker_process": True})
    def live(i):
        return i

    assert ray_tpu.get(live.remote(3)) == 3
    rt = global_worker.runtime
    pids = [w.pid for w in rt._process_pool._all if not w.dead]
    assert pids
    folded = rt.profile_pid(pids[0], duration=0.3, hz=50)
    assert folded  # the worker's serve loop stack at minimum
    assert "(" in folded and ")" in folded
    with pytest.raises(ValueError):
        rt.profile_pid(99999999, duration=0.1, hz=10)


# ---------------------------------------------------------------------------
# Acceptance: 2-daemon cluster -> merged flame with >= 2 origins
# ---------------------------------------------------------------------------


@pytest.mark.slow
def test_profile_flame_two_daemon_cluster(monkeypatch):
    """With RAY_TPU_PROFILE_HZ>0 on a 2-daemon cluster,
    /api/profile/flame returns one merged flamegraph containing stacks
    from head (driver), daemon, and worker components."""
    monkeypatch.setenv("RAY_TPU_METRICS_EXPORT_INTERVAL_S", "0.2")
    monkeypatch.setenv("RAY_TPU_PROFILE_HZ", "50")
    ray_tpu.shutdown()
    ray_tpu.init(num_cpus=4, num_tpus=0)
    from ray_tpu.dashboard.head import DashboardHead
    procs = []
    head = None
    try:
        host, port = ray_tpu.start_head_server(port=0, host="127.0.0.1")
        procs = [_spawn_daemon(
            port, num_cpus=2, resources={"remote": 2},
            env={"RAY_TPU_PROFILE_HZ": "50",
                 "RAY_TPU_METRICS_EXPORT_INTERVAL_S": "0.2"})
            for _ in range(2)]
        _wait_for_resource("remote", 4)

        # Worker-process tasks on the head give the flame a "worker"
        # component; remote tasks exercise both daemons' samplers.
        @ray_tpu.remote(resources={"remote": 1},
                        runtime_env={"worker_process": False})
        def remote_work(x):
            t0 = time.monotonic()
            while time.monotonic() - t0 < 0.05:
                pass
            return x

        @ray_tpu.remote(runtime_env={"worker_process": True})
        def head_work(x):
            t0 = time.monotonic()
            while time.monotonic() - t0 < 0.05:
                pass
            return x

        for _ in range(3):
            ray_tpu.get([remote_work.remote(i) for i in range(8)],
                        timeout=60)
            ray_tpu.get([head_work.remote(i) for i in range(4)],
                        timeout=60)
            time.sleep(0.5)
        head = DashboardHead(port=0)
        dport = head.start()

        def origins_on_flame():
            status, body = _get(dport, "/api/profile/flame")
            assert status == 200
            text = body.decode()
            roots = {line.split(";", 1)[0] for line in text.splitlines()
                     if line.strip()}
            return roots, text

        deadline = time.monotonic() + 30
        while True:
            roots, text = origins_on_flame()
            comps = {r.split("@", 1)[0] for r in roots}
            nodes = {r.split("@", 1)[1].split("/", 1)[0]
                     for r in roots if "@" in r}
            if {"driver", "daemon", "worker"} <= comps and \
                    len(nodes) >= 2:
                break
            if time.monotonic() > deadline:
                raise AssertionError(
                    f"flame never converged: comps={comps} "
                    f"nodes={nodes}\n{text[:2000]}")
            time.sleep(0.5)
        assert len(roots) >= 3  # >= 2 origins demanded; we get 3+
    finally:
        if head is not None:
            head.stop()
        for p in procs:
            p.terminate()
        for p in procs:
            try:
                p.wait(timeout=10)
            except subprocess.TimeoutExpired:
                p.kill()
        ray_tpu.shutdown()
