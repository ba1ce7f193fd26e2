"""CPU profiling: stack sampling without external tooling.

Analog of the reference's dashboard profiling endpoints
(dashboard/modules/reporter/profile_manager.py:54 — py-spy flamegraphs /
speedscope traces on demand). py-spy is not a dependency here; instead
every ray_tpu process can sample ITS OWN threads via
``sys._current_frames`` at a fixed rate and emit collapsed ("folded")
stacks or a speedscope document. Cross-process profiling works by asking
the target process to sample itself: node daemons answer a ``profile``
control message (multinode.py), so ``ray-tpu profile --node <id>``
needs no ptrace and no extra binaries. When py-spy IS installed, it is
preferred for arbitrary pids (native stacks, no cooperation needed).

Beyond the on-demand path, :class:`ProfilerAgent` runs a CONTINUOUS
low-rate sampler in every process (reference: Google-Wide Profiling —
always-on fleet sampling at a rate cheap enough to never turn off).
Samples accumulate as folded stacks tagged per thread with a
running/waiting annotation; the metrics cadence drains them into
``profile_batch`` frames toward the head's profile store
(``_private/profile_store.py``). ``RAY_TPU_PROFILE_HZ`` (flag
``profile_hz``) sets the rate; ``0`` disables the sampler entirely.

The agent's tick doubles as the process's heartbeat: how late it woke is
``ray_tpu_loop_lag_seconds{loop="sampler.<component>"}``; a tick later than
``LATE_TICK_S`` is a late tick, put down to a cause by the kernel's own
cumulative counters, read at every tick so that a late one has a "before"
(``HostCounters``, ``late_cause``), and kept for the train step's call site
(``parallel/compile_events.py``), which lays a long step against the late
ticks inside it. While something records, every tick is a span
``host::tick`` whose duration is its lateness.

Sampler loops here must use ABSOLUTE-DEADLINE scheduling (sleep to the
next grid tick, skip missed ticks) — a constant-period ``sleep`` adds
every stack walk's cost to the interval and silently decays the rate;
an AST lint (tests/test_log_lint.py) bans constant ``time.sleep``
arguments anywhere in this module.
"""

from __future__ import annotations

import collections
import gc
import os
import sys
import threading
import time
import uuid
from typing import Dict, List, NamedTuple, Optional, Tuple

__all__ = ["sample_self", "folded_to_speedscope", "profile_self",
           "pyspy_available", "profile_pid_pyspy", "merge_folded",
           "ProfilerAgent", "configured_profile_hz", "ensure_profiler",
           "global_profiler", "shutdown_profiler", "HostCounters",
           "late_cause", "LATE_TICK_S"]

#: Default continuous-sampling rate: low enough that walking a handful
#: of thread stacks costs well under 1% CPU, high enough that a 5s
#: metrics tick ships ~50 samples per process.
DEFAULT_PROFILE_HZ = 10.0

# -- what counts as late, and as a stalled step: the thresholds, in one place
#: A tick that wakes later than this is a late tick. A fifth of a period at
#: the default rate: a sound process wakes within 1-2 ms, and one that shares
#: its cores with busy processes of higher priority wakes 24-78 ms late at
#: every tick (a sleeper is let in early: meanwhile 6 ms of the loop's own
#: work take 100-700), which 0.05 would pass over (PERF.md, PR 47).
LATE_TICK_S = 0.02
#: A step is stalled when its interval, less its save, report and batch, is
#: longer than the median of its last ``STALL_HISTORY`` by more than
#: ``max(STALL_FLOOR_S, STALL_SHARE x median)``; judged from
#: ``STALL_MIN_HISTORY`` intervals on (``compile_events.first_call``).
STALL_FLOOR_S = 0.05
STALL_SHARE = 0.05
STALL_HISTORY = 32
STALL_MIN_HISTORY = 4
#: "Nothing else moved": every counter's delta under this share of the
#: lateness (half of what names a cause).
QUIET_SHARE = 0.25
#: Late ticks an agent keeps for ``late_between``.
_LATE_KEPT = 256
HOST_TICK = "host::tick"
_PRESSURES = ("cpu", "io", "memory")
#: ``/proc/stat`` counts in these, summed over this many CPUs (asked once:
#: either call is a system call, and a sandboxed kernel makes it a slow one).
_CLK_TCK = os.sysconf("SC_CLK_TCK")
_CPUS = os.cpu_count() or 1
#: A late tick's attributes: what each counter moved by while it slept.
_DELTA_ATTRIBUTE = {
    "runqueue": "runnable_s", "throttled": "throttled_s",
    "steal": "steal_s", "gc": "gc_s", "cpu": "process_cpu_s",
    "pressure_cpu": "psi_cpu_s", "pressure_io": "psi_io_s",
    "pressure_memory": "psi_memory_s"}


def configured_profile_hz() -> float:
    """Continuous sampler rate; honors the documented uppercase env
    spelling first, then the flag table (live runtime config > env >
    default). ``<= 0`` disables the always-on sampler."""
    raw = os.environ.get("RAY_TPU_PROFILE_HZ", "")
    if raw:
        try:
            return float(raw)
        except ValueError:
            pass
    from ray_tpu._private.ray_config import runtime_config_value
    return float(runtime_config_value("profile_hz", DEFAULT_PROFILE_HZ))


def merge_folded(dst: Dict[str, int], src: Dict[str, int]
                 ) -> Dict[str, int]:
    """Merge folded-stack counts ``src`` into ``dst`` (in place; also
    returned). Addition is associative and commutative, so batches can
    merge in any grouping/order — the property the head-side store and
    the cluster-burst fan-in both rely on."""
    for key, count in src.items():
        dst[key] = dst.get(key, 0) + count
    return dst


def sample_self(duration_s: float = 5.0, hz: int = 100,
                skip_profiler: bool = True,
                stats: Optional[dict] = None) -> Dict[str, int]:
    """Sample every thread's Python stack for ``duration_s`` seconds at
    ``hz``; returns collapsed stacks ("thr;outer;...;inner" -> count,
    flamegraph.pl / speedscope input format).

    The sampler sleeps to the NEXT ABSOLUTE tick, not for a fixed
    period: ``sleep(period)`` after each sample would add the walk cost
    of every deep stack to the interval, silently dropping the
    effective rate below ``hz``. When a walk overruns one or more
    ticks, the missed ticks are skipped (not compressed into a burst)
    so samples stay evenly spaced. Pass a ``stats`` dict to receive
    ``{"ticks", "elapsed_s", "achieved_hz"}`` — the honest rate, which
    the speedscope export reports and uses to weight samples."""
    counts: Dict[str, int] = {}
    me = threading.get_ident()
    names = {t.ident: t.name for t in threading.enumerate()}
    period = 1.0 / max(hz, 1)
    t0 = time.monotonic()
    deadline = t0 + duration_s
    next_tick = t0
    ticks = 0
    while True:
        now = time.monotonic()
        if now >= deadline:
            break
        for ident, frame in sys._current_frames().items():
            if skip_profiler and ident == me:
                continue
            stack: List[str] = []
            f = frame
            while f is not None:
                code = f.f_code
                stack.append(f"{code.co_name} "
                             f"({code.co_filename.rsplit('/', 1)[-1]}:"
                             f"{f.f_lineno})")
                f = f.f_back
            name = names.get(ident) or str(ident)
            key = ";".join([name] + stack[::-1])
            counts[key] = counts.get(key, 0) + 1
        ticks += 1
        next_tick += period
        now = time.monotonic()
        while next_tick <= now:  # overran: skip missed ticks, stay on grid
            next_tick += period
        time.sleep(max(0.0, min(next_tick, deadline) - now))
    if stats is not None:
        elapsed = max(time.monotonic() - t0, 1e-9)
        stats["ticks"] = ticks
        stats["elapsed_s"] = elapsed
        stats["achieved_hz"] = ticks / elapsed
    return counts


def folded_to_speedscope(counts: Dict[str, int], name: str = "ray_tpu",
                         hz: int = 100,
                         achieved_hz: Optional[float] = None) -> dict:
    """Collapsed stacks -> a speedscope 'sampled' profile document
    (https://www.speedscope.app file-format-schema). When the sampler's
    measured ``achieved_hz`` is known, it weights the samples (each
    sample represents the real inter-tick interval, not the requested
    one) and is reported in the document."""
    frame_index: Dict[str, int] = {}
    frames: List[dict] = []
    samples: List[List[int]] = []
    weights: List[float] = []
    dt = 1.0 / max(achieved_hz or hz, 1e-9)
    for key, count in sorted(counts.items()):
        stack_ids = []
        for part in key.split(";"):
            if part not in frame_index:
                frame_index[part] = len(frames)
                frames.append({"name": part})
            stack_ids.append(frame_index[part])
        samples.append(stack_ids)
        weights.append(count * dt)
    total = sum(weights) or 1.0
    return {
        "$schema": "https://www.speedscope.app/file-format-schema.json",
        "shared": {"frames": frames},
        "profiles": [{
            "type": "sampled",
            "name": name,
            "unit": "seconds",
            "startValue": 0,
            "endValue": total,
            "samples": samples,
            "weights": weights,
        }],
        "name": name,
        "activeProfileIndex": 0,
        "exporter": "ray_tpu-profiler",
        "requestedHz": hz,
        "achievedHz": achieved_hz,
    }


def profile_self(duration_s: float = 5.0, hz: int = 100,
                 fmt: str = "folded"):
    """One-call self-profile: 'folded' text, 'speedscope' dict, or the
    raw 'dict' mapping (what cluster bursts ship so the head can merge
    before rendering)."""
    stats: dict = {}
    counts = sample_self(duration_s, hz, stats=stats)
    if fmt == "dict":
        return counts
    if fmt == "folded":
        return "\n".join(f"{k} {v}" for k, v in sorted(counts.items()))
    if fmt == "speedscope":
        return folded_to_speedscope(counts, hz=hz,
                                    achieved_hz=stats.get("achieved_hz"))
    raise ValueError(f"unknown profile format {fmt!r}")


# -- what kept a thread off the CPU: the kernel's cumulative counters ------


def _read(path: str) -> Optional[str]:
    try:
        with open(path) as f:
            return f.read()
    except OSError:
        return None


def schedstat_seconds(text: Optional[str]
                      ) -> Optional[Tuple[float, float]]:
    """``(on the CPU, runnable and not run)`` seconds of a
    ``/proc/<...>/schedstat`` (nanoseconds, then a count of timeslices)."""
    fields = (text or "").split()
    if len(fields) < 2 or not (fields[0].isdigit() and fields[1].isdigit()):
        return None
    return int(fields[0]) / 1e9, int(fields[1]) / 1e9


def throttled_seconds(text: Optional[str]) -> Optional[float]:
    """Seconds a cgroup's tasks were held at their CPU quota, from its
    ``cpu.stat``: v2's ``throttled_usec`` or v1's ``throttled_time``
    (nanoseconds)."""
    for line in (text or "").splitlines():
        key, _, value = line.partition(" ")
        if value.strip().isdigit():
            if key == "throttled_usec":
                return int(value) / 1e6
            if key == "throttled_time":
                return int(value) / 1e9
    return None


def steal_seconds(text: Optional[str]) -> Optional[float]:
    """Seconds the hypervisor ran something else, a CPU: the steal column
    of ``/proc/stat``'s first line (ticks summed over the CPUs)."""
    fields = (text or "").split("\n", 1)[0].split()
    if len(fields) < 9 or fields[0] != "cpu" or not fields[8].isdigit():
        return None
    return int(fields[8]) / _CLK_TCK / _CPUS


def pressure_seconds(text: Optional[str]) -> Optional[float]:
    """Seconds some task waited for the resource: ``some ... total=`` of a
    ``/proc/pressure/<resource>`` (microseconds)."""
    for line in (text or "").splitlines():
        if line.startswith("some "):
            total = line.rpartition("total=")[2]
            if total.isdigit():
                return int(total) / 1e6
    return None


def cgroup_cpu_stat(cgroup_text: Optional[str],
                    root: str = "/sys/fs/cgroup") -> Optional[str]:
    """The ``cpu.stat`` that holds this process's throttling, from its
    ``/proc/self/cgroup``: v1's ``cpu`` controller where one is mounted,
    else the unified hierarchy. None where neither file is there."""
    candidates = []
    for line in (cgroup_text or "").splitlines():
        _, _, rest = line.partition(":")
        controllers, _, path = rest.partition(":")
        path = path.strip("/")
        if "cpu" in controllers.split(","):
            candidates[:0] = [os.path.join(root, name, path, "cpu.stat")
                              for name in (controllers, "cpu")]
        elif not controllers:
            candidates.append(os.path.join(root, path, "cpu.stat"))
    for path in candidates:
        if throttled_seconds(_read(path)) is not None:
            return path
    return None


class HostCounters:
    """Cumulative seconds of everything that can hold the calling thread
    off the CPU, as the kernel and the collector count them. Each source is
    optional: what this machine does not have is absent from ``read()``,
    and so from the verdict."""

    def __init__(self) -> None:
        # Held open and read at offset 0 every tick (a tenth of the cost of
        # opening each anew); ``thread-self`` is the thread that opens it.
        paths = {"runqueue": "/proc/thread-self/schedstat",
                 "throttled": cgroup_cpu_stat(_read("/proc/self/cgroup")),
                 "steal": "/proc/stat"}
        paths.update(("pressure_" + what, "/proc/pressure/" + what)
                     for what in _PRESSURES)
        self._files = {}
        for cause, path in paths.items():
            try:
                self._files[cause] = os.open(path, os.O_RDONLY)
            except (OSError, TypeError):  # not on this machine
                pass
        self._gc_s = 0.0
        self._gc_t0: Optional[float] = None

    def close(self) -> None:
        while self._files:
            os.close(self._files.popitem()[1])

    def _text(self, cause: str) -> Optional[str]:
        try:
            return os.pread(self._files[cause], 4096, 0).decode()
        except (KeyError, OSError):
            return None

    def on_gc(self, phase: str, info: dict) -> None:
        """A ``gc.callbacks`` entry: seconds inside collections."""
        if phase == "start":
            self._gc_t0 = time.perf_counter()
        elif self._gc_t0 is not None:
            self._gc_s += time.perf_counter() - self._gc_t0
            self._gc_t0 = None

    def read(self) -> Dict[str, float]:
        """``{cause: cumulative seconds}``, and under ``cpu`` the seconds
        the whole process has run (no cause: what tells a thread that
        waited for the interpreter's lock from a process that stood
        still)."""
        sched = schedstat_seconds(self._text("runqueue"))
        out = {
            "runqueue": sched and sched[1],
            "throttled": throttled_seconds(self._text("throttled")),
            "steal": steal_seconds(self._text("steal")),
            "gc": self._gc_s,
            "cpu": time.process_time(),
        }
        for what in _PRESSURES:
            out["pressure_" + what] = pressure_seconds(
                self._text("pressure_" + what))
        return {k: v for k, v in out.items() if v is not None}

    def thread_cpu(self) -> Optional[Dict[int, float]]:
        """``{native thread id: seconds on the CPU}`` of this process's
        threads (``/proc/self/task/*/schedstat``): a file a thread, so read
        only while something records, and not at all (None) where the
        kernel keeps no ``schedstat``."""
        if "runqueue" not in self._files:
            return None
        out = {}
        try:
            tids = os.listdir("/proc/self/task")
        except OSError:
            return None
        for tid in tids:
            sched = schedstat_seconds(
                _read(f"/proc/self/task/{tid}/schedstat"))
            if sched is not None:
                out[int(tid)] = sched[0]
        return out


class _Reading(NamedTuple):
    """The counters as one tick read them: the next tick's "before"."""
    counters: Dict[str, float]  # HostCounters.read()
    threads: Optional[Dict[int, float]]  # .thread_cpu(), while recording
    at: float  # time.perf_counter()


def _thread_name(tid: int) -> str:
    for thread in threading.enumerate():
        if thread.native_id == tid:
            return thread.name
    return (_read(f"/proc/self/task/{tid}/comm") or str(tid)).strip()


def late_cause(late_s: float, deltas: Dict[str, float],
               since_s: Optional[float] = None) -> str:
    """Why a tick woke ``late_s`` late, from what each counter of
    ``HostCounters.read()`` moved by in the ``since_s`` seconds since it was
    last read. The thread's own ``runqueue`` runs only while the thread
    wants the CPU, so all of it is the lateness's; every other counter runs
    through the whole interval (the sleep before the tick was due too: this
    machine's other tenants, a collection that the tick never met), so the
    lateness gets its share of the interval of it. The cause that moved
    most is named if that covers at least half the lateness. Where the
    thread was not even runnable, nothing else moved (under ``QUIET_SHARE``)
    and the process ran meanwhile, the tick waited for the interpreter's
    lock: ``gil``. Else ``unknown``."""
    share = min(1.0, late_s / since_s) if since_s else 1.0
    causes = {k: v if k == "runqueue" else v * share
              for k, v in deltas.items() if k != "cpu"}
    if causes:
        cause = max(causes, key=causes.get)
        if causes[cause] >= late_s / 2:
            return cause
    if ("runqueue" in causes
            and deltas.get("cpu", 0.0) * share >= late_s / 2
            and max(causes.values()) < late_s * QUIET_SHARE):
        return "gil"
    return "unknown"


#: Innermost-frame function names that mean the thread is parked, not
#: burning CPU — the running/waiting annotation distinguishes "the loop
#: is hot" from "the loop is blocked on IO/a lock" in flamegraphs.
_WAIT_FRAME_NAMES = frozenset({
    "wait", "wait_for", "sleep", "select", "poll", "epoll", "kqueue",
    "accept", "recv", "recv_into", "recvfrom", "read", "read1",
    "readinto", "readline", "acquire", "join", "get", "settimeout",
    "flush", "dowait", "_recv_msg", "recv_frame",
})


class ProfilerAgent:
    """Always-on background stack sampler for THIS process.

    Walks ``sys._current_frames()`` at ``hz`` on a daemon thread and
    accumulates folded stacks keyed
    ``"<thread> [running|waiting];outer;...;inner"``. The transport
    drains on the metrics cadence via :meth:`drain` and refunds failed
    publishes via :meth:`refund` so samples survive a dropped frame.
    ``hz <= 0`` builds a disabled agent (no thread, drains are empty).
    """

    def __init__(self, component: str, hz: Optional[float] = None,
                 start: bool = True):
        self.component = component
        self.hz = configured_profile_hz() if hz is None else float(hz)
        self.pid = os.getpid()
        self._lock = threading.Lock()
        self._counts: Dict[str, int] = {}
        self._samples = 0  # stack walks accumulated since last drain
        self._window_t0 = time.monotonic()
        self._stop = threading.Event()
        self._thread: Optional[threading.Thread] = None
        # (woke, lateness, cause) of the newest late ticks, on
        # ``time.perf_counter``; one trace holds all of this agent's ticks.
        self._late: collections.deque = collections.deque(maxlen=_LATE_KEPT)
        self._trace_id = uuid.uuid4().hex[:16]
        if start and self.hz > 0:
            self._thread = threading.Thread(
                target=self._loop, daemon=True,
                name=f"ray_tpu-profiler-{component}")
            self._thread.start()

    @property
    def enabled(self) -> bool:
        return self.hz > 0 and not self._stop.is_set()

    def _loop(self) -> None:
        from ray_tpu._private import builtin_metrics
        period = 1.0 / max(self.hz, 1e-3)
        # The clock of a span's ``perf_start``: a tick is a span.
        next_tick = time.perf_counter()
        me = threading.get_ident()
        host = HostCounters()
        gc.callbacks.append(host.on_gc)
        try:
            before = _Reading(host.read(), None, next_tick)
            while not self._stop.is_set():
                now = time.perf_counter()
                if now < next_tick:
                    # Event wait doubles as the pacing sleep: a stop() wakes
                    # the loop immediately instead of after one more period.
                    if self._stop.wait(next_tick - now):
                        return
                try:
                    before = self._tick(next_tick, time.perf_counter(),
                                        host, before)
                    walked = self._sample_once(me)
                    builtin_metrics.record_profile_samples(walked)
                except Exception:  # noqa: BLE001 - sampling must never kill host
                    pass
                next_tick += period
                now = time.perf_counter()
                while next_tick <= now:  # overran: skip ticks, stay on grid
                    next_tick += period
        finally:
            gc.callbacks.remove(host.on_gc)
            host.close()

    def _tick(self, due: float, woke: float, host: HostCounters,
              before: _Reading) -> _Reading:
        """One wake-up, ``woke - due`` late. Returns the counters as read
        now: the next tick's ``before``."""
        from ray_tpu._private import builtin_metrics
        from ray_tpu.util import tracing
        late = max(0.0, woke - due)
        builtin_metrics.record_sampler_lag(self.component, late)
        recording = tracing.finished_span_context() is not None
        now = _Reading(host.read(),
                       host.thread_cpu() if recording else None, woke)
        attributes = {}
        if late > LATE_TICK_S:
            deltas = {k: v - before.counters[k]
                      for k, v in now.counters.items()
                      if k in before.counters}
            cause = late_cause(late, deltas, woke - before.at)
            builtin_metrics.process_late_seconds().inc(
                late, tags={"cause": cause})
            self._late.append((woke, late, cause))
            if recording:
                attributes = {_DELTA_ATTRIBUTE[k]: round(v, 6)
                              for k, v in deltas.items()}
                attributes["cause"] = cause
                if now.threads and before.threads:
                    ran = {tid: s - before.threads[tid]
                           for tid, s in now.threads.items()
                           if tid in before.threads}
                    tid = max(ran, key=ran.get, default=None)
                    if tid is not None:
                        attributes.update(
                            busiest_thread=_thread_name(tid),
                            busiest_thread_cpu_s=round(ran[tid], 6))
        if recording:
            tracing.record_complete_span(
                HOST_TICK,
                {"trace_id": self._trace_id, "parent_id": None,
                 "sampled": True},
                # The anchor only: now, moved back to when it was due.
                wall_start=time.time() + (due - time.perf_counter()),
                duration=late, attributes=attributes, perf_start=due)
        return now

    def late_between(self, t0: float, t1: float) -> Tuple[float, str]:
        """Seconds of this agent's late ticks that fell inside ``[t0, t1]``
        (``time.perf_counter``), and the cause that holds most of them
        (``none`` where the process ran all along)."""
        by_cause: Dict[str, float] = {}
        for woke, late, cause in list(self._late):
            inside = min(woke, t1) - max(woke - late, t0)
            if inside > 0:
                by_cause[cause] = by_cause.get(cause, 0.0) + inside
        if not by_cause:
            return 0.0, "none"
        return sum(by_cause.values()), max(by_cause, key=by_cause.get)

    def _sample_once(self, skip_ident: Optional[int] = None) -> int:
        """One walk over every thread; returns the number of stacks
        sampled. Public for tests and tick-less (worker) callers."""
        names = {t.ident: t.name for t in threading.enumerate()}
        walked = 0
        fresh: Dict[str, int] = {}
        for ident, frame in sys._current_frames().items():
            if ident == skip_ident:
                continue
            stack: List[str] = []
            f = frame
            while f is not None:
                code = f.f_code
                stack.append(f"{code.co_name} "
                             f"({code.co_filename.rsplit('/', 1)[-1]}:"
                             f"{f.f_lineno})")
                f = f.f_back
            if not stack:
                continue
            # stack[0] is the INNERMOST frame: a leaf parked in a wait
            # primitive marks the whole sample as blocked, anything
            # else as on-CPU (approximate — the GIL was held by someone
            # else during the walk — but cheap and overwhelmingly right
            # for the park-vs-burn question).
            leaf = stack[0].split(" ", 1)[0]
            state = "waiting" if leaf in _WAIT_FRAME_NAMES else "running"
            name = names.get(ident) or str(ident)
            key = ";".join([f"{name} [{state}]"] + stack[::-1])
            fresh[key] = fresh.get(key, 0) + 1
            walked += 1
        if fresh:
            with self._lock:
                merge_folded(self._counts, fresh)
                self._samples += walked
        return walked

    def drain(self) -> Optional[dict]:
        """Take (and clear) the accumulated stacks. Returns
        ``{"stacks", "samples", "duration_s"}`` or None when empty."""
        now = time.monotonic()
        with self._lock:
            if not self._counts:
                self._window_t0 = now
                return None
            stacks, self._counts = self._counts, {}
            samples, self._samples = self._samples, 0
            t0, self._window_t0 = self._window_t0, now
        return {"stacks": stacks, "samples": samples,
                "duration_s": max(0.0, now - t0)}

    def refund(self, stacks: Dict[str, int]) -> None:
        """Merge a failed-publish batch back into the accumulator so a
        dropped frame loses no samples (they ship on the next tick)."""
        with self._lock:
            merge_folded(self._counts, stacks)

    def stop(self) -> None:
        self._stop.set()
        if self._thread is not None:
            self._thread.join(timeout=2.0)
            self._thread = None


_agent_lock = threading.Lock()
_agent: Optional[ProfilerAgent] = None


def ensure_profiler(component: str) -> Optional[ProfilerAgent]:
    """Start (or return) this process's singleton ProfilerAgent. None
    when the configured rate disables sampling."""
    global _agent
    with _agent_lock:
        if _agent is not None and _agent.enabled:
            return _agent
        agent = ProfilerAgent(component)
        if not agent.enabled:
            return None
        _agent = agent
        return agent


def global_profiler() -> Optional[ProfilerAgent]:
    return _agent


def shutdown_profiler() -> None:
    """Stop and forget the process profiler (runtime shutdown; a later
    ``ensure_profiler`` starts a fresh one)."""
    global _agent
    with _agent_lock:
        agent, _agent = _agent, None
    if agent is not None:
        agent.stop()


def pyspy_available() -> bool:
    import shutil
    return shutil.which("py-spy") is not None


def profile_pid_pyspy(pid: int, duration_s: float = 5.0,
                      fmt: str = "speedscope") -> bytes:
    """Profile an arbitrary pid with py-spy (when installed): returns the
    raw output file bytes (reference: profile_manager.py py-spy record)."""
    import subprocess
    import tempfile
    suffix = ".speedscope.json" if fmt == "speedscope" else ".txt"
    out = tempfile.NamedTemporaryFile(suffix=suffix, delete=False)
    out.close()
    pyspy_fmt = "speedscope" if fmt == "speedscope" else "raw"
    subprocess.run(
        ["py-spy", "record", "--pid", str(pid), "--duration",
         str(int(duration_s)), "--format", pyspy_fmt, "--output", out.name],
        check=True, capture_output=True, timeout=duration_s + 30)
    with open(out.name, "rb") as f:
        return f.read()
