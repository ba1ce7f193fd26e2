"""From a profiler trace (``.xplane.pb``) to the numbers the per-layer
metrics read.

The JAX profiler writes one ``<host>.xplane.pb`` under
``<dir>/plugins/profile/<time>/``. ``jax.profiler.ProfileData`` reads it
with nothing but JAX: planes, their lines, and events with a start and a
duration in nanoseconds on the trace's own clock. On a TPU each chip is a
plane ``/device:TPU:<n>``. Its line ``XLA Ops`` holds one event per device
operation, named by the whole text of the HLO instruction (``%fusion.12 =
bf16[...] fusion(...), kind=kOutput``); ``Async XLA Ops`` holds one event
per asynchronous operation from its start to its done; ``XLA Modules`` holds
one event per executed program. The host is ``/host:CPU`` with one line per
thread, where the benchmark's ``jax.profiler.TraceAnnotation`` spans land
beside JAX's own, on the same clock. (Seen in the trace recorded on the v5e
in PR 22, ``tests/data``; the events carry no category or FLOP stats.)

This file is the yardstick's reduction: a later PR may not edit it, and a
test holds it to a trace recorded on the chip (``tests/data``). Everything
but busy time is read on the first chip's plane, so a four-chip cell
reports what one of its chips saw.
"""

from __future__ import annotations

import glob
import gzip
import os
import re
from typing import Any, Dict, Iterable, List, Optional, Tuple

DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
OPS_LINE = "XLA Ops"
ASYNC_LINE = "Async XLA Ops"
MODULES_LINE = "XLA Modules"
#: Spans the benchmark's own loop writes (runners name them
#: ``bench/<what>``): the host side of the idle-gap attribution.
HOST_SPAN_PREFIX = "bench/"
#: ``%name = type opcode(operands), attributes``: the instruction's name
#: and its opcode. No type contains a lower-case word followed by "(".
_INSTRUCTION = re.compile(r"^%?(?P<name>[^\s=]+)(?: = .*? (?P<opcode>[a-z][a-z0-9\-]*)\()?",
                          re.DOTALL)
_TARGET = re.compile(r'custom_call_target="([^"]*)"')
#: Collective operations, by opcode or (wrapped in an async fusion) by name.
COLLECTIVE = re.compile(
    r"(all-gather|all-reduce|reduce-scatter|all-to-all|"
    r"collective-permute|collective-broadcast)")
#: Instructions that only wrap others: their children are on the line too,
#: so counting both would count the time twice.
CONTROL = ("while", "conditional", "call")
#: The custom-call target of a Mosaic (Pallas) kernel.
MOSAIC_TARGET = "tpu_custom_call"
Interval = Tuple[float, float]


def find_xplane(trace_dir: str) -> Optional[str]:
    """The newest ``.xplane.pb`` under a directory ``start_trace`` wrote to."""
    found = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                      recursive=True)
    return max(found, key=os.path.getmtime) if found else None


def load(path: str):
    """``ProfileData`` of an ``.xplane.pb`` file, gzipped or not."""
    from jax.profiler import ProfileData
    if path.endswith(".gz"):
        with gzip.open(path, "rb") as f:
            return ProfileData.from_serialized_xspace(f.read())
    return ProfileData.from_file(path)


def merged(intervals: Iterable[Interval]) -> List[Interval]:
    """The union of intervals as a sorted list of disjoint intervals."""
    out: List[List[float]] = []
    for start, end in sorted(intervals):
        if out and start <= out[-1][1]:
            out[-1][1] = max(out[-1][1], end)
        else:
            out.append([start, end])
    return [(a, b) for a, b in out]


def union_seconds(intervals: Iterable[Interval]) -> float:
    """Length of the union of ``(start, end)`` intervals."""
    return sum(end - start for start, end in merged(intervals))


def trace_options():
    """How the benchmark records a trace: device operations and the host's
    ``TraceAnnotation`` spans, no Python call tracing and no HLO text (they
    are most of a trace's bytes and the reduction reads neither)."""
    import jax
    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0
    options.host_tracer_level = 1
    options.enable_hlo_proto = False
    return options


def parse_op(text: str) -> Tuple[str, str, Optional[str]]:
    """(instruction name, opcode, custom-call target) of an ``XLA Ops``
    event's name. A name that is not instruction text is its own name and
    opcode."""
    m = _INSTRUCTION.match(text)
    if m is None:
        return text, text, None
    name = m.group("name")
    opcode = m.group("opcode") or name.split(".")[0]
    target = None
    if opcode == "custom-call":
        t = _TARGET.search(text)
        target = t.group(1) if t else None
    return name, opcode, target


def is_collective(name: str, opcode: str) -> bool:
    """A collective by its opcode (``all-reduce``, ``all-gather-start``) or,
    where XLA wrapped it (``async-start`` of ``%all-gather-fusion``), by the
    instruction's name."""
    return bool(COLLECTIVE.search(opcode) or COLLECTIVE.search(name))


def reduce_trace(data, window: Optional[Interval] = None) -> Dict[str, Any]:
    """Reduce ``ProfileData`` to the dictionary the metric readers take.

    ``window`` is (start, end) in seconds on the trace's clock; default: from
    the first to the last host span of the benchmark (``bench/...``), else
    the first to the last device operation. All times are seconds.

    Returns ``{"window_s", "busy_s", "devices": [...], "steps_device_s":
    [...], "collective_s", "mosaic_s", "device_ops": [[name, s]...],
    "idle_gaps": [[what, s]...], "host_spans": {name: [(t0, t1)...]}}``:
    ``busy_s`` is the union of the device's operations, ``collective_s``
    the union of the intervals in which a collective ran or was in flight,
    ``mosaic_s`` the summed time of Pallas kernels, ``steps_device_s`` the
    device time of each run of the program that took most time (the step),
    ``device_ops`` the time by instruction name, control flow left out.
    ``busy_s`` is averaged over the chips; the rest is read on the first.
    """
    device_planes, host_spans = [], {}
    for plane in data.planes:
        if DEVICE_PLANE.match(plane.name):
            device_planes.append(plane)
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for ev in line.events:
                    if ev.name.startswith(HOST_SPAN_PREFIX):
                        host_spans.setdefault(ev.name, []).append(
                            (ev.start_ns * 1e-9,
                             (ev.start_ns + ev.duration_ns) * 1e-9))
    for spans in host_spans.values():
        spans.sort()

    device_planes.sort(key=lambda p: int(DEVICE_PLANE.match(p.name).group(1)))
    per_device = []
    for plane in device_planes:
        ops, spans, modules = [], [], []
        for line in plane.lines:
            if line.name in (OPS_LINE, ASYNC_LINE):
                for ev in line.events:
                    name, opcode, target = parse_op(ev.name)
                    t0 = ev.start_ns * 1e-9
                    item = (t0, t0 + ev.duration_ns * 1e-9, name, opcode,
                            target)
                    (ops if line.name == OPS_LINE else spans).append(item)
            elif line.name == MODULES_LINE:
                for ev in line.events:
                    t0 = ev.start_ns * 1e-9
                    modules.append((t0, t0 + ev.duration_ns * 1e-9, ev.name))
        if ops:
            per_device.append({"plane": plane.name, "ops": ops,
                               "async": spans, "modules": sorted(modules)})
    if not per_device:
        return {}

    if window is None:
        if host_spans:
            window = (min(s[0][0] for s in host_spans.values()),
                      max(s[-1][1] for s in host_spans.values()))
        else:
            window = (min(op[0] for d in per_device for op in d["ops"]),
                      max(op[1] for d in per_device for op in d["ops"]))
    w0, w1 = window

    def clip(t0: float, t1: float) -> Optional[Interval]:
        a, b = max(t0, w0), min(t1, w1)
        return (a, b) if b > a else None

    # Busy time is averaged over the chips. Everything else is read on one
    # chip, the first: the chips of a mesh run the same program in step,
    # and only the first chip's plane holds the asynchronous operations.
    busy = []
    for dev in per_device:
        ivals = [c for c in (clip(t0, t1) for t0, t1, *_ in dev["ops"]) if c]
        busy.append(union_seconds(ivals))
        dev["busy"] = merged(ivals)
    first = per_device[0]
    collective, mosaic, by_name = [], 0.0, {}
    for t0, t1, name, opcode, target in first["ops"]:
        c = clip(t0, t1)
        if c is None or opcode in CONTROL:
            continue  # a loop's body is on the line too
        by_name[name] = by_name.get(name, 0.0) + c[1] - c[0]
        if is_collective(name, opcode):
            collective.append(c)
        elif target == MOSAIC_TARGET:
            mosaic += c[1] - c[0]
    # An asynchronous collective is in flight from its start to its done;
    # on the operations' line those two are instants.
    for t0, t1, name, opcode, _ in first["async"]:
        c = clip(t0, t1)
        if c is not None and is_collective(name, opcode):
            collective.append(c)
    # One program run = one entry of the modules line; the step is the
    # program that takes most of the time, so short helper programs (a loss
    # read back, a checksum) do not dilute the median.
    by_module: Dict[str, List[float]] = {}
    for t0, t1, name in first["modules"]:
        if t0 >= w0 and t1 <= w1:
            by_module.setdefault(name, []).append(t1 - t0)
    steps = max(by_module.values(), key=sum) if by_module else []

    ops_ranked = sorted(by_name.items(), key=lambda kv: -kv[1])
    return {
        "window_s": w1 - w0,
        "busy_s": sum(busy) / len(busy),
        "devices": [d["plane"] for d in per_device],
        "steps_device_s": steps,
        "collective_s": union_seconds(collective),
        "mosaic_s": mosaic,
        "device_ops": [[name, secs] for name, secs in ops_ranked[:10]],
        "idle_gaps": idle_gaps(first["busy"], (w0, w1), host_spans),
        "host_spans": host_spans,
    }


def idle_gaps(busy: List[Interval], window: Interval,
              host_spans: Dict[str, List[Interval]],
              top: int = 10) -> List[List[Any]]:
    """Idle time of one device inside ``window``, attributed to what the
    benchmark's loop was doing: each gap between device operations is split
    among the host spans (``bench/<what>``) that overlap it, innermost span
    first, and the rest is ``other``. Returns the ``top`` largest totals as
    ``[what, seconds]``."""
    w0, w1 = window
    gaps, cursor = [], w0
    for t0, t1 in busy:
        if t0 > cursor:
            gaps.append((cursor, t0))
        cursor = max(cursor, t1)
    if w1 > cursor:
        gaps.append((cursor, w1))
    # Innermost first: a shorter span nested in a longer one takes the time.
    spans = sorted(((t1 - t0, t0, t1, name[len(HOST_SPAN_PREFIX):])
                    for name, ivals in host_spans.items()
                    for t0, t1 in ivals))
    totals: Dict[str, float] = {}
    for g0, g1 in gaps:
        left = [(g0, g1)]
        for _, s0, s1, what in spans:
            if s1 <= g0 or s0 >= g1 or not left:
                continue
            rest = []
            for a, b in left:
                lo, hi = max(a, s0), min(b, s1)
                if hi > lo:
                    totals[what] = totals.get(what, 0.0) + hi - lo
                    if a < lo:
                        rest.append((a, lo))
                    if hi < b:
                        rest.append((hi, b))
                else:
                    rest.append((a, b))
            left = rest
        for a, b in left:
            totals["other"] = totals.get("other", 0.0) + b - a
    ranked = sorted(totals.items(), key=lambda kv: -kv[1])
    return [[what, secs] for what, secs in ranked[:top]]


def describe(data, max_events: int = 6) -> str:
    """What is in a trace, for a person: planes, lines, counts and the
    first events of each line with their stats. Look at one trace by hand
    before writing code against it."""
    out = []
    for plane in data.planes:
        out.append(f"PLANE {plane.name!r}")
        for line in plane.lines:
            events = list(line.events)
            out.append(f"  LINE {line.name!r}: {len(events)} events")
            for ev in events[:max_events]:
                stats = {k: (v if len(str(v)) < 60 else str(v)[:57] + "...")
                         for k, v in ev.stats}
                out.append(f"    {ev.name[:80]!r} start {ev.start_ns:.0f} "
                           f"dur {ev.duration_ns:.0f} {stats}")
    return "\n".join(out)
