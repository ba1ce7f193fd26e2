"""One run of one cell of the benchmark.

    python benchmark/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

from the root of a checkout, on a machine that holds the chips the cell
asks for. The last line of standard output is one JSON object with
``correct``, ``attempted``, ``failed``, ``metrics`` and ``device`` (and
``breakdown`` with ``--trace 1``); the line before it holds the run's
diagnostics for a person. ``--trace 0`` reports the cell's end-to-end
metrics, ``--trace 1`` its per-layer metrics from a profiler trace of a few
units. Where JAX finds no TPU, or fewer chips than the cell asks for, it
exits non-zero and prints no result: there is no CPU mode.

Which cells, configurations, traffic mixes and metrics exist is data:
``BENCHMARK.json`` and the files it names (see ``harness.py``).
"""

from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path[:0] = [os.path.dirname(HERE), HERE]


def main(argv=None) -> None:
    import harness
    parser = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    parser.add_argument("--workload", required=True)
    parser.add_argument("--seed", type=int, default=0)
    parser.add_argument("--seconds", type=float, required=True)
    parser.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = parser.parse_args(argv)

    spec = harness.load_spec()
    cell = harness.load_cell(spec, args.workload)
    runner = harness.load_module("runners", cell.traffic["runner"])
    record = runner.run(cell, harness.RunArgs(
        seed=args.seed, seconds=args.seconds, trace=bool(args.trace),
        t_start=T_START))

    group, kind = ("per_layer", "layer_metrics") if args.trace else \
        ("end_to_end", "end_to_end")
    line = {
        "correct": bool(record["correct"]),
        "attempted": int(record["attempted"]),
        "failed": int(record["failed"]),
        "metrics": harness.read_metrics(spec, group, kind, cell.name,
                                        record),
        "device": dict(record["device"]),
    }
    trace = record.get("trace")
    if args.trace:
        if not trace or not trace.get("busy_s"):
            sys.exit("the traced run found no device operation in its trace")
        line["device"]["busy_s"] = trace["busy_s"]
        line["device"]["window_s"] = trace["window_s"]
        line["breakdown"] = {"device_ops": trace["device_ops"][:10],
                             "idle_gaps": trace["idle_gaps"][:10]}
    diagnostics = {k: record.get(k) for k in (
        "checks", "setup", "counters", "in_window", "reference", "read_back",
        "losses", "device_path", "memory_stats", "save_seconds",
        "trace_bytes")}
    diagnostics["window"] = dict(record["window"],
                                 units=len(record["window"]["unit_ends"]))
    if trace:
        # Read by a person where no metric of the cell carries them.
        diagnostics["trace"] = {k: trace.get(k) for k in (
            "window_s", "busy_s", "collective_s", "mosaic_s",
            "steps_device_s")}
    diagnostics["spans_s"] = {
        name: {"n": len(spans), "median": harness.median(
            t1 - t0 for t0, t1 in spans),
            "min": min(t1 - t0 for t0, t1 in spans),
            "max": max(t1 - t0 for t0, t1 in spans)}
        for name, spans in record["spans"].items() if spans}
    # Each save's stall in its order: a run's first has no writer beside it.
    diagnostics["save_spans"] = [t1 - t0 for t0, t1 in
                                 record["spans"].get("save", [])]
    print("diagnostics: " + json.dumps(diagnostics))
    print(json.dumps(line))


if __name__ == "__main__":
    main()
