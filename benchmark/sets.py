"""Sets of runs of one cell, as the driver makes them, and their spread:
how a bound in ``BENCHMARK.json`` is measured.

    chiprun --timeout 1800 -- python3 benchmark/sets.py --workload <cell> \\
        [--sets 2] [--runs 6] [--seed 100] [--trace 0]

Each run is ``run.py`` in a process of its own (this one never touches JAX,
so the chip is free for each), with ``--seconds`` from ``BENCHMARK.json``
and a seed of its own: ``--seed`` + 100 * set + run. Every run's output goes
to ``chiprun_out/<cell>.<seed>.out`` and its last line is printed; then, for
each set and metric, the median and the spread (the distance between the
quartiles over the median), leaving the first run of the first set out of
``setup_s`` because it may compile. A bound is about five times the widest
spread over the cells, at least 0.01.
"""

from __future__ import annotations

import argparse
import json
import os
import statistics
import subprocess
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)


def spread(values):
    """(median, (q3 - q1) / median), quartiles by linear interpolation."""
    q1, q2, q3 = statistics.quantiles(values, n=4, method="inclusive")
    return q2, (q3 - q1) / q2


def main(argv=None) -> None:
    parser = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    parser.add_argument("--workload", required=True)
    parser.add_argument("--sets", type=int, default=2)
    parser.add_argument("--runs", type=int, default=6)
    parser.add_argument("--seed", type=int, default=100)
    parser.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = parser.parse_args(argv)
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        spec = json.load(f)
    out_dir = os.path.join(ROOT, "chiprun_out")
    os.makedirs(out_dir, exist_ok=True)

    sets = []
    for s in range(args.sets):
        lines = []
        for r in range(args.runs):
            seed = args.seed + 100 * s + r
            done = subprocess.run(
                spec["command"] + [
                    "--workload", args.workload, "--seed", str(seed),
                    "--seconds", str(spec["run_seconds"]),
                    "--trace", str(args.trace)],
                cwd=ROOT, capture_output=True, text=True)
            with open(os.path.join(
                    out_dir, f"{args.workload}.{seed}.out"), "w") as f:
                f.write(done.stdout + "\n--- stderr\n" + done.stderr[-4000:])
            last = (done.stdout.strip().splitlines() or [""])[-1]
            print(f"set {s + 1} run {r + 1} seed {seed} rc "
                  f"{done.returncode}: {last}", flush=True)
            if done.returncode == 0:
                lines.append(json.loads(last))
        sets.append(lines)

    for s, lines in enumerate(sets):
        for name in sorted({m for line in lines for m in line["metrics"]}):
            values = [line["metrics"][name]["value"] for line in lines
                      if name in line["metrics"]]
            if name == "setup_s" and s == 0:
                values = values[1:]
            if len(values) >= 2:
                median, rel = spread(values)
                print(f"set {s + 1} {name}: median {median!r} spread "
                      f"{100 * rel:.3f} % over {len(values)} runs")
    if not all(line["correct"] for lines in sets for line in lines) or \
            any(len(lines) < args.runs for lines in sets):
        sys.exit("a run failed or was not correct")


if __name__ == "__main__":
    main()
