"""Sets of runs of one cell, as the driver makes them, and their spread:
how a bound in ``BENCHMARK.json`` is measured.

    chiprun --timeout 1800 -- python3 benchmark/sets.py --workload <cell> \\
        [--sets 2] [--runs 6] [--seed 100] [--same-seeds] [--first 1] \\
        [--trace 0]

Each run is ``run.py`` in a process of its own (this one never touches JAX,
so the chip is free for each), with ``--seconds`` from ``BENCHMARK.json``
and a seed of its own: ``--seed`` + 100 * set + run, or with
``--same-seeds`` ``--seed`` + run in every set, as the driver's two sets
are. A cell whose runs fill the machine's disk makes a set a call:
``--sets 1 --first 2`` is the second set. Every run's output goes to
``chiprun_out/<cell>.set<k>.<seed>.out`` and its last line is printed, with
what its diagnostics line says of the loop's spans (how many, their
median) and the rate of its whole units; then, for each set, metric and
span, the median and the spread (the distance between the quartiles over
the median), leaving the call's first run out of ``setup_s`` because it may
compile. The spread is given three ways: by ``statistics.quantiles(values,
n=4)`` as it stands, which is the driver's rule and the wider; by the same
with the run farthest from the median left out, as the driver judges a
bound too tight; and with ``method="inclusive"`` (numpy's quartiles), by
which the readings in ``PERF.md`` up to PR 51 were taken. A bound is about
five times the widest spread over the cells, at least 0.01.
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
sys.path.insert(0, HERE)

import harness  # noqa: E402


def spread(values, method="exclusive"):
    """(median, (q3 - q1) / median)."""
    q1, q2, q3 = statistics.quantiles(values, n=4, method=method)
    return q2, (q3 - q1) / q2


def summary(label, values):
    """One line: the median and the spread three ways. As the driver reads
    it for a bound that is too loose (the quartiles of all the runs); as it
    reads it for one that is too tight (the run farthest from the median
    left out); and by inclusive quartiles."""
    median, rel = spread(values)
    text = f"{label}: median {median!r} spread {100 * rel:.3f} %"
    if len(values) >= 4:
        kept = sorted(values, key=lambda v: abs(v - median))[:-1]
        text += f", {100 * spread(kept)[1]:.3f} % without the farthest run"
    return (f"{text}, {100 * spread(values, 'inclusive')[1]:.3f} % by "
            f"inclusive quartiles, over {len(values)} runs")


def diagnostics_of(stdout):
    """What the run's ``diagnostics:`` line says of the loop: its spans
    ({name: {"n", "median", ...}}) and, as one more entry of one reading,
    the rate of its whole units by the end-to-end reader of it (in a traced
    set the line holds no end-to-end metric, and the rate is wanted beside
    the spans' spreads all the same). {} where there is no such line."""
    for line in reversed(stdout.splitlines()):
        if line.startswith("diagnostics: "):
            said = json.loads(line[len("diagnostics: "):])
            spans = dict(said.get("spans_s", {}))
            rate = harness.load_module("end_to_end", "tokens_per_s").read(
                {"window": said["window"]})
            if rate is not None:
                spans["whole units, tokens/s"] = {
                    "n": said["window"]["units"], "median": rate}
            return spans
    return {}


def main(argv=None) -> None:
    parser = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    parser.add_argument("--workload", required=True)
    parser.add_argument("--sets", type=int, default=2)
    parser.add_argument("--runs", type=int, default=6)
    parser.add_argument("--seed", type=int, default=100)
    parser.add_argument("--same-seeds", action="store_true")
    parser.add_argument("--first", type=int, default=1,
                        help="number of this call's first set")
    parser.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = parser.parse_args(argv)
    spec = harness.load_spec()
    out_dir = os.path.join(ROOT, "chiprun_out")
    os.makedirs(out_dir, exist_ok=True)

    sets = []
    for s in range(args.first, args.first + args.sets):
        lines, spans = [], []
        for r in range(args.runs):
            seed = args.seed + r + (
                0 if args.same_seeds else 100 * (s - 1))
            done = subprocess.run(
                spec["command"] + [
                    "--workload", args.workload, "--seed", str(seed),
                    "--seconds", str(spec["run_seconds"]),
                    "--trace", str(args.trace)],
                cwd=ROOT, capture_output=True, text=True)
            with open(os.path.join(
                    out_dir, f"{args.workload}.set{s}.{seed}.out"),
                    "w") as f:
                f.write(done.stdout + "\n--- stderr\n" + done.stderr[-4000:])
            last = (done.stdout.strip().splitlines() or [""])[-1]
            print(f"set {s} run {r + 1} seed {seed} rc "
                  f"{done.returncode}: {last}", flush=True)
            if done.returncode == 0:
                lines.append(json.loads(last))
                spans.append(diagnostics_of(done.stdout))
                print(f"set {s} run {r + 1} spans: " + ", ".join(
                    f"{name} n {v['n']} median {v['median']:.4f}"
                    for name, v in sorted(spans[-1].items())), flush=True)
        sets.append((s, lines, spans))

    for s, lines, spans in sets:
        for name in sorted({m for line in lines for m in line["metrics"]}):
            values = [line["metrics"][name]["value"] for line in lines
                      if name in line["metrics"]]
            if name == "setup_s" and s == args.first:
                values = values[1:]
            if len(values) >= 2:
                print(summary(f"set {s} {name}", values))
        for name in sorted({n for run in spans for n in run}):
            seen = [run[name] for run in spans if name in run]
            if len(seen) >= 2:
                print(summary(f"set {s} span {name} (a run's median), "
                              f"counts {[v['n'] for v in seen]}",
                              [v["median"] for v in seen]))
    if not all(line["correct"] for _, lines, _ in sets for line in lines) \
            or any(len(lines) < args.runs for _, lines, _ in sets):
        sys.exit("a run failed or was not correct")


if __name__ == "__main__":
    main()
