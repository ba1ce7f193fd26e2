"""The three rehearsals that cost no chip time, for every cell, in one
command (on-chip-measurement guide, section 2). Run it before any chip call
and after adding a cell:

    python benchmark/rehearse.py [--workload <name>] [--skip-compile]

1. Each cell end to end here at a tiny size (``runner.shrink``), on the CPU
   with the Pallas kernels interpreted, once plain and once traced: wrong
   paths, arguments and control flow.
2. The four-chip cells on four virtual CPU devices (the same run, given
   ``--xla_force_host_platform_device_count=4``): wrong meshes and
   sharding rules.
3. Each configuration's real step compiled for a described ``v5e:2x2``
   topology (``runner.compile_for``), with ``memory_analysis()`` printed:
   what the chip's compiler refuses, and whether the cell fits.

Nothing here is a measurement: a time from this file is never written under
the name of a device metric.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

os.environ["JAX_PLATFORMS"] = "cpu"
os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "") +
                           " --xla_force_host_platform_device_count=4")
os.environ.setdefault("TPU_LOG_DIR", "disabled")

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path[:0] = [os.path.dirname(HERE), HERE]


def main(argv=None) -> None:
    import harness
    parser = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    parser.add_argument("--workload", action="append")
    parser.add_argument("--skip-compile", action="store_true")
    parser.add_argument("--skip-tiny", action="store_true")
    args = parser.parse_args(argv)

    spec = harness.load_spec()
    names = args.workload or [w["name"] for w in spec["workloads"]]
    failed = []
    if not args.skip_tiny:
        for name in names:
            cell = harness.load_cell(spec, name)
            runner = harness.load_module("runners", cell.traffic["runner"])
            for trace in (False, True):
                record = runner.run(runner.shrink(cell), harness.RunArgs(
                    seed=0, seconds=6.0, trace=trace,
                    t_start=time.perf_counter(), rehearsal=True))
                group, kind = ("per_layer", "layer_metrics") if trace else \
                    ("end_to_end", "end_to_end")
                try:
                    named = harness.read_metrics(spec, group, kind, name,
                                                 record)
                except ValueError as exc:  # no peak for a CPU: as it must
                    named = {str(exc): None}
                print(f"tiny {name} trace={int(trace)}: correct "
                      f"{record['correct']} {record['checks']} attempted "
                      f"{record['attempted']} failed {record['failed']}; "
                      f"readers answered for {sorted(named)}; reference "
                      f"{record['reference']}")
                if not record["correct"]:
                    failed.append(name)

    if not args.skip_compile:
        import jax
        from jax.experimental import topologies
        from jax.experimental.compilation_cache import compilation_cache
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
        # The kernels ask jax.default_backend() whether to interpret, and
        # that still says cpu here. An executable built for a described
        # chip cannot be read back without one: no persistent cache.
        import ray_tpu.ops  # noqa: F401
        flash = sys.modules["ray_tpu.ops.flash_attention"]
        interpret, flash._interpret = flash._interpret, lambda: False
        jax.config.update("jax_enable_compilation_cache", False)
        compilation_cache.reset_cache()
        try:
            done = set()
            for name in names:
                cell = harness.load_cell(spec, name)
                if cell.config_name in done:
                    continue
                done.add(cell.config_name)
                runner = harness.load_module("runners",
                                             cell.traffic["runner"])
                report = runner.compile_for(cell, topo.devices[:cell.chips])
                print(f"compiled {cell.config_name} for v5e:2x2 "
                      f"({cell.chips} chip(s)): {json.dumps(report)}")
                if report["problems"] or not report["kernel_calls"]:
                    failed.append(cell.config_name)
        finally:
            flash._interpret = interpret
    if failed:
        sys.exit(f"rehearsal failed for {failed}")
    print("rehearsals passed")


if __name__ == "__main__":
    main()
