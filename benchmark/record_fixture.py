"""Record the small chip trace the benchmark's tests hold ``xplane.py`` to.

    chiprun -- python benchmark/record_fixture.py --chips 1
    chiprun --chips 4 -- python benchmark/record_fixture.py --chips 4

A tiny GPT (two layers, width 512, heads of 128, the flash kernels) takes
three steps under the profiler through the program's own step builder, each
inside the same ``bench/<what>`` host spans a runner writes, with a 20 ms
sleep in ``bench/report`` so that there is an idle gap to attribute. The
trace goes to ``chiprun_out/fixture_<n>chip.xplane.pb.gz`` beside a
description of what is in it (``.txt``) and the numbers ``reduce_trace``
read from it (``.json``); copy the first and the last into ``tests/data``.
There is no CPU mode.
"""

from __future__ import annotations

import argparse
import gzip
import json
import os
import sys
import tempfile
import time

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
sys.path[:0] = [ROOT, HERE]


def main(argv=None) -> None:
    parser = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    parser.add_argument("--chips", type=int, choices=(1, 4), default=1)
    parser.add_argument("--steps", type=int, default=3)
    args = parser.parse_args(argv)

    import jax
    import jax.numpy as jnp
    import numpy as np

    import xplane
    from ray_tpu.models import gpt
    from ray_tpu.parallel import MeshConfig, build_mesh
    from ray_tpu.parallel.sharding import ShardingRules
    from ray_tpu.parallel.train_step import (init_train_state,
                                             make_train_step,
                                             memory_efficient_optimizer)

    devices = jax.devices()
    if devices[0].platform != "tpu" or len(devices) < args.chips:
        sys.exit(f"record_fixture needs {args.chips} TPU chip(s); JAX found "
                 f"{len(devices)} x {devices[0].platform}")
    layout = {"dp": 1, "fsdp": 2, "tp": 2} if args.chips == 4 else \
        {"dp": 1, "fsdp": 1, "tp": 1}
    mesh = build_mesh(MeshConfig(**layout), devices=devices[:args.chips])
    cfg = gpt.config("gpt-tiny", vocab_size=2048, n_layers=2, d_model=512,
                     n_heads=4, d_ff=2048, rotary_dim=64, max_seq_len=512,
                     dtype=jnp.bfloat16, param_dtype=jnp.bfloat16,
                     remat=True, attn_impl="flash", loss_chunk=1024)
    rules, optimizer = ShardingRules(), memory_efficient_optimizer()
    state = init_train_state(cfg, mesh, rules, optimizer, seed=0)
    step = make_train_step(cfg, mesh, rules, optimizer)
    toks = np.random.default_rng(0).integers(0, cfg.vocab_size, (8, 513))
    batch = {"tokens": jnp.asarray(toks[:, :-1], jnp.int32),
             "targets": jnp.asarray(toks[:, 1:], jnp.int32)}
    for _ in range(2):
        state, metrics = step(state, batch)
        jax.block_until_ready(metrics)

    out_dir = os.path.join(ROOT, "chiprun_out")
    os.makedirs(out_dir, exist_ok=True)
    with tempfile.TemporaryDirectory() as trace_dir:
        jax.profiler.start_trace(trace_dir,
                                 profiler_options=xplane.trace_options())
        with jax.profiler.TraceAnnotation("bench/window"):
            for i in range(args.steps):
                with jax.profiler.TraceAnnotation("bench/step"):
                    state, metrics = step(state, batch)
                    jax.block_until_ready(metrics)
                with jax.profiler.TraceAnnotation("bench/report"):
                    float(metrics["loss"])
                    time.sleep(0.020)
        jax.profiler.stop_trace()
        path = xplane.find_xplane(trace_dir)
        with open(path, "rb") as f:
            raw = f.read()
    stem = os.path.join(out_dir, f"fixture_{args.chips}chip")
    with gzip.open(stem + ".xplane.pb.gz", "wb") as f:
        f.write(raw)
    data = xplane.load(stem + ".xplane.pb.gz")
    with open(stem + ".txt", "w") as f:
        f.write(xplane.describe(data, max_events=12))
    reduced = xplane.reduce_trace(data)
    reduced.pop("host_spans", None)
    with open(stem + ".json", "w") as f:
        json.dump(reduced, f, indent=1)
    print(f"{len(raw)} bytes of trace, "
          f"{os.path.getsize(stem + '.xplane.pb.gz')} gzipped")
    print(json.dumps(reduced)[:3000])


if __name__ == "__main__":
    main()
