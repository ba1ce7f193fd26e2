"""chip_smoke.py: the product's main path, once, on the TPU.

The quickest proof that ray_tpu still starts on the chip. One process
drives, through the entry points a user calls:

* Train  ``ray_tpu.init()`` finds the chip by itself, then
         ``JaxTrainer(...).fit()`` takes real ``gpt-1.3b`` steps (full width
         and depth, Pallas flash attention, full remat, chunked loss,
         adafactor) on one repeated batch and the loss falls.
* Serve  ``serve.run`` of the DDIM deployment in examples/serve_diffusion.py
         (``ddpm-cifar``, ``@serve.batch``) answers requests from a replica
         whose parameters live on the chip.
* Cache  the persistent compile cache took entries; a second run hits.

``python chip_smoke.py`` needs one chip. ``--chips 4`` runs instead, and
only, the sharded step (one worker, four chips, ``fsdp=2 x tp=2``) against
the same step on one device. Any failed phase raises: there is no CPU mode
and nothing is caught. The last stdout line is the one JSON object the
driver reads; everything else is on earlier lines. This is a smoke, not a
benchmark: its times are printed to be looked at, not compared.
"""

from __future__ import annotations

import argparse
import importlib.util
import json
import math
import os
import statistics
import sys
import time

_HERE = os.path.dirname(os.path.abspath(__file__))

# gpt-1.3b as bench.py's recorded single-chip recipe runs it.
TRAIN_OVERRIDES = dict(attn_impl="flash", remat_policy="full",
                       loss_chunk=4096)


def check(ok: bool, what) -> None:
    """A failed check fails the smoke (under ``python -O`` too, where an
    assert statement would pass in silence)."""
    if not ok:
        raise AssertionError(what)


def train_loop(config: dict) -> None:
    """examples/gptj_finetune.py's loop shape, on one repeated batch from a
    fixed seed, reporting what the checks below need."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from ray_tpu.air import session
    from ray_tpu.models import gpt
    from ray_tpu.parallel import MeshConfig
    from ray_tpu.parallel.sharding import ShardingRules
    from ray_tpu.parallel.train_step import (init_train_state,
                                             make_train_step,
                                             memory_efficient_optimizer)
    from ray_tpu.train import prepare_mesh

    mesh = prepare_mesh(MeshConfig(**config["mesh"]))
    cfg = gpt.config(config["preset"], max_seq_len=config["seq"],
                     **config["overrides"])
    rules = ShardingRules()
    # warmup_steps=1: the first update has lr 0, every later one the full
    # rate, so a handful of steps moves the loss.
    optimizer = memory_efficient_optimizer(learning_rate=1e-4,
                                           warmup_steps=1)
    state = init_train_state(cfg, mesh, rules, optimizer,
                             seed=config["seed"])
    step = make_train_step(cfg, mesh, rules, optimizer)

    toks = np.random.default_rng(config["seed"]).integers(
        0, cfg.vocab_size, (config["batch"], config["seq"] + 1))
    data = {"tokens": jnp.asarray(toks[:, :-1], jnp.int32),
            "targets": jnp.asarray(toks[:, 1:], jnp.int32)}
    for i in range(config["steps"]):
        t0 = time.perf_counter()
        state, metrics = step(state, data)
        jax.block_until_ready(metrics)
        session.report({"step": i, "loss": float(metrics["loss"]),
                        "step_s": time.perf_counter() - t0})

    device = mesh.devices.flat[0]
    # The step as it was lowered for this device: its text says whether
    # the Mosaic kernels are in it (interpret mode and the blockwise path
    # leave no tpu_custom_call).
    text = step.lower(state, data).as_text()
    session.report({"summary": {
        "platform": device.platform,
        "device_ids": sorted(d.id for d in mesh.devices.flat),
        "kernel_calls": text.count("tpu_custom_call"),
        "vocab_size": cfg.vocab_size,
        "param_bytes_on_first_device": sum(
            shard.data.nbytes
            for leaf in jax.tree.leaves(state["params"])
            for shard in leaf.addressable_shards
            if shard.device == device),
        "memory_stats": device.memory_stats() or {},
    }})


def run_trainer(preset: str, batch: int, seq: int, steps: int, *,
                chips: int = 1, mesh: dict | None = None,
                overrides: dict | None = None) -> dict:
    """One ``JaxTrainer.fit()`` with one worker holding ``chips`` chips;
    returns {"losses", "step_s", **summary}."""
    from ray_tpu.air import ScalingConfig
    from ray_tpu.train import JaxTrainer

    mesh = {"dp": 1, "fsdp": 1, "tp": 1, **(mesh or {})}
    result = JaxTrainer(
        train_loop,
        train_loop_config={
            "preset": preset, "batch": batch, "seq": seq, "steps": steps,
            "seed": 0, "mesh": mesh,
            "overrides": TRAIN_OVERRIDES if overrides is None else overrides},
        scaling_config=ScalingConfig(num_workers=1, use_tpu=True,
                                     tpus_per_worker=chips),
    ).fit()
    *per_step, last = result.metrics_history
    report = dict(last["summary"],
                  losses=[m["loss"] for m in per_step],
                  step_s=[m["step_s"] for m in per_step])
    check(len(report["losses"]) == steps, report)
    check(all(math.isfinite(x) for x in report["losses"]), report["losses"])
    check(len(set(report["device_ids"])) == chips, report["device_ids"])
    return report


def train_phase(preset: str, batch: int, seq: int, steps: int,
                overrides: dict | None = None) -> dict:
    """One chip: finite losses that start near ln(vocab) and fall."""
    report = run_trainer(preset, batch, seq, steps, overrides=overrides)
    losses = report["losses"]
    uniform = math.log(report["vocab_size"])
    check(abs(losses[1] - uniform) < 1.0, (losses, uniform))
    check(losses[-1] < losses[0], losses)
    mem = report["memory_stats"]
    print(f"train: {preset} batch {batch} seq {seq}: losses "
          f"{[round(x, 4) for x in losses]} (ln vocab {uniform:.2f})")
    print(f"train: step seconds {[round(t, 3) for t in report['step_s']]} "
          f"(host clock around block_until_ready; the first includes the "
          f"compile), median of the rest "
          f"{statistics.median(report['step_s'][1:]):.4f}")
    print(f"train: platform {report['platform']}, devices "
          f"{report['device_ids']}, tpu_custom_call x"
          f"{report['kernel_calls']}, params on device "
          f"{report['param_bytes_on_first_device'] / 1e9:.2f} GB, "
          f"peak_bytes_in_use {mem.get('peak_bytes_in_use')}, "
          f"peak_bytes_reserved {mem.get('peak_bytes_reserved')}, "
          f"bytes_limit {mem.get('bytes_limit')}")
    return report


def mesh_phase(preset: str, batch: int, seq: int, steps: int,
               overrides: dict | None = None) -> dict:
    """Four chips in one worker, ``fsdp=2 x tp=2``, against the same
    config, seed and batch on a one-device mesh in the same process."""
    import jax.numpy as jnp
    if overrides is None:
        overrides = dict(TRAIN_OVERRIDES, param_dtype=jnp.bfloat16)
    sharded = run_trainer(preset, batch, seq, steps, chips=4,
                          mesh={"fsdp": 2, "tp": 2}, overrides=overrides)
    single = run_trainer(preset, batch, seq, steps, overrides=overrides)
    share = (sharded["param_bytes_on_first_device"]
             / single["param_bytes_on_first_device"])
    gaps = [abs(a - b) for a, b in zip(sharded["losses"], single["losses"])]
    print(f"mesh: {preset} batch {batch} seq {seq} on devices "
          f"{sharded['device_ids']} (fsdp=2 x tp=2) vs device "
          f"{single['device_ids']}")
    print(f"mesh: losses sharded {[round(x, 4) for x in sharded['losses']]}"
          f" single {[round(x, 4) for x in single['losses']]} max gap "
          f"{max(gaps):.4f}")
    print(f"mesh: params on first device "
          f"{sharded['param_bytes_on_first_device']} B sharded, "
          f"{single['param_bytes_on_first_device']} B single "
          f"({share:.3f} of it); tpu_custom_call x{sharded['kernel_calls']} "
          f"sharded, x{single['kernel_calls']} single; step seconds "
          f"sharded {[round(t, 3) for t in sharded['step_s']]} single "
          f"{[round(t, 3) for t in single['step_s']]}")
    # fsdp x tp splits every matrix four ways and only the small vectors
    # stay whole, so the share sits at or just above a quarter.
    check(0.2 < share <= 0.5, share)
    check(max(gaps) < 2e-2, gaps)
    return {"sharded": sharded, "single": single}


def serve_phase(preset: str, batch: int, steps: int, requests: int) -> dict:
    """The DDIM deployment of examples/serve_diffusion.py behind
    ``serve.run``: ``requests`` requests through the handle, batched up to
    ``batch``, ``steps`` DDIM steps each."""
    import numpy as np

    import ray_tpu
    from ray_tpu import serve
    from ray_tpu.models import diffusion

    spec = importlib.util.spec_from_file_location(
        "serve_diffusion", os.path.join(_HERE, "examples",
                                        "serve_diffusion.py"))
    example = importlib.util.module_from_spec(spec)
    sys.modules[spec.name] = example  # the replica unpickles it by name
    spec.loader.exec_module(example)

    t0 = time.perf_counter()
    handle = serve.run(example.DiffusionModel.bind(preset, steps, batch))
    start_s = time.perf_counter() - t0
    try:
        t0 = time.perf_counter()
        images = ray_tpu.get([handle.remote(f"prompt {i}")
                              for i in range(requests)])
        burst_s = time.perf_counter() - t0
        placement = ray_tpu.get(handle.placement.remote())
    finally:
        serve.shutdown()
    cfg = diffusion.config(preset)
    shape = (cfg.image_size, cfg.image_size, cfg.in_channels)
    check(len(images) == requests, len(images))
    for img in images:
        img = np.asarray(img)
        check(img.shape == shape, img.shape)
        check(np.isfinite(img).all(), "non-finite image")
    check(len(placement["device_ids"]) == 1, placement)
    print(f"serve: {preset} DDIM-{steps}, batch <= {batch}: replica ready "
          f"in {start_s:.2f}s (its compiles included), then {requests} "
          f"requests answered in {burst_s:.3f}s, images {shape}, params on "
          f"{placement}")
    return {"placement": placement, "answered": len(images)}


def main(argv=None) -> None:
    parser = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    parser.add_argument(
        "--chips", type=int, choices=(1, 4), default=1,
        help="4: run only the four-chip sharded step and its one-device "
             "comparison (default 1: the Train, Serve and Cache phases)")
    args = parser.parse_args(argv)

    # init() first, in a process that has not imported JAX, with no
    # num_tpus argument and no faked count: it must find the chips itself.
    import ray_tpu
    check("RAY_TPU_NUM_CHIPS" not in os.environ, "RAY_TPU_NUM_CHIPS is set")
    t0 = time.perf_counter()
    # The DDIM replica compiles its programs before it reports ready (27 s
    # on a cold cache), so Serve's 30 s start-up bound gets room.
    ray_tpu.init(_system_config={"serve_startup_timeout_s": 300.0})
    init_s = time.perf_counter() - t0
    check("jax" not in sys.modules, "ray_tpu.init() imported jax")
    try:
        import jax

        from ray_tpu._private.jax_compat import (compile_cache_dir,
                                                 enable_compile_cache)
        devices = jax.devices()
        device = {"platform": devices[0].platform,
                  "kind": devices[0].device_kind, "count": len(devices)}
        if device["platform"] != "tpu":
            sys.exit(f"chip_smoke needs a TPU; JAX found {device}")
        if device["count"] < args.chips:
            sys.exit(f"chip_smoke --chips {args.chips} needs that many "
                     f"chips; JAX found {device['count']}")
        print(f"device: {device}")
        resources = ray_tpu.cluster_resources()
        print(f"init: {init_s:.2f}s, resources "
              f"{ {k: v for k, v in resources.items() if k != 'memory'} }")
        check(resources.get("TPU") == device["count"], resources)

        enable_compile_cache()
        cache_dir = compile_cache_dir()
        cache = {"hits": 0, "misses": 0}

        def count_cache_event(event: str, **_) -> None:
            if event.endswith("/cache_hits"):
                cache["hits"] += 1
            elif event.endswith("/cache_misses"):
                cache["misses"] += 1

        def cache_entries() -> int:
            # JAX keeps an access-time file beside each cached executable.
            return sum(not name.endswith("-atime")
                       for name in os.listdir(cache_dir)) if os.path.isdir(
                           cache_dir) else 0

        jax.monitoring.register_event_listener(count_cache_event)
        entries_before = cache_entries()

        if args.chips == 4:
            mesh = mesh_phase("gpt-1.3b", batch=8, seq=1024, steps=3)
            for report in mesh.values():
                check(report["platform"] == "tpu", report["platform"])
                check(report["kernel_calls"] > 0,
                      "no Mosaic kernel in the step")
        else:
            train = train_phase("gpt-1.3b", batch=12, seq=1024, steps=6)
            check(train["platform"] == "tpu", train["platform"])
            check(train["kernel_calls"] > 0, "no Mosaic kernel in the step")
            served = serve_phase("ddpm-cifar", batch=8, steps=10,
                                 requests=16)
            check(served["placement"]["platforms"] == ["tpu"], served)
    finally:
        ray_tpu.shutdown()

    from ray_tpu._private import native_build
    print(f"native engines built from src/ray_tpu_native: "
          f"{native_build.built_components()}")
    entries = cache_entries()
    print(f"cache: {cache_dir}: {entries_before} entries before, {entries} "
          f"after; this run hit {cache['hits']} and missed "
          f"{cache['misses']}")
    check(entries > 0, f"nothing was cached under {cache_dir}")
    print(json.dumps({"ok": True, "device": device}))


if __name__ == "__main__":
    main()
