"""GPT-J fine-tune through JaxTrainer — the north-star workload.

BASELINE.json's headline: fine-tune GPT-J-6B at >=40% MFU on a v4-64 via
``JaxTrainer`` with pjit/GSPMD sharding, no GPU resources requested. This
script is that workload, parameterized so the same code runs three ways:

* ``--preset gpt-tiny`` (default): smoke-run anywhere on a virtual CPU
  mesh (the SURVEY §4 fake-TPU strategy) — CI-sized shapes.
* ``--preset gpt-410m``: the single-chip benchmark model (bench.py's
  tuned recipe: Pallas flash attention, selective remat, chunked CE).
* ``--preset gptj-6b``: the real thing on a TPU pod slice — the mesh in
  ScalingConfig is laid over the slice's ICI topology, parameters are
  initialized directly in sharded form (a 6B model never materializes on
  one host), gradients psum over ICI.

Run (CPU mesh smoke):
    XLA_FLAGS=--xla_force_host_platform_device_count=8 \\
        python examples/gptj_finetune.py --steps 4 --cpu-mesh
"""

from __future__ import annotations

import argparse
import time


def train_loop(config: dict) -> None:
    import numpy as np

    import jax.numpy as jnp

    from ray_tpu.air import session
    from ray_tpu.models import gpt
    from ray_tpu.parallel import MeshConfig
    from ray_tpu.parallel.sharding import ShardingRules
    from ray_tpu.parallel.train_step import (default_optimizer,
                                             init_train_state,
                                             make_train_step)
    from ray_tpu.train import prepare_mesh

    mesh = prepare_mesh(MeshConfig(**config["mesh"]))
    cfg = gpt.config(config["preset"], **config.get("overrides", {}))
    rules = ShardingRules(
        sequence="sp" if config["mesh"].get("sp", 1) > 1 else None)
    optimizer = default_optimizer(learning_rate=config["lr"],
                                  total_steps=config["steps"])
    state = init_train_state(cfg, mesh, rules, optimizer,
                             seed=config["seed"])
    step = make_train_step(cfg, mesh, rules, optimizer)

    # Synthetic next-token data; swap in ray_tpu.data iter_jax_batches for
    # a real corpus (session.get_dataset_shard gives the per-worker shard).
    rng = np.random.default_rng(config["seed"] + session.get_world_rank())
    batch, seq = config["batch"], config["seq"]
    n_params = cfg.num_params()
    flops_per_token = gpt.flops_per_token(cfg)
    # Per-device peak matmul FLOP/s for the MFU estimate (same table as
    # bench.py); meaningless on the CPU smoke run, labeled accordingly.
    import jax
    kind = mesh.devices.flat[0].device_kind.lower()
    peaks = {"tpu v4": 275e12, "tpu v5 lite": 197e12, "tpu v5": 459e12,
             "tpu v6 lite": 918e12}
    peak = next((v for k, v in peaks.items() if k in kind), None)
    n_devices = mesh.size

    for i in range(config["steps"]):
        toks = rng.integers(0, cfg.vocab_size, (batch, seq + 1))
        data = {"tokens": jnp.asarray(toks[:, :-1], jnp.int32),
                "targets": jnp.asarray(toks[:, 1:], jnp.int32)}
        t0 = time.perf_counter()
        state, metrics = step(state, data)
        jax.block_until_ready(metrics)
        dt = time.perf_counter() - t0
        loss = float(metrics["loss"])
        tokens_per_s = batch * seq / dt
        report = {
            "step": i,
            "loss": loss,
            "tokens_per_s": tokens_per_s,
            "n_params": n_params,
        }
        if peak is not None:
            report["approx_mfu"] = (tokens_per_s * flops_per_token
                                    / (peak * n_devices))
        session.report(report)


def main():
    parser = argparse.ArgumentParser()
    parser.add_argument("--preset", default="gpt-tiny",
                        choices=["gpt-tiny", "gpt-410m", "gptj-6b"])
    parser.add_argument("--steps", type=int, default=4)
    parser.add_argument("--batch", type=int, default=None)
    parser.add_argument("--seq", type=int, default=None)
    parser.add_argument("--num-workers", type=int, default=1)
    parser.add_argument("--dp", type=int, default=2)
    parser.add_argument("--fsdp", type=int, default=2)
    parser.add_argument("--tp", type=int, default=2)
    parser.add_argument("--sp", type=int, default=1)
    parser.add_argument("--lr", type=float, default=1e-5)
    parser.add_argument("--cpu-mesh", action="store_true",
                        help="run on JAX's virtual CPU devices and reserve "
                             "no chips")
    args = parser.parse_args()

    import ray_tpu
    from ray_tpu._private.jax_compat import enable_compile_cache
    from ray_tpu.air import ScalingConfig
    from ray_tpu.train import JaxTrainer

    enable_compile_cache()
    if args.cpu_mesh:
        import jax
        jax.config.update("jax_platforms", "cpu")

    ray_tpu.init()
    sizes = {"gpt-tiny": (4, 128), "gpt-410m": (16, 1024),
             "gptj-6b": (32, 2048)}
    batch, seq = sizes[args.preset]
    overrides = {}
    if args.preset != "gpt-tiny":
        # bench.py's tuned single-chip recipe scales up unchanged.
        overrides = dict(attn_impl="flash", remat_policy="selective",
                         loss_chunk=2048)
    trainer = JaxTrainer(
        train_loop,
        train_loop_config={
            "preset": args.preset,
            "overrides": overrides,
            "mesh": {"dp": args.dp, "fsdp": args.fsdp, "tp": args.tp,
                     "sp": args.sp},
            "steps": args.steps,
            "batch": args.batch or batch,
            "seq": args.seq or seq,
            "lr": args.lr,
            "seed": 0,
        },
        # Each worker reserves the chips its mesh spans. Without them the
        # trainer cannot be placed, and says so; only the CPU-mesh smoke
        # run (fake-TPU strategy) asks for none.
        scaling_config=ScalingConfig(
            num_workers=args.num_workers,
            use_tpu=not args.cpu_mesh,
            tpus_per_worker=(args.dp * args.fsdp * args.tp * args.sp
                             // args.num_workers)),
    )
    result = trainer.fit()
    print("final metrics:", result.metrics)


if __name__ == "__main__":
    main()
