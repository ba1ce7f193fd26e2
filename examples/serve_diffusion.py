"""Stable-Diffusion-style batch inference on Serve TPU replicas.

BASELINE.json config 5: "Ray Serve Stable-Diffusion batch inference on
TPU replicas". A Serve deployment holds the jitted DDIM sampler
(models/diffusion.py — the whole reverse process is ONE compiled XLA
program); ``@serve.batch`` coalesces concurrent requests into one device
batch, so replica throughput rides the chip's batched UNet rate instead
of request-at-a-time latency. Each replica reserves one chip and keeps its
parameters there, so several replicas on one host never share a chip.

Run (CPU smoke, tiny UNet):
    python examples/serve_diffusion.py --cpu --preset unet-tiny --requests 8

Run (one TPU chip, SD-shaped latent UNet):
    python examples/serve_diffusion.py --preset sd-base --requests 8
"""

from __future__ import annotations

import argparse
import time

import numpy as np

from ray_tpu import serve


@serve.deployment(name="diffusion", ray_actor_options={"num_tpus": 1})
class DiffusionModel:
    def __init__(self, preset: str, ddim_steps: int, max_batch: int):
        import jax

        import ray_tpu
        from ray_tpu._private.jax_compat import enable_compile_cache
        from ray_tpu.models import diffusion

        enable_compile_cache()
        self.cfg = diffusion.config(preset)
        # The chip this replica reserved. A key committed there takes the
        # jitted init, and so the parameters, to the same device.
        device = ray_tpu.get_tpu_devices()[0]
        self.params = jax.jit(lambda key: diffusion.init(self.cfg, key))(
            jax.device_put(jax.random.PRNGKey(0), device))
        self._seed = 0
        # One compiled program: every batch is sampled at max_batch and
        # cut to the demand, so a short tail never compiles a new shape
        # in front of a waiting request.
        self._sample = jax.jit(lambda params, key: diffusion.ddim_sample(
            params, self.cfg, key, max_batch, n_steps=ddim_steps))
        # Compile it here, before the replica reports ready: a compile
        # inside a request blocks the replica's event loop, and with it
        # the controller's health probes.
        jax.block_until_ready(
            self._sample(self.params, jax.random.PRNGKey(self._seed)))

        # Dynamic batching: concurrent callers coalesce into one
        # device batch (reference: serve/batching.py).
        @serve.batch(max_batch_size=max_batch, batch_wait_timeout_s=0.05)
        async def generate(prompts):
            self._seed += 1
            imgs = np.asarray(self._sample(
                self.params, jax.random.PRNGKey(self._seed)))
            return [imgs[i] for i in range(len(prompts))]

        self._generate = generate

    async def __call__(self, prompt: str = "an image"):
        return await self._generate(prompt)

    def placement(self) -> dict:
        """Where the parameters live, as JAX reports it."""
        import jax
        devices = {d for leaf in jax.tree.leaves(self.params)
                   for d in leaf.devices()}
        return {"platforms": sorted({d.platform for d in devices}),
                "device_ids": sorted(d.id for d in devices)}


def main() -> None:
    parser = argparse.ArgumentParser()
    parser.add_argument("--preset", default="unet-tiny",
                        choices=["unet-tiny", "ddpm-cifar", "sd-base"])
    parser.add_argument("--requests", type=int, default=8)
    parser.add_argument("--ddim-steps", type=int, default=10)
    parser.add_argument("--max-batch", type=int, default=8)
    parser.add_argument("--cpu", action="store_true",
                        help="run on JAX's CPU backend and reserve no chip")
    args = parser.parse_args()

    import jax

    import ray_tpu

    deployment = DiffusionModel
    if args.cpu:
        jax.config.update("jax_platforms", "cpu")
        deployment = DiffusionModel.options(ray_actor_options={})
    # The replica compiles its programs before it reports ready: minutes
    # for sd-base on a cold cache, against a default bound of 30 s.
    ray_tpu.init(_system_config={"serve_startup_timeout_s": 1200.0})

    handle = serve.run(deployment.bind(
        args.preset, args.ddim_steps, args.max_batch))

    img = ray_tpu.get(handle.remote("first"))
    print(f"image shape: {np.asarray(img).shape}")

    t0 = time.perf_counter()
    refs = [handle.remote(f"prompt {i}") for i in range(args.requests)]
    imgs = ray_tpu.get(refs)
    dt = time.perf_counter() - t0
    print(f"{len(imgs)} images in {dt:.2f}s "
          f"({len(imgs) / dt:.2f} images/s, preset={args.preset}, "
          f"ddim_steps={args.ddim_steps}, "
          f"placement={ray_tpu.get(handle.placement.remote())})")
    ray_tpu.shutdown()


if __name__ == "__main__":
    main()
