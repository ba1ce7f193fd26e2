"""The program's gradients against the plain reference's, at the published
widths, on the chip. Too dear for every run's set-up (a second program the
size of the step), so it is run once per PR that touches the model's
arithmetic, and its result goes into PERF.md:

    chiprun -- python benchmark/check_grads.py --config <configuration>

The configuration's widths, dtypes, kernels, remat and chunked loss, cut to
``--layers`` layers so that float32 gradients fit beside the model; weights
from ``--seed`` with biases and LayerNorm vectors drawn away from their
init. The program differentiates ``gpt.loss_fn`` as the train step does
(bfloat16 parameters, so bfloat16 gradients); the reference differentiates
``reference/<family>.py``'s loss in float32 at the highest matmul
precision. Each leaf is compared by the Frobenius norm of the difference
over that of the reference's gradient. There is no CPU mode; the
benchmark's tests make the same comparison at a tiny width.
"""

from __future__ import annotations

import argparse
import json
import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
sys.path[:0] = [ROOT, HERE]


def main(argv=None) -> None:
    import harness
    parser = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    parser.add_argument("--config", required=True)
    parser.add_argument("--layers", type=int, default=2)
    parser.add_argument("--sequences", type=int, default=1)
    parser.add_argument("--seed", type=int, default=0)
    args = parser.parse_args(argv)

    import jax
    import jax.numpy as jnp
    import numpy as np

    from ray_tpu.models import gpt
    if jax.devices()[0].platform != "tpu":
        sys.exit(f"check_grads needs a TPU; JAX found {jax.devices()}")
    config = harness.load_json(os.path.join(HERE, "configs",
                                            args.config + ".json"))
    family = harness.load_module("families", "gpt")
    program = dict(config["program"])
    program["overrides"] = dict(program["overrides"], n_layers=args.layers)
    cfg = family.config(program)
    seq = config["layout"]["seq_len"]
    reference = harness.load_module("reference",
                                    config["reference"]["family"])

    params = family.draw_vectors(
        jax.jit(lambda key: gpt.init(cfg, key))(
            jax.random.PRNGKey(args.seed)), args.seed + 1)
    rows = np.random.default_rng(args.seed).integers(
        0, cfg.vocab_size, (args.sequences, seq + 1), dtype=np.int32)
    tokens, targets = jnp.asarray(rows[:, :-1]), jnp.asarray(rows[:, 1:])
    # One sequence a chunk, so that the chunked loss takes its path.
    check_cfg = cfg if args.sequences * seq > (cfg.loss_chunk or 0) else \
        family.replace(cfg, loss_chunk=seq // 2)
    got_loss, got = jax.jit(jax.value_and_grad(
        lambda p: gpt.loss_fn(p, check_cfg, tokens, targets)[0]))(params)
    params32 = jax.tree.map(lambda x: x.astype(jnp.float32), params)
    want_loss, want = jax.jit(jax.value_and_grad(
        lambda p: reference.loss(p, tokens, targets,
                                 **reference.arguments(config))))(params32)
    report = {"config": args.config, "layers": args.layers,
              "sequences": args.sequences, "seq_len": seq,
              "device": jax.devices()[0].device_kind,
              "loss": {"program": float(got_loss),
                       "reference": float(want_loss)}, "leaves": {}}
    flat_want = jax.tree_util.tree_leaves_with_path(want)
    for (path, w), g in zip(flat_want, jax.tree.leaves(got)):
        diff = jnp.linalg.norm((g.astype(jnp.float32) - w).ravel())
        report["leaves"][jax.tree_util.keystr(path)] = float(
            diff / jnp.linalg.norm(w.ravel()))
    report["worst"] = max(report["leaves"].values())
    os.makedirs(os.path.join(ROOT, "chiprun_out"), exist_ok=True)
    with open(os.path.join(ROOT, "chiprun_out", "check_grads.json"),
              "w") as f:
        json.dump(report, f, indent=1)
    print(json.dumps(report))


if __name__ == "__main__":
    main()
