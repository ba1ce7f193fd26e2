"""The ``nemotron_h`` family's gradients against the plain reference's, at
the published widths, on the chip (``check_grads.py`` is typed to the
``gpt`` family and may not be edited). Run once per PR that touches the
model's arithmetic; its result goes into PERF.md:

    chiprun -- python benchmark/check_grads_nemotron_h.py --config <configuration>

The configuration's widths, dtypes, kernels, remat, chunked loss and share of
the experts, on ``--layers`` of its layers from published layer ``--first``
(3 from 40: an expert layer, a state-space layer and the attention layer,
every kind the cell has, in the cell's order) and one sequence of ``--seq``
tokens (1024: four chunks of the scan; the reference walks the recurrence
a position at a time and keeps its state at every position for the backward
pass, 2 MB a position at these widths: 2 GB a layer, and at 2048 tokens its
program asked the chip for 13.7 GB and was refused); weights from
``--seed`` as the cell draws them. The program differentiates its loss as
the train step does (bfloat16 parameters, so bfloat16 gradients). The
reference differentiates ``reference/<family>.py``'s loss, float32 inside at
the highest matmul precision, with respect to the same bfloat16 leaves, so
its gradient is rounded once, on the way out (0.1 % of a leaf's norm). Each
leaf is compared by the Frobenius norm of the difference over that of the
reference's gradient (the correction bias steers the selection alone: both
sides give it none, and it reads 0). Tokens whose experts differ between the
two contribute another gradient, which the experts' weights and the router
show most. ``reference.grad_tol`` is set about 2x above the worst leaf
measured, and the run exits non-zero above it. There is no CPU mode; the
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


def stretch(config, family, first: int, layers: int):
    """The configuration on ``layers`` layers from published layer
    ``first`` (file and program alike)."""
    config = family.with_layers(config, layers)
    program = dict(config["program"])
    program["overrides"] = dict(program["overrides"], first_layer=first)
    deployment = dict(config["deployment"],
                      layers_run={"first": first, "count": layers})
    return dict(config, program=program, deployment=deployment)


def main(argv=None) -> None:
    import harness
    parser = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    parser.add_argument("--config", required=True)
    parser.add_argument("--first", type=int, default=40)
    parser.add_argument("--layers", type=int, default=3)
    parser.add_argument("--seq", type=int, default=1024)
    parser.add_argument("--seed", type=int, default=0)
    args = parser.parse_args(argv)

    import jax
    import jax.numpy as jnp
    import numpy as np
    if jax.devices()[0].platform != "tpu":
        sys.exit(f"check_grads_nemotron_h needs a TPU; JAX found "
                 f"{jax.devices()}")
    config = harness.load_json(os.path.join(HERE, "configs",
                                            args.config + ".json"))
    family = harness.load_module("families", config["program"]["family"])
    reference = harness.load_module("reference",
                                    config["reference"]["family"])
    config = stretch(config, family, args.first, args.layers)
    program = config["program"]
    cfg = family.config(program)
    params = family.init(cfg, args.seed, program)
    rows = np.random.default_rng(args.seed).integers(
        0, family.vocab_size(cfg), (1, args.seq + 1), dtype=np.int32)
    tokens, targets = jnp.asarray(rows[:, :-1]), jnp.asarray(rows[:, 1:])

    got_loss, got = jax.jit(jax.value_and_grad(
        lambda p: family.loss(p, cfg, tokens, targets)))(params)
    got = jax.tree.map(lambda g: np.asarray(g.astype(jnp.float32)), got)
    want_loss, want = jax.jit(jax.value_and_grad(
        lambda p: reference.loss(p, tokens, targets,
                                 **reference.arguments(config))))(params)
    report = {"config": args.config, "layers": "".join(
                  {"mamba": "M", "experts": "E", "attention": "*"}[kind]
                  for kind in cfg.layers),
              "first_layer": args.first, "seq_len": args.seq,
              "device": jax.devices()[0].device_kind,
              "loss": {"program": float(got_loss),
                       "reference": float(want_loss)}, "leaves": {}}
    flat_want = jax.tree_util.tree_leaves_with_path(want)
    for (path, w), g in zip(flat_want, jax.tree.leaves(got)):
        w = np.asarray(w.astype(jnp.float32), np.float64)
        norm = np.linalg.norm(w.ravel())
        report["leaves"][jax.tree_util.keystr(path)] = float(
            np.linalg.norm((g - w).ravel()) / (norm or 1.0))
    report["worst"] = max(report["leaves"].values())
    report["ok"] = report["worst"] <= config["reference"]["grad_tol"]
    os.makedirs(os.path.join(ROOT, "chiprun_out"), exist_ok=True)
    with open(os.path.join(ROOT, "chiprun_out",
                           "check_grads_nemotron_h.json"), "w") as f:
        json.dump(report, f, indent=1)
    print(json.dumps(report))
    if not report["ok"]:
        sys.exit("check_grads_nemotron_h: a leaf is outside "
                 "reference.grad_tol")


if __name__ == "__main__":
    main()
