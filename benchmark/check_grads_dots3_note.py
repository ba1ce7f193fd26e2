"""The ``dots3_note`` family's gradients against the plain reference's,
at the published widths, on the chip (``check_grads.py`` is typed to the
``gpt`` family and may not be edited). Run once per PR that touches the
model's arithmetic; its result goes into PERF.md:

    chiprun -- python benchmark/check_grads_dots3_note.py --config <configuration>

The configuration's widths, dtypes, kernels, remat and chunked loss, its
share of the experts and of the vocabulary, cut to the first ``--layers`` of
the layers it runs (2: the dense layer and the first expert layer, both
full, each with an indexer of its own; 3 adds a window layer, with the
second geometry) and to one sequence of ``--seq`` tokens (4096: at
``index_topk`` or fewer the selection keeps every causal key and decides
nothing); weights from ``--seed`` as the cell draws them. Both
sides differentiate both terms, the cross-entropy and the indexers' loss.
The program differentiates its loss as the train step does (bfloat16
parameters, so bfloat16 gradients). The reference differentiates
``reference/<family>.py``'s loss, float32 inside at the highest matmul
precision, with respect to the same bfloat16 leaves, so its gradient is
rounded once, on the way out (0.1 % of a leaf's norm). Each leaf is compared
by the Frobenius norm of the difference over that of the reference's
gradient; the expert bias has no gradient on either side. Queries whose
selected keys, and tokens whose experts, differ between the two contribute
another gradient. ``reference.grad_tol`` is set about 2x above the worst leaf
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


def main(argv=None) -> None:
    import harness
    parser = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    parser.add_argument("--config", required=True)
    parser.add_argument("--layers", type=int, default=2)
    parser.add_argument("--seq", type=int, default=4096)
    parser.add_argument("--seed", type=int, default=0)
    args = parser.parse_args(argv)

    import jax
    import jax.numpy as jnp
    import numpy as np
    if jax.devices()[0].platform != "tpu":
        sys.exit(f"check_grads_dots3_note needs a TPU; JAX found "
                 f"{jax.devices()}")
    config = harness.load_json(os.path.join(HERE, "configs",
                                            args.config + ".json"))
    family = harness.load_module("families", config["program"]["family"])
    reference = harness.load_module("reference",
                                    config["reference"]["family"])
    config = family.with_layers(config, args.layers)
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
    report = {"config": args.config, "layers": args.layers,
              "seq_len": args.seq, "device": jax.devices()[0].device_kind,
              "loss": {"program": float(got_loss),
                       "reference": float(want_loss)}, "leaves": {}}
    flat_want = jax.tree_util.tree_leaves_with_path(want)
    for (path, w), g in zip(flat_want, jax.tree.leaves(got)):
        w = np.asarray(w.astype(jnp.float32), np.float64)
        norm = np.linalg.norm(w.ravel())
        if norm == 0.0 and not np.any(g):
            continue  # the expert bias: no gradient on either side
        report["leaves"][jax.tree_util.keystr(path)] = float(
            np.linalg.norm((g - w).ravel()) / norm)
    report["worst"] = max(report["leaves"].values())
    report["ok"] = report["worst"] <= config["reference"]["grad_tol"]
    os.makedirs(os.path.join(ROOT, "chiprun_out"), exist_ok=True)
    with open(os.path.join(ROOT, "chiprun_out",
                           "check_grads_dots3_note.json"), "w") as f:
        json.dump(report, f, indent=1)
    print(json.dumps(report))
    if not report["ok"]:
        sys.exit("check_grads_dots3_note: a leaf is outside reference.grad_tol")


if __name__ == "__main__":
    main()
