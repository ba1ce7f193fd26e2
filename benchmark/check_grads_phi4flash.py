"""The ``phi4flash`` family's gradients against the plain reference's, at
the published widths, on the chip (``check_grads.py`` is typed to the
``gpt`` family and may not be edited). Run once per PR that touches the
model's arithmetic; its result goes into PERF.md:

    chiprun -- python benchmark/check_grads_phi4flash.py --config <configuration>

The configuration's widths, dtypes, kernels, remat and chunked loss, cut to
the published layers ``--layers`` (0 1 16 17 18 19: a pair of every kind, so
that the memory and the K/V handed on have a reader) and to ``--sequences``
sequences of ``--seq`` tokens (1 of 2048: eight chunks of the scan's
kernels, four tiles of the window a row of the flash kernels'); weights
from ``--seed`` as the cell draws them. The program differentiates its loss
as the train step does (bfloat16 parameters, so bfloat16 gradients). The
reference differentiates ``reference/<family>.py``'s loss, float32 inside at
the highest matmul precision, with respect to the same bfloat16 leaves, so
its gradient is rounded once, on the way out (0.1 % of a leaf's norm). Each
leaf is compared by the Frobenius norm of the difference over that of the
reference's gradient.

Two kinds of leaf are judged by the size of the reference's gradient, not
against ``grad_tol`` outright. The key projection's bias ``bk``: a constant
added to every key moves every score of a query alike and no softmax, so
its gradient is zero but for rounding on both sides; it is left out, and the
run fails if the reference's reads over ``ZERO`` of the same layer's ``bq``
(then it is not that). The four lambda vectors of a layer: lambda is one
number a layer, so each vector's gradient is that number's times a vector
the weights give (``d lambda / d lq1 = exp(lq1 . lk1) lk1`` ...), and the
number is a sum over every token of terms of both signs. ``lambda_terms``
reads the terms from the reference in forward mode (``d nll_t / d lambda``
of every token, one pass a layer): their mean is the gradient, their mean
absolute value (the ``mass``) what it is left of. bfloat16 rounds each term
by up to ``BF16_EPS`` of itself, so the program's number is held to the
larger of ``grad_tol`` of the reference's and ``BF16_EPS`` of the mass: where
the sum stands above the rounding of its terms the lambda vectors answer to
``grad_tol`` like every leaf, and where it does not no comparison at this
precision can hold it closer. What needs no such room is held tight: each
vector's gradient lies along its direction (``DIRECTION_TOL``) and the four
give the same number (the path from lambda to the leaves: the exponentials,
the signs, which vector multiplies which).

One sequence of 2048 stands for the cell's 16384 because the reference's
backward pass through a literal walk over time keeps a float32 state
[5120, 16] a token a Mamba layer (0.67 GB at 2048, 5.4 GB at 16384) and S x S
maps a block of heads: 16384 does not fit beside the program. What depends
on the length is crossed at 2048 as at 16384, only less often: 8 of the
scan's chunks (7 borders the state and its cotangent cross, both ways), 4 x 4
of the flash kernels' tiles with the window's edge inside the second, the
convolution's tile seams; the forward pass is checked at 16384 in every run.
``reference.grad_tol`` is set about 2x above the worst leaf measured, and
the run exits non-zero above it. There is no CPU mode; the benchmark's
tests make the same comparison at a tiny width.
"""

from __future__ import annotations

import argparse
import json
import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
sys.path[:0] = [ROOT, HERE]


#: bfloat16's rounding of one term (8 bits of mantissa).
BF16_EPS = 2.0 ** -8
#: A lambda vector's gradient off its direction, and the four numbers'
#: spread, over their size: bfloat16's rounding of the leaves themselves.
DIRECTION_TOL = 0.02
#: ``bk``'s gradient over ``bq``'s in the reference: zero but for rounding.
ZERO = 1e-3
_LAMBDAS = ("q1", "k1", "q2", "k2")


def _lambda_directions(stack, prefix, row):
    """{vector: d lambda / d vector} of one layer, float64: ``lambda =
    exp(lq1 . lk1) - exp(lq2 . lk2) + l0``."""
    import jax.numpy as jnp
    import numpy as np
    v = {n: np.asarray(stack[f"{prefix}lambda_{n}"][row].astype(jnp.float32),
                       np.float64) for n in _LAMBDAS}
    e1, e2 = np.exp(v["q1"] @ v["k1"]), np.exp(v["q2"] @ v["k2"])
    return {"q1": e1 * v["k1"], "k1": e1 * v["q1"],
            "q2": -e2 * v["k2"], "k2": -e2 * v["q2"]}


def lambda_terms(config, reference, params, tokens, targets):
    """{(stack, prefix, row): d nll_t / d lambda of every token [B, S]} by
    the reference in forward mode: a tangent on ``lambda_q1`` along its
    direction moves that layer's lambda alone."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    kw = reference.arguments(config)
    pushed = jax.jit(lambda p, t: jax.jvp(
        lambda q: reference.nll(q, tokens, targets, **kw), (p,), (t,))[1])
    zeros = jax.tree.map(jnp.zeros_like, params)
    out = {}
    for name, stack in params.items():
        for leaf in (stack if isinstance(stack, dict) else ()):
            if not leaf.endswith("lambda_q1"):
                continue
            prefix = leaf[:-len("lambda_q1")]
            for row in range(stack[leaf].shape[0]):
                along = _lambda_directions(stack, prefix, row)["q1"]
                step = (along / (along @ along)).astype(np.float32)
                tangent = zeros[name][leaf].at[row].set(
                    jnp.asarray(step, zeros[name][leaf].dtype))
                moved = float(along @ np.asarray(
                    tangent[row].astype(jnp.float32), np.float64))
                terms = pushed(params, dict(zeros, **{name: dict(
                    zeros[name], **{leaf: tangent})}))
                out[name, prefix, row] = np.asarray(terms, np.float64) / moved
    return out


def compared(config, family, reference, cfg, params, tokens, targets):
    """({leaf: |program's gradient - reference's| / |reference's|}, {layer:
    its lambda's gradient on both sides, the mass it is left of, what it is
    held to and whether it holds}, the two losses, bk's gradient over bq's
    in the reference at its largest)."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    tol = config["reference"]["grad_tol"]
    got_loss, got = jax.jit(jax.value_and_grad(
        lambda p: family.loss(p, cfg, tokens, targets)))(params)
    want_loss, want = jax.jit(jax.value_and_grad(
        lambda p: reference.loss(p, tokens, targets,
                                 **reference.arguments(config))))(params)
    got, want = (jax.tree.map(lambda g: np.asarray(
        g.astype(jnp.float32), np.float64), tree) for tree in (got, want))

    def off(g, w):
        return float(np.linalg.norm((g - w).ravel())
                     / np.linalg.norm(w.ravel()))

    leaves, zero = {}, 0.0
    for (path, w), g in zip(jax.tree_util.tree_leaves_with_path(want),
                            jax.tree.leaves(got)):
        name = jax.tree_util.keystr(path)
        if name.endswith("_bk']"):
            zero = max(zero, float(np.linalg.norm(w) / np.linalg.norm(
                want[path[0].key][path[1].key[:-1] + "q"])))
        elif "lambda_" not in name:
            leaves[name] = off(g, w)
    lambdas = {}
    for (stack, prefix, row), terms in lambda_terms(
            config, reference, params, tokens, targets).items():
        along = _lambda_directions(params[stack], prefix, row)
        numbers, astray = {"program": [], "reference": []}, []
        for n in _LAMBDAS:
            leaf = f"{prefix}lambda_{n}"
            for side, tree in (("program", got), ("reference", want)):
                g = tree[stack][leaf][row]
                number = float(g @ along[n] / (along[n] @ along[n]))
                numbers[side].append(number)
                if side == "program":
                    astray.append(float(np.linalg.norm(
                        g - number * along[n]) / np.linalg.norm(g)))
        ours, theirs = (float(np.mean(numbers[side]))
                        for side in ("program", "reference"))
        mass = float(np.abs(terms).mean())
        held_to = max(tol * abs(theirs), BF16_EPS * mass)
        one = {"program": ours, "reference": theirs,
               "reference_by_terms": float(terms.mean()), "mass": mass,
               "left_of_the_mass": abs(theirs) / mass,
               "err": abs(ours - theirs), "held_to": held_to,
               "held_to_grad_tol": tol * abs(theirs) >= BF16_EPS * mass,
               "err_over_reference": abs(ours - theirs) / abs(theirs),
               "off_direction": max(astray),
               "spread": float((max(numbers["program"])
                                - min(numbers["program"])) / abs(ours)),
               "leaves": {n: off(got[stack][f"{prefix}lambda_{n}"][row],
                                 want[stack][f"{prefix}lambda_{n}"][row])
                          for n in _LAMBDAS}}
        one["ok"] = bool(one["err"] <= held_to
                         and one["off_direction"] <= DIRECTION_TOL
                         and one["spread"] <= DIRECTION_TOL)
        lambdas[f"['{stack}']['{prefix}lambda'][{row}]"] = one
    return leaves, lambdas, float(got_loss), float(want_loss), zero


def main(argv=None) -> None:
    import harness
    parser = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    parser.add_argument("--config", required=True)
    parser.add_argument("--layers", type=int, nargs="+",
                        default=[0, 1, 16, 17, 18, 19])
    parser.add_argument("--sequences", type=int, default=1)
    parser.add_argument("--seq", type=int, default=2048)
    parser.add_argument("--seed", type=int, default=0)
    args = parser.parse_args(argv)

    import jax
    import jax.numpy as jnp
    import numpy as np
    if jax.devices()[0].platform != "tpu":
        sys.exit(f"check_grads_phi4flash needs a TPU; JAX found "
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
        0, family.vocab_size(cfg), (args.sequences, args.seq + 1),
        dtype=np.int32)
    leaves, lambdas, got_loss, want_loss, zero = compared(
        config, family, reference, cfg, params, jnp.asarray(rows[:, :-1]),
        jnp.asarray(rows[:, 1:]))
    report = {"config": args.config, "layers": args.layers,
              "sequences": args.sequences, "seq_len": args.seq,
              "device": jax.devices()[0].device_kind,
              "loss": {"program": got_loss, "reference": want_loss},
              "leaves": leaves, "lambdas": lambdas, "bk_over_bq": zero,
              "worst": max(leaves.values())}
    report["ok"] = bool(
        report["worst"] <= config["reference"]["grad_tol"]
        and all(one["ok"] for one in lambdas.values()) and zero <= ZERO)
    os.makedirs(os.path.join(ROOT, "chiprun_out"), exist_ok=True)
    with open(os.path.join(ROOT, "chiprun_out",
                           "check_grads_phi4flash.json"), "w") as f:
        json.dump(report, f, indent=1)
    print(json.dumps(report))
    if not report["ok"]:
        sys.exit("check_grads_phi4flash: a leaf is outside "
                 "reference.grad_tol, a lambda's gradient outside what it "
                 "is held to, or bk's is not zero")


if __name__ == "__main__":
    main()
