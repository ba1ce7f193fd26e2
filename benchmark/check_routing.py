"""How far an expert model's forward is from its plain reference at the
published widths, on the chip, and how much of that is routing:

    chiprun -- python benchmark/check_routing.py --config <configuration> --seeds 20

Routing is discontinuous. The program routes from bfloat16 activations, the
reference from float32, so for some tokens the last expert picked and the
first one not picked swap, and such a token's logits move by far more than
rounding explains although nothing is wrong. This measures it, per seed and
over all seeds, on ``reference.sequences`` seeded sequences of the
configuration's ``seq_len`` and ``reference.positions`` sampled positions:

    (a) ``differ_share``  sampled positions whose set of experts differs from
                          the reference's in any expert layer
    (b) ``alike``         logit error (RMS and worst, over the RMS of the
                          reference's logits) on the positions that route alike
    (c) ``all``           the same over all sampled positions, and the loss

The configuration's tolerances (``reference.logit_rms_tol`` and its kin, which
the runner holds every run to on its one seed) are set about 2x above (c);
(b) is what a model without routing shows, and is held here to
``reference.alike_rms_tol`` / ``alike_max_tol``, about 2x above what was
measured: the tight check, which a dropped norm vector or a lower precision
fails where (c)'s tolerances are too wide to see it. Exits non-zero where
any seed is outside either. Too dear for every run's set-up at 20 seeds. There is no
CPU mode; the benchmark's tests make the comparison at a tiny width.
"""

from __future__ import annotations

import argparse
import json
import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
sys.path[:0] = [ROOT, HERE]


def compare(config, family, reference, cfg, params, seed: int):
    """One seed's comparison on the parameters given: the three figures."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    spec, seq = config["reference"], config["layout"]["seq_len"]
    n_seq, n_pos = spec["sequences"], spec["positions"]
    rng = np.random.default_rng(seed)
    rows = rng.integers(0, family.vocab_size(cfg), (n_seq, seq + 1),
                        dtype=np.int32)
    where = jnp.asarray(np.sort(np.stack([
        np.append(rng.choice(seq - 1, n_pos - 1, replace=False), seq - 1)
        for _ in range(n_seq)]), axis=-1).astype(np.int32))
    tokens, targets = jnp.asarray(rows[:, :-1]), jnp.asarray(rows[:, 1:])

    def program(params, tokens, targets, where):
        logits, picked = family.picked_experts(params, cfg, tokens)
        _, losses = family.logits_and_losses(params, cfg, tokens, targets)
        at = jnp.take_along_axis(logits, where[..., None], axis=1)
        chosen = jnp.take_along_axis(
            picked, where[None, :, :, None], axis=2)
        return at.astype(jnp.float32), losses, chosen

    got, got_loss, got_picked = jax.jit(program)(params, tokens, targets,
                                                 where)
    want, want_loss, rms, want_picked = reference.forward(
        params, tokens, targets, where, with_picked=True,
        **reference.arguments(config))
    want_picked = np.take_along_axis(
        np.asarray(want_picked), np.asarray(where)[None, :, :, None], axis=2)
    same = (np.sort(np.asarray(got_picked), -1)
            == np.sort(want_picked, -1)).all(-1).all(0)  # [B, P]
    err = (np.asarray(got, np.float64) - np.asarray(want, np.float64)
           ) / float(rms)

    def figures(rows):
        return {"rms": float(np.sqrt((rows ** 2).mean())),
                "max": float(np.abs(rows).max())} if rows.size else None

    return {"seed": seed, "ref_logit_rms": float(rms),
            "differ_share": float(1.0 - same.mean()),
            "alike": figures(err[same]), "all": figures(err),
            "loss_err": float(np.abs(np.asarray(got_loss, np.float64)
                                     - np.asarray(want_loss, np.float64)
                                     ).max())}


def main(argv=None) -> None:
    import harness
    parser = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    parser.add_argument("--config", required=True)
    parser.add_argument("--seeds", type=int, default=20)
    parser.add_argument("--first-seed", type=int, default=1000)
    args = parser.parse_args(argv)

    import jax
    if jax.devices()[0].platform != "tpu":
        sys.exit(f"check_routing needs a TPU; JAX found {jax.devices()}")
    config = harness.load_json(os.path.join(HERE, "configs",
                                            args.config + ".json"))
    program = config["program"]
    family = harness.load_module("families", program["family"])
    reference = harness.load_module("reference",
                                    config["reference"]["family"])
    cfg = family.config(program)
    runs = []
    for seed in range(args.first_seed, args.first_seed + args.seeds):
        params = family.init(cfg, seed, program)
        runs.append(compare(config, family, reference, cfg, params, seed + 2))
        del params
        print(json.dumps(runs[-1]), flush=True)

    def worst(key, sub=None):
        values = [r[key] if sub is None else r[key][sub] for r in runs
                  if sub is None or r[key] is not None]
        return {"min": min(values), "max": max(values),
                "mean": sum(values) / len(values)}

    report = {"config": args.config, "seeds": args.seeds,
              "device": jax.devices()[0].device_kind,
              "differ_share": worst("differ_share"),
              "alike_rms": worst("alike", "rms"),
              "alike_max": worst("alike", "max"),
              "all_rms": worst("all", "rms"), "all_max": worst("all", "max"),
              "loss_err": worst("loss_err"), "runs": runs}
    tol = config["reference"]
    report["ok"] = bool(
        report["alike_rms"]["max"] <= tol["alike_rms_tol"]
        and report["alike_max"]["max"] <= tol["alike_max_tol"]
        and report["all_rms"]["max"] <= tol["logit_rms_tol"]
        and report["all_max"]["max"] <= tol["logit_max_tol"]
        and report["loss_err"]["max"] <= tol["loss_tol"])
    os.makedirs(os.path.join(ROOT, "chiprun_out"), exist_ok=True)
    with open(os.path.join(ROOT, "chiprun_out", "check_routing.json"),
              "w") as f:
        json.dump(report, f, indent=1)
    print(json.dumps({k: v for k, v in report.items() if k != "runs"}))
    if not report["ok"]:
        sys.exit("check_routing: outside the configuration's tolerances")


if __name__ == "__main__":
    main()
