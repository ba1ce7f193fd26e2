"""Faults planted in the ``glm_moe_dsa`` program, each through the runner's
own comparison, the one that decides ``correct``
(``runners/train.py: _reference_check``: the configuration's sequence
length, positions and limits, the weights the cell draws from the seed): the
untouched program has to come out ``ok``, every fault not. Run once per PR
that touches the model's arithmetic or the configuration's limits; its
readings go into the configuration's ``reference.why`` and PERF.md:

    chiprun -- python3 benchmark/check_faults_glm_moe_dsa.py --config <configuration> --seeds 3000000019 2147483659 [--untouched <seed> ...]

A fault changes the program's side alone: the reference reads the weights
as the cell drew them, and is computed once a seed. The terms of the forward
pass, each taken out by hand:

- ``selection``: none, every layer attends over all its causal keys;
  ``relu``: the indexer's scores without their ReLU; ``index_weights``: w =
  1 for every indexer head; ``index_rope``: the indexer's q and k unrotated;
- ``shared_dense``: the layers that share a selection ignore it and attend
  over all their causal keys; ``shared_window``: they take another
  selection of the right size, the nearest ``index_topk`` keys;
- ``q_norm``: ``g_q`` = 1 (the low-rank query's norm without its scale);
  ``score_scale``: the main attention's scores without their 256^-1/2;
  ``routed_scaling_factor``: 1 for the published factor; ``shared_expert``:
  left out (``shared_w_down`` = 0);

and the control of a lower precision, ``eight_bit_residual``: the residual
stream rounded to float8_e4m3 at every layer's input, where the
configuration states bfloat16. Each line says which of the limits the run
is outside of (``failed``); ``--untouched`` adds seeds on which only the
untouched program runs. The last line is ``{"ok": ...}`` and the run exits
non-zero unless every untouched run is ``ok`` and no fault is. There is no
CPU mode but ``--tiny`` (the family's tiny configuration in float32 under
limits of 1e-3, for the benchmark's tests).
"""

from __future__ import annotations

import argparse
import json
import os
import sys
from dataclasses import replace

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
sys.path[:0] = [ROOT, HERE]

# What does not depend on which faults are planted is the earlier scripts'.
from check_faults_kimi_linear import (LIMITS, _Planted, _swapped,  # noqa: E402
                                      prepared)
from check_faults_lfm2 import _computed_once  # noqa: E402
from check_faults_phi4flash import _changed  # noqa: E402


def _causal(shape):
    import jax
    import jax.numpy as jnp
    rows = jax.lax.broadcasted_iota(jnp.int32, shape[-2:], 0)
    cols = jax.lax.broadcasted_iota(jnp.int32, shape[-2:], 1)
    return rows, cols, jnp.broadcast_to(cols <= rows, shape)


def faults():
    """name -> (attributes to swap as (module, name, plain -> planted), the
    config's fields to replace, the parameters' change or None)."""
    import jax
    import jax.numpy as jnp
    from ray_tpu.models import glm_moe_dsa
    from ray_tpu.ops import dsa

    def everything(scores, topk):
        return _causal(scores.shape)[2].astype(jnp.int8)

    def block_with(shared_selection=None, stream=None):
        """``_block`` on another residual stream, or with another selection
        handed to the layers that share one."""
        def planted(plain):
            def block(cfg, kind, h, layer, positions, shared):
                if stream is not None:
                    h = stream(h)
                if shared_selection is not None and kind.endswith("shared"):
                    shared = {glm_moe_dsa.SELECTION: shared_selection(
                        cfg, shared[glm_moe_dsa.SELECTION])}
                return plain(cfg, kind, h, layer, positions, shared)
            return block
        return [(glm_moe_dsa, "_block", planted)]

    def window(cfg, selection):
        rows, cols, causal = _causal(selection.shape)
        return (causal & (rows - cols < cfg.index_topk)).astype(jnp.int8)

    return {
        "untouched": ([], {}, None),
        "selection": ([(dsa, "select", lambda _: everything)], {}, None),
        "relu": ([(jax.nn, "relu", lambda _: lambda x: x)], {}, None),
        "index_weights": ([(dsa, "index_scores", lambda plain: lambda q, k, w:
                           plain(q, k, jnp.ones_like(w)))], {}, None),
        "index_rope": ([(glm_moe_dsa, "_partly_rotated",
                         lambda _: lambda x, positions, cfg: x)], {}, None),
        "shared_dense": (block_with(shared_selection=lambda cfg, selection:
                                    everything(selection, 0)), {}, None),
        "shared_window": (block_with(shared_selection=window), {}, None),
        "q_norm": ([], {}, _changed("q_norm_scale", jnp.ones_like)),
        "score_scale": ([(dsa, "_score_scale",
                          lambda _: lambda scale, D: 1.0)], {}, None),
        "routed_scaling_factor": ([], {"routed_scaling_factor": 1.0}, None),
        "shared_expert": ([], {}, _changed("shared_w_down", jnp.zeros_like)),
        "eight_bit_residual": (block_with(stream=lambda h: h.astype(
            jnp.float8_e4m3fn).astype(h.dtype)), {}, None),
    }


def check(config, family, cfg, mesh, params, seed: int, name: str,
          kept=None):
    """One fault through ``_reference_check`` as the runner calls it: its
    record, with ``failed``, the limits it is outside of. ``kept``: a
    dictionary that holds the seed's reference from one fault to the next
    (None: computed again)."""
    import harness
    runner = harness.load_module("runners", "train")
    swaps, fields, change = faults()[name]
    if kept is not None:
        reference = harness.load_module("reference",
                                        config["reference"]["family"])
        swaps = swaps + [(reference, "forward", _computed_once(kept))]
    with _swapped(swaps):
        found = runner._reference_check(
            config, _Planted(family, change), replace(cfg, **fields), mesh,
            params, config["layout"]["seq_len"], seed + 2)
    spec = config["reference"]
    out = {"fault": name, "seed": seed, "ok": found["ok"]}
    out.update({key: found[key] for key in LIMITS})
    out["failed"] = [limit for key, limit in LIMITS.items()
                     if not found[key] <= spec[limit]]
    return out


def main(argv=None) -> None:
    parser = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    parser.add_argument("--config", required=True)
    parser.add_argument("--seeds", type=int, nargs="*", default=[0])
    parser.add_argument("--untouched", type=int, nargs="*", default=[])
    parser.add_argument("--only", nargs="*",
                        help="these faults alone (untouched is one)")
    parser.add_argument("--set", nargs="*", default=[], metavar="KEY=NUMBER",
                        help="numbers of the configuration's program group "
                        "replaced (attention_q_gain=2): for sizing them")
    parser.add_argument("--tiny", action="store_true")
    args = parser.parse_args(argv)

    import jax
    if not args.tiny and jax.devices()[0].platform != "tpu":
        sys.exit(f"check_faults_glm_moe_dsa needs a TPU; JAX found "
                 f"{jax.devices()}")
    config, family, cfg, mesh = prepared(args.config, args.tiny)
    for key, number in (pair.split("=") for pair in args.set):
        config["program"][key] = float(number)
    spec = config["reference"]
    print(json.dumps({"limits": {k: spec[k] for k in LIMITS.values()},
                      "positions": spec["positions"],
                      "seq_len": config["layout"]["seq_len"],
                      "set": args.set,
                      "device": jax.devices()[0].device_kind}), flush=True)
    lines = []
    plan = [(seed, ["untouched"]) for seed in args.untouched] \
        + [(seed, args.only or list(faults())) for seed in args.seeds]
    for seed, names in plan:
        params, kept = family.init(cfg, seed, config["program"]), {}
        for name in names:
            lines.append(check(config, family, cfg, mesh, params, seed, name,
                               kept))
            print(json.dumps(lines[-1]), flush=True)
        del params
    ok = all(line["ok"] == (line["fault"] == "untouched") for line in lines)
    os.makedirs(os.path.join(ROOT, "chiprun_out"), exist_ok=True)
    with open(os.path.join(ROOT, "chiprun_out",
                           "check_faults_glm_moe_dsa.json"), "w") as f:
        json.dump({"lines": lines, "ok": ok}, f, indent=1)
    print(json.dumps({"ok": ok}))
    if not ok:
        sys.exit("check_faults_glm_moe_dsa: an untouched run is not ok, or "
                 "a fault is")


if __name__ == "__main__":
    main()
