"""Faults planted in the ``mellum`` program, each through the runner's own
comparison, the one that decides ``correct`` (``runners/train.py:
_reference_check``: the configuration's sequence length, positions and
limits, the weights the cell draws from the seed): the untouched program has
to come out ``ok``, every fault not. Run once per PR that touches the
model's arithmetic or the configuration's limits; its readings go into the
configuration's ``reference.why`` and PERF.md:

    chiprun -- python3 benchmark/check_faults_mellum.py --config <configuration> --seeds 3000000019 2147483659 [--untouched <seed> ...]

A fault changes the program's side alone: the reference reads the weights
as the cell drew them, and is computed once a seed.

- the rope tables: ``yarn_ramp`` (plain rope's frequencies on the full
  layer, its factor kept), ``attention_factor`` (the full layer's cos and
  sin times 1), ``yarn_on_window`` (the window layers rotated by the full
  layer's table and factor too);
- the masks and the heads: ``window`` (every layer causal),
  ``window_off_by_one`` (``i - j <= sliding_window``), ``kv_pairing``
  (query head i reading KV head i // 8 - 1), ``qk_norm`` (q and k un-normed);
- the router: ``norm_topk_prob`` (the eight probabilities unnormalised),
  ``sigmoid_for_softmax`` (the experts scored each by itself);

and the control of a lower precision, ``eight_bit_residual``: the residual
stream rounded to float8_e4m3 at every layer's input, where the
configuration states bfloat16. Each line says which of the limits the run is
outside of (``failed``); ``--untouched`` adds seeds on which only the
untouched program runs. The last line is ``{"ok": ...}`` and the run exits
non-zero unless every untouched run is ``ok`` and no fault is. ``--set
attention_qk_gain=2 router_spread=1.5 ...`` replaces numbers of the
configuration's ``program`` group and ``--positions`` the comparison's
sample, which is how they were sized. There is no CPU mode but ``--tiny``
(the family's tiny configuration in float32 under limits of 1e-3, for the
tests).

Every fault decides at the cell's size (``UNSEEN`` is empty): even one key
more in the window of three of four layers moves the logits by twice what
routing from bfloat16 does, because at the cell's attention gain a query's
softmax lies on few keys.
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

#: Faults the comparison cannot hold (module text): none.
UNSEEN = frozenset()


def faults(cfg):
    """name -> (attributes to swap as (module, name, plain -> planted), the
    config's fields to replace, the parameters' change or None)."""
    import jax.numpy as jnp
    from ray_tpu.models import lm, mellum

    ropes = {kind: dict(parameters)
             for kind, parameters in cfg.rope_parameters.items()}

    def full_layer(**changed):
        return {"rope_parameters": dict(ropes, full_attention=dict(
            ropes["full_attention"], **changed))}

    def un_normed(plain):
        return lambda x, scale, eps: x if x.ndim == 4 else plain(x, scale,
                                                                 eps)

    def shifted(params):
        return {name: dict(stack, wk=jnp.roll(stack["wk"], 1, axis=2),
                           wv=jnp.roll(stack["wv"], 1, axis=2))
                if isinstance(stack, dict) else stack
                for name, stack in params.items()}

    def by_itself(plain):
        def expert_ffn(x, layer, **kw):
            bias = jnp.zeros(layer["router"].shape[-1], jnp.float32)
            routed, shared, aux = plain(x, dict(layer, router_bias=bias),
                                        **dict(kw, score="sigmoid"))
            # The gauge the family's metrics read of a softmax router.
            return routed, shared, dict(aux, picked_mass=jnp.float32(0.0))
        return expert_ffn

    def eight_bit(plain):
        def block(cfg, kind, h, layer, positions):
            return plain(cfg, kind, h.astype(jnp.float8_e4m3fn).astype(
                h.dtype), layer, positions)
        return block

    return {
        "untouched": ([], {}, None),
        "yarn_ramp": ([], full_layer(factor=1.0), None),
        "attention_factor": ([], full_layer(attention_factor=1.0), None),
        "yarn_on_window": ([], {"rope_parameters": {
            kind: ropes["full_attention"] for kind in ropes}}, None),
        "window": ([], {"sliding_window": 1 << 30}, None),
        "window_off_by_one": ([], {"sliding_window":
                                   cfg.sliding_window + 1}, None),
        "kv_pairing": ([], {}, shifted),
        "qk_norm": ([(lm, "rmsnorm", un_normed)], {}, None),
        "norm_topk_prob": ([], {"norm_topk_prob": False}, None),
        "sigmoid_for_softmax": ([(lm, "expert_ffn", by_itself)], {}, None),
        "eight_bit_residual": ([(mellum, "_block", eight_bit)], {}, None),
    }


def check(config, family, cfg, mesh, params, seed: int, name: str,
          kept=None):
    """One fault through ``_reference_check`` as the runner calls it: its
    record, with ``failed``, the limits it is outside of. ``kept``: a
    dictionary that holds the seed's reference from one fault to the next
    (None: computed again)."""
    import harness
    runner = harness.load_module("runners", "train")
    swaps, fields, change = faults(cfg)[name]
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
    parser.add_argument("--set", nargs="*", default=[], metavar="NAME=NUMBER",
                        help="numbers of the configuration's program group "
                        "replaced (attention_qk_gain=2): for sizing them")
    parser.add_argument("--positions", type=int,
                        help="reference.positions replaced: for sizing it")
    parser.add_argument("--tiny", action="store_true")
    args = parser.parse_args(argv)

    import jax
    if not args.tiny and jax.devices()[0].platform != "tpu":
        sys.exit(f"check_faults_mellum needs a TPU; JAX found "
                 f"{jax.devices()}")
    config, family, cfg, mesh = prepared(args.config, args.tiny)
    config["program"] = dict(config["program"], **{
        name: float(number) for name, number in (
            pair.split("=") for pair in args.set)})
    if args.positions:
        config["reference"] = dict(config["reference"],
                                   positions=args.positions)
    spec, program = config["reference"], config["program"]
    print(json.dumps({"limits": {k: spec[k] for k in LIMITS.values()},
                      "positions": spec["positions"],
                      "seq_len": config["layout"]["seq_len"],
                      "program": {k: v for k, v in program.items()
                                  if isinstance(v, float)},
                      "device": jax.devices()[0].device_kind}), flush=True)
    lines = []
    plan = [(seed, ["untouched"]) for seed in args.untouched] \
        + [(seed, args.only or list(faults(cfg))) for seed in args.seeds]
    for seed, names in plan:
        params, kept = family.init(cfg, seed, program), {}
        for name in names:
            lines.append(check(config, family, cfg, mesh, params, seed, name,
                               kept))
            print(json.dumps(lines[-1]), flush=True)
        del params
    ok = all(line["ok"] == (line["fault"] == "untouched") for line in lines
             if line["fault"] not in UNSEEN)
    os.makedirs(os.path.join(ROOT, "chiprun_out"), exist_ok=True)
    with open(os.path.join(ROOT, "chiprun_out",
                           "check_faults_mellum.json"), "w") as f:
        json.dump({"lines": lines, "ok": ok}, f, indent=1)
    print(json.dumps({"ok": ok}))
    if not ok:
        sys.exit("check_faults_mellum: an untouched run is not ok, or a "
                 "fault is")


if __name__ == "__main__":
    main()
