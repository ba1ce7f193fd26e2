"""Faults planted in the ``nemotron_h`` program, each through the runner's own
comparison, the one that decides ``correct`` (``runners/train.py:
_reference_check``: the configuration's sequence length, positions and
limits, the weights the cell draws from the seed): the untouched program has
to come out ``ok``, every fault not. Run once per PR that touches the
model's arithmetic or the configuration's limits; its readings go into the
configuration's ``reference.why`` and PERF.md:

    chiprun -- python3 benchmark/check_faults_nemotron_h.py --config <configuration> --seeds 3000000019 2147483659 [--untouched <seed> ...]

A fault changes the program's side alone: the reference reads the weights
as the cell drew them, and is computed once a seed.

- the experts: ``relu_not_squared`` (a ReLU between an expert's two
  matrices, routed and shared alike), ``silu_for_relu`` (a SiLU, squared),
  ``shared_expert`` (the shared expert's sum left out);
- the router: ``routed_scaling_factor`` (1 for 2.5), ``norm_topk_prob``
  (the six scores unnormalised), ``router_bias`` (the correction bias out of
  the selection);
- the state-space layer: ``group_zero`` (every head reading group 0's B and
  C), ``whole_row_norm`` (the gated norm's mean of squares over all 4096
  channels), ``conv_bias`` (the conv's bias zero), ``D`` (the skip zero);
- the attention layer: ``attention_scale`` (scores times ``head_dim ** -1``
  for ``head_dim ** -0.5``), ``rope`` (a rotary embedding of theta 10000
  applied to q and k, which the family's attention has none of),
  ``kv_pairing`` (query head i reading KV head i % 2 for i // 16);

and the control of a lower precision, ``eight_bit_residual``: the residual
stream rounded to float8_e4m3 at every layer's input, where the
configuration states bfloat16. Each line says which of the limits the run is
outside of (``failed``); ``--untouched`` adds seeds on which only the
untouched program runs. The last line is ``{"ok": ...}`` and the run exits
non-zero unless every untouched run is ``ok`` and no fault is. ``--set
attention_qk_gain=2 router_bias_max=0.1 ...`` replaces numbers of the
configuration's ``program`` group and ``--positions`` the comparison's
sample, which is how they were sized. There is no CPU mode but ``--tiny``
(the family's tiny configuration in float32 under limits of 1e-3, for the
tests).
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

#: Faults the comparison cannot hold: none.
UNSEEN = frozenset()


def faults(cfg):
    """name -> (attributes to swap as (module, name, plain -> planted), the
    config's fields to replace, the parameters' change or None)."""
    import jax
    import jax.numpy as jnp
    from ray_tpu.models import lm, nemotron_h
    from ray_tpu.ops import moe

    def zeroed(leaf):
        """The parameters with ``leaf`` zero in every unit's either layer."""
        def change(params):
            return {run: dict(stack, **{
                name: jnp.zeros_like(a) for name, a in stack.items()
                if name in (leaf, "a_" + leaf, "b_" + leaf)})
                if isinstance(stack, dict) else stack
                for run, stack in params.items()}
        return change

    def activation(act):
        return lambda plain: dict(plain, relu2=act)

    def group_zero(plain):
        def state_space(u, dt, A, B, C, D, chunk):
            B, C = (jnp.broadcast_to(a[:, :, :1], a.shape) for a in (B, C))
            return plain(u, dt, A, B, C, D, chunk)
        return state_space

    def whole_row(plain):
        return lambda *args, group, **kw: plain(*args, **kw)

    def attention_with(change):
        def planted(plain):
            def attention(q, k, v, cfg, **kw):
                return plain(*change(q, k, v, kw), cfg, **kw)
            return attention
        return planted

    def rescaled(q, k, v, kw):
        kw["scale"] = 1.0 / q.shape[-1]
        return q, k, v

    def rotated(q, k, v, kw):
        positions = lm.positions_of(q[..., 0, 0])
        return (lm.rope(q, positions, 10000.0),
                lm.rope(k, positions, 10000.0), v)

    def paired_by_remainder(q, k, v, kw):
        rep = q.shape[2] // k.shape[2]
        return q, jnp.tile(k, (1, 1, rep, 1)), jnp.tile(v, (1, 1, rep, 1))

    def eight_bit(plain):
        def layer(cfg, kind, h, leaves):
            return plain(cfg, kind, h.astype(jnp.float8_e4m3fn).astype(
                h.dtype), leaves)
        return layer

    return {
        "untouched": ([], {}, None),
        "relu_not_squared": ([(moe, "ACTIVATIONS",
                               activation(jax.nn.relu))], {}, None),
        "silu_for_relu": ([(moe, "ACTIVATIONS", activation(
            lambda x: jnp.square(jax.nn.silu(x))))], {}, None),
        "shared_expert": ([], {}, zeroed("shared_w_down")),
        "routed_scaling_factor": ([], {"routed_scaling_factor": 1.0}, None),
        "norm_topk_prob": ([], {"norm_topk_prob": False}, None),
        "router_bias": ([], {}, zeroed("router_bias")),
        "group_zero": ([(lm, "state_space", group_zero)], {}, None),
        "whole_row_norm": ([(lm, "gated_norm", whole_row)], {}, None),
        "conv_bias": ([], {}, zeroed("conv_b")),
        "D": ([], {}, zeroed("D")),
        "attention_scale": ([(lm, "attention", attention_with(rescaled))],
                            {}, None),
        "rope": ([(lm, "attention", attention_with(rotated))], {}, None),
        "kv_pairing": ([(lm, "attention",
                         attention_with(paired_by_remainder))], {}, None),
        "eight_bit_residual": ([(nemotron_h, "_layer", eight_bit)], {},
                               None),
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
        sys.exit(f"check_faults_nemotron_h needs a TPU; JAX found "
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
                           "check_faults_nemotron_h.json"), "w") as f:
        json.dump({"lines": lines, "ok": ok}, f, indent=1)
    print(json.dumps({"ok": ok}))
    if not ok:
        sys.exit("check_faults_nemotron_h: an untouched run is not ok, or a "
                 "fault is")


if __name__ == "__main__":
    main()
