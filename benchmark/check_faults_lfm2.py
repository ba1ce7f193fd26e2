"""Faults planted in the ``lfm2_moe`` program, each through the runner's
own comparison, the one that decides ``correct``
(``runners/train.py: _reference_check``: the configuration's sequences,
sequence length, positions and limits, the weights the cell draws from the
seed): the untouched program has to come out ``ok``, every fault not. Run
once per PR that touches the model's arithmetic or the configuration's
limits; its readings go into the configuration's ``reference.why`` and
PERF.md:

    chiprun -- python3 benchmark/check_faults_lfm2.py --config <configuration> --seeds 3000000019 2147483659

A fault changes the program's side alone: the reference reads the weights
as the cell drew them. The terms of the forward pass, each taken out by hand:

- ``gate_before``: the convolution over x alone (B = 1); ``gate_after``:
  the convolution's output as it is (C = 1); ``a_tap``: the oldest tap of
  every convolution zero (``conv_w[0]``);
- ``rope``: q and k unrotated; ``qk_norm``: q and k as their projections
  leave them;
- ``norm_topk_prob``: the picked scores as they are, not renormalised;
  ``expert_bias``: selection by the scores alone;

and the control of a lower precision, ``eight_bit_residual``: the residual
stream rounded to float8_e4m3 at every layer's input, where the
configuration states bfloat16. Each line says which of the limits the run
is outside of (``failed``); the last line is ``{"ok": ...}`` and the run exits
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

# What does not depend on which faults are planted is the Kimi script's.
from check_faults_kimi_linear import (LIMITS, _Planted, _swapped,  # noqa: E402
                                      prepared)


def _chunk_of_ones(chunk: int):
    """``lm.short_conv`` with chunk ``chunk`` of ``bcx`` (0: B, 1: C) ones."""
    def planted(plain):
        def short_conv(bcx, w):
            d = bcx.shape[-1] // 3
            return plain(bcx.at[..., chunk * d:(chunk + 1) * d].set(1.0), w)
        return short_conv
    return planted


def _oldest_tap_zeroed(params):
    """The parameters with tap 0 of every convolution zero."""
    return {name: dict(stack, conv_w=stack["conv_w"].at[:, 0].set(0.0))
            if isinstance(stack, dict) and "conv_w" in stack else stack
            for name, stack in params.items()}


def faults():
    """name -> (attributes to swap as (module, name, plain -> planted), the
    config's fields to replace, the parameters' change or None)."""
    import jax.numpy as jnp
    from ray_tpu.models import lfm2, lm

    def eight_bit(plain):
        def block(cfg, kind, h, layer, positions):
            return plain(cfg, kind, h.astype(jnp.float8_e4m3fn).astype(
                h.dtype), layer, positions)
        return block

    def no_norm_over_a_head(plain):
        return lambda x, scale, eps: x if x.ndim == 4 else plain(x, scale,
                                                                 eps)

    return {
        "untouched": ([], {}, None),
        "gate_before": ([(lm, "short_conv", _chunk_of_ones(0))], {}, None),
        "gate_after": ([(lm, "short_conv", _chunk_of_ones(1))], {}, None),
        "a_tap": ([], {}, _oldest_tap_zeroed),
        "rope": ([(lm, "rope", lambda _: lambda x, positions, theta: x)], {},
                 None),
        "qk_norm": ([(lm, "rmsnorm", no_norm_over_a_head)], {}, None),
        "norm_topk_prob": ([], {"norm_topk_prob": False}, None),
        "expert_bias": ([], {"use_expert_bias": False}, None),
        "eight_bit_residual": ([(lfm2, "_block", eight_bit)], {}, None),
    }


def _computed_once(kept: dict):
    """``reference.forward`` computed once a seed: a fault changes the
    program's side alone, so every fault of a seed is held against the same
    reference, on the same weights, tokens and positions."""
    def planted(plain):
        def forward(*args, **kw):
            if "want" not in kept:
                kept["want"] = plain(*args, **kw)
            return kept["want"]
        return forward
    return planted


def check(config, family, cfg, mesh, params, seed: int, name: str,
          kept=None):
    """One fault through ``_reference_check`` as the runner calls it:
    its record, with ``failed``, the limits it is outside of. ``kept``: a
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
    parser.add_argument("--seeds", type=int, nargs="+", default=[0])
    parser.add_argument("--tiny", action="store_true")
    args = parser.parse_args(argv)

    import jax
    if not args.tiny and jax.devices()[0].platform != "tpu":
        sys.exit(f"check_faults_lfm2 needs a TPU; JAX found "
                 f"{jax.devices()}")
    config, family, cfg, mesh = prepared(args.config, args.tiny)
    spec = config["reference"]
    print(json.dumps({"limits": {k: spec[k] for k in LIMITS.values()},
                      "positions": spec["positions"],
                      "seq_len": config["layout"]["seq_len"],
                      "device": jax.devices()[0].device_kind}), flush=True)
    lines = []
    for seed in args.seeds:
        params = family.init(cfg, seed, config["program"])
        kept = {}
        for name in faults():
            lines.append(check(config, family, cfg, mesh, params, seed, name,
                               kept))
            print(json.dumps(lines[-1]), flush=True)
        del params
    ok = all(line["ok"] == (line["fault"] == "untouched") for line in lines)
    os.makedirs(os.path.join(ROOT, "chiprun_out"), exist_ok=True)
    with open(os.path.join(ROOT, "chiprun_out",
                           "check_faults_lfm2.json"), "w") as f:
        json.dump({"lines": lines, "ok": ok}, f, indent=1)
    print(json.dumps({"ok": ok}))
    if not ok:
        sys.exit("check_faults_lfm2: an untouched run is not ok, or "
                 "a fault is")


if __name__ == "__main__":
    main()
