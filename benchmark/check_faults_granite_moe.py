"""Faults planted in the ``granitemoehybrid_moe`` program (``models/
granite.py`` with experts), each through the runner's own comparison, the
one that decides ``correct`` (``runners/train.py: _reference_check``: the
configuration's sequence length, positions and limits, the weights the cell
draws from the seed): the untouched program has to come out ``ok``, every
fault not. Run once per PR that touches the model's arithmetic or the
configuration's limits; its readings go into the configuration's
``reference.why`` and PERF.md:

    chiprun -- python3 benchmark/check_faults_granite_moe.py --config <configuration> --seeds 3000000019 2147483659 [--untouched <seed> ...]

A fault changes the program's side alone: the reference reads the weights
as the cell drew them, and is computed once a seed.

- the gate: ``no_renormalise`` (a softmax over all 72 experts, the ten
  picked as they are), ``top_9`` (nine experts a token);
- the experts: ``halves_swapped`` (the gated half of ``W_in`` taken for the
  other), ``routed_sum`` (the experts' sum left out), ``shared_swiglu`` (the
  shared SwiGLU left out), ``residual_on_shared_only`` (``residual_
  multiplier`` applied to s alone: r enters the stream whole),
  ``held_shifted`` (the held weights taken for experts 1-9 where they are
  0-8);
- the state-space layer: ``D`` (the skip zero), ``conv_bias`` (the conv's
  bias zero), ``gate_after_norm`` (the norm before the gate);
- the attention layer: ``attention_scale`` (scores times ``head_dim **
  -0.5`` for ``attention_multiplier``), ``kv_pairing`` (query head i reading
  KV head i % 8 for i // 4);
- the head: ``logits_scaling`` (8, granite-4.0-h-micro's, for 16);

and the control of a lower precision, ``eight_bit_residual``: the residual
stream rounded to float8_e4m3 at every layer's input, where the
configuration states bfloat16. Each line says which of the limits the run is
outside of (``failed``); ``--untouched`` adds seeds on which only the
untouched program runs. The last line is ``{"ok": ...}`` and the run exits
non-zero unless every untouched run is ``ok`` and no fault is. ``--set
expert_gain=2 attention_qk_gain=3 ...`` replaces numbers of the
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
    import jax.numpy as jnp
    from ray_tpu.models import granite, lm

    def in_every_run(change):
        return lambda params: {
            run: change(dict(stack)) if isinstance(stack, dict) else stack
            for run, stack in params.items()}

    def zeroed(leaf):
        return in_every_run(lambda w: dict(w, **{
            leaf: jnp.zeros_like(w[leaf])} if leaf in w else {}))

    def experts_with(**changed):
        def planted(plain):
            return lambda x, layer, **kw: plain(x, layer,
                                                **dict(kw, **changed))
        return planted

    def routed_whole(plain):
        def expert_ffn(x, layer, **kw):
            routed, shared, aux = plain(x, layer, **kw)
            return routed / cfg.residual_multiplier, shared, aux
        return expert_ffn

    def attention_with(change):
        def planted(plain):
            def attention(q, k, v, cfg, **kw):
                return plain(*change(q, k, v, kw), cfg, **kw)
            return attention
        return planted

    def rescaled(q, k, v, kw):
        kw["scale"] = q.shape[-1] ** -0.5
        return q, k, v

    def paired_by_remainder(q, k, v, kw):
        rep = q.shape[2] // k.shape[2]
        return q, jnp.tile(k, (1, 1, rep, 1)), jnp.tile(v, (1, 1, rep, 1))

    def norm_first(plain):
        return lambda *args, gate_first, **kw: plain(
            *args, gate_first=False, **kw)

    def eight_bit(plain):
        def block(cfg, kind, h, layer, positions):
            return plain(cfg, kind, h.astype(jnp.float8_e4m3fn).astype(
                h.dtype), layer, positions)
        return block

    first, count = cfg.experts_held or (0, cfg.num_local_experts)
    shifted = (first + 1, count) if first + 1 + count \
        <= cfg.num_local_experts else (first - 1, count)
    return {
        "untouched": ([], {}, None),
        "no_renormalise": ([(lm, "expert_ffn",
                             experts_with(normalize=False))], {}, None),
        "top_9": ([], {"num_experts_per_tok": cfg.num_experts_per_tok - 1},
                  None),
        "halves_swapped": ([], {}, in_every_run(lambda w: dict(
            w, w_gate=w["w_up"], w_up=w["w_gate"]))),
        "routed_sum": ([], {}, zeroed("w_down")),
        "shared_swiglu": ([], {}, zeroed("mlp_out")),
        "residual_on_shared_only": ([(lm, "expert_ffn", routed_whole)], {},
                                    None),
        "held_shifted": ([], {"experts_held": shifted}, None),
        "D": ([], {}, zeroed("D")),
        "conv_bias": ([], {}, zeroed("conv_b")),
        "gate_after_norm": ([(lm, "gated_norm", norm_first)], {}, None),
        "attention_scale": ([(lm, "attention", attention_with(rescaled))],
                            {}, None),
        "kv_pairing": ([(lm, "attention",
                         attention_with(paired_by_remainder))], {}, None),
        "logits_scaling": ([], {"logits_scaling": cfg.logits_scaling / 2},
                           None),
        "eight_bit_residual": ([(granite, "_block", eight_bit)], {}, None),
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
                        "replaced (expert_gain=2): for sizing them")
    parser.add_argument("--positions", type=int,
                        help="reference.positions replaced: for sizing it")
    parser.add_argument("--tiny", action="store_true")
    args = parser.parse_args(argv)

    import jax
    if not args.tiny and jax.devices()[0].platform != "tpu":
        sys.exit(f"check_faults_granite_moe needs a TPU; JAX found "
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
                                  if isinstance(v, (int, float))},
                      "device": jax.devices()[0].device_kind}), flush=True)
    lines = []
    plan = [(seed, ["untouched"]) for seed in args.untouched] \
        + [(seed, args.only or list(faults(cfg))) for seed in args.seeds]
    for seed, names in plan:
        params, kept = family.init(cfg, seed, program, mesh), {}
        for name in names:
            lines.append(check(config, family, cfg, mesh, params, seed, name,
                               kept))
            print(json.dumps(lines[-1]), flush=True)
        del params
    ok = all(line["ok"] == (line["fault"] == "untouched") for line in lines
             if line["fault"] not in UNSEEN)
    os.makedirs(os.path.join(ROOT, "chiprun_out"), exist_ok=True)
    with open(os.path.join(ROOT, "chiprun_out",
                           "check_faults_granite_moe.json"), "w") as f:
        json.dump({"lines": lines, "ok": ok}, f, indent=1)
    print(json.dumps({"ok": ok}))
    if not ok:
        sys.exit("check_faults_granite_moe: an untouched run is not ok, or a "
                 "fault is")


if __name__ == "__main__":
    main()
