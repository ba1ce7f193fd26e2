"""Faults planted in the ``dots3_note`` program, each through the runner's
own comparison, the one that decides ``correct``
(``runners/train.py: _reference_check``: the configuration's sequence
length, positions and limits, the weights the cell draws from the seed): the
untouched program has to come out ``ok``, every fault not. Run once per PR
that touches the model's arithmetic or the configuration's limits; its
readings go into the configuration's ``reference.why`` and PERF.md:

    chiprun -- python3 benchmark/check_faults_dots3_note.py --config <configuration> --seeds 3000000019 2147483659 [--untouched <seed> ...]

A fault changes the program's side alone: the reference reads the weights
as the cell drew them, and is computed once a seed. The terms of the forward
pass, each taken out by hand:

- ``gate_full`` / ``gate_window``: the gate a head of the full / the window
  layers left out (1 for every head);
- ``s_q`` / ``s_kv``: the scalar on the normed low-rank query / on the
  normed latent left out, in both kinds of layer;
- ``window_512``: a window of 512 keys for 513 (``t - s < 512``);
- ``rope_bases_swapped``: the full layers rotated by ``swa_rope_theta`` and
  the window layers by ``rope_theta``;
- ``selection``: none, the full layers attend over all their causal keys;
  ``relu``: the indexer's scores without their ReLU;
- ``shared_expert``: left out (``shared_w_down`` = 0);

and the control of a lower precision, ``eight_bit_residual``: the residual
stream rounded to float8_e4m3 at every layer's input, where the
configuration states bfloat16. Each line says which of the limits the run
is outside of (``failed``); ``--untouched`` adds seeds on which only the
untouched program runs. The last line is ``{"ok": ...}`` and the run exits
non-zero unless every untouched run is ``ok`` and no fault is, but those of
``FAINT``, which no limit on logits can decide and which are read and
reported all the same. There is no
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
from check_faults_glm_moe_dsa import _causal  # noqa: E402
from check_faults_kimi_linear import (LIMITS, _Planted, _swapped,  # noqa: E402
                                      prepared)
from check_faults_lfm2 import _computed_once  # noqa: E402
from check_faults_phi4flash import _changed  # noqa: E402


#: Faults that the comparison cannot tell from the untouched program, and
#: what tells them instead.
FAINT = {
    "window_512": "one key of 513 a query, the farthest: it moves a window "
    "layer's output by a fraction of a per cent, under what bfloat16 moves "
    "it. tests/test_dots3_note.py tells it, in float32 at ten times the "
    "agreement (test_a_window_one_key_shorter_moves_the_logits, at a window "
    "one key longer than the tile as here), with the kernels' own test "
    "against the literal mask and window_tile_census' count of the table.",
}


def faults():
    """name -> (attributes to swap as (module, name, plain -> planted), the
    config's fields to replace as cfg -> {field: value}, the parameters'
    change or None)."""
    import jax
    import jax.numpy as jnp
    from ray_tpu.models import dots3_note
    from ray_tpu.ops import dsa

    def everything(scores, topk):
        return _causal(scores.shape)[2].astype(jnp.int8)

    def ungated(kind):
        return [(dots3_note, "_gated", lambda plain: lambda x, attn, w_gate,
                 scope: (attn, jnp.float32(1.0)) if scope.endswith(kind)
                 else plain(x, attn, w_gate, scope))]

    def latent_with(kinds, **changed):
        """``Dots3NoteConfig.latent`` with fields of the ``kinds`` of layer
        replaced."""
        return [(dots3_note.Dots3NoteConfig, "latent", lambda plain:
                 lambda self, kind: replace(plain(self, kind), **changed)
                 if kind in kinds else plain(self, kind))]

    def on_stream(stream):
        """``_block`` on another residual stream."""
        return [(dots3_note, "_block", lambda plain: lambda cfg, kind, h,
                 *rest: plain(cfg, kind, stream(h), *rest))]

    both = ("full", "window")
    none = lambda cfg: {}  # noqa: E731
    return {
        "untouched": ([], none, None),
        "gate_full": (ungated("full"), none, None),
        "gate_window": (ungated("window"), none, None),
        "s_q": (latent_with(both, q_lora_scale=None), none, None),
        "s_kv": (latent_with(both, kv_lora_scale=None), none, None),
        "window_512": ([], lambda cfg: {
            "sliding_window_size": cfg.sliding_window_size - 1}, None),
        "rope_bases_swapped": ([], lambda cfg: {
            "rope_theta": cfg.swa_rope_theta,
            "swa_rope_theta": cfg.rope_theta}, None),
        "selection": ([(dsa, "select", lambda _: everything)], none, None),
        "relu": ([(jax.nn, "relu", lambda _: lambda x: x)], none, None),
        "shared_expert": ([], none,
                          _changed("shared_w_down", jnp.zeros_like)),
        "eight_bit_residual": (on_stream(lambda h: h.astype(
            jnp.float8_e4m3fn).astype(h.dtype)), none, None),
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
            config, _Planted(family, change), replace(cfg, **fields(cfg)),
            mesh, params, config["layout"]["seq_len"], seed + 2)
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
        sys.exit(f"check_faults_dots3_note needs a TPU; JAX found "
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
    ok = all(line["ok"] == (line["fault"] == "untouched") for line in lines
             if line["fault"] not in FAINT)
    os.makedirs(os.path.join(ROOT, "chiprun_out"), exist_ok=True)
    with open(os.path.join(ROOT, "chiprun_out",
                           "check_faults_dots3_note.json"), "w") as f:
        json.dump({"lines": lines, "ok": ok}, f, indent=1)
    print(json.dumps({"ok": ok}))
    if not ok:
        sys.exit("check_faults_dots3_note: an untouched run is not ok, or "
                 "a fault is")


if __name__ == "__main__":
    main()
