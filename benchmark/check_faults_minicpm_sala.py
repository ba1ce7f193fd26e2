"""Faults planted in the ``minicpm_sala`` program, each through the runner's
own comparison, the one that decides ``correct`` (``runners/train.py:
_reference_check``: the configuration's sequence length, positions and
limits, the weights the cell draws from the seed): the untouched program has
to come out ``ok``, every fault not. Run once per PR that touches the
model's arithmetic or the configuration's limits; its readings go into the
configuration's ``reference.why`` and PERF.md:

    chiprun -- python3 benchmark/check_faults_minicpm_sala.py --config <configuration> --seeds 3000000019 2147483659 [--untouched <seed> ...]

A fault changes the program's side alone: the reference reads the weights
as the cell drew them, and is computed once a seed.

- the selection: ``selection`` (none: every sequence attends densely),
  ``nearest_blocks`` (the 64 blocks nearest the query in the scores'
  place), ``init_block`` (the first block not forced), ``max_pool`` (a
  block scored by the four kernels that start in it, without the one that
  reaches in from before), ``group_sum`` (a group's selection by its first
  head's scores alone);
- the sparse mixer: ``qk_norm`` (q and k of both mixers un-normed),
  ``sparse_gate`` (W_g zero: the gate a constant half), ``sparse_scale``
  (scores not divided by sqrt(128), in the selection and the attention);
- the linear mixer: ``decay`` (lambda = 1), ``decay_layer_factor`` (every
  layer at layer 0's), ``linear_rope`` (no positions), ``output_norm`` (the
  gate alone behind the recurrence), ``linear_scale`` (its 1/sqrt(128));
- the shell's scalars: ``scale_emb`` (1), ``residual_scale`` (r = 1),
  ``head_divisor`` (1);

and the control of a lower precision, ``eight_bit_residual``: the residual
stream rounded to float8_e4m3 at every layer's input, where the
configuration states bfloat16. Each line says which of the limits the run is
outside of (``failed``); ``--untouched`` adds seeds on which only the
untouched program runs. The last line is ``{"ok": ...}`` and the run exits
non-zero unless every untouched run is ``ok`` and no fault is. There is no
CPU mode but ``--tiny`` (the family's tiny configuration in float32 under
limits of 1e-3, for the tests).

``linear_scale`` is read and does not decide (``UNSEEN``): the output norm
behind the recurrence divides any factor on ``o`` out again (up to its
epsilon), so the comparison cannot hold it. The term is held where the
recurrence's output is read itself: ``tests/test_lightning.py`` (the
kernels, the chunked form and the literal recurrence, each with its scale).
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


#: Faults the comparison cannot hold (module text).
UNSEEN = frozenset({"linear_scale"})


def faults():
    """name -> (attributes to swap as (module, name, plain -> planted), the
    config's fields to replace, the parameters' change or None)."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from ray_tpu.models import lm, minicpm_sala
    from ray_tpu.ops import infllm, lightning

    def nearest(_):
        def scores(q, kc, sizes, scale=None, rows=None):
            B, S = q.shape[:2]
            blocks = S // sizes.block
            return jnp.broadcast_to(jnp.arange(blocks, dtype=jnp.float32),
                                    (B, kc.shape[2], S, blocks))
        return scores

    def own_kernels(_):
        def pooled(summed, sizes, blocks):
            ratio = sizes.block // sizes.stride
            padded = jnp.pad(summed, ((0, 0),) * (summed.ndim - 1)
                             + ((0, ratio * blocks - summed.shape[-1]),),
                             constant_values=-1.0)
            return padded.reshape(*summed.shape[:-1], blocks, ratio).max(-1)
        return pooled

    def first_head(plain):
        def scores(q, kc, sizes, *args, **kw):
            per = q.shape[2] // kc.shape[2]
            return plain(jnp.repeat(q[:, :, ::per], per, axis=2), kc, sizes,
                         *args, **kw)
        return scores

    def un_normed(_):
        def qkv(cfg, x, layer):
            return tuple(jnp.einsum("bsd,dhk->bshk", x,
                                    layer[w].astype(cfg.dtype))
                         for w in ("wq", "wk", "wv"))
        return qkv

    def half_gate(params):
        stacks = [name for name in params if name.endswith("_sparse")]
        return dict(params, **{name: dict(
            params[name], w_g=jnp.zeros_like(params[name]["w_g"]))
            for name in stacks})

    def no_decay(plain):
        return lambda cfg, layer: np.zeros_like(plain(cfg, layer))

    def first_layers(plain):
        return lambda cfg, layer: plain(cfg, 0)

    def gate_alone(_):
        def gated(x, z, scale, eps, *, gate_first, activation):
            return (x.astype(jnp.float32) * jax.nn.sigmoid(
                z[..., :x.shape[-1]].astype(jnp.float32))).astype(x.dtype)
        return gated

    def eight_bit(plain):
        def block(cfg, kind, h, layer, positions):
            return plain(cfg, kind, h.astype(jnp.float8_e4m3fn).astype(
                h.dtype), layer, positions)
        return block

    one = lambda _: (lambda scale, width: 1.0)
    return {
        "untouched": ([], {}, None),
        "selection": ([], {"sparse_dense_len": 1 << 30}, None),
        "nearest_blocks": ([(infllm, "block_scores", nearest)], {}, None),
        "init_block": ([], {"sparse_init_blocks": 0}, None),
        "max_pool": ([(infllm, "_pooled", own_kernels)], {}, None),
        "group_sum": ([(infllm, "block_scores", first_head)], {}, None),
        "qk_norm": ([(minicpm_sala, "_qkv", un_normed)], {}, None),
        "sparse_gate": ([], {}, half_gate),
        "sparse_scale": ([(infllm, "_score_scale", one)], {}, None),
        "decay": ([(minicpm_sala, "decay_slopes", no_decay)], {}, None),
        "decay_layer_factor": ([(minicpm_sala, "decay_slopes",
                                 first_layers)], {}, None),
        "linear_rope": ([(lm, "rope", lambda _: (lambda x, *a: x))], {},
                        None),
        "output_norm": ([(lm, "gated_norm", gate_alone)], {}, None),
        "linear_scale": ([(lightning, "_scale", one)], {}, None),
        "scale_emb": ([], {"scale_emb": 1.0}, None),
        "residual_scale": ([], {"scale_depth": 32 ** 0.5}, None),
        "head_divisor": ([], {"dim_model_base": 4096}, None),
        "eight_bit_residual": ([(minicpm_sala, "_block", eight_bit)], {},
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
    parser.add_argument("--set", nargs="*", default=[], metavar="LEAF=GAIN",
                        help="gains of the configuration's program.gains "
                        "replaced (wo=4): for sizing them")
    parser.add_argument("--positions", type=int,
                        help="reference.positions replaced: for sizing it")
    parser.add_argument("--dense-len", type=int,
                        help="sparse_config.dense_len replaced on both "
                        "sides: what the selection adds to the difference")
    parser.add_argument("--tiny", action="store_true")
    args = parser.parse_args(argv)

    import jax
    if not args.tiny and jax.devices()[0].platform != "tpu":
        sys.exit(f"check_faults_minicpm_sala needs a TPU; JAX found "
                 f"{jax.devices()}")
    config, family, cfg, mesh = prepared(args.config, args.tiny)
    config["program"]["gains"] = dict(
        config["program"]["gains"],
        **{leaf: float(gain) for leaf, gain in (
            pair.split("=") for pair in args.set)})
    if args.positions:
        config["reference"] = dict(config["reference"],
                                   positions=args.positions)
    if args.dense_len:
        config = family.with_layers(config, config["num_hidden_layers"],
                                    args.dense_len)
        cfg = family.config(config["program"])
    spec = config["reference"]
    print(json.dumps({"limits": {k: spec[k] for k in LIMITS.values()},
                      "positions": spec["positions"],
                      "seq_len": config["layout"]["seq_len"],
                      "gains": config["program"]["gains"],
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
             if line["fault"] not in UNSEEN)
    os.makedirs(os.path.join(ROOT, "chiprun_out"), exist_ok=True)
    with open(os.path.join(ROOT, "chiprun_out",
                           "check_faults_minicpm_sala.json"), "w") as f:
        json.dump({"lines": lines, "ok": ok}, f, indent=1)
    print(json.dumps({"ok": ok}))
    if not ok:
        sys.exit("check_faults_minicpm_sala: an untouched run is not ok, or a "
                 "fault is")


if __name__ == "__main__":
    main()
