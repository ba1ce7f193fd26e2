"""Faults planted in the ``phi4flash`` program, each through the runner's
own comparison, the one that decides ``correct``
(``runners/train.py: _reference_check``: the configuration's sequences,
sequence length, positions and limits, the weights the cell draws from the
seed): the untouched program has to come out ``ok``, every fault not. Run
once per PR that touches the model's arithmetic or the configuration's
limits; its readings go into the configuration's ``reference.why`` and
PERF.md:

    chiprun -- python3 benchmark/check_faults_phi4flash.py --config <configuration> --seeds 3000000019 2147483659

A fault changes the program's side alone: the reference reads the weights
as the cell drew them and is computed once a seed. The terms of the forward
pass, each taken out by hand:

- differential attention: ``p2_not_subtracted`` (lambda 0), ``subln`` (the
  norm over a differential head left out), ``one_minus_l0`` (that factor
  left out), ``l0_of_the_cut`` (``l0`` of a layer's place among those that
  run, not of its published index), ``k_pairing`` (query head 2j against k
  head 2g + 1 and 2j + 1 against 2g), ``window`` (the window layers see
  every key before them);
- the shared values: ``cross_own_kv`` (a cross layer reads k, v of its own
  input, by the middle layer's projections), ``memory_after_gate`` (the
  memory handed on is ``y * silu(z)``), ``gmu_gate`` (the gated memory unit
  without its gate);
- Mamba: ``skip_d`` (``D`` zero), ``b_dt`` (zero), ``a_tap`` (the oldest
  tap of every convolution zero);
- ``layernorm_bias``: the bias of every layer's first LayerNorm zero;

and the control of a lower precision, ``eight_bit_residual``: the residual
stream rounded to float8_e4m3 at every pair's input, where the
configuration states bfloat16. Each line says which of the limits the run
is outside of (``failed``); the last line is ``{"ok": ...}`` and the run
exits non-zero unless every untouched run is ``ok`` and no fault is. There
is no CPU mode but ``--tiny`` (the family's tiny configuration in float32
under limits of 1e-3, for the benchmark's tests).
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

# What does not depend on which faults are planted is the Kimi script's and
# the LFM2 script's.
from check_faults_kimi_linear import (LIMITS, _Planted, _swapped,  # noqa: E402
                                      prepared)
from check_faults_lfm2 import _computed_once  # noqa: E402


def _changed(leaf: str, change):
    """The parameters with ``change`` of ``leaf`` in every stack that has
    one."""
    def changed(params):
        return {name: dict(stack, **{leaf: change(stack[leaf])})
                if isinstance(stack, dict) and leaf in stack else stack
                for name, stack in params.items()}
    return changed


def _own_kv_swaps(phi4flash, lm):
    """A cross layer's k, v from its own input: the middle pair hands on its
    attention layer's Wk, bk, Wv, bv beside k and v, and a cross layer
    projects its own normed input with them."""
    import jax.numpy as jnp

    def block(plain):
        def planted(cfg, kind, h, pair, positions, shared):
            if kind == "cross":
                shared = dict(shared, k=tuple(shared[n] for n in (
                    "wk", "bk", "wv", "bv")))
            h, aux = plain(cfg, kind, h, pair, positions, shared)
            if kind == "middle":
                aux[lm.HANDED_ON].update(
                    {n: pair["b_" + n] for n in ("wk", "bk", "wv", "bv")})
            return h, aux
        return planted

    def differential(plain):
        def planted(cfg, x, layer, l0, kv=None, window=None):
            if kv is not None and isinstance(kv[0], tuple):
                wk, bk, wv, bv = (a.astype(cfg.dtype) for a in kv[0])
                kv = (jnp.einsum("bsd,dhk->bshk", x, wk) + bk,
                      jnp.einsum("bsd,dhk->bshk", x, wv) + bv)
            return plain(cfg, x, layer, l0, kv=kv, window=window)
        return planted

    return [(phi4flash, "_block", block),
            (phi4flash, "_differential", differential)]


def faults(cfg=None):
    """name -> (attributes to swap as (module, name, plain -> planted), the
    config's fields to replace, the parameters' change or None). ``cfg``:
    the program's config, for the fault that needs the layers that run."""
    import jax
    import jax.numpy as jnp
    from ray_tpu.models import lm, phi4flash
    zeros = jnp.zeros_like

    def eight_bit(plain):
        def block(cfg, kind, h, pair, positions, shared):
            return plain(cfg, kind, h.astype(jnp.float8_e4m3fn).astype(
                h.dtype), pair, positions, shared)
        return block

    def swapped_keys(plain):
        def to_query_heads(cfg, k, v):
            pairs = k.reshape(k.shape[:2] + (k.shape[2] // 2, 2, k.shape[3]))
            return plain(cfg, pairs[:, :, :, ::-1].reshape(k.shape), v)
        return to_query_heads

    def gated_memory(plain):
        def mamba(cfg, x, layer):
            out, y, floor = plain(cfg, x, layer)
            z = jnp.einsum("bsd,de->bse", x, layer["w_in"].astype(cfg.dtype)
                           )[..., cfg.d_inner:]
            return out, y * jax.nn.silu(z), floor
        return mamba

    def place_among_those_run(plain):
        return lambda index: plain(cfg.layers.index(index))

    def model(name, planted):
        return [(phi4flash, name, planted)]

    return {
        "untouched": ([], {}, None),
        "p2_not_subtracted": (model("_lambda", lambda _: lambda layer, l0:
                                    jnp.float32(0.0)), {}, None),
        "subln": (model("_subln", lambda _: lambda o, scale, l0:
                        o * (1.0 - l0)), {}, None),
        "one_minus_l0": (model("_subln", lambda _: lambda o, scale, l0:
                               lm.rmsnorm(o, scale, phi4flash._SUBLN_EPS)),
                         {}, None),
        "l0_of_the_cut": (model("lambda_init", place_among_those_run), {},
                          None),
        "k_pairing": (model("_to_query_heads", swapped_keys), {}, None),
        "window": ([], {"sliding_window": 1 << 30}, None),
        "cross_own_kv": (_own_kv_swaps(phi4flash, lm), {}, None),
        "memory_after_gate": (model("_mamba", gated_memory), {}, None),
        "gmu_gate": (model("_gmu", lambda _: lambda cfg, x, layer, m:
                           jnp.einsum("bse,ed->bsd", m, layer["w_o"].astype(
                               cfg.dtype))), {}, None),
        "skip_d": ([], {}, _changed("a_D", zeros)),
        "b_dt": ([], {}, _changed("a_b_dt", zeros)),
        "a_tap": ([], {}, _changed("a_conv_w",
                                   lambda w: w.at[:, 0].set(0.0))),
        "layernorm_bias": ([], {}, _changed("a_ln1_bias", zeros)),
        "eight_bit_residual": (model("_block", eight_bit), {}, None),
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
    parser.add_argument("--seeds", type=int, nargs="+", default=[0])
    parser.add_argument("--tiny", action="store_true")
    args = parser.parse_args(argv)

    import jax
    if not args.tiny and jax.devices()[0].platform != "tpu":
        sys.exit(f"check_faults_phi4flash needs a TPU; JAX found "
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
        for name in faults(cfg):
            lines.append(check(config, family, cfg, mesh, params, seed, name,
                               kept))
            print(json.dumps(lines[-1]), flush=True)
        del params
    ok = all(line["ok"] == (line["fault"] == "untouched") for line in lines)
    os.makedirs(os.path.join(ROOT, "chiprun_out"), exist_ok=True)
    with open(os.path.join(ROOT, "chiprun_out",
                           "check_faults_phi4flash.json"), "w") as f:
        json.dump({"lines": lines, "ok": ok}, f, indent=1)
    print(json.dumps({"ok": ok}))
    if not ok:
        sys.exit("check_faults_phi4flash: an untouched run is not ok, or "
                 "a fault is")


if __name__ == "__main__":
    main()
