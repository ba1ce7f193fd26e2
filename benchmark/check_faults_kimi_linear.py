"""Faults planted in the ``kimi_linear`` program, each through the runner's
own comparison, the one that decides ``correct``
(``runners/train.py: _reference_check``: the configuration's sequence
length, positions and limits, the weights the cell draws from the seed): the
untouched program has to come out ``ok``, every fault not. Run once per PR
that touches the model's arithmetic or the configuration's limits; its
readings go into the configuration's ``reference.why`` and PERF.md:

    chiprun -- python3 benchmark/check_faults_kimi_linear.py --config <configuration> --seeds 3000000019 2147483659

A fault changes the program's side alone: the reference reads the weights
as the cell drew them. The terms of the forward pass, each taken out by hand:

- ``delta_term``: ``S += beta k v^T`` alone, nothing of what the state
  already holds for k taken back (a scan over tokens in float32);
- ``decay``: a = 0; ``beta``: 1 for the sigmoid; ``qk_norm``: q and k as the
  convolutions leave them (q still times ``head_dim^-0.5``); ``conv``: q, k
  and v without their short convolutions; ``output_gate``: 0.5 for the
  sigmoid (``w_gb`` = 0);
- ``rope_on_mla``: the latent layer's rotation put back (``mla_use_nope``
  false); ``routed_scaling_factor``: 1 for the published factor;
  ``shared_expert``: left out (``shared_w_down`` = 0);

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
import contextlib
import json
import os
import sys
from dataclasses import replace

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
sys.path[:0] = [ROOT, HERE]

LIMITS = {"logit_rms_err": "logit_rms_tol", "logit_max_err": "logit_max_tol",
          "loss_err": "loss_tol"}


def _additive_rule(q, k, v, a, beta):
    """``lm.delta_rule`` without the delta: S_t = Diag(exp(a_t)) S_(t-1) +
    beta_t k_t v_t^T, o_t = S_t^T q_t, token by token in float32."""
    import jax
    import jax.numpy as jnp
    f32 = jnp.float32
    batch, _, heads, width = q.shape

    def step(state, token):
        q_t, k_t, v_t, a_t, beta_t = token
        state = jnp.exp(a_t)[..., None] * state \
            + (beta_t[..., None] * k_t)[..., None] * v_t[..., None, :]
        return state, jnp.einsum("bhkv,bhk->bhv", state, q_t)

    _, out = jax.lax.scan(
        step, jnp.zeros((batch, heads, width, v.shape[-1]), f32),
        tuple(x.astype(f32).swapaxes(0, 1) for x in (q, k, v, a, beta)))
    return out.swapaxes(0, 1).astype(v.dtype)


def _rule_with(plain, **changed):
    def rule(q, k, v, a, beta):
        args = dict(q=q, k=k, v=v, a=a, beta=beta)
        args.update({name: fn(args[name]) for name, fn in changed.items()})
        return plain(**args)
    return rule


def _zeroed(leaf: str):
    """The parameters with ``leaf`` zero in every stack that has one."""
    import jax.numpy as jnp

    def change(params):
        return {name: dict(stack, **{leaf: jnp.zeros_like(stack[leaf])})
                if isinstance(stack, dict) and leaf in stack else stack
                for name, stack in params.items()}
    return change


def faults():
    """name -> (attributes to swap as (module, name, plain -> planted), the
    config's fields to replace, the parameters' change or None)."""
    import jax
    import jax.numpy as jnp
    from ray_tpu.models import kimi_linear, lm
    from ray_tpu.ops import kda

    def eight_bit(plain):
        def block(cfg, kind, h, layer, positions):
            return plain(cfg, kind, h.astype(jnp.float8_e4m3fn).astype(
                h.dtype), layer, positions)
        return block

    def rule(**changed):
        return [(lm, "delta_rule", lambda plain: _rule_with(plain,
                                                            **changed))]

    return {
        "untouched": ([], {}, None),
        "delta_term": ([(lm, "delta_rule", lambda _: _additive_rule)], {},
                       None),
        "decay": (rule(a=jnp.zeros_like), {}, None),
        "beta": (rule(beta=jnp.ones_like), {}, None),
        # The rule normalises inside (``ops/kda.py``: the chunk's function
        # calls ``_unit_rows``), and the layer's short convolutions and
        # their SiLU are one call, ``lm.conv_silu``: the SiLU stays.
        "qk_norm": ([(kda, "_unit_rows", lambda _: lambda y, scale=1.0: (
            y.astype(jnp.float32) * scale).astype(y.dtype))], {}, None),
        "conv": ([(lm, "conv_silu", lambda _: lambda x, w, b=None:
                   jax.nn.silu(x.astype(jnp.float32)).astype(x.dtype))], {},
                 None),
        "output_gate": ([], {}, _zeroed("w_gb")),
        "rope_on_mla": ([], {"mla_use_nope": False}, None),
        "routed_scaling_factor": ([], {"routed_scaling_factor": 1.0}, None),
        "shared_expert": ([], {}, _zeroed("shared_w_down")),
        "eight_bit_residual": ([(kimi_linear, "_block", eight_bit)], {},
                               None),
    }


@contextlib.contextmanager
def _swapped(swaps):
    plain = [(module, name, getattr(module, name))
             for module, name, _ in swaps]
    for module, name, planted in swaps:
        setattr(module, name, planted(getattr(module, name)))
    try:
        yield
    finally:
        for module, name, was in plain:
            setattr(module, name, was)


class _Planted:
    """The family as the runner's comparison asks for it, the program's
    side alone on changed parameters."""

    def __init__(self, family, change):
        self.vocab_size = family.vocab_size
        self._family, self._change = family, change

    def logits_and_losses(self, params, cfg, tokens, targets):
        return self._family.logits_and_losses(
            self._change(params) if self._change else params, cfg, tokens,
            targets)


def check(config, family, cfg, mesh, params, seed: int, name: str):
    """One fault through ``_reference_check`` as the runner calls it:
    its record, with ``failed``, the limits it is outside of."""
    import harness
    runner = harness.load_module("runners", "train")
    swaps, fields, change = faults()[name]
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


def prepared(name: str, tiny: bool):
    """(configuration, family, program config, mesh) of ``configs/<name>``;
    ``tiny``: at the family's tiny size, float32, under limits of 1e-3."""
    import harness
    import jax
    from ray_tpu.parallel import MeshConfig, build_mesh
    config = harness.load_json(os.path.join(HERE, "configs", name + ".json"))
    family = harness.load_module("families", config["program"]["family"])
    if tiny:
        config = family.tiny(config)
        config["reference"] = dict(
            config["reference"], positions=16, logit_rms_tol=1e-3,
            logit_max_tol=1e-2, loss_tol=1e-4)
    mesh = build_mesh(MeshConfig(**config["layout"]["mesh"]),
                      devices=jax.devices()[:config["layout"]["chips"]])
    return config, family, family.config(config["program"]), mesh


def main(argv=None) -> None:
    parser = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    parser.add_argument("--config", required=True)
    parser.add_argument("--seeds", type=int, nargs="+", default=[0])
    parser.add_argument("--tiny", action="store_true")
    args = parser.parse_args(argv)

    import jax
    if not args.tiny and jax.devices()[0].platform != "tpu":
        sys.exit(f"check_faults_kimi_linear needs a TPU; JAX found "
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
        for name in faults():
            lines.append(check(config, family, cfg, mesh, params, seed, name))
            print(json.dumps(lines[-1]), flush=True)
        del params
    ok = all(line["ok"] == (line["fault"] == "untouched") for line in lines)
    os.makedirs(os.path.join(ROOT, "chiprun_out"), exist_ok=True)
    with open(os.path.join(ROOT, "chiprun_out",
                           "check_faults_kimi_linear.json"), "w") as f:
        json.dump({"lines": lines, "ok": ok}, f, indent=1)
    print(json.dumps({"ok": ok}))
    if not ok:
        sys.exit("check_faults_kimi_linear: an untouched run is not ok, or "
                 "a fault is")


if __name__ == "__main__":
    main()
