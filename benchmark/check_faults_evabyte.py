"""Faults planted in the ``evabyte`` program, each through the runner's own
comparison, the one that decides ``correct`` (``runners/train.py:
_reference_check``: the configuration's sequence length, positions and
limits, the weights the cell draws from the seed): the untouched program has
to come out ``ok``, every fault not. Run once per PR that touches the
model's arithmetic or the configuration's limits; its readings go into the
configuration's ``reference.why`` and PERF.md:

    chiprun -- python3 benchmark/check_faults_evabyte.py --config <configuration> --seeds 3000000019 2147483659 [--untouched <seed> ...]

A fault changes the program's side alone: the reference reads the weights
as the cell drew them, and is computed once a seed. Four faults are another
mask: each is a function ``(t, column, Summaries) -> bool`` from which both
the kernels' mask and their table of tiles are made again
(``flash_attention._summaries_mask`` / ``_summaries_tiles``), so a fault may
see tiles the untouched table leaves out:

- ``summaries``: none is seen, local attention alone; ``own_chunks``: the
  whole chunks of a query's own window are seen as summaries too (counted
  twice); ``sliding_window``: the ``window_size`` keys before a query in
  the block's place (what ``window=2048`` gives: the easy wrong answer);
- ``chunk_offset``: the chunks pooled one byte late (chunk j pools keys 16 j
  + 1 .. 16 j + 16); ``rope_after_pooling``: the keys pooled unrotated and
  the pooled key rotated at its chunk's first position; ``mu``: left out;
  ``phi``: zero, the pooling a mean;
- ``unit_offset``: ``g`` for ``1 + g`` in the layers' norms;
  ``head_targets``: head i held to byte t + i (the loss alone moves);

and the control of a lower precision, ``eight_bit_residual``: the residual
stream rounded to float8_e4m3 at every layer's input, where the
configuration states bfloat16. Each line says which of the limits the run is
outside of (``failed``); ``--untouched`` adds seeds on which only the
untouched program runs. The last line is ``{"ok": ...}`` and the run exits
non-zero unless every untouched run is ``ok`` and no fault is. There is no
CPU mode but ``--tiny`` (the family's tiny configuration in float32 under
limits of 1e-3, for the benchmark's tests).

``head_targets`` is read and does not decide (``BY_CHANCE``): it moves no
logit, and at random weights every head's loss is ln 320 whichever byte it
is held to, so its reading is sampling noise that lands either side of
``loss_tol`` (the configuration's ``reference.why`` has six seeds). The
term is held exactly where the weights need not be trained to show it:
``tests/test_evabyte.py`` and ``tests/test_decoder_shell.py``.
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


#: Faults whose reading on the chip is chance (module text).
BY_CHANCE = frozenset({"head_targets"})


def _masked_by(allowed):
    """The swaps that make the kernels' mask and table from ``allowed(t,
    column, eva)``: ``t`` and ``column`` integer arrays that broadcast,
    numpy's for the table and jax.numpy's in a kernel (logical operators
    alone: Mosaic has no select between vectors of booleans)."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    import ray_tpu.ops  # noqa: F401
    flash = sys.modules["ray_tpu.ops.flash_attention"]

    def mask(_):
        def planted(qi, ki, blk_q, blk_k, eva):
            t = qi * blk_q + jax.lax.broadcasted_iota(
                jnp.int32, (blk_q, blk_k), 0)
            column = ki * blk_k + jax.lax.broadcasted_iota(
                jnp.int32, (blk_q, blk_k), 1)
            return allowed(t, column, eva)
        return planted

    def tiles(_):
        def planted(S, blk_q, blk_k, eva):
            columns = np.arange(eva.rows + S, dtype=np.int64)[None, :]
            seen = np.stack([
                allowed(np.arange(first, first + blk_q,
                                  dtype=np.int64)[:, None], columns,
                        eva).reshape(blk_q, -1, blk_k).any((0, 2))
                for first in range(0, S, blk_q)])
            return seen, np.zeros_like(seen)
        return planted

    return [(flash, "_summaries_mask", mask),
            (flash, "_summaries_tiles", tiles)]


def _parts(t, column, eva):
    """(is a summary, the window's start, the column as a key)."""
    return column < eva.rows, t - t % eva.window, column - eva.rows


def _local_alone(t, column, eva):
    summary, start, key = _parts(t, column, eva)
    return ~summary & (start <= key) & (key <= t)


def _own_chunks_too(t, column, eva):
    summary, start, key = _parts(t, column, eva)
    return summary & (column * eva.chunk + eva.chunk - 1 <= t) \
        | ~summary & (start <= key) & (key <= t)


def _sliding(t, column, eva):
    summary, start, key = _parts(t, column, eva)
    return summary & (column * eva.chunk < start) \
        | ~summary & (t - eva.window < key) & (key <= t)


def faults():
    """name -> (attributes to swap as (module, name, plain -> planted), the
    config's fields to replace, the parameters' change or None)."""
    import jax.numpy as jnp
    from ray_tpu.models import evabyte, lm
    from ray_tpu.ops import eva

    def late(plain):
        def pool(k, v, phi, mu, chunk):
            return plain(jnp.roll(k, -1, axis=1), jnp.roll(v, -1, axis=1),
                         phi, mu, chunk)
        return pool

    def unrotated(plain):
        theta = evabyte.PRESETS["evabyte-6.5b"].rope_theta

        def pool(k, v, phi, mu, chunk):
            at = lm.positions_of(k[..., 0, 0])
            kc, vc = plain(lm.rope(k, -at, theta), v, phi,
                           jnp.zeros_like(mu), chunk)
            kc = lm.rope(kc, at[:, ::chunk], theta).astype(jnp.float32) \
                + mu.astype(jnp.float32)
            return kc.astype(k.dtype), vc
        return pool

    def eight_bit(plain):
        def block(cfg, kind, h, layer, positions):
            return plain(cfg, kind, h.astype(jnp.float8_e4m3fn).astype(
                h.dtype), layer, positions)
        return block

    def a_byte_early(plain):
        def shifted(targets, mask, heads):
            return plain(jnp.pad(targets[:, :-1], ((0, 0), (1, 0))), mask,
                         heads)
        return shifted

    def no_offset(_):
        return lambda cfg, h, g: lm.rmsnorm(h, g.astype(jnp.float32),
                                            cfg.rms_norm_eps)

    return {
        "untouched": ([], {}, None),
        "summaries": (_masked_by(_local_alone), {}, None),
        "own_chunks": (_masked_by(_own_chunks_too), {}, None),
        "sliding_window": (_masked_by(_sliding), {}, None),
        "chunk_offset": ([(eva, "pool", late)], {}, None),
        "rope_after_pooling": ([(eva, "pool", unrotated)], {}, None),
        "mu": ([], {}, _changed("eva_mu", jnp.zeros_like)),
        "phi": ([], {}, _changed("eva_phi", jnp.zeros_like)),
        "unit_offset": ([(evabyte, "_norm", no_offset)], {}, None),
        "head_targets": ([(lm, "shifted_targets", a_byte_early)], {}, None),
        "eight_bit_residual": ([(evabyte, "_block", eight_bit)], {}, None),
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
    parser.add_argument("--tiny", action="store_true")
    args = parser.parse_args(argv)

    import jax
    if not args.tiny and jax.devices()[0].platform != "tpu":
        sys.exit(f"check_faults_evabyte needs a TPU; JAX found "
                 f"{jax.devices()}")
    config, family, cfg, mesh = prepared(args.config, args.tiny)
    config["program"]["gains"] = dict(
        config["program"]["gains"],
        **{leaf: float(gain) for leaf, gain in (
            pair.split("=") for pair in args.set)})
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
             if line["fault"] not in BY_CHANCE)
    os.makedirs(os.path.join(ROOT, "chiprun_out"), exist_ok=True)
    with open(os.path.join(ROOT, "chiprun_out",
                           "check_faults_evabyte.json"), "w") as f:
        json.dump({"lines": lines, "ok": ok}, f, indent=1)
    print(json.dumps({"ok": ok}))
    if not ok:
        sys.exit("check_faults_evabyte: an untouched run is not ok, or a "
                 "fault is")


if __name__ == "__main__":
    main()
