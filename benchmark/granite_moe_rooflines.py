"""Roofline shares of the Mosaic kernels in a ``granitemoehybrid_moe`` step,
for the ``kernel.granite_moe_*_roofline`` readers: what
``flops_granite_moe.py`` says the calls execute against ``peaks.json``, over
the time the trace gives them.

``mosaic`` is every Mosaic call of the step (the scans with their convs and
gate-norms, the flash kernels of the attention layer, every layer's grouped
products and its share's way back to tokens, each at the calls a step
``step_kernel_calls`` counts, the expert layers' rows at the share of the
assignments the program's counters say fell on held experts) over
``trace.mosaic_s``: it needs no kernel's name among the trace's ten longest
operations. The counts are of the products a grid step makes and of the rows
the products are given, so the share cannot pass 100 %. None on a record of
another family or without a trace.
"""

from __future__ import annotations

from typing import Any, Dict, Optional

import flops
import flops_granite_moe as counts
import harness

FAMILY = "granitemoehybrid_moe"


def shapes(record) -> Optional[Dict[str, Any]]:
    """What the counts need from the record's cell, or None where the cell
    is not of this family or the run was not traced."""
    trace = record.get("trace") or {}
    config = record["cell"]["config"]
    steps = len(trace.get("steps_device_s") or ())
    if config.get("program", {}).get("family") != FAMILY or not steps:
        return None
    layout, program = config["layout"], config["program"]
    cfg = harness.load_module("families", program["family"]).config(program)
    kind = record["device"]["kind"]
    return {"steps": steps, "trace": trace,
            "calls": counts.step_kernel_calls(
                config, layout["batch"], layout["seq_len"], cfg.attn_blk_q,
                cfg.attn_blk_k, bool(cfg.remat),
                harness.load_module("layer_metrics",
                                    "moe.held_share").read(record)),
            "peak_flops": flops.peak(kind),
            "peak_bytes": flops.peak(kind, "hbm_bytes_per_s")}


def mosaic(record) -> Optional[float]:
    """Per cent: the least time for every Mosaic call of the step over
    ``trace.mosaic_s``."""
    found = shapes(record)
    if found is None or not found["trace"].get("mosaic_s"):
        return None
    least = sum(one["calls"] * counts.least_seconds(
        one, found["peak_flops"], found["peak_bytes"])
        for one in found["calls"].values())
    return 100.0 * least * found["steps"] / found["trace"]["mosaic_s"]
