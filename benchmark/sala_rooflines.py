"""Roofline shares of the Mosaic kernels in a ``minicpm_sala`` step, for the
``kernel.sala_*_roofline`` readers: what ``flops_minicpm_sala.py`` says the
calls need at the least against ``peaks.json``, over the time the trace
gives them.

``mosaic`` is every Mosaic call of the step (block-sparse attention's three
kernels, the recurrence's two, the gated norm's two, each at the calls a
step ``step_kernel_calls`` counts) over ``trace.mosaic_s``: it needs no
kernel's name among the trace's ten longest operations. ``kernel`` reads one
kernel's time on the busiest instruction of its name among those ten, where
it is there (a kernel called from a block's forward and its rematerialised
forward is two instructions, each called once a layer of its kind and
step). The attention's count is over the pairs the selection defines: what
the tiles compute and mask beyond them is not credited, so the shares read
low by that much and cannot pass 100 %. None on a record of another family,
below ``dense_len`` or without a trace.
"""

from __future__ import annotations

from typing import Any, Dict, Optional

import flops
import flops_minicpm_sala as counts
import harness
from kernel_rooflines import _busiest


def shapes(record) -> Optional[Dict[str, Any]]:
    """What the counts need from the record's cell, or None where the cell
    is not of this family or the run was not traced."""
    trace = record.get("trace") or {}
    config = record["cell"]["config"]
    steps = len(trace.get("steps_device_s") or ())
    if config.get("model_type") != "minicpm_sala" or not steps:
        return None
    layout = config["layout"]
    if layout["seq_len"] <= config["assumed"]["sparse_config"]["dense_len"]:
        return None
    program = config["program"]
    cfg = harness.load_module("families", program["family"]).config(program)
    kind = record["device"]["kind"]
    return {"config": config, "steps": steps, "trace": trace,
            "calls": counts.step_kernel_calls(
                config, layout["batch"], layout["seq_len"], bool(cfg.remat)),
            "peak_flops": flops.peak(kind),
            "peak_bytes": flops.peak(kind, "hbm_bytes_per_s")}


def kernel(record, name: str) -> Optional[float]:
    """Per cent of the roofline of one call of the kernel ``name``."""
    found = shapes(record)
    if found is None:
        return None
    secs = _busiest(found["trace"], name)
    if secs is None:
        return None
    call = found["calls"][name]
    least = counts.least_seconds(call, found["peak_flops"],
                                 found["peak_bytes"])
    kind = "sparse" if name.startswith("sala") else "lightning"
    return 100.0 * least / (
        secs / (counts.count(found["config"], kind) * found["steps"]))


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
