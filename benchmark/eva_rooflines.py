"""Roofline shares of EVA attention's kernels in an ``evabyte`` step, for the
``kernel.eva_*_roofline`` readers: what ``flops_evabyte.py`` says one call
needs at the least against ``peaks.json``, over the time the trace gives it.

The trace keeps the ten longest operations by instruction name
(``trace.device_ops``), summed over the window. Every layer is of one kind,
so the model is one scan forward and one backward; a kernel appears there
once per place it is called from (a block's forward and its rematerialised
forward are two instructions), each called once a layer and step with the
same shapes. A kernel's time is read on **one** instruction, the busiest of
its name. The count is over the pairs EVA defines: what the tiles compute
and mask beyond them (half of a diagonal tile, the columns of a summary tile
past a window's count: 144 tiles' worth of steps for 120 of pairs at 32768)
is work done and not credited, so the shares read low by that much and can
never pass 100 %. None where no instruction of the name is among the ten, on
a record of another family, or without a trace.
"""

from __future__ import annotations

from typing import Any, Dict, Optional

import flops
import flops_evabyte
import harness
from kernel_rooflines import _busiest


def shapes(record) -> Optional[Dict[str, Any]]:
    """What the counts need from the record's cell, or None where the cell
    is not of this family or the run was not traced."""
    trace = record.get("trace") or {}
    config = record["cell"]["config"]
    steps = len(trace.get("steps_device_s") or ())
    if config.get("model_type") != "evabyte" or not steps:
        return None
    program = config["program"]
    cfg = harness.load_module("families", program["family"]).config(program)
    kind = record["device"]["kind"]
    return {"config": config, "steps": steps, "trace": trace,
            "batch": config["layout"]["batch"],
            "seq_len": config["layout"]["seq_len"],
            "blk_k": cfg.attn_blk_k, "remat": bool(cfg.remat),
            "peak_flops": flops.peak(kind),
            "peak_bytes": flops.peak(kind, "hbm_bytes_per_s")}


def kernel(record, name: str) -> Optional[float]:
    """Per cent of the roofline of one call of a kernel of ``ops/eva.py``."""
    found = shapes(record)
    if found is None:
        return None
    secs = _busiest(found["trace"], name)
    if secs is None:
        return None
    least = flops_evabyte.least_seconds(
        flops_evabyte.attention_call(
            name, found["config"], found["batch"], found["seq_len"],
            found["blk_k"]),
        found["peak_flops"], found["peak_bytes"])
    calls = found["config"]["num_hidden_layers"] * found["steps"]
    return 100.0 * least / (secs / calls)


def mosaic(record) -> Optional[float]:
    """Per cent: the least time for every Mosaic call of the step over
    ``trace.mosaic_s``."""
    found = shapes(record)
    if found is None or not found["trace"].get("mosaic_s"):
        return None
    calls = flops_evabyte.step_kernel_calls(
        found["config"], found["batch"], found["seq_len"], found["remat"],
        found["blk_k"])
    least = sum(one["calls"] * flops_evabyte.least_seconds(
        one, found["peak_flops"], found["peak_bytes"])
        for one in calls.values())
    return 100.0 * least * found["steps"] / found["trace"]["mosaic_s"]
