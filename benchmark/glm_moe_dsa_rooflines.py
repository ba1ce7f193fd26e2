"""Roofline shares of the selection's kernels in a ``glm_moe_dsa`` step, for
the ``kernel.dsa_*_roofline`` readers: what ``flops_glm_moe_dsa.py`` says one
call needs at the least against ``peaks.json``, over the time the trace
gives it.

The trace keeps the ten longest operations by instruction name
(``trace.device_ops``), summed over the window. A kernel appears there once
per place it is called from: every run of layers of one kind is a scan of
its own, and a block's forward and its rematerialised forward are two
instructions. A kernel's time is read on **one** instruction, the busiest of
its name, which is the longest run's (``flops_glm_moe_dsa.longest_run``: the
expert layers that share a selection): it is called once per layer of that
run and step, and every call of a kernel has the same shapes. None where no
instruction of the name is among the ten, on a record of another family, or
without a trace.
"""

from __future__ import annotations

from typing import Any, Dict, Optional

import flops
import flops_glm_moe_dsa
import harness
from kernel_rooflines import _busiest


def shapes(record) -> Optional[Dict[str, Any]]:
    """What the counts need from the record's cell, or None where the cell
    is not of this family or the run was not traced."""
    trace = record.get("trace") or {}
    config = record["cell"]["config"]
    steps = len(trace.get("steps_device_s") or ())
    if config.get("model_type") != "glm_moe_dsa" or not steps:
        return None
    program = config["program"]
    cfg = harness.load_module("families", program["family"]).config(program)
    kind = record["device"]["kind"]
    return {"config": config, "steps": steps, "trace": trace,
            "batch": config["layout"]["batch"],
            "seq_len": config["layout"]["seq_len"],
            "remat": bool(cfg.remat),
            "peak_flops": flops.peak(kind),
            "peak_bytes": flops.peak(kind, "hbm_bytes_per_s")}


def kernel(record, name: str) -> Optional[float]:
    """Per cent of the roofline of one call of a kernel of ``ops/dsa.py``."""
    found = shapes(record)
    if found is None:
        return None
    secs = _busiest(found["trace"], name)
    if secs is None:
        return None
    layers = flops_glm_moe_dsa.longest_run(found["config"])
    least = flops_glm_moe_dsa.least_seconds(
        flops_glm_moe_dsa.attention_call(
            name, found["config"], found["batch"], found["seq_len"]),
        found["peak_flops"], found["peak_bytes"])
    return 100.0 * least / (secs / (layers * found["steps"]))


def mosaic(record) -> Optional[float]:
    """Per cent: the least time for every Mosaic call of the step over
    ``trace.mosaic_s``; the grouped matmuls' rows at the share of the
    assignments the program's counters say fell on held experts."""
    found = shapes(record)
    if found is None or not found["trace"].get("mosaic_s"):
        return None
    calls = flops_glm_moe_dsa.step_kernel_calls(
        found["config"], found["batch"], found["seq_len"], found["remat"],
        harness.load_module("layer_metrics", "moe.held_share").read(record))
    least = sum(one["calls"] * flops_glm_moe_dsa.least_seconds(
        one, found["peak_flops"], found["peak_bytes"])
        for one in calls.values())
    return 100.0 * least * found["steps"] / found["trace"]["mosaic_s"]
