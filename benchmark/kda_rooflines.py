"""Roofline shares of the delta rule's kernels in a ``kimi_linear`` step, for
the ``kernel.kda_*_roofline`` readers: what ``flops_kimi_linear.py`` says
one call executes against ``peaks.json``, over the time the trace gives it.

The trace keeps the ten longest operations by instruction name
(``trace.device_ops``), summed over the window. A kernel appears there once
per place it is called from: every run of layers of one kind is a scan of
its own, and a block's forward and its rematerialised forward are two
instructions. Which of them made the list cannot be told from the names, so
a kernel's time is read on **one** instruction, the busiest of its name,
which is the longest run's (``flops_kimi_linear.longest_kda_run``): it is
called once per layer of that run and step, and every call of a kernel has
the same shapes. None where no instruction of the name is among the ten, on
a record of another family, or without a trace.
"""

from __future__ import annotations

from typing import Any, Dict, Optional

import flops
import flops_kimi_linear
import harness
from kernel_rooflines import _busiest


def shapes(record) -> Optional[Dict[str, Any]]:
    """What the counts need from the record's cell, or None where the cell
    is not of this family or the run was not traced."""
    trace = record.get("trace") or {}
    config = record["cell"]["config"]
    steps = len(trace.get("steps_device_s") or ())
    if config.get("model_type") != "kimi_linear" or not steps:
        return None
    from ray_tpu.ops.kda import CHUNK
    program = config["program"]
    cfg = harness.load_module("families", program["family"]).config(program)
    kind = record["device"]["kind"]
    return {"config": config, "steps": steps, "trace": trace,
            "batch": config["layout"]["batch"],
            "seq_len": config["layout"]["seq_len"],
            "chunk": CHUNK, "blk_q": cfg.attn_blk_q,
            "blk_k": cfg.attn_blk_k, "remat": bool(cfg.remat),
            "peak_flops": flops.peak(kind),
            "peak_bytes": flops.peak(kind, "hbm_bytes_per_s")}


def kernel(record, name: str) -> Optional[float]:
    """Per cent of the roofline of one call of ``kda_fwd`` or ``kda_bwd``."""
    found = shapes(record)
    if found is None:
        return None
    layers = flops_kimi_linear.longest_kda_run(found["config"])
    secs = _busiest(found["trace"], name)
    if secs is None or not layers:
        return None
    call = flops_kimi_linear.kda_call(
        name, found["config"], found["batch"], found["seq_len"],
        found["chunk"])
    least = flops_kimi_linear.least_seconds(
        call, found["peak_flops"], found["peak_bytes"])
    return 100.0 * least / (secs / (layers * found["steps"]))


def mosaic(record) -> Optional[float]:
    """Per cent: the least time for every Mosaic call of the step over
    ``trace.mosaic_s``; the grouped matmuls' rows at the share of the
    assignments the program's counters say fell on held experts."""
    found = shapes(record)
    if found is None or not found["trace"].get("mosaic_s"):
        return None
    calls = flops_kimi_linear.step_kernel_calls(
        found["config"], found["batch"], found["seq_len"], found["chunk"],
        found["blk_q"], found["blk_k"], found["remat"],
        harness.load_module("layer_metrics", "moe.held_share").read(record))
    least = sum(one["calls"] * flops_kimi_linear.least_seconds(
        one, found["peak_flops"], found["peak_bytes"])
        for one in calls.values())
    return 100.0 * least * found["steps"] / found["trace"]["mosaic_s"]
