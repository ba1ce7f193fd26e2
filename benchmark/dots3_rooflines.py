"""Roofline shares of the Pallas kernels in a ``dots3_note`` step, and the
gates' means, for the ``kernel.dots3_*`` and ``attn.gate_mean_*`` readers:
what ``flops_dots3_note.py`` says one call needs at the least against
``peaks.json``, over the time the trace gives it.

The trace keeps the ten longest operations by instruction name
(``trace.device_ops``), summed over the window. A kernel appears there once
per place it is called from: every run of layers of one kind is a scan of
its own, and a block's forward and its rematerialised forward are two
instructions. A kernel's time is read on **one** instruction, the busiest of
its name, which is the longest run's of the kernel's kind of layer
(``flops_dots3_note.longest_run``): it is called once per layer of that run
and step, and every call of a kernel has the same shapes. None where no
instruction of the name is among the ten, on a record of another family, or
without a trace.
"""

from __future__ import annotations

from typing import Any, Dict, Optional

import flops
import flops_dots3_note as counts
import harness
from kernel_rooflines import _busiest

GATE_GAUGE = "ray_tpu_train_attn_gate_mean"


def shapes(record) -> Optional[Dict[str, Any]]:
    """What the counts need from the record's cell, or None where the cell
    is not of this family or the run was not traced."""
    trace = record.get("trace") or {}
    config = record["cell"]["config"]
    steps = len(trace.get("steps_device_s") or ())
    if config.get("model_type") != "dots3_note" or not steps:
        return None
    layout, program = config["layout"], config["program"]
    cfg = harness.load_module("families", program["family"]).config(program)
    kind = record["device"]["kind"]
    return {"config": config, "steps": steps, "trace": trace,
            "calls": counts.step_kernel_calls(
                config, layout["batch"], layout["seq_len"], bool(cfg.remat),
                harness.load_module("layer_metrics",
                                    "moe.held_share").read(record)),
            "peak_flops": flops.peak(kind),
            "peak_bytes": flops.peak(kind, "hbm_bytes_per_s")}


def kernel(record, name: str) -> Optional[float]:
    """Per cent of the roofline of one call of the attention kernel ``name``
    (``dsa_*``: a full layer's, over the selected pairs; ``flash_*_win``: a
    window layer's, over the window's pairs)."""
    found = shapes(record)
    if found is None or name not in found["calls"]:
        return None
    secs = _busiest(found["trace"], name)
    layers = counts.longest_run(
        found["config"],
        "window" if name.endswith(counts.WINDOW_SUFFIX) else "full")
    if secs is None or not layers:
        return None
    least = counts.least_seconds(found["calls"][name], found["peak_flops"],
                                 found["peak_bytes"])
    return 100.0 * least / (secs / (layers * found["steps"]))


def mosaic(record) -> Optional[float]:
    """Per cent: the least time for every Mosaic call of the step over
    ``trace.mosaic_s``; the grouped matmuls' rows at the share of the
    assignments the program's counters say fell on held experts."""
    found = shapes(record)
    if found is None or not found["trace"].get("mosaic_s"):
        return None
    least = sum(one["calls"] * counts.least_seconds(
        one, found["peak_flops"], found["peak_bytes"])
        for one in found["calls"].values())
    return 100.0 * least * found["steps"] / found["trace"]["mosaic_s"]


def gate_mean(kind: str) -> Optional[float]:
    """The program's gauge ``ray_tpu_train_attn_gate_mean`` of one kind of
    layer (``full`` or ``window``): the series tagged with it. None where
    the program registered no such gauge (a parent without the family) or
    never fed the series."""
    try:
        from ray_tpu.util import metrics
    except ImportError:
        return None
    for entry in metrics.snapshot():
        if entry["name"] == GATE_GAUGE:
            found = [value for key, value in entry["series"].items()
                     if kind in key]
            return float(found[0]) if found else None
    return None
