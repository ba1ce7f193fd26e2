"""Roofline shares of the Mosaic kernels in a ``mellum`` step, for the
``kernel.mellum_*_roofline`` readers: what ``flops_mellum.py`` says the
calls execute against ``peaks.json``, over the time the trace gives them.

``mosaic`` is every Mosaic call of the step (the flash kernels of the
window layers and of the full one, the grouped products, each at the calls a
step ``step_kernel_calls`` counts) over ``trace.mosaic_s``: it needs no
kernel's name among the trace's ten longest operations. ``flash`` and
``grouped_matmul`` read one kernel's time on **one** instruction, the
busiest of its name among those ten (``kernel_rooflines._busiest``): every
run of layers of one kind is a scan of its own, so that instruction is the
longest run's, called once a layer of that run and step, and every call of
a kernel has the same shapes. The count is of executed tiles and of the
rows the products are given, so no share can pass 100 %. None on a record of
another family, without a trace, or where no instruction of the name is
among the ten.
"""

from __future__ import annotations

import itertools
from typing import Any, Dict, Optional

import flops
import flops_mellum as counts
import harness
from kernel_rooflines import _busiest


def shapes(record) -> Optional[Dict[str, Any]]:
    """What the counts need from the record's cell, or None where the cell
    is not of this family or the run was not traced."""
    trace = record.get("trace") or {}
    config = record["cell"]["config"]
    steps = len(trace.get("steps_device_s") or ())
    if config.get("model_type") != "mellum" or not steps:
        return None
    layout, program = config["layout"], config["program"]
    cfg = harness.load_module("families", program["family"]).config(program)
    kind = record["device"]["kind"]
    return {"config": config, "steps": steps, "trace": trace,
            "calls": counts.step_kernel_calls(
                config, layout["batch"], layout["seq_len"], cfg.attn_blk_q,
                cfg.attn_blk_k, bool(cfg.remat)),
            "peak_flops": flops.peak(kind),
            "peak_bytes": flops.peak(kind, "hbm_bytes_per_s")}


def longest_run(config, kind: Optional[str] = None) -> int:
    """Layers in the longest run of one kind of layer (of ``kind``, if
    given)."""
    kinds = config["layer_types"][:config["num_hidden_layers"]]
    return max((len(list(run)) for k, run in itertools.groupby(kinds)
                if kind in (None, k)), default=0)


def _one_call(found, name: str, layers: int) -> Optional[float]:
    """Per cent of the roofline of one call of the kernel ``name``, read on
    its busiest instruction, which ``layers`` calls a step."""
    secs = _busiest(found["trace"], name)
    if secs is None or name not in found["calls"] or not layers:
        return None
    least = counts.least_seconds(found["calls"][name], found["peak_flops"],
                                 found["peak_bytes"])
    return 100.0 * least / (secs / (layers * found["steps"]))


def flash(record, kernel: str) -> Optional[float]:
    """A window layer's ``kernel`` (``flash_fwd``, ``flash_bwd_dq`` or
    ``flash_bwd_dkv``), read on the longest run of window layers. At tiles
    of 512 only ``flash_bwd_dkv_win`` is among the trace's ten, so it alone
    has a reader (PERF.md, PR 58: the two others from a whole table)."""
    found = shapes(record)
    if found is None:
        return None
    return _one_call(found, kernel + counts.WINDOW_SUFFIX,
                     longest_run(found["config"], "sliding_attention"))


def grouped_matmul(record) -> Optional[float]:
    """``gmm`` (a product or its rows' cotangent): every one of a layer's
    products has the same FLOPs and least bytes, and the busiest instruction
    is the slowest of them, in the longest run of layers."""
    found = shapes(record)
    if found is None:
        return None
    return _one_call(found, "gmm", longest_run(found["config"]))


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
