"""Roofline shares of the Mosaic kernels in a ``nemotron_h`` step, for the
``kernel.nemotron_*_roofline`` readers: what ``flops_nemotron_h.py`` says the
calls execute against ``peaks.json``, over the time the trace gives them.

``mosaic`` is every Mosaic call of the step (the grouped scans with their
convs and grouped gate-norms, the flash kernels of the attention layer, the
grouped products and the share's way back to tokens, each at the calls a
step ``step_kernel_calls`` counts, the expert layers' rows at the share of
the assignments the program's counters say fell on held experts) over
``trace.mosaic_s``: it needs no kernel's name among the trace's ten longest
operations. ``ssd`` reads one kernel's time on **one** instruction, the
busiest of its name among those ten (``kernel_rooflines._busiest``): the
units of an expert and a state-space layer are one scan, so that instruction
is called once a unit of the longest run and step, and every call of a
kernel has the same shapes. Only ``ssd_bwd`` is among the ten in this
family's cell (PERF.md, PR 62: ``ssd_fwd``'s two instructions and the
grouped products' twelve are each under the tenth, and have their shares
from a whole table there). The counts are
of the products a grid step makes and of the rows the products are given, so
no share can pass 100 %. None on a record of another family, without a
trace, or where no instruction of the name is among the ten.
"""

from __future__ import annotations

import itertools
from typing import Any, Dict, Optional

import flops
import flops_nemotron_h as counts
import harness
from kernel_rooflines import _busiest


def shapes(record) -> Optional[Dict[str, Any]]:
    """What the counts need from the record's cell, or None where the cell
    is not of this family or the run was not traced."""
    trace = record.get("trace") or {}
    config = record["cell"]["config"]
    steps = len(trace.get("steps_device_s") or ())
    if config.get("model_type") != "nemotron_h" or not steps:
        return None
    layout, program = config["layout"], config["program"]
    cfg = harness.load_module("families", program["family"]).config(program)
    kind = record["device"]["kind"]
    return {"config": config, "steps": steps, "trace": trace,
            "calls": counts.step_kernel_calls(
                config, layout["batch"], layout["seq_len"], cfg.attn_blk_q,
                cfg.attn_blk_k, bool(cfg.remat), cfg.chunk_size,
                harness.load_module("layer_metrics",
                                    "moe.held_share").read(record)),
            "peak_flops": flops.peak(kind),
            "peak_bytes": flops.peak(kind, "hbm_bytes_per_s")}


def longest_run(config, kind: str) -> int:
    """Layers of ``kind`` in the stretch of alternating expert and
    state-space layers that holds the most of them: the units of the scan
    whose instructions are the busiest."""
    kinds = counts.layer_kinds(config)
    return max((sum(k == kind for k in stretch)
                for mixed, stretch in itertools.groupby(
                    kinds, lambda k: k != "attention") if mixed), default=0)


def _one_call(found, name: str, layers: int) -> Optional[float]:
    """Per cent of the roofline of one call of the kernel ``name``, read on
    its busiest instruction, which ``layers`` calls a step."""
    secs = _busiest(found["trace"], name)
    if secs is None or name not in found["calls"] or not layers:
        return None
    least = counts.least_seconds(found["calls"][name], found["peak_flops"],
                                 found["peak_bytes"])
    return 100.0 * least / (secs / (layers * found["steps"]))


def ssd(record, kernel: str) -> Optional[float]:
    """``ssd_fwd`` or ``ssd_bwd`` of the grouped scan, read on the longest
    run of units."""
    found = shapes(record)
    if found is None:
        return None
    return _one_call(found, kernel, longest_run(found["config"], "mamba"))


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
