"""Roofline shares of the kernels in a ``phi4flash`` step: what
``flops_phi4flash.py`` says a call executes against ``peaks.json``, over the
time the trace gives it. None on a record of another family or without a
trace.

``mosaic`` (``kernel.phi4flash_mosaic_roofline``) takes every Mosaic call of
the step over ``trace.mosaic_s``, the summed time of every Pallas kernel,
which needs no kernel's name. ``kernel`` (``kernel.selective_scan_bwd_roofline``)
takes one call of the selective scan's forward or backward over a call's
time on **one** instruction, the busiest of that name among the trace's ten
longest operations (``trace.device_ops``, as ``kda_rooflines.py`` reads its
pair): a kernel is one instruction a run of pairs (and its rematerialised
forward another), the busiest is the longest run's, the self pairs'
(``flops_phi4flash.longest_mamba_run``), called once a Mamba layer of that
run a step, and every call has the same shapes. None where no instruction
of the name is among the ten.
"""

from __future__ import annotations

from typing import Optional

import flops
import flops_phi4flash
import harness
from kernel_rooflines import _busiest


def kernel(record, name: str) -> Optional[float]:
    """Per cent of the roofline of one call of ``selective_scan_fwd`` or
    ``selective_scan_bwd``: its bytes' time (``selective_scan_call``)."""
    trace = record.get("trace") or {}
    config = record["cell"]["config"]
    steps = len(trace.get("steps_device_s") or ())
    if config.get("model_type") != "phi4flash" or not steps:
        return None
    layers = flops_phi4flash.longest_mamba_run(config)
    secs = _busiest(trace, name)
    if secs is None or not layers:
        return None
    kind, layout = record["device"]["kind"], config["layout"]
    least = flops_phi4flash.least_seconds(
        flops_phi4flash.selective_scan_call(
            name, config, layout["batch"], layout["seq_len"]),
        flops.peak(kind), flops.peak(kind, "hbm_bytes_per_s"))
    return 100.0 * least / (secs / (layers * steps))


def mosaic(record) -> Optional[float]:
    """Per cent: the least time for every Mosaic call of the step (the
    selective scan's and the convolution's pairs by their bytes, the flash
    kernels by their executed tiles' products) over ``trace.mosaic_s``.
    None where the cell is not of this family, the run was not traced or no
    kernel ran."""
    trace = record.get("trace") or {}
    config = record["cell"]["config"]
    steps = len(trace.get("steps_device_s") or ())
    if config.get("model_type") != "phi4flash" or not steps \
            or not trace.get("mosaic_s"):
        return None
    from ray_tpu.ops.flash_attention import worth_keeping
    program, layout = config["program"], config["layout"]
    cfg = harness.load_module("families", program["family"]).config(program)
    kind, seq = record["device"]["kind"], layout["seq_len"]
    calls = flops_phi4flash.step_kernel_calls(
        config, layout["batch"], seq, cfg.attn_blk_q, cfg.attn_blk_k,
        bool(cfg.remat),
        {"window": worth_keeping(seq, 2 * cfg.head_dim, cfg.sliding_window),
         "causal": worth_keeping(seq, 2 * cfg.head_dim)})
    least = sum(one["calls"] * flops_phi4flash.least_seconds(
        one, flops.peak(kind), flops.peak(kind, "hbm_bytes_per_s"))
        for one in calls.values())
    return 100.0 * least * steps / trace["mosaic_s"]
