"""The roofline share of all the kernels in an ``lfm2_moe`` step, for the
``kernel.lfm2_mosaic_roofline`` reader: what ``flops_lfm2.py`` says the
step's calls execute against ``peaks.json``, over ``trace.mosaic_s``, the
summed time of every Pallas kernel, which needs no kernel's name.

The gated short convolution's pair has no reader of its own: the trace
keeps the ten longest operations by instruction name
(``trace.device_ops``), and in this family's cell the attention layers'
flash kernels are nine of them (one instruction a run of layers: three
kernels, three or four runs) and a projection the tenth; a
``short_conv_fwd`` or ``short_conv_bwd`` instruction is 2.4-4.4 ms a step
where the tenth is 13.9 (PERF.md, PR 37). None on a record of another
family or without a trace.
"""

from __future__ import annotations

from typing import Optional

import flops
import flops_lfm2
import harness


def mosaic(record) -> Optional[float]:
    """Per cent: the least time for every Mosaic call of the step over
    ``trace.mosaic_s``; the grouped matmuls' rows at the share of the
    assignments the program's counters say fell on held experts. None where
    the cell is not of this family, the run was not traced or no kernel
    ran."""
    trace = record.get("trace") or {}
    config = record["cell"]["config"]
    steps = len(trace.get("steps_device_s") or ())
    if config.get("model_type") != "lfm2_moe" or not steps \
            or not trace.get("mosaic_s"):
        return None
    from ray_tpu.ops.flash_attention import worth_keeping
    program, layout = config["program"], config["layout"]
    cfg = harness.load_module("families", program["family"]).config(program)
    kind = record["device"]["kind"]
    calls = flops_lfm2.step_kernel_calls(
        config, layout["batch"], layout["seq_len"], cfg.attn_blk_q,
        cfg.attn_blk_k, bool(cfg.remat),
        worth_keeping(layout["seq_len"], cfg.head_dim),
        harness.load_module("layer_metrics", "moe.held_share").read(record))
    least = sum(one["calls"] * flops_lfm2.least_seconds(
        one, flops.peak(kind), flops.peak(kind, "hbm_bytes_per_s"))
        for one in calls.values())
    return 100.0 * least * steps / trace["mosaic_s"]
