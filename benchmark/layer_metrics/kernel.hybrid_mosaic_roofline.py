"""The least time the chip could take for everything the step's Mosaic
kernels execute over the time they took (``trace.mosaic_s``), in per cent:
the chunked state-space scans ``ssd_fwd`` and ``ssd_bwd`` and the three
flash kernels, each call's larger of FLOPs over the bf16 peak and bytes over
the HBM bandwidth (``flops_granite.step_kernel_calls``: causal tiles once,
the forward kernels twice where the block is rematerialised). It needs no
kernel's name: the layer scan runs every run of state-space layers as a loop
of its own, so ``ssd_fwd`` is six instructions and ``ssd_bwd`` three, none
among the ten operations a trace keeps. None on a record of another family
or without a trace."""

import flops
import flops_granite
import harness


def read(record):
    trace = record.get("trace") or {}
    config = record["cell"]["config"]
    steps = len(trace.get("steps_device_s") or ())
    if config.get("model_type") != "granitemoehybrid" or not steps \
            or not trace.get("mosaic_s"):
        return None
    program = config["program"]
    cfg = harness.load_module("families", program["family"]).config(program)
    kind = record["device"]["kind"]
    calls = flops_granite.step_kernel_calls(
        config, config["layout"]["batch"], config["layout"]["seq_len"],
        cfg.attn_blk_q, cfg.attn_blk_k, bool(cfg.remat))
    least = sum(one["calls"] * max(
        one["flops"] / flops.peak(kind),
        one["bytes"] / flops.peak(kind, "hbm_bytes_per_s"))
        for one in calls.values())
    return 100.0 * least * steps / trace["mosaic_s"]
