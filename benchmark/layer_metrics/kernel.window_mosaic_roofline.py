"""The least time the chip could take for everything the step's Mosaic
kernels execute over the time they took (``trace.mosaic_s``), in per cent:
the flash kernels of the window layers (the tiles the window leaves) and of
the full layers, and the expert layers' grouped matmuls at the rows this
chip computed, each call's larger of FLOPs over the bf16 peak and bytes over
the HBM bandwidth (``flops_afmoe.step_kernel_calls``). The grouped matmuls'
rows are the share of the assignments the program's counters say fell on
held experts (``moe.held_share``), the even share where there is no counter.
It needs no kernel's name, so it reads whichever instructions the trace's
ten longest are. None on a record of another family or without a trace."""

import flops
import flops_afmoe
import harness


def read(record):
    trace = record.get("trace") or {}
    config = record["cell"]["config"]
    steps = len(trace.get("steps_device_s") or ())
    if config.get("model_type") != "afmoe" or not steps \
            or not trace.get("mosaic_s"):
        return None
    program = config["program"]
    cfg = harness.load_module("families", program["family"]).config(program)
    kind = record["device"]["kind"]
    calls = flops_afmoe.step_kernel_calls(
        config, config["layout"]["batch"], config["layout"]["seq_len"],
        cfg.attn_blk_q, cfg.attn_blk_k, bool(cfg.remat),
        harness.load_module("layer_metrics", "moe.held_share").read(record))
    least = sum(one["calls"] * flops_afmoe.least_seconds(
        one, flops.peak(kind), flops.peak(kind, "hbm_bytes_per_s"))
        for one in calls.values())
    return 100.0 * least * steps / trace["mosaic_s"]
