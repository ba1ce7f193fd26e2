"""Roofline shares of the kernels in a ``deepseek_v3`` step, for the
``kernel.*_roofline`` readers: what ``flops_deepseek.py`` says a kernel
executes against ``peaks.json``, over the time the trace gives it.

The trace keeps the ten longest operations by instruction name
(``trace.device_ops``), summed over the window. A kernel appears there once
per place it is called from: the leading dense layers' scan and the expert
layers' scan are different instructions, and so are a block's forward and
its rematerialised forward. Which of them made the list cannot be told from
the names, so a kernel's time is read on **one** instruction, the busiest of
its name, which is the expert layers' scan wherever there are more expert
layers than dense ones (otherwise: None); it is called once per expert layer
and step, and every call of a kernel has the same shapes. ``kernel.mosaic_roofline``
needs no names: ``trace.mosaic_s`` is all of them.
"""

from __future__ import annotations

from typing import Any, Dict, Optional

import flops
import flops_deepseek
import harness


def _shapes(record) -> Optional[Dict[str, Any]]:
    """What the count needs from the record's cell, or None where the cell
    is not of this family or the run was not traced."""
    trace = record.get("trace") or {}
    config = record["cell"]["config"]
    steps = len(trace.get("steps_device_s") or ())
    if config.get("model_type") != "deepseek_v3" or not steps:
        return None
    program = config["program"]
    cfg = harness.load_module("families", program["family"]).config(program)
    return {"config": config, "steps": steps, "trace": trace,
            "batch": config["layout"]["batch"],
            "seq_len": config["layout"]["seq_len"],
            "blk_q": cfg.attn_blk_q, "blk_k": cfg.attn_blk_k,
            "remat": bool(cfg.remat),
            "moe_layers": config["num_hidden_layers"]
            - config["first_k_dense_replace"],
            "kind": record["device"]["kind"]}


def _busiest(trace, kernel: str) -> Optional[float]:
    """Seconds of the busiest instruction called ``kernel`` or
    ``kernel.<n>`` among the trace's longest operations."""
    found = [secs for name, secs in trace.get("device_ops") or ()
             if name == kernel or name.startswith(kernel + ".")]
    return max(found) if found else None


def _share(shapes, kernel: str, call: Dict[str, float]) -> Optional[float]:
    """Per cent of the roofline of one call of ``kernel``."""
    secs = _busiest(shapes["trace"], kernel)
    if secs is None or shapes["moe_layers"] <= \
            shapes["config"]["first_k_dense_replace"]:
        return None
    per_call = secs / (shapes["moe_layers"] * shapes["steps"])
    least = max(call["flops"] / flops.peak(shapes["kind"]),
                call["bytes"] / flops.peak(shapes["kind"],
                                           "hbm_bytes_per_s"))
    return 100.0 * least / per_call


def flash(record, kernel: str) -> Optional[float]:
    shapes = _shapes(record)
    if shapes is None:
        return None
    config = shapes["config"]
    return _share(shapes, kernel, flops_deepseek.flash_call(
        kernel, shapes["batch"] * config["num_attention_heads"],
        shapes["seq_len"],
        config["qk_nope_head_dim"] + config["qk_rope_head_dim"],
        config["v_head_dim"], shapes["blk_q"], shapes["blk_k"]))


def grouped_matmul(record) -> Optional[float]:
    """``gmm`` (a product or its rows' cotangent): every one of a layer's
    products has the same FLOPs and least bytes, and the busiest
    instruction is the slowest of them. The weights' cotangent, ``tgmm``,
    has no reader: its instructions are never among the trace's ten."""
    shapes = _shapes(record)
    if shapes is None:
        return None
    layer = flops_deepseek.grouped_matmul_layer(
        shapes["config"], shapes["batch"] * shapes["seq_len"],
        shapes["remat"])
    one = {k: layer[k] / layer["products"] for k in ("flops", "bytes")}
    return _share(shapes, "gmm", one)


def mosaic(record) -> Optional[float]:
    shapes = _shapes(record)
    if shapes is None or not shapes["trace"].get("mosaic_s"):
        return None
    executed = sum(flops_deepseek.step_kernel_flops(
        shapes["config"], shapes["batch"], shapes["seq_len"],
        shapes["blk_q"], shapes["blk_k"], shapes["remat"]).values())
    return 100.0 * executed * shapes["steps"] / (
        shapes["trace"]["mosaic_s"] * flops.peak(shapes["kind"]))
