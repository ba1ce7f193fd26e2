"""Roofline shares of the window layers' flash kernels in an ``afmoe`` step,
for the ``kernel.flash_*_win_roofline`` readers: what ``flops_afmoe.py`` says
one call executes against ``peaks.json``, over the time the trace gives it.

The trace keeps the ten longest operations by instruction name
(``trace.device_ops``), summed over the window. A kernel appears there once
per place it is called from: every run of layers of one kind is a scan of
its own (``flops_afmoe.layer_kinds``), so a window layer's kernel is as many
instructions as there are runs of window layers. Which of them made the list
cannot be told from the names, so a kernel's time is read on **one**
instruction, the busiest of its name, which is the longest run's: it is
called once per layer of that run and step, and every call of a kernel has
the same shapes. None where no instruction of the name is among the ten, on
a record of another family, without a trace, or where the window does not
cut the sequence (the kernels then run under the causal names).
"""

from __future__ import annotations

import itertools
from typing import Optional

import flops
import flops_afmoe
import harness
from kernel_rooflines import _busiest


def longest_window_run(config) -> int:
    """Layers in the longest run of one kind of window layer."""
    return max((len(list(run)) for (_, sliding), run in itertools.groupby(
        flops_afmoe.layer_kinds(config)) if sliding), default=0)


def flash(record, kernel: str) -> Optional[float]:
    """Per cent of the roofline of one call of the window layers'
    ``kernel`` (``flash_fwd``, ``flash_bwd_dq`` or ``flash_bwd_dkv``)."""
    trace = record.get("trace") or {}
    config = record["cell"]["config"]
    steps = len(trace.get("steps_device_s") or ())
    if config.get("model_type") != "afmoe" or not steps:
        return None
    seq_len = config["layout"]["seq_len"]
    layers = longest_window_run(config)
    secs = _busiest(trace, kernel + flops_afmoe.WINDOW_SUFFIX)
    if secs is None or not layers or config["sliding_window"] >= seq_len:
        return None
    program = config["program"]
    cfg = harness.load_module("families", program["family"]).config(program)
    call = flops_afmoe.flash_call(
        kernel, config["layout"]["batch"] * config["num_attention_heads"],
        seq_len, config["sliding_window"], config["head_dim"],
        cfg.attn_blk_q, cfg.attn_blk_k)
    kind = record["device"]["kind"]
    least = flops_afmoe.least_seconds(
        call, flops.peak(kind), flops.peak(kind, "hbm_bytes_per_s"))
    return 100.0 * least / (secs / (layers * steps))
