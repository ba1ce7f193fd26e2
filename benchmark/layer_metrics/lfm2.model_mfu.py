"""Model FLOP/s utilization of a short-convolution / grouped-query-attention
expert model on the chip's share it holds: tokens per second times the FLOPs
a token costs here (``flops_lfm2.py``: 6 per matmul parameter a token goes
through on this chip, the routed experts at this chip's share of the
assignments, attention over S keys; the convolution's gates and recompute
not counted) over chips times the chip's published bf16 peak: this cell's
share of the whole step's peak."""

import flops
import flops_lfm2
import harness


def read(record):
    rate = harness.load_module("end_to_end", "tokens_per_s").read(record)
    config = record["cell"]["config"]
    if rate is None or config.get("model_type") != "lfm2_moe":
        return None
    per_token = flops_lfm2.model_flops_per_token(
        config, record["model"]["seq_len"])
    return rate * per_token / (
        record["cell"]["chips"] * flops.peak(record["device"]["kind"]))
