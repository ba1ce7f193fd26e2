"""Model FLOP/s utilization of a Mellum model on the chip: tokens per second
times the FLOPs a token costs (``flops_mellum.py``: 6 per matmul parameter a
token goes through, its ``num_experts_per_tok`` experts and not all of them,
attention over S keys in a full layer and over ``min(S, sliding_window)`` in
a window layer; recompute not counted) over chips times the chip's published
bf16 peak: this cell's share of the whole step's peak."""

import flops
import flops_mellum
import harness


def read(record):
    rate = harness.load_module("end_to_end", "tokens_per_s").read(record)
    config = record["cell"]["config"]
    if rate is None or config.get("model_type") != "mellum":
        return None
    per_token = flops_mellum.model_flops_per_token(
        config, record["model"]["seq_len"])
    return rate * per_token / (
        record["cell"]["chips"] * flops.peak(record["device"]["kind"]))
