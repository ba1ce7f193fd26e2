"""Model FLOP/s utilization of a hybrid state-space model: tokens per second
times the FLOPs a token costs (``flops_granite.py``: 6 per matmul parameter,
the tied table once as the head, full S x S attention in the attention
layers, the literal recurrence in the state-space layers; recompute not
counted) over chips times the chip's published bf16 peak."""

import flops
import flops_granite
import harness


def read(record):
    rate = harness.load_module("end_to_end", "tokens_per_s").read(record)
    config = record["cell"]["config"]
    if rate is None or config.get("model_type") != "granitemoehybrid":
        return None
    per_token = flops_granite.model_flops_per_token(
        config, record["model"]["seq_len"])
    return rate * per_token / (
        record["cell"]["chips"] * flops.peak(record["device"]["kind"]))
