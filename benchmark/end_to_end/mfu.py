"""Model FLOP/s utilization: tokens per second times the model's FLOPs per
token (``flops.py``: 6 per matmul parameter plus full S x S attention,
recompute not counted) over chips times the chip's published bf16 peak."""

import flops
import harness


def read(record):
    rate = harness.load_module("end_to_end", "tokens_per_s").read(record)
    if rate is None:
        return None
    cell = record["cell"]
    per_token = flops.model_flops_per_token(cell["config"],
                                            record["model"]["seq_len"])
    return rate * per_token / (
        cell["chips"] * flops.peak(record["device"]["kind"]))
