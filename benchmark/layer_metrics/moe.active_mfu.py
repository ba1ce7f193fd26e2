"""Model FLOP/s utilization of an expert model: tokens per second times the
FLOPs a token's *active* parameters cost (``flops_deepseek.py``: its 6
routed experts and the shared one, not all 64; full S x S attention;
recompute not counted) over chips times the chip's published bf16 peak."""

import flops
import flops_deepseek
import harness


def read(record):
    rate = harness.load_module("end_to_end", "tokens_per_s").read(record)
    config = record["cell"]["config"]
    if rate is None or config.get("model_type") != "deepseek_v3":
        return None
    per_token = flops_deepseek.model_flops_per_token(
        config, record["model"]["seq_len"])
    return rate * per_token / (
        record["cell"]["chips"] * flops.peak(record["device"]["kind"]))
