"""Model FLOP/s utilization of a Granite 4.0-H model with experts on the
chip's share it holds: tokens per second times the FLOPs a token costs here
(``flops_granite_moe.py``: 6 per matmul parameter a token goes through on
this chip, its 10 routed experts at this chip's share of the assignments
beside the shared SwiGLU and the router in every layer, attention over S
keys in the attention layer, the literal recurrence of the state-space
layers; the conv, the gates and recompute not counted) over chips times the
chip's published bf16 peak: this cell's share of the whole step's peak."""

import flops
import flops_granite_moe
import harness


def read(record):
    rate = harness.load_module("end_to_end", "tokens_per_s").read(record)
    config = record["cell"]["config"]
    if rate is None or config.get("program", {}).get("family") \
            != "granitemoehybrid_moe":
        return None
    per_token = flops_granite_moe.model_flops_per_token(
        config, record["model"]["seq_len"])
    return rate * per_token / (
        record["cell"]["chips"] * flops.peak(record["device"]["kind"]))
