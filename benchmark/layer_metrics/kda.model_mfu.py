"""Model FLOP/s utilization of a delta-rule / latent-attention expert model
on the chip's share it holds: tokens per second times the FLOPs a token
costs here (``flops_kimi_linear.py``: 6 per matmul parameter a token goes
through on this chip, the routed experts at this chip's share of the
assignments, the latent layers' attention over S keys, the delta rule as
the literal recurrence; recompute and what the chunked form adds not
counted) over chips times the chip's published bf16 peak."""

import flops
import flops_kimi_linear
import harness


def read(record):
    rate = harness.load_module("end_to_end", "tokens_per_s").read(record)
    config = record["cell"]["config"]
    if rate is None or config.get("model_type") != "kimi_linear":
        return None
    per_token = flops_kimi_linear.model_flops_per_token(
        config, record["model"]["seq_len"])
    return rate * per_token / (
        record["cell"]["chips"] * flops.peak(record["device"]["kind"]))
