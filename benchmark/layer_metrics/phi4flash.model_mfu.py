"""Model FLOP/s utilization of a Mamba-1 / differential-attention hybrid:
tokens per second times the FLOPs a token costs (``flops_phi4flash.py``: 6
per matmul parameter with the tied table once, attention's two products over
the keys a query sees with ``q k^T`` at 64 and ``p V_g`` at 128, the literal
recurrence a state element; gates, norms and recompute not counted) over
chips times the chip's published bf16 peak: this cell's share of the whole
step's peak."""

import flops
import flops_phi4flash
import harness


def read(record):
    rate = harness.load_module("end_to_end", "tokens_per_s").read(record)
    config = record["cell"]["config"]
    if rate is None or config.get("model_type") != "phi4flash":
        return None
    per_token = flops_phi4flash.model_flops_per_token(
        config, record["model"]["seq_len"])
    return rate * per_token / (
        record["cell"]["chips"] * flops.peak(record["device"]["kind"]))
