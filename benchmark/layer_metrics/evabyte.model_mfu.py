"""Model FLOP/s utilization of an EvaByte model on the chip: tokens (bytes)
per second times the FLOPs a token costs (``flops_evabyte.py``: 6 per matmul
parameter, the attention over the pairs EVA defines, the pooling's three
contractions, the head of all the prediction heads; recompute and what a
masked tile computes beyond its pairs not counted) over chips times the
chip's published bf16 peak: this cell's share of the whole step's peak."""

import flops
import flops_evabyte
import harness


def read(record):
    rate = harness.load_module("end_to_end", "tokens_per_s").read(record)
    config = record["cell"]["config"]
    if rate is None or config.get("model_type") != "evabyte":
        return None
    per_token = flops_evabyte.model_flops_per_token(
        config, record["model"]["seq_len"])
    return rate * per_token / (
        record["cell"]["chips"] * flops.peak(record["device"]["kind"]))
