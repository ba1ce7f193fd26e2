"""Model FLOP/s utilization of a MiniCPM-SALA model on the chip: tokens per
second times the FLOPs a token costs (``flops_minicpm_sala.py``: 6 per
matmul parameter, the sparse layers' attention over the pairs the selection
defines, the compressed scores forward only, the recurrence's four products
a chunk; recompute and what a masked tile computes beyond its pairs not
counted) over chips times the chip's published bf16 peak: this cell's share
of the whole step's peak."""

import flops
import flops_minicpm_sala
import harness


def read(record):
    rate = harness.load_module("end_to_end", "tokens_per_s").read(record)
    config = record["cell"]["config"]
    if rate is None or config.get("model_type") != "minicpm_sala":
        return None
    per_token = flops_minicpm_sala.model_flops_per_token(
        config, record["model"]["seq_len"])
    return rate * per_token / (
        record["cell"]["chips"] * flops.peak(record["device"]["kind"]))
