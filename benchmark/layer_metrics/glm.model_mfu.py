"""Model FLOP/s utilization of a latent-attention expert model with a
learned selection of keys on the chip's share it holds: tokens per second
times the FLOPs a token costs here (``flops_glm_moe_dsa.py``: 6 per matmul
parameter a token goes through on this chip, the routed experts at this
chip's share of the assignments, the main attention over the selected pairs,
the indexer's scores over the causal pairs; recompute, the head-summed
probabilities and what a masked tile computes beyond its selected pairs not
counted) over chips times the chip's published bf16 peak: this cell's share
of the whole step's peak."""

import flops
import flops_glm_moe_dsa
import harness


def read(record):
    rate = harness.load_module("end_to_end", "tokens_per_s").read(record)
    config = record["cell"]["config"]
    if rate is None or config.get("model_type") != "glm_moe_dsa":
        return None
    per_token = flops_glm_moe_dsa.model_flops_per_token(
        config, record["model"]["seq_len"])
    return rate * per_token / (
        record["cell"]["chips"] * flops.peak(record["device"]["kind"]))
