"""Model FLOP/s utilization of the two-geometry latent-attention expert model
on the chip's share it holds: tokens per second times the FLOPs a token
costs here (``flops_dots3_note.py``: 6 per matmul parameter a token goes
through on this chip, the gates' among them, the routed experts at this
chip's share of the assignments, a full layer's attention over the selected
pairs and its indexer's scores over the causal pairs, a window layer's over
the window's pairs; recompute, the head-summed probabilities and what a
masked tile computes beyond its kept pairs not counted) over chips times the
chip's published bf16 peak: this cell's share of the whole step's peak."""

import flops
import flops_dots3_note
import harness


def read(record):
    rate = harness.load_module("end_to_end", "tokens_per_s").read(record)
    config = record["cell"]["config"]
    if rate is None or config.get("model_type") != "dots3_note":
        return None
    per_token = flops_dots3_note.model_flops_per_token(
        config, record["model"]["seq_len"])
    return rate * per_token / (
        record["cell"]["chips"] * flops.peak(record["device"]["kind"]))
