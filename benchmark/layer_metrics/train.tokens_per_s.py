"""``tokens_per_s`` (whole units over the host clock, every stall inside a
unit counted) in a cell whose units hold a save: the same number, kept
among the per-layer metrics because from run to run it spreads too widely
to carry a bound (0.4-5 % on the v5e, PR 22)."""

import harness


def read(record):
    return harness.load_module("end_to_end", "tokens_per_s").read(record)
