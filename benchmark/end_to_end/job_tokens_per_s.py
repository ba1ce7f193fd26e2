"""``tokens_per_s`` (tokens of the whole units over the host clock from the
window's start to the end of the last whole unit, every stall inside a unit
counted) in a cell whose units hold a save: the same number by the same
reader, under a name of its own because a save's stall makes it spread by
whole per cents from run to run, where the cells without saves repeat to a
hundredth of one and hold their rate to the tightest bound."""

import harness


def read(record):
    return harness.load_module("end_to_end", "tokens_per_s").read(record)
