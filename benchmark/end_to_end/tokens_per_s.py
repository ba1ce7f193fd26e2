"""Tokens of the whole units completed in the window over the host-clock
time from the window's start to the end of the last whole unit. Every stall
inside a unit counts: report round trips, waits for data, saves."""


def read(record):
    window = record["window"]
    ends = window["unit_ends"]
    if not ends:
        return None
    tokens = len(ends) * window["steps_per_unit"] * window["tokens_per_step"]
    return tokens / (ends[-1] - window["t0"])
