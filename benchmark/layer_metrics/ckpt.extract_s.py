"""The rest of a save's stall: median stall minus the program's own write
time (``ckpt.write_s``): gather to the host, extraction, copies, CRC."""

import harness


def read(record):
    stall = harness.load_module("end_to_end", "ckpt_stall_s").read(record)
    write = harness.load_module("layer_metrics", "ckpt.write_s").read(record)
    if stall is None or write is None:
        return None
    return stall - write
