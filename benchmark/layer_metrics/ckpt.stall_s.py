"""Median, over the saves in the window, of the seconds the train loop is
blocked in ``session.report_sharded`` (call to return, after a
``block_until_ready`` on the state, so that queued device work is not
charged to the save). The cell's end-to-end metric until PR 55
(``ckpt_stall_s``); since then the stall is inside ``job_tokens_per_s``'s
units and is read here, unbounded, in every traced run."""

import harness


def read(record):
    return harness.median(t1 - t0 for t0, t1 in
                          record["spans"].get("save", []))
