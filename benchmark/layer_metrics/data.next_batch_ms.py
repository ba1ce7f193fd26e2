"""Median milliseconds of the window's ``data::next_batch`` spans: the
program's own timing of ``next`` on ``iter_jax_batches`` with the transfer to
the device inside it (``data::to_device``). ``train.data_wait_ms`` times the
same from the runner, with the fingerprint's read-back added. None where the
cell draws no batch from a dataset or nothing recorded."""

import program_spans


def read(record):
    m = program_spans.median_seconds(record, "data::next_batch")
    return None if m is None else m * 1e3
