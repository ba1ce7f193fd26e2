"""The program's own counters and gauges (``ray_tpu.util.metrics``), for the
per-layer metrics whose ``source`` is ``program_counter`` and whose counter
the runner's record does not carry.

The registry is the process's: the step feeds it while the cell runs and it
outlives ``ray_tpu.shutdown()``, so a reader takes it afterwards, in this
process, as ``program_spans.py`` takes the span buffer. A program that has
no such metric (the parent of the PR that added it) leaves the reader with
None. The totals are the process's, warm-up included: a reader that wants
the window's share takes a ratio of two counters fed together.
"""

from __future__ import annotations

from typing import Optional


def value(name: str) -> Optional[float]:
    """Sum over the series of the counter or gauge ``name``; None where the
    program registered none or never fed it."""
    try:
        from ray_tpu.util import metrics
    except ImportError:
        return None
    for entry in metrics.snapshot():
        if entry["name"] == name and entry["series"]:
            return float(sum(entry["series"].values()))
    return None
