"""The program's own account of set-up: its histograms
``ray_tpu_train_setup_seconds{stage, within}`` (one observation a stage of
the way from process start to the first timed step, fed whether or not
anybody traces) and ``ray_tpu_jax_compile_seconds{phase, within}`` (what JAX
reports of each program it makes: ``trace``, ``lower``, ``backend``, each
less what it enclosed, so they tile), for the per-layer metrics that move
``setup_s``.

Read from the process's registry after the run, by label, as
``program_counters.py`` reads counters. ``within`` names the stage that was
open when the observation was made (``none``: the outermost), so a sum over
``within="none"`` counts no second twice. A program that has no such
histogram (the parent of the PR that added them) leaves every reader with
None.

The totals are the process's. In this benchmark's runs every stage happens
once, before the window; after it the runner lowers the step once more for
its text (the stage ``aot_lower``, which no reader counts) and, in a cell
that saves, reads the newest checkpoint back: the programs that makes
(``within="none"``) are in ``other_programs_seconds`` although they are not
set-up (PERF.md gives their size).
"""

from __future__ import annotations

from typing import Dict, Optional

SETUP = "ray_tpu_train_setup_seconds"
COMPILE = "ray_tpu_jax_compile_seconds"
#: What tiles a program's making; ``cache_load`` lies inside ``backend``.
PHASES = ("trace", "lower", "backend")
#: After the window in every run of this benchmark: not set-up.
NOT_SETUP = ("aot_lower",)


def _entry(name: str) -> Optional[Dict]:
    try:
        from ray_tpu.util import metrics
    except ImportError:
        return None
    for entry in metrics.snapshot():
        if entry["name"] == name and entry.get("counts"):
            return entry
    return None


def _select(name: str, field: str, labels: Dict[str, str]) -> Optional[float]:
    """Sum of ``field`` (``sums`` or ``counts``) over the series of the
    histogram ``name`` that carry ``labels``; None where there is none."""
    entry = _entry(name)
    if entry is None:
        return None
    keys = entry["tag_keys"]
    picked = [value for series, value in entry[field].items()
              if all(dict(zip(keys, series)).get(k) == v
                     for k, v in labels.items())]
    return float(sum(picked)) if picked else None


def seconds(name: str, **labels: str) -> Optional[float]:
    return _select(name, "sums", labels)


def count(name: str, **labels: str) -> Optional[float]:
    return _select(name, "counts", labels)


def stage_seconds(stage: str, within: str = "none") -> Optional[float]:
    return seconds(SETUP, stage=stage, within=within)


def first_call_phase(phase: str) -> Optional[float]:
    """Seconds of ``phase`` inside the step's first call; 0.0 where the
    call was observed and JAX reported no such phase in it."""
    if stage_seconds("first_call") is None:
        return None
    return seconds(COMPILE, phase=phase, within="first_call") or 0.0


def other_programs_seconds() -> Optional[float]:
    """Every phase JAX reported outside any stage: the programs that are
    neither the step nor ``init``."""
    if _entry(SETUP) is None:
        return None
    return sum(seconds(COMPILE, phase=phase, within="none") or 0.0
               for phase in PHASES)


def outermost_stages_seconds() -> Optional[float]:
    """Every stage of set-up that ran inside no other, summed: ``init``,
    the three Train stages, ``mesh``, ``state_init``, ``first_call``, and a
    ``native_build`` that ran outside ``init``."""
    entry = _entry(SETUP)
    if entry is None:
        return None
    keys = entry["tag_keys"]
    total = 0.0
    for series, value in entry["sums"].items():
        labels = dict(zip(keys, series))
        if labels.get("within") == "none" \
                and labels.get("stage") not in NOT_SETUP:
            total += value
    return total
