"""``check_faults_evabyte.py`` at the family's tiny size, on the CPU in
float32: the runner's own comparison passes the untouched program and
refuses each planted fault (another mask, another pooling, a term left out,
a head held to another byte, the residual stream at 8 bits). On the chip the
same script runs at the configuration's size under the configuration's
limits."""

import os

import pytest

import check_faults_evabyte as script
import harness

NAMES = list(script.faults())
SEED = 5


@pytest.fixture(scope="module")
def planted():
    name = next(
        os.path.basename(entry["file"])[:-len(".json")]
        for entry in harness.load_spec()["configs"]
        if harness.load_json(os.path.join(harness.ROOT, entry["file"]))[
            "program"]["family"] == "evabyte")
    config, family, cfg, mesh = script.prepared(name, tiny=True)
    params = family.init(cfg, SEED, config["program"])
    kept = {}
    return lambda fault: script.check(config, family, cfg, mesh, params,
                                      SEED, fault, kept)


def test_every_term_of_the_issue_is_planted():
    assert set(NAMES) == {
        "untouched", "summaries", "own_chunks", "sliding_window",
        "chunk_offset", "rope_after_pooling", "mu", "phi", "unit_offset",
        "head_targets", "eight_bit_residual"}


@pytest.mark.parametrize("name", NAMES)
def test_the_comparison_refuses_the_fault_and_nothing_else(planted, name):
    """Every fault is outside a limit; all but ``head_targets`` (the logits
    are the untouched program's: the loss alone moves) outside the logits'."""
    line = planted(name)
    assert line["ok"] == (name == "untouched"), line
    moved = "loss_tol" if name == "head_targets" else "logit_rms_tol"
    assert (moved in line["failed"]) == (name != "untouched")
