"""``check_faults_phi4flash.py`` at the family's tiny size, on the CPU in
float32: the runner's own comparison passes the untouched program and
refuses each planted fault (a term of the forward pass taken out, the
residual stream at 8 bits). On the chip the same script runs at the
configuration's size under the configuration's limits."""

import os

import pytest

import check_faults_phi4flash as script
import harness

NAMES = list(script.faults())
SEED = 5


@pytest.fixture(scope="module")
def planted():
    name = next(
        os.path.basename(entry["file"])[:-len(".json")]
        for entry in harness.load_spec()["configs"]
        if harness.load_json(os.path.join(harness.ROOT, entry["file"]))[
            "program"]["family"] == "phi4flash")
    config, family, cfg, mesh = script.prepared(name, tiny=True)
    # At an inner width of 256 the recurrence is a thousandth of what D
    # passes through, and the step's bias moves the logits by 8e-5 of their
    # RMS (two hundred times float32's 4e-7): limits of 2e-5, not 1e-3.
    config["reference"] = dict(config["reference"], logit_rms_tol=2e-5,
                               logit_max_tol=2e-4)
    params = family.init(cfg, SEED, config["program"])
    kept = {}
    return lambda fault: script.check(config, family, cfg, mesh, params,
                                      SEED, fault, kept)


def test_every_fault_of_the_issue_is_planted():
    assert set(NAMES) == {
        "untouched", "p2_not_subtracted", "subln", "one_minus_l0",
        "l0_of_the_cut", "k_pairing", "window", "cross_own_kv",
        "memory_after_gate", "gmu_gate", "skip_d", "b_dt", "a_tap",
        "layernorm_bias", "eight_bit_residual"}


@pytest.mark.parametrize("name", NAMES)
def test_the_comparison_refuses_the_fault_and_nothing_else(planted, name):
    line = planted(name)
    assert line["ok"] == (name == "untouched"), line
    assert ("logit_rms_tol" in line["failed"]) == (name != "untouched")
