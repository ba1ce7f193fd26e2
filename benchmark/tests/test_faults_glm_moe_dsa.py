"""``check_faults_glm_moe_dsa.py`` at the family's tiny size, on the CPU in
float32: the runner's own comparison passes the untouched program and
refuses each planted fault (a term of the forward pass taken out, a
selection ignored or replaced, the residual stream at 8 bits). On the chip
the same script runs at the configuration's size under the configuration's
limits."""

import os

import pytest

import check_faults_glm_moe_dsa as script
import harness

NAMES = list(script.faults())
SEED = 5


@pytest.fixture(scope="module")
def planted():
    name = next(
        os.path.basename(entry["file"])[:-len(".json")]
        for entry in harness.load_spec()["configs"]
        if harness.load_json(os.path.join(harness.ROOT, entry["file"]))[
            "program"]["family"] == "glm_moe_dsa")
    config, family, cfg, mesh = script.prepared(name, tiny=True)
    params = family.init(cfg, SEED, config["program"])
    kept = {}
    return lambda fault: script.check(config, family, cfg, mesh, params,
                                      SEED, fault, kept)


def test_every_term_of_the_issue_is_planted():
    assert set(NAMES) == {
        "untouched", "selection", "relu", "index_weights", "index_rope",
        "shared_dense", "shared_window", "q_norm", "score_scale",
        "routed_scaling_factor", "shared_expert", "eight_bit_residual"}


@pytest.mark.parametrize("name", NAMES)
def test_the_comparison_refuses_the_fault_and_nothing_else(planted, name):
    line = planted(name)
    assert line["ok"] == (name == "untouched"), line
    assert ("logit_rms_tol" in line["failed"]) == (name != "untouched")
